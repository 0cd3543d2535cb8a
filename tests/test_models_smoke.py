"""Per-architecture smoke tests (deliverable f): reduced same-family config,
one forward + one train step on CPU, asserting output shapes and no NaNs;
plus prefill/decode consistency against the full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, reduced_config
from repro.models import model as M
from repro.models.runtime import CPU_TEST as RT
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import make_train_step


def _batch(cfg, rng, B=2, S=24, with_labels=True):
    batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab)}
    if with_labels:
        batch["labels"] = jax.random.randint(rng, (B, S), 0, cfg.vocab)
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            rng, (B, cfg.encoder_len, cfg.d_model))
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            rng, (B, cfg.prefix_len, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nans(arch):
    cfg = reduced_config(arch)
    rng = jax.random.PRNGKey(0)
    params = M.init_params(rng, cfg)
    B, S = 2, 24
    batch = _batch(cfg, rng, B, S)
    logits, aux = M.forward(params, cfg, RT, batch)
    exp_len = S + (cfg.prefix_len if cfg.family == "vlm" else 0)
    assert logits.shape == (B, exp_len, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch):
    cfg = reduced_config(arch)
    rng = jax.random.PRNGKey(1)
    params = M.init_params(rng, cfg)
    step = make_train_step(cfg, RT, AdamWConfig(peak_lr=1e-3))
    ost = init_opt_state(params)
    batch = _batch(cfg, rng)
    new_params, ost, met = step(params, ost, batch)
    assert np.isfinite(float(met["loss"]))
    assert np.isfinite(float(met["grad_norm"]))
    # params must actually change
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()),
                     params, new_params))
    assert delta > 0.0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch):
    cfg = reduced_config(arch)
    rng = jax.random.PRNGKey(2)
    params = M.init_params(rng, cfg)
    B, S = 2, 16
    batch = _batch(cfg, rng, B, S, with_labels=False)
    logits_full, _ = M.forward(params, cfg, RT, batch)

    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :S - 1]
    cache = M.init_cache(cfg, RT, B, max_len=32)
    last_logits, cache = M.prefill(params, cfg, RT, pre, cache)
    off = cfg.prefix_len if cfg.family == "vlm" else 0
    ref = logits_full[:, off + S - 2]
    np.testing.assert_allclose(np.asarray(last_logits), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    pos = jnp.int32(off + S - 1)
    dec_logits, _ = M.decode_step(params, cfg, RT,
                                  batch["tokens"][:, S - 1:S], pos, cache)
    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(logits_full[:, -1]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ["gemma3-4b", "mixtral-8x7b"])
def test_ring_cache_matches_full_cache(arch):
    """Sliding-window archs: ring-buffer cache must reproduce full-cache
    decode logits once the window is the binding constraint."""
    import dataclasses

    cfg = reduced_config(arch)
    rt_ring = dataclasses.replace(RT, ring_cache=True)
    rng = jax.random.PRNGKey(3)
    params = M.init_params(rng, cfg)
    B, S = 1, 12
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab)

    def roll(rt):
        cache = M.init_cache(cfg, rt, B, max_len=32)
        logits, cache = M.prefill(params, cfg, rt,
                                  {"tokens": tokens[:, :4]}, cache)
        outs = [logits]
        for t in range(4, S):
            logits, cache = M.decode_step(params, cfg, rt,
                                          tokens[:, t:t + 1],
                                          jnp.int32(t), cache)
            outs.append(logits)
        return np.stack([np.asarray(o) for o in outs])

    full = roll(RT)
    ring = roll(rt_ring)
    if arch == "mixtral-8x7b":      # every layer windowed -> exact match
        np.testing.assert_allclose(ring, full, rtol=2e-4, atol=2e-4)
    else:
        # gemma3 keeps full-length caches in baseline mode for its global
        # layers; ring mode only legal when pattern is uniform — shapes only
        assert ring.shape == full.shape


@pytest.mark.parametrize("arch", ["smollm-135m", "mamba2-370m"])
def test_pallas_runtime_matches_reference(arch):
    """`Runtime(use_pallas=True)` (kernels in interpret mode here) gives the
    reference path's loss and gradients, and — for attention archs — its
    prefill logits, where the fresh-cache prefill runs the flash kernel."""
    import dataclasses

    cfg = reduced_config(arch)
    rng = jax.random.PRNGKey(3)
    params = M.init_params(rng, cfg)
    batch = _batch(cfg, rng, B=2, S=32)
    rt_k = dataclasses.replace(RT, use_pallas=True, interpret=True)

    def loss(rt):
        return jax.value_and_grad(
            lambda p: M.loss_fn(p, cfg, rt, batch)[0])(params)

    (l_ref, g_ref), (l_k, g_k) = loss(RT), loss(rt_k)
    np.testing.assert_allclose(float(l_k), float(l_ref), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_k), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)

    def prefill(rt):
        cache = M.init_cache(cfg, rt, 2, 48)
        return M.prefill(params, cfg, rt, {"tokens": batch["tokens"]},
                         cache)[0]

    np.testing.assert_allclose(np.asarray(prefill(rt_k)),
                               np.asarray(prefill(RT)), rtol=1e-4, atol=1e-4)
