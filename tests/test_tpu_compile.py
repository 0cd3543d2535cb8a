"""Compile-only checks for a described TPU v5e (no chip needed): the Pallas
kernels of the model runtime at real widths, and the float32 optimizer
programs of the explorer at the `gpt175b_train_dse` bucket sizes, must be
accepted by the TPU compiler. Nothing runs; the float64 analytical
evaluator is not compiled here (it is placed on the host CPU, and its v5e
compile takes minutes).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("window", [None, 512])
def test_flash_attention_compiles_smollm_widths(one_chip, window):
    """smollm-135m attention: B=8, S=2048, 9 q heads over 3 kv heads,
    head_dim 64, bf16; forward and (custom-VJP) backward."""
    from repro.kernels.flash_attention.ops import mha

    B, S, Hq, Hkv, hd = 8, 2048, 9, 3, 64
    q = _sds(one_chip, (B, S, Hq, hd), jnp.bfloat16)
    kv = _sds(one_chip, (B, S, Hkv, hd), jnp.bfloat16)

    def loss(q, k, v):
        out = mha(q, k, v, None, None, window=window, use_pallas=True)
        return out.astype(jnp.float32).sum()

    fwd = jax.jit(lambda q, k, v: mha(q, k, v, None, None, window=window,
                                      use_pallas=True)).lower(q, kv, kv)
    assert _has_kernel(fwd.compile())
    bwd = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(q, kv,
                                                                      kv)
    assert _has_kernel(bwd.compile())


def test_ssd_scan_compiles_mamba2_widths(one_chip):
    """mamba2-370m SSD: H=32 heads of P=64, state N=128, chunk 128."""
    from repro.kernels.ssd_scan.ops import ssd

    B, S, H, P, N = 4, 2048, 32, 64, 128
    args = (_sds(one_chip, (B, S, H, P), jnp.bfloat16),
            _sds(one_chip, (B, S, H), jnp.float32),
            _sds(one_chip, (H,), jnp.float32),
            _sds(one_chip, (B, S, N), jnp.bfloat16),
            _sds(one_chip, (B, S, N), jnp.bfloat16),
            _sds(one_chip, (H,), jnp.float32))
    c = jax.jit(lambda *a: ssd(*a, chunk=128, use_pallas=True)).lower(*args)
    assert _has_kernel(c.compile())


# gpt175b_train_dse: 28 evaluations at q=4 over 64 candidates of 13 dims —
# the largest GP buffer bucket is 32 rows
_OBS, _CAND, _Q, _DIM = 28, 64, 4, 13


def test_gp_fit_pair_compiles(one_chip):
    from repro.core.gp import _fit_pair_jit, bucket_size

    B = bucket_size(_OBS)
    f32 = jnp.float32
    c = _fit_pair_jit.lower(
        _sds(one_chip, (B, _DIM), f32), _sds(one_chip, (2, B), f32),
        _sds(one_chip, (B,), f32), _sds(one_chip, (), f32),
        _sds(one_chip, (), f32), 80).compile()
    assert c.memory_analysis() is not None


def test_acquire_scan_compiles(one_chip, monkeypatch):
    """The scanned q-EHVI program, with the argument shapes the loop builds
    for the last acquisition of the campaign (captured on the host)."""
    import repro.core.mfmobo as M

    rng = np.random.default_rng(0)
    X = rng.random((_OBS - _Q, _DIM))
    Y = np.stack([1e3 * (1.0 + X[:, 0]), 1e3 * (2.0 - X[:, 1])], 1)
    models = M._fit_models(X, Y)
    seen = {}

    def capture(*args):
        seen["args"], seen["q"] = args[:-1], args[-1]
        return jnp.zeros(args[-1], jnp.int32)

    monkeypatch.setattr(M, "_acquire_scan_jit", capture)
    M._acquire_batch_device(models, rng.random((_CAND, _DIM)),
                            M.obj_space([tuple(y) for y in Y]),
                            M.hv_ref(15000.0), q=_Q)
    monkeypatch.undo()
    sds = [_sds(one_chip, np.shape(a), jnp.asarray(a).dtype)
           for a in seen["args"]]
    c = M._acquire_scan_jit.lower(*sds, q=seen["q"]).compile()
    assert c.memory_analysis() is not None
