"""Flash-attention Pallas kernel vs the pure-jnp oracle: shape/dtype sweep,
causal + sliding-window + GQA, interpret=True on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

CASES = [
    # (B, S, Hq, Hkv, hd, causal, window)
    (1, 64, 4, 4, 16, True, None),
    (2, 128, 4, 2, 32, True, None),          # GQA 2x
    (1, 96, 8, 1, 16, True, None),           # MQA, ragged seq vs blocks
    (2, 128, 4, 4, 64, True, 32),            # sliding window
    (1, 256, 2, 2, 16, False, None),         # bidirectional
    (1, 80, 3, 1, 16, True, 24),             # non-pow2 heads + window
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref(case, dtype):
    B, S, Hq, Hkv, hd, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2 ** 31), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), dtype)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    ref = attention_ref(q, k, v, pos, pos, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_k=32, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_block_size_invariance():
    B, S, H, hd = 1, 128, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    outs = [flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                            interpret=True)
            for bq, bk in ((16, 16), (32, 64), (128, 128))]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=1e-5, atol=1e-5)


def test_flash_fully_masked_rows_are_finite():
    """Window smaller than block: early tokens attend only to themselves;
    no NaNs from empty softmax rows."""
    B, S, H, hd = 1, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    out = flash_attention(q, k, v, causal=True, window=1,
                          block_q=32, block_k=32, interpret=True)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("window", [None, 24])
def test_mha_pallas_grad_matches_ref(window):
    """`ops.mha(use_pallas=True)` is differentiable: the kernel forward
    carries the oracle's VJP, so its gradients equal the oracle's."""
    from repro.kernels.flash_attention.ops import mha

    B, S, Hq, Hkv, hd = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (B, S, Hq, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    w = jax.random.normal(ks[3], (B, S, Hq, hd))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))

    def loss(use_pallas):
        return lambda q, k, v: (mha(q, k, v, pos, pos, window=window,
                                    use_pallas=use_pallas,
                                    interpret=True) * w).sum()

    gp = jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
