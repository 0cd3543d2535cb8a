"""SSD-scan Pallas kernel + chunked oracle vs the sequential recurrence:
shape/dtype/chunk sweep, decode-step consistency, interpret=True on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import (
    ssd_chunked_ref,
    ssd_decode_step_ref,
    ssd_ref,
)

CASES = [
    # (B, S, H, P, N, chunk)
    (1, 32, 2, 8, 8, 8),
    (2, 64, 4, 16, 16, 16),
    (1, 100, 2, 16, 8, 32),      # padding path (100 % 32 != 0)
    (2, 128, 2, 32, 16, 128),    # single chunk
]


def _inputs(case, dtype=jnp.float32, seed=0):
    B, S, H, P, N, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, N), dtype)
    D = jnp.linspace(0.2, 1.0, H)
    return x, dt, A, Bm, Cm, D


@pytest.mark.parametrize("case", CASES)
def test_chunked_matches_sequential(case):
    x, dt, A, Bm, Cm, D = _inputs(case)
    y0, h0 = ssd_ref(x, dt, A, Bm, Cm, D)
    y1, h1 = ssd_chunked_ref(x, dt, A, Bm, Cm, D, chunk=case[-1])
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_matches_sequential(case, dtype):
    x, dt, A, Bm, Cm, D = _inputs(case, dtype)
    y0, h0 = ssd_ref(x, dt, A, Bm, Cm, D)
    y2, h2 = ssd_scan(x, dt, A, Bm, Cm, D, chunk=case[-1], interpret=True)
    tol = 3e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(y2, np.float32),
                               np.asarray(y0, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h0),
                               rtol=tol, atol=tol)


def test_decode_step_consistency():
    """Running the recurrence one token at a time reproduces the scan."""
    case = (2, 16, 2, 8, 8, 8)
    x, dt, A, Bm, Cm, D = _inputs(case, seed=3)
    y_full, h_full = ssd_ref(x, dt, A, Bm, Cm, D)
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    h = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        y_t, h = ssd_decode_step_ref(h, x[:, t], dt[:, t], A, Bm[:, t],
                                     Cm[:, t], D)
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full),
                               rtol=2e-4, atol=2e-4)


def test_ssd_pallas_grad_matches_ref():
    """`ops.ssd(use_pallas=True)` is differentiable through the chunked
    oracle's VJP: its gradients equal the oracle's."""
    from repro.kernels.ssd_scan.ops import ssd

    case = (2, 64, 2, 16, 8, 16)
    x, dt, A, Bm, Cm, D = _inputs(case, seed=5)

    def loss(use_pallas):
        def f(x, dt, A, Bm, Cm, D):
            y, h = ssd(x, dt, A, Bm, Cm, D, chunk=case[-1],
                       use_pallas=use_pallas, interpret=True)
            return (y ** 2).sum() + h.sum()
        return f

    gp = jax.grad(loss(True), argnums=tuple(range(6)))(x, dt, A, Bm, Cm, D)
    gr = jax.grad(loss(False), argnums=tuple(range(6)))(x, dt, A, Bm, Cm, D)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
