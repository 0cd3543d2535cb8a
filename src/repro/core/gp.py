"""Gaussian-process surrogate in pure JAX (paper §VII: GP surrogates per
fidelity). Matern-5/2 ARD kernel, Cholesky posterior, marginal-likelihood
hyperparameter fit by Adam on (lengthscales, signal, noise).

Compiled hot path (DESIGN.md §10): every GP lives in a static-shape padded
buffer of pow2 capacity B >= n, with a 0/1 row mask. Padded rows are made
exactly inert by the block-diagonal trick — kernel rows/columns zeroed,
unit diagonal, zero targets — so the Cholesky factor of the padded matrix
is [[L, 0], [0, I]] and every downstream solve reproduces the unpadded
result bitwise. That lets:

  * `fit` run the whole Adam loop as one jitted `lax.scan` (one XLA call
    per (B, d, iters) bucket instead of `iters` eager dispatches),
  * `predict` run as a single jitted triangular solve,
  * `condition_on` append an observation as a rank-1 Cholesky update at a
    *traced* index — O(B^2), no re-factorization, no retrace as n grows
    within a bucket.

The pre-compilation NumPy implementation is retained verbatim in
`repro.core.gp_ref.NumpyGP` as the property-test oracle.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

MIN_BUCKET = 8


def bucket_size(n: int, minimum: int = MIN_BUCKET) -> int:
    """Smallest power of two >= max(n, minimum) — the static buffer
    capacities fit/condition_on compile against."""
    return max(minimum, 1 << max(int(n) - 1, 0).bit_length())


@dataclasses.dataclass
class GPParams:
    log_ls: jnp.ndarray        # (d,)
    log_sf: jnp.ndarray        # ()
    log_noise: jnp.ndarray     # ()


def _matern52(x1, x2, ls, sf):
    d = jnp.sqrt(jnp.maximum(
        jnp.sum(((x1[:, None, :] - x2[None, :, :]) / ls) ** 2, -1), 1e-12))
    s5 = jnp.sqrt(5.0) * d
    return sf * (1 + s5 + 5.0 * d * d / 3.0) * jnp.exp(-s5)


def _masked_kernel(X, mask, ls, sf, noise):
    """K over the padded buffer: real block intact, padded rows/cols = e_i
    (unit diagonal) so chol/solves factor through the padding untouched."""
    K = _matern52(X, X, ls, sf) * (mask[:, None] * mask[None, :])
    return K + jnp.diag(jnp.where(mask > 0, noise, 1.0))


def _nll_masked(raw, X, y, mask, n_real):
    ls = jnp.exp(raw["log_ls"])
    sf = jnp.exp(raw["log_sf"])
    noise = jnp.exp(raw["log_noise"]) + 1e-6
    K = _masked_kernel(X, mask, ls, sf, noise)
    L = jnp.linalg.cholesky(K)
    a = jax.scipy.linalg.cho_solve((L, True), y)
    return (0.5 * y @ a + jnp.sum(jnp.log(jnp.diag(L)))
            + 0.5 * n_real * jnp.log(2 * jnp.pi))


def _adam_scan(X, y, mask, n_real, lr, iters):
    """The reference Adam loop as a lax.scan. The eager loop `break`s (and
    keeps the pre-update params) the first time the NLL goes non-finite;
    here a `frozen` flag makes every subsequent update a no-op, which lands
    on the same parameters."""
    d = X.shape[1]
    raw = {"log_ls": jnp.zeros(d, X.dtype) + jnp.log(0.3),
           "log_sf": jnp.zeros((), X.dtype),
           "log_noise": jnp.zeros((), X.dtype) + jnp.log(0.05)}
    grad_fn = jax.value_and_grad(lambda r: _nll_masked(r, X, y, mask, n_real))
    m0 = jax.tree.map(jnp.zeros_like, raw)
    v0 = jax.tree.map(jnp.zeros_like, raw)

    def step(carry, t):
        raw, m, v, frozen = carry
        val, g = grad_fn(raw)
        frozen = frozen | ~jnp.isfinite(val)
        m2 = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v2 = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        raw2 = jax.tree.map(
            lambda p, m_, v_: p - lr * (m_ / (1 - 0.9 ** t))
            / (jnp.sqrt(v_ / (1 - 0.999 ** t)) + 1e-8), raw, m2, v2)
        pick = lambda new, old: jax.tree.map(
            lambda a, b: jnp.where(frozen, b, a), new, old)
        return (pick(raw2, raw), pick(m2, m), pick(v2, v), frozen), None

    ts = jnp.arange(1, iters + 1, dtype=X.dtype)
    (raw, _, _, _), _ = jax.lax.scan(step, (raw, m0, v0, jnp.array(False)), ts)
    return raw


def _posterior(raw, X, y, mask):
    ls = jnp.exp(raw["log_ls"])
    sf = jnp.exp(raw["log_sf"])
    noise = jnp.exp(raw["log_noise"]) + 1e-6
    K = _masked_kernel(X, mask, ls, sf, noise)
    L = jnp.linalg.cholesky(K)
    alpha = jax.scipy.linalg.cho_solve((L, True), y)
    return L, alpha


@partial(jax.jit, static_argnames=("iters",))
def _fit_one_jit(X, y, mask, n_real, lr, iters):
    raw = _adam_scan(X, y, mask, n_real, lr, iters)
    L, alpha = _posterior(raw, X, y, mask)
    return raw, L, alpha


@partial(jax.jit, static_argnames=("iters",))
def _fit_pair_jit(X, Y2, mask, n_real, lr, iters):
    """Both objective GPs share X: vmap the whole fit over the target axis
    so one XLA program refits the (throughput, power) pair."""
    def one(y):
        raw = _adam_scan(X, y, mask, n_real, lr, iters)
        L, alpha = _posterior(raw, X, y, mask)
        return raw, L, alpha
    return jax.vmap(one)(Y2)


def _sum_rows(x):
    """Sum over the buffer axis (axis 0) one row at a time, in index order.
    Padded rows are zero and add exact zeros, so the result does not depend
    on the buffer capacity; a vectorized reduction or dot associates by its
    length, which on XLA:CPU moves the last bit between buckets."""
    return jax.lax.fori_loop(0, x.shape[0], lambda j, acc: acc + x[j],
                             jnp.zeros(x.shape[1:], x.dtype))


@jax.jit
def _predict_jit(Xs, X, mask, L, alpha, log_ls, log_sf, mean, std):
    ls = jnp.exp(log_ls)
    sf = jnp.exp(log_sf)
    Ks = _matern52(Xs, X, ls, sf) * mask[None, :]
    mu = _sum_rows(Ks.T * alpha[:, None])
    v = jax.scipy.linalg.solve_triangular(L, Ks.T, lower=True)
    var = jnp.maximum(sf - _sum_rows(v * v), 1e-10)
    return mu * std + mean, jnp.sqrt(var) * std


@jax.jit
def _rank1_jit(X, y, mask, L, log_ls, log_sf, log_noise, n, x_new, y_norm):
    """Append (x_new, y_norm) at traced row n of the padded buffer: one
    masked kernel row, one triangular solve for the new Cholesky row, two
    O(B^2) triangular solves for alpha. Row n of L is e_n before the
    update (padding identity), so overwriting it in place is exact."""
    ls = jnp.exp(log_ls)
    sf = jnp.exp(log_sf)
    noise = jnp.exp(log_noise) + 1e-6
    k = _matern52(x_new[None, :], X, ls, sf)[0] * mask
    c = jax.scipy.linalg.solve_triangular(L, k, lower=True)
    dd = jnp.sqrt(jnp.maximum(sf + noise - c @ c, 1e-10))
    L2 = L.at[n, :].set(c).at[n, n].set(dd)
    X2 = X.at[n, :].set(x_new)
    y2 = y.at[n].set(y_norm)
    mask2 = mask.at[n].set(1.0)
    alpha2 = jax.scipy.linalg.cho_solve((L2, True), y2)
    return X2, y2, mask2, L2, alpha2


@dataclasses.dataclass
class GP:
    """Fitted GP over a padded buffer of capacity B (pow2 bucket >= n).

    `X`/`y`(normalized)/`chol`/`alpha` are (B, ...) device arrays; `mask`
    flags the n real rows. `params` is a plain host dict shared (by object
    identity) across `condition_on` fantasies — no hyperparameter refit.
    """
    X: jnp.ndarray             # (B, d)
    y: jnp.ndarray             # (B,) normalized targets, 0 on padding
    params: dict
    mean: float
    std: float
    chol: jnp.ndarray          # (B, B) lower; identity on padded rows
    alpha: jnp.ndarray         # (B,)
    mask: jnp.ndarray = None   # (B,) 1.0 = real row
    n: int = 0                 # real observation count

    @staticmethod
    def _pad(X: np.ndarray, y_norm: np.ndarray, capacity: int, dtype
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        n, d = X.shape
        Xp = np.zeros((capacity, d), dtype)
        Xp[:n] = X
        yp = np.zeros(capacity, dtype)
        yp[:n] = y_norm
        mask = np.zeros(capacity, dtype)
        mask[:n] = 1.0
        return jnp.asarray(Xp), jnp.asarray(yp), jnp.asarray(mask)

    @staticmethod
    def fit(X: np.ndarray, y: np.ndarray, iters: int = 80,
            lr: float = 0.05, seed: int = 0,
            dtype: np.dtype = np.float32) -> "GP":
        """One jitted XLA program per (bucket, d, iters) shape: Adam over
        the masked marginal likelihood via lax.scan, then the posterior
        factorization. `dtype` is threaded through the whole fit (float64
        needs JAX_ENABLE_X64/ jax.config x64 to take effect)."""
        X = np.asarray(X, dtype)
        mean, std = float(np.mean(y)), float(np.std(y) + 1e-9)
        yn = ((np.asarray(y) - mean) / std).astype(dtype)
        Xp, yp, mask = GP._pad(X, yn, bucket_size(len(X)), dtype)
        raw, L, alpha = _fit_one_jit(Xp, yp, mask, jnp.asarray(len(X), dtype),
                                     jnp.asarray(lr, dtype), iters)
        return GP(Xp, yp, jax.tree.map(np.asarray, raw), mean, std, L, alpha,
                  mask, len(X))

    @staticmethod
    def fit_pair(X: np.ndarray, ys: Tuple[np.ndarray, np.ndarray],
                 iters: int = 80, lr: float = 0.05,
                 dtype: np.dtype = np.float32) -> Tuple["GP", "GP"]:
        """Fit two GPs sharing the same inputs (the per-objective surrogate
        pair) in a single vmapped XLA call."""
        X = np.asarray(X, dtype)
        stats = [(float(np.mean(y)), float(np.std(y) + 1e-9)) for y in ys]
        Y2 = np.stack([((np.asarray(y) - m) / s).astype(dtype)
                       for y, (m, s) in zip(ys, stats)])
        B = bucket_size(len(X))
        Xp, _, mask = GP._pad(X, Y2[0], B, dtype)
        Yp = np.zeros((2, B), dtype)
        Yp[:, :len(X)] = Y2
        raw, L, alpha = _fit_pair_jit(Xp, jnp.asarray(Yp), mask,
                                      jnp.asarray(len(X), dtype),
                                      jnp.asarray(lr, dtype), iters)
        out = []
        for i, (m, s) in enumerate(stats):
            params = {k: np.asarray(v[i]) for k, v in raw.items()}
            out.append(GP(Xp, jnp.asarray(Yp[i]), params, m, s, L[i],
                          alpha[i], mask, len(X)))
        return out[0], out[1]

    @property
    def capacity(self) -> int:
        return self.X.shape[0]

    @property
    def dtype(self):
        return self.X.dtype

    def X_real(self) -> np.ndarray:
        return np.asarray(self.X[:self.n])

    def y_real(self) -> np.ndarray:
        return np.asarray(self.y[:self.n])

    def with_capacity(self, capacity: int) -> "GP":
        """Re-pad into a larger buffer. The padded kernel is block-diagonal
        [[K, 0], [0, I]], so the grown Cholesky/alpha are just the old ones
        with identity/zero padding — no refactorization."""
        B0 = self.capacity
        if capacity <= B0:
            return self
        d = self.X.shape[1]
        X2 = np.zeros((capacity, d), self.dtype)
        X2[:B0] = np.asarray(self.X)
        y2 = np.zeros(capacity, self.dtype)
        y2[:B0] = np.asarray(self.y)
        m2 = np.zeros(capacity, self.dtype)
        m2[:B0] = np.asarray(self.mask)
        L2 = np.eye(capacity, dtype=self.dtype)
        L2[:B0, :B0] = np.asarray(self.chol)
        a2 = np.zeros(capacity, self.dtype)
        a2[:B0] = np.asarray(self.alpha)
        return GP(jnp.asarray(X2), jnp.asarray(y2), self.params, self.mean,
                  self.std, jnp.asarray(L2), jnp.asarray(a2),
                  jnp.asarray(m2), self.n)

    def predict(self, Xs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Posterior mean/std at Xs (de-normalized), one jitted call."""
        mu, sd = _predict_jit(
            jnp.asarray(np.asarray(Xs, self.dtype)), self.X, self.mask,
            self.chol, self.alpha, jnp.asarray(self.params["log_ls"]),
            jnp.asarray(self.params["log_sf"]),
            jnp.asarray(self.mean, self.dtype),
            jnp.asarray(self.std, self.dtype))
        return np.asarray(mu, np.float64), np.asarray(sd, np.float64)

    def condition_on(self, x: np.ndarray, y: float) -> "GP":
        """Posterior GP after observing (x, y) — a rank-1 Cholesky append
        at a traced index, no hyperparameter refit, no retrace while the
        observation count stays within the capacity bucket. This is the
        'fantasy' update used by greedy q-EHVI (DESIGN.md §5)."""
        g = self.with_capacity(bucket_size(self.n + 1))
        yn = (float(y) - g.mean) / g.std
        X2, y2, m2, L2, a2 = _rank1_jit(
            g.X, g.y, g.mask, g.chol, jnp.asarray(g.params["log_ls"]),
            jnp.asarray(g.params["log_sf"]),
            jnp.asarray(g.params["log_noise"]), g.n,
            jnp.asarray(np.asarray(x, g.dtype).reshape(-1)),
            jnp.asarray(yn, g.dtype))
        return GP(X2, y2, g.params, g.mean, g.std, L2, a2, m2, g.n + 1)
