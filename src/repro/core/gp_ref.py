"""NumPy reference GP — the pre-compilation implementation of core/gp.py,
retained verbatim as the property-test oracle for the jitted path
(DESIGN.md §9). Per-candidate NumPy linear algebra, eager JAX autodiff for
the hyperparameter fit; O(n^3) re-solve in `condition_on`.

Not used by the exploration loop: `repro.core.gp.GP` is the production
surrogate. Tests assert the two agree within float32 tolerance.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ehvi import ehvi_2d_ref
from repro.core.pareto import pareto_front


def _matern52(x1, x2, ls, sf):
    d = jnp.sqrt(jnp.maximum(
        jnp.sum(((x1[:, None, :] - x2[None, :, :]) / ls) ** 2, -1), 1e-12))
    s5 = jnp.sqrt(5.0) * d
    return sf * (1 + s5 + 5.0 * d * d / 3.0) * jnp.exp(-s5)


def _nll(raw, X, y):
    ls = jnp.exp(raw["log_ls"])
    sf = jnp.exp(raw["log_sf"])
    noise = jnp.exp(raw["log_noise"]) + 1e-6
    K = _matern52(X, X, ls, sf) + noise * jnp.eye(len(X))
    L = jnp.linalg.cholesky(K)
    a = jax.scipy.linalg.cho_solve((L, True), y)
    return (0.5 * y @ a + jnp.sum(jnp.log(jnp.diag(L)))
            + 0.5 * len(X) * jnp.log(2 * jnp.pi))


@dataclasses.dataclass
class NumpyGP:
    X: np.ndarray
    y: np.ndarray
    params: dict
    mean: float
    std: float
    chol: np.ndarray
    alpha: np.ndarray

    @staticmethod
    def fit(X: np.ndarray, y: np.ndarray, iters: int = 80,
            lr: float = 0.05, seed: int = 0) -> "NumpyGP":
        X = jnp.asarray(X, jnp.float32)
        mean, std = float(np.mean(y)), float(np.std(y) + 1e-9)
        yn = jnp.asarray((np.asarray(y) - mean) / std, jnp.float32)
        d = X.shape[1]
        raw = {"log_ls": jnp.zeros(d) + jnp.log(0.3),
               "log_sf": jnp.asarray(0.0),
               "log_noise": jnp.asarray(jnp.log(0.05))}
        grad_fn = jax.jit(jax.value_and_grad(lambda r: _nll(r, X, yn)))
        m = jax.tree.map(jnp.zeros_like, raw)
        v = jax.tree.map(jnp.zeros_like, raw)
        for t in range(1, iters + 1):
            val, g = grad_fn(raw)
            if not np.isfinite(float(val)):
                break
            m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
            v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
            raw = jax.tree.map(
                lambda p, m_, v_: p - lr * (m_ / (1 - 0.9 ** t))
                / (jnp.sqrt(v_ / (1 - 0.999 ** t)) + 1e-8), raw, m, v)
        ls = jnp.exp(raw["log_ls"])
        sf = jnp.exp(raw["log_sf"])
        noise = jnp.exp(raw["log_noise"]) + 1e-6
        K = _matern52(X, X, ls, sf) + noise * jnp.eye(len(X))
        L = np.asarray(jnp.linalg.cholesky(K))
        alpha = np.asarray(jax.scipy.linalg.cho_solve((jnp.asarray(L), True), yn))
        return NumpyGP(np.asarray(X), np.asarray(yn),
                       jax.tree.map(np.asarray, raw), mean, std, L, alpha)

    def predict(self, Xs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Posterior mean/std at Xs (de-normalized), batched over rows."""
        ls = np.exp(self.params["log_ls"])
        sf = np.exp(self.params["log_sf"])
        Ks = np.asarray(_matern52(jnp.asarray(Xs, jnp.float32),
                                  jnp.asarray(self.X), jnp.asarray(ls),
                                  jnp.asarray(sf)))
        mu = Ks @ self.alpha
        v = np.linalg.solve(self.chol, Ks.T)
        var = np.maximum(sf - np.sum(v * v, axis=0), 1e-10)
        return mu * self.std + self.mean, np.sqrt(var) * self.std

    def condition_on(self, x: np.ndarray, y: float) -> "NumpyGP":
        """Fantasy update: rank-1 Cholesky append + full re-solve."""
        ls = np.exp(self.params["log_ls"])
        sf = float(np.exp(self.params["log_sf"]))
        noise = float(np.exp(self.params["log_noise"])) + 1e-6
        x = np.asarray(x, np.float32).reshape(1, -1)
        k = np.asarray(_matern52(jnp.asarray(x), jnp.asarray(self.X),
                                 jnp.asarray(ls), jnp.asarray(sf)))[0]
        c = np.linalg.solve(self.chol, k)
        d = math.sqrt(max(sf + noise - float(c @ c), 1e-10))
        n = len(self.X)
        L = np.zeros((n + 1, n + 1), dtype=self.chol.dtype)
        L[:n, :n] = self.chol
        L[n, :n] = c
        L[n, n] = d
        X2 = np.concatenate([self.X, x.astype(self.X.dtype)], axis=0)
        yn = (float(y) - self.mean) / self.std
        y2 = np.concatenate([self.y, np.asarray([yn], self.y.dtype)])
        alpha = np.linalg.solve(L.T, np.linalg.solve(L, y2))
        return NumpyGP(X2, y2, self.params, self.mean, self.std, L, alpha)


def acquire_batch_ref(models: Tuple[NumpyGP, NumpyGP], cand_x: np.ndarray,
                      evaluated: np.ndarray, ref: np.ndarray,
                      q: int = 1) -> List[int]:
    """Greedy q-EHVI with rank-1 fantasization — the pre-compilation
    `_acquire_batch` loop, kept as the oracle for the scanned JAX version."""
    g_t, g_p = models
    fantasy_pts = np.asarray(evaluated, float).reshape(-1, 2)
    chosen: List[int] = []
    q = max(1, min(q, len(cand_x)))
    while len(chosen) < q:
        mu_t, s_t = g_t.predict(cand_x)
        mu_p, s_p = g_p.predict(cand_x)
        mu = np.stack([mu_t, mu_p], 1)
        sg = np.stack([s_t, s_p], 1)
        front = (pareto_front(fantasy_pts) if len(fantasy_pts)
                 else np.zeros((0, 2)))
        scores = ehvi_2d_ref(mu, sg, front, np.asarray(ref, float))
        if chosen:
            scores[np.asarray(chosen)] = -np.inf
        j = int(np.argmax(scores))
        chosen.append(j)
        if len(chosen) == q:
            break
        g_t = g_t.condition_on(cand_x[j], float(mu_t[j]))
        g_p = g_p.condition_on(cand_x[j], float(mu_p[j]))
        fantasy_pts = np.concatenate([fantasy_pts, mu[j:j + 1]], axis=0)
    return chosen
