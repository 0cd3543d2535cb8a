# Frozen copy of src/repro/core/gp_ref.py (commit eba02b4), part of the
# benchmark's plain reference: it imports nothing of the program, so a
# change to the program cannot move the yardstick. Edits from the
# original are marked "bench reference:".
"""NumPy reference GP — the pre-compilation implementation of core/gp.py,
retained verbatim as the property-test oracle for the jitted path
(DESIGN.md §9). Per-candidate NumPy linear algebra, eager JAX autodiff for
the hyperparameter fit; O(n^3) re-solve in `condition_on`.

Not used by the exploration loop: the program's jitted GP is the production
surrogate. Tests assert the two agree within float32 tolerance.

bench reference: `low=True` computes the same GP one precision lower, as
the control of the benchmark's check: every input, kernel matrix, factor,
solve, parameter update and posterior is rounded to bfloat16 (products
accumulate in float32 and are rounded, as a bfloat16 matmul does).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np


def _r(x, low):
    """bench reference: x, rounded to bfloat16 when `low` (kept float32)."""
    if not low:
        return x
    if isinstance(x, np.ndarray) or np.isscalar(x):
        return np.asarray(np.asarray(x, np.float32).astype(ml_dtypes.bfloat16),
                          np.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _matern52(x1, x2, ls, sf, low=False):
    d = jnp.sqrt(jnp.maximum(
        jnp.sum(_r(((x1[:, None, :] - x2[None, :, :]) / ls) ** 2, low), -1),
        1e-12))
    s5 = jnp.sqrt(5.0) * _r(d, low)
    return _r(sf * (1 + s5 + 5.0 * d * d / 3.0) * jnp.exp(-s5), low)


def _nll(raw, X, y, low=False):
    ls = jnp.exp(raw["log_ls"])
    sf = jnp.exp(raw["log_sf"])
    noise = jnp.exp(raw["log_noise"]) + 1e-6
    K = _r(_matern52(X, X, ls, sf, low) + noise * jnp.eye(len(X)), low)
    L = _r(jnp.linalg.cholesky(K), low)
    a = _r(jax.scipy.linalg.cho_solve((L, True), y), low)
    return (0.5 * y @ a + jnp.sum(jnp.log(jnp.diag(L)))
            + 0.5 * len(X) * jnp.log(2 * jnp.pi))


_nll_grad = jax.jit(jax.value_and_grad(_nll), static_argnames=("low",))


@dataclasses.dataclass
class NumpyGP:
    X: np.ndarray
    y: np.ndarray
    params: dict
    mean: float
    std: float
    chol: np.ndarray
    alpha: np.ndarray
    low: bool = False

    @staticmethod
    def fit(X: np.ndarray, y: np.ndarray, iters: int = 80,
            lr: float = 0.05, seed: int = 0, low: bool = False
            ) -> "NumpyGP":
        X = _r(jnp.asarray(X, jnp.float32), low)
        mean, std = float(np.mean(y)), float(np.std(y) + 1e-9)
        yn = _r(jnp.asarray((np.asarray(y) - mean) / std, jnp.float32), low)
        d = X.shape[1]
        raw = {"log_ls": jnp.zeros(d) + jnp.log(0.3),
               "log_sf": jnp.asarray(0.0),
               "log_noise": jnp.asarray(jnp.log(0.05))}
        # bench reference: the data are arguments of one jitted function
        # (not constants of a fresh one per fit), so a fit compiles once
        # per training-set size; the arithmetic is unchanged
        def grad_fn(r):
            return _nll_grad(r, X, yn, low=low)
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
            raw = jax.tree.map(lambda p: _r(p, low), raw)
        ls = jnp.exp(raw["log_ls"])
        sf = jnp.exp(raw["log_sf"])
        noise = jnp.exp(raw["log_noise"]) + 1e-6
        K = _r(_matern52(X, X, ls, sf, low) + noise * jnp.eye(len(X)), low)
        L = np.asarray(_r(jnp.linalg.cholesky(K), low))
        alpha = np.asarray(_r(jax.scipy.linalg.cho_solve(
            (jnp.asarray(L), True), yn), low))
        return NumpyGP(np.asarray(X), np.asarray(yn),
                       jax.tree.map(np.asarray, raw), mean, std, L, alpha,
                       low)

    def predict(self, Xs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Posterior mean/std at Xs (de-normalized), batched over rows."""
        ls = np.exp(self.params["log_ls"])
        sf = np.exp(self.params["log_sf"])
        low = self.low
        Ks = np.asarray(_matern52(_r(jnp.asarray(Xs, jnp.float32), low),
                                  jnp.asarray(self.X), jnp.asarray(ls),
                                  jnp.asarray(sf), low))
        mu = _r(Ks @ self.alpha, low)
        v = _r(np.linalg.solve(self.chol, Ks.T), low)
        var = _r(np.maximum(sf - np.sum(v * v, axis=0), 1e-10), low)
        return (_r(mu * self.std + self.mean, low),
                _r(np.sqrt(var) * self.std, low))

    def condition_on(self, x: np.ndarray, y: float) -> "NumpyGP":
        """Fantasy update: rank-1 Cholesky append + full re-solve."""
        ls = np.exp(self.params["log_ls"])
        sf = float(np.exp(self.params["log_sf"]))
        noise = float(np.exp(self.params["log_noise"])) + 1e-6
        low = self.low
        x = _r(np.asarray(x, np.float32).reshape(1, -1), low)
        k = np.asarray(_matern52(jnp.asarray(x), jnp.asarray(self.X),
                                 jnp.asarray(ls), jnp.asarray(sf), low))[0]
        c = _r(np.linalg.solve(self.chol, k), low)
        d = float(_r(math.sqrt(max(sf + noise - float(c @ c), 1e-10)), low))
        n = len(self.X)
        L = np.zeros((n + 1, n + 1), dtype=self.chol.dtype)
        L[:n, :n] = self.chol
        L[n, :n] = c
        L[n, n] = d
        X2 = np.concatenate([self.X, x.astype(self.X.dtype)], axis=0)
        yn = float(_r((float(y) - self.mean) / self.std, low))
        y2 = np.concatenate([self.y, np.asarray([yn], self.y.dtype)])
        alpha = _r(np.linalg.solve(L.T, np.linalg.solve(L, y2)), low)
        return NumpyGP(X2, y2, self.params, self.mean, self.std, L, alpha,
                       low)


def ehvi_scores(models: Tuple[NumpyGP, NumpyGP], cand_x: np.ndarray,
                fantasy_pts: np.ndarray, ref: np.ndarray):
    """(q-EHVI scores of every candidate, posterior means (N, 2)) against
    the front of `fantasy_pts`, as one step of the greedy loop scores
    them."""
    from bench.reference.ehvi import ehvi_2d_ref
    from bench.reference.pareto import pareto_front

    g_t, g_p = models
    mu_t, s_t = g_t.predict(cand_x)
    mu_p, s_p = g_p.predict(cand_x)
    mu = np.stack([mu_t, mu_p], 1)
    sg = np.stack([s_t, s_p], 1)
    front = (pareto_front(fantasy_pts) if len(fantasy_pts)
             else np.zeros((0, 2)))
    scores = ehvi_2d_ref(mu, sg, front, np.asarray(ref, float))
    return _r(scores, g_t.low), mu


def pick_gaps(models: Tuple[NumpyGP, NumpyGP], cand_x: np.ndarray,
              evaluated: np.ndarray, ref: np.ndarray, picks: Sequence[int],
              control: Tuple[NumpyGP, NumpyGP] = None) -> List[float]:
    """Greedy q-EHVI with rank-1 fantasization (the pre-compilation
    `_acquire_batch` loop), led through the given picks.

    bench reference: at each position k the reference scores every
    candidate and reads the share of its best score that `picks[k]` gives
    up (0 when `picks[k]` is its first choice, inf when `picks[k]` was
    picked before); with `control` models it reads that share for the
    control's first choice instead. Both then fantasize `picks[k]` at
    their own posterior mean, as the loop does."""
    cand_x = np.asarray(cand_x)
    sides = [list(models)] + ([list(control)] if control else [])
    fants = [np.asarray(evaluated, float).reshape(-1, 2) for _ in sides]
    gaps: List[float] = []
    for k, j in enumerate(picks):
        scored = [ehvi_scores(m, cand_x, f, ref)
                  for m, f in zip(sides, fants)]
        for s, _ in scored:
            s[np.asarray(picks[:k], int)] = -np.inf
        scores = scored[0][0]
        a = int(np.argmax(scored[-1][0])) if control else int(j)
        best = float(np.max(scores))
        got = float(scores[a])
        gaps.append(0.0 if got >= best else
                    (best - got) / best if best > 0 else math.inf)
        for f, (side, (_, mu)) in enumerate(zip(sides, scored)):
            side[0] = side[0].condition_on(cand_x[j], float(mu[j, 0]))
            side[1] = side[1].condition_on(cand_x[j], float(mu[j, 1]))
            fants[f] = np.concatenate([fants[f], mu[j:j + 1]], axis=0)
    return gaps
