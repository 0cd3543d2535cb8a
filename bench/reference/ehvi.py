# Frozen copy of the NumPy part of src/repro/core/ehvi.py (commit eba02b4),
# part of the benchmark's plain reference: it imports nothing of the
# program, so a change to the program cannot move the yardstick. Only the
# NumPy reference `ehvi_2d_ref` and its helpers are copied; the jitted
# kernel is the program's.
"""Exact 2-objective Expected Hypervolume Improvement (paper §VII).

Derivation (max-max space, independent Gaussian posteriors):

    HVI(y) = integral_{a=ref1}^{y1} (y2 - U(a))^+ da,
    U(a)   = max(ref2, max{v_j : f_j >= a})     (front upper envelope)

so with y1 independent of y2:

    EHVI = sum_strips  [ integral_strip P(y1 > a) da ] x E[(y2 - b_s)^+]

where the front splits obj-1 into strips with constant envelope b_s.
Both factors are closed-form:
    integral_l^u (1 - Phi((a-mu)/s)) da = s [H(z_u) - H(z_l)],
        H(z) = z (1 - Phi(z)) - phi(z)
    E[(Y - b)^+] = (mu - b)(1 - Phi(z_b)) + s phi(z_b),  z_b = (b-mu)/s

Two implementations (DESIGN.md §10): `ehvi_2d` is the jitted JAX kernel
(candidates x strips in one vmapped broadcast, front padded to a pow2
bucket with (+inf, -inf) sentinels that sort past the real points and
collapse to zero-width strips); `ehvi_2d_ref` is the retained NumPy
reference the JAX path is property-tested against.
"""
from __future__ import annotations

import math

import numpy as np
from scipy.special import erf as _erf

_SQRT2 = math.sqrt(2.0)
_SQRT_2PI = math.sqrt(2.0 * math.pi)


# ---------------------------------------------------------------------------
# NumPy reference (property-test oracle)
# ---------------------------------------------------------------------------


def _phi(z):
    return np.exp(-0.5 * z * z) / _SQRT_2PI


def _Phi(z):
    return 0.5 * (1.0 + _erf(np.asarray(z, float) / _SQRT2))


def _H(z):
    return z * (1.0 - _Phi(z)) - _phi(z)


def _strip_mass(l, u, mu, s):
    """integral_l^u P(Y1 > a) da, broadcast over strips x candidates."""
    s = np.maximum(s, 1e-12)
    zl = (l - mu) / s
    hu = np.where(np.isinf(u), 0.0, _H(np.where(np.isinf(u), 0.0,
                                                (u - mu) / s)))
    return s * (hu - _H(zl))


def _excess(b, mu, s):
    """E[(Y2 - b)^+], broadcast over strips x candidates."""
    s = np.maximum(s, 1e-12)
    z = (b - mu) / s
    return (mu - b) * (1.0 - _Phi(z)) + s * _phi(z)


def ehvi_2d_ref(mu: np.ndarray, sigma: np.ndarray, front: np.ndarray,
                ref: np.ndarray) -> np.ndarray:
    """NumPy EHVI for N candidates. mu/sigma (N, 2); front (F, 2) current
    Pareto set (may be empty); ref (2,). Returns (N,)."""
    mu = np.atleast_2d(np.asarray(mu, float))
    sigma = np.atleast_2d(np.asarray(sigma, float))
    ref = np.asarray(ref, float)
    if len(front) == 0:
        edges = np.array([ref[0], np.inf])
        bs = np.array([ref[1]])
    else:
        fr = np.asarray(front, float)
        order = np.argsort(fr[:, 0])            # ascending in obj1
        f = fr[order, 0]
        v = fr[order, 1]
        # envelope per strip: strip k = (edge_k, edge_{k+1}] with
        # edges = [ref1, f_1, ..., f_F, inf); U on (f_k, f_{k+1}] = v_{k+1}
        edges = np.concatenate([[ref[0]], f, [np.inf]])
        # strip k = (edge_k, edge_{k+1}]: level to beat is v_{k+1} (v is
        # descending in obj2 as obj1 ascends -> suffix max = next v);
        # strip F (beyond the front) only needs ref2
        bs = np.maximum(np.concatenate([v, [ref[1]]]), ref[1])
    l = edges[:-1, None]                        # (S, 1)
    u = edges[1:, None]
    b = bs[:, None]
    keep = (u > l)                              # degenerate strips drop out
    mass = np.maximum(_strip_mass(l, u, mu[None, :, 0], sigma[None, :, 0]),
                      0.0)
    exc = np.maximum(_excess(b, mu[None, :, 1], sigma[None, :, 1]), 0.0)
    return np.where(keep, mass * exc, 0.0).sum(axis=0)
