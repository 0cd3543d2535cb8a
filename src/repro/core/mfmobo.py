"""Multi-Fidelity Multi-Objective Bayesian Optimization — paper Algorithm 1.

Two evaluation fidelities (f1 = analytical, f0 = GNN-based — paper §VII
notes CA simulation is kept out of the loop for cost), GP surrogates per
(fidelity x objective), EHVI acquisition with hypervolume reference
(throughput 0, peak power). The schedule:

    evaluations [0, N1-d1):           evaluate f1, acquire with M1
    evaluations [N1-d1, N1-d1+k):     evaluate f0, acquire with M1 (handover)
    evaluations [N1-d1+k, ...):       evaluate f0, acquire with M0

Each iteration proposes a batch of q candidates by greedy q-EHVI with
fantasized observations (DESIGN.md §5): pick the EHVI argmax, condition the
GPs on its posterior mean (GP.condition_on), extend the fantasy front, and
repeat — then evaluate the whole batch in one call. With q=1 the loop is
the paper's serial Algorithm 1.

This module keeps the algorithmic primitives (Trace, GP fitting in the
log-objective space, greedy q-EHVI acquisition, valid-candidate sampling).
The loop itself, with the Fig. 8 baselines (random search and
single-fidelity MOBO), lives one layer up in
`repro.explore.runner.ExplorationLoop` — a resumable state machine that
campaigns (repro.explore.campaign) checkpoint and resume:

    ExplorationLoop(LoopConfig(strategy="mfmobo", N0=..., N1=..., q=...),
                    f0, f1=f1, on_handover=...).run()   # -> Trace
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry as tm
from repro.core import eval_compiled
from repro.core.compiler import (Strategy, _strategy_grid,
                                 strategy_memory_need)
from repro.core.design_space import (DIMS, DesignBatch, JointDesign,
                                     WSCDesign, decode_batch,
                                     decode_joint_batch, sample,
                                     sample_joint)
from repro.core.ehvi import ehvi_padded
from repro.core.evaluator import wafers_for_budget
from repro.core.gp import GP, _predict_jit, _rank1_jit, bucket_size
from repro.core.pareto import pareto_front, to_max_space
from repro.core.validator import validate_batch, validate_joint_batch


@dataclasses.dataclass
class Trace:
    xs: List[np.ndarray]
    designs: List[WSCDesign]
    ys: List[Tuple[float, float]]         # (throughput, power)
    hv: List[float]                       # hypervolume after each evaluation
    n_evals: int = 0                      # total evals incl. f1-only points
    # per-fidelity-stage eval-cache traffic ({"f0"/"f1": {hits, misses,
    # entries_added}}), recorded by the exploration loop so the cost of the
    # fidelity handover is visible in campaign artifacts / BENCH_dse.json
    stage_cache: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)

    def points_max(self) -> np.ndarray:
        t = np.array([y[0] for y in self.ys])
        p = np.array([y[1] for y in self.ys])
        return to_max_space(t, p)

    def pareto(self) -> np.ndarray:
        return pareto_front(self.points_max())

    def cache_hit_rates(self) -> Dict[str, float]:
        out = {}
        for stage, sc in self.stage_cache.items():
            n = sc.get("hits", 0) + sc.get("misses", 0)
            out[stage] = sc.get("hits", 0) / n if n else 0.0
        return out


def _valid_candidates(rng: np.random.Generator, n: int,
                      max_tries: int = 8) -> Tuple[np.ndarray, List[WSCDesign]]:
    """Sample until n validator-approved candidates are collected, topping
    up with fresh batches for up to `max_tries` rounds. Each round decodes
    and validates the whole draw at once (`validate_batch`); the rng stream,
    accepted set, and ordering are identical to the retired per-design
    loop. A design space whose acceptance rate is too low to fill the
    request raises — with the observed rate — instead of silently handing
    the acquisition a short (or empty) candidate set."""
    xs, ds = [], []
    n_drawn = 0
    with tm.span("candidates", items=n):
        for _ in range(max_tries):
            us = sample(rng, n)
            n_drawn += len(us)
            with tm.span("candidates.validate", items=len(us)):
                rs = validate_batch(decode_batch(us))
            for u, r in zip(us, rs):
                if r.ok:
                    xs.append(u)
                    ds.append(r.design)
                if len(xs) >= n:
                    return np.array(xs), ds
    rate = len(xs) / max(n_drawn, 1)
    raise RuntimeError(
        f"design-space sampling produced only {len(xs)}/{n} valid "
        f"candidates after {max_tries} rounds of {n} draws (acceptance "
        f"rate {rate:.1%}) — the validator is rejecting (nearly) "
        "everything; loosen the design-space bounds or raise max_tries")


def _grid_seed_strategies(designs, wl, space):
    """Heuristic strategy seeds for joint sampling: each design's
    first-feasible row of the sorted strategy grid (what grid-mode
    evaluation would try first), as (N, 7) encoded strategy columns plus a
    found-mask. Vectorized over the cached `_strategy_grid`, at the same
    area-matched system size the validator gates on (`wafers_for_budget`
    per design). Each seed is then re-checked under the v2 memory model
    (`strategy_memory_need`); a training seed that only fits with
    activation recompute carries recompute=True into the search — the
    validator would reject the plain row with "strategy_memory", so the
    fallback keeps the seed alive and hands q-EHVI a live recompute
    signal."""
    g = _strategy_grid(wl)
    db = DesignBatch.from_designs(list(designs))
    nw = np.array([wafers_for_budget(d, wl) for d in designs], np.float64)
    tc = db.total_cores.astype(np.float64) * nw
    mem = (db.buffer_kb * 1024.0 * db.total_cores
           + db.dram_gb_per_reticle * 1e9 * db.n_reticles) * nw
    o = g["order"]
    m = ((g["chunks"][None, o] * g["tp"][None, o] <= tc[:, None])
         & (g["tp"][None, o] <= tc[:, None])
         & (g["need"][None, o] <= mem[:, None]))
    found = m.any(axis=1)
    idx = o[np.argmax(m, axis=1)]
    ep = g["ep"][idx] if "ep" in g else np.ones_like(idx)
    need_plain = strategy_memory_need(wl, g["tp"][idx], g["pp"][idx],
                                      g["dp"][idx], g["mb"][idx], ep=ep)
    need_rc = strategy_memory_need(wl, g["tp"][idx], g["pp"][idx],
                                   g["dp"][idx], g["mb"][idx], ep=ep,
                                   recompute=True)
    rc = ((wl.phase == "train") & (need_plain > mem) & (need_rc <= mem))
    enc = np.zeros((len(designs), space.n_dims))
    for i in np.flatnonzero(found):
        s = Strategy(int(g["tp"][idx[i]]), int(g["pp"][idx[i]]),
                     int(g["dp"][idx[i]]), int(g["mb"][idx[i]]),
                     ep=int(ep[i]), recompute=bool(rc[i]))
        enc[i] = space.encode_strategy(s)
    return enc, found


def _valid_candidates_joint(rng: np.random.Generator, n: int, space, wl,
                            max_tries: int = 8
                            ) -> Tuple[np.ndarray, List]:
    """Joint-mode `_valid_candidates`: sample (13 + 7)-dim joint points,
    seed every other draw's strategy columns from the grid heuristic
    (`enumerate_strategies` demoted to seeding — the sorted grid's first
    feasible row), validate architecture + strategy together
    (`validate_joint_batch`, `repro.dist` oracle included), and return
    (encoded points, JointDesigns with spares resolved)."""
    nd = len(DIMS)
    xs, pts = [], []
    n_drawn = 0
    with tm.span("candidates", items=n):
        for _ in range(max_tries):
            us = sample_joint(rng, n, space)
            n_drawn += len(us)
            with tm.span("candidates.validate", items=len(us)):
                batch = decode_joint_batch(us, space)
                seeded = list(range(0, len(batch), 2))
                enc, found = _grid_seed_strategies(
                    [batch[i].design for i in seeded], wl, space)
                for j, i in enumerate(seeded):
                    if found[j]:
                        us[i, nd:] = enc[j]
                        batch[i] = JointDesign(
                            batch[i].design, space.decode_strategy(us[i, nd:]))
                rs = validate_joint_batch(batch, wl)
            for u, p, r in zip(us, batch, rs):
                if r.ok:
                    xs.append(u)
                    pts.append(JointDesign(r.design, p.strategy))
                if len(xs) >= n:
                    return np.array(xs), pts
    rate = len(xs) / max(n_drawn, 1)
    raise RuntimeError(
        f"joint-space sampling produced only {len(xs)}/{n} valid "
        f"candidates after {max_tries} rounds of {n} draws (acceptance "
        f"rate {rate:.1%}) — loosen the strategy-space bounds or raise "
        "max_tries")


def _fit_models(X: np.ndarray, Y: np.ndarray) -> Tuple[GP, GP]:
    # one vmapped XLA call refits both objective surrogates on the shared X
    with tm.span("propose.fit", items=len(X)):
        return GP.fit_pair(X, (np.log1p(np.maximum(Y[:, 0], 0.0)),
                               -np.log(np.maximum(Y[:, 1], 1.0))))


@partial(jax.jit, static_argnames=("q",))
@jax.named_scope("propose.acquire")
def _acquire_scan_jit(X, mask, n0, yt, Lt, at, ls_t, sf_t, noise_t, mt, st,
                      yp, Lp, ap, ls_p, sf_p, noise_p, mp, sp,
                      cand, fant, fant_mask, nf0, ref, q):
    """The whole greedy q-EHVI loop as one XLA program: lax.scan over the q
    picks, each step = batched posterior predict for both objectives +
    padded EHVI over the fantasy front + argmax + rank-1 fantasization of
    both GPs in the shared padded buffer."""

    def step(carry, _):
        (X, mask, n, yt, Lt, at, yp, Lp, ap, fant, fmask, nf, chosen) = carry
        mu_t, sd_t = _predict_jit(cand, X, mask, Lt, at, ls_t, sf_t, mt, st)
        mu_p, sd_p = _predict_jit(cand, X, mask, Lp, ap, ls_p, sf_p, mp, sp)
        mu = jnp.stack([mu_t, mu_p], 1)
        sg = jnp.stack([sd_t, sd_p], 1)
        scores = ehvi_padded(mu, sg, fant, fmask, ref)
        scores = jnp.where(chosen, -jnp.inf, scores)
        j = jnp.argmax(scores)
        chosen = chosen.at[j].set(True)
        # fantasize the observation at the posterior mean and condition
        X2, yt2, mask2, Lt2, at2 = _rank1_jit(
            X, yt, mask, Lt, ls_t, sf_t, noise_t, n, cand[j],
            (mu_t[j] - mt) / st)
        _, yp2, _, Lp2, ap2 = _rank1_jit(
            X, yp, mask, Lp, ls_p, sf_p, noise_p, n, cand[j],
            (mu_p[j] - mp) / sp)
        fant = fant.at[nf].set(mu[j])
        fmask = fmask.at[nf].set(1.0)
        return (X2, mask2, n + 1, yt2, Lt2, at2, yp2, Lp2, ap2,
                fant, fmask, nf + 1, chosen), j

    chosen0 = jnp.zeros(cand.shape[0], bool)
    carry0 = (X, mask, n0, yt, Lt, at, yp, Lp, ap, fant, fant_mask, nf0,
              chosen0)
    _, js = jax.lax.scan(step, carry0, None, length=q)
    return js


def _acquire_scan(models: Tuple[GP, GP], cand_x: np.ndarray,
                  evaluated: np.ndarray, ref: np.ndarray, q: int):
    """Pad the proposal's inputs to their buckets and dispatch
    `_acquire_scan_jit`; returns its device index vector."""
    g_t, g_p = models
    if g_t.n != g_p.n:
        raise ValueError("objective GPs must share the training set")
    q = max(1, min(q, len(cand_x)))
    # the scan length is bucketed too: greedy picks are a prefix-stable
    # sequence, so running a padded qpad-step scan and keeping the first q
    # indices returns exactly the q-step result while q_eff taking every
    # value in 1..q (budget/boundary clamping) reuses ONE compiled program
    qpad = bucket_size(q, minimum=4)
    B = bucket_size(g_t.n + qpad)       # room for qpad rank-1 appends
    g_t = g_t.with_capacity(B)
    g_p = g_p.with_capacity(B)
    dt = np.float32
    fantasy = np.asarray(evaluated, float).reshape(-1, 2)
    Bf = bucket_size(len(fantasy) + qpad, minimum=4)
    fant = np.zeros((Bf, 2), dt)
    fant[:len(fantasy)] = fantasy
    fmask = np.zeros(Bf, dt)
    fmask[:len(fantasy)] = 1.0
    p_t, p_p = g_t.params, g_p.params
    return _acquire_scan_jit(
        g_t.X, g_t.mask, jnp.asarray(g_t.n),
        g_t.y, g_t.chol, g_t.alpha, jnp.asarray(p_t["log_ls"]),
        jnp.asarray(p_t["log_sf"]), jnp.asarray(p_t["log_noise"]),
        jnp.asarray(g_t.mean, dt), jnp.asarray(g_t.std, dt),
        g_p.y, g_p.chol, g_p.alpha, jnp.asarray(p_p["log_ls"]),
        jnp.asarray(p_p["log_sf"]), jnp.asarray(p_p["log_noise"]),
        jnp.asarray(g_p.mean, dt), jnp.asarray(g_p.std, dt),
        jnp.asarray(np.asarray(cand_x, dt)), jnp.asarray(fant),
        jnp.asarray(fmask), jnp.asarray(len(fantasy)),
        jnp.asarray(np.asarray(ref, dt)), qpad)


def _acquire_batch_device(models: Tuple[GP, GP], cand_x: np.ndarray,
                          evaluated: np.ndarray, ref: np.ndarray,
                          q: int = 1):
    """`_acquire_batch` without the host sync: returns the padded device
    index vector straight from `_acquire_scan_jit` (the first q entries
    are the picks) for the fused analytical evaluator
    (`repro.core.evaluator.evaluate_pool_fused`), which reads it once."""
    with tm.span("propose.acquire", items=q):
        return _acquire_scan(models, cand_x, evaluated, ref, q)


def _acquire_batch(models: Tuple[GP, GP], cand_x: np.ndarray,
                   evaluated: np.ndarray, ref: np.ndarray,
                   q: int = 1) -> List[int]:
    """Greedy q-EHVI with fantasized observations. Returns q distinct
    candidate indices; q=1 reduces exactly to the scalar EHVI argmax.
    The NumPy reference loop lives in `repro.core.gp_ref.acquire_batch_ref`
    (property-tested equivalent)."""
    q = max(1, min(q, len(cand_x)))
    with tm.span("propose.acquire", items=q):
        js = tm.to_host(_acquire_scan(models, cand_x, evaluated, ref, q),
                        "picks")
    return [int(j) for j in js[:q]]


def _acquire(models: Tuple[GP, GP], cand_x: np.ndarray,
             evaluated: np.ndarray, ref: np.ndarray) -> int:
    return _acquire_batch(models, cand_x, evaluated, ref, q=1)[0]


# shape buckets already pre-compiled in THIS process — campaign fleets run
# many campaigns per worker, so repeated `warm_optimizer_kernels` calls
# (fig8 used to pay one per campaign grid) skip buckets whose programs XLA
# already holds. Keyed by everything the compiled shapes depend on.
_WARMED_BUCKETS: set = set()


def warm_optimizer_kernels(n_obs_max: int, n_candidates: int = 256,
                           q: int = 1, dim: Optional[int] = None,
                           force: bool = False,
                           workload=None, n_designs_max: int = 0,
                           max_strategies: int = 24) -> int:
    """Pre-compile the jitted optimizer programs for every capacity bucket
    a campaign of up to `n_obs_max` observations touches (GP pair fit +
    scanned q-EHVI acquire, one compile per pow2 bucket). Compilation is a
    one-time ~1s/bucket cost; calling this before a timed region keeps it
    out of measured proposal walls. Warm-ups are memoized per process:
    buckets already compiled this process are skipped (`force=True`
    re-runs them), so per-campaign calls in a grid or a fleet worker cost
    nothing after the first. Returns the number of buckets *newly* warmed.
    Fantasy-front buffers track the training buffer in campaign use
    (evaluated count == observation count), so warming the training buckets
    covers the acquire shapes too.

    With `workload` set, the compiled analytical evaluator programs warm
    alongside the optimizer ones (`eval_compiled.warm_evaluator_kernels`,
    same per-(bucket, workload-shape) memoization and `force=` semantics):
    the design-axis buckets up to `n_designs_max` (defaults to the q
    bucket) plus the fused gather program for the `n_candidates` pool."""
    d = len(DIMS) if dim is None else dim
    rng = np.random.default_rng(0)
    qpad = bucket_size(max(1, min(q, n_candidates)), minimum=4)
    warmed = 0
    seen = set()
    for n in range(2, max(int(n_obs_max), 2) + 1):
        B = bucket_size(n + qpad)
        key = (B, n_candidates, qpad, d)
        if B in seen or (key in _WARMED_BUCKETS and not force):
            continue
        seen.add(B)
        _WARMED_BUCKETS.add(key)
        warmed += 1
        nn = max(2, B - qpad)           # largest n landing in this bucket
        X = rng.random((nn, d))
        Y = np.stack([1e3 * (1.0 + X[:, 0]), 1e3 * (2.0 - X[:, 1])], 1)
        models = _fit_models(X, Y)
        ev = obj_space([tuple(y) for y in Y])
        cand = rng.random((n_candidates, d))
        _acquire_batch(models, cand, ev, hv_ref(1e4), q=q)
    if workload is not None:
        warmed += eval_compiled.warm_evaluator_kernels(
            workload, n_designs_max=max(int(n_designs_max), qpad),
            max_strategies=max_strategies, pool_sizes=(n_candidates,),
            force=force)
    return warmed


def obj_space(ys: List[Tuple[float, float]]) -> np.ndarray:
    """(log throughput, -log power) — the space GPs and HV operate in."""
    t = np.log1p(np.maximum(np.array([y[0] for y in ys]), 0.0))
    p = -np.log(np.maximum(np.array([y[1] for y in ys]), 1.0))
    return np.stack([t, p], 1)


def hv_ref(peak_power: float) -> np.ndarray:
    """Hypervolume reference point (throughput 0, peak power)."""
    return np.array([0.0, -np.log(max(peak_power, 1.0))])

