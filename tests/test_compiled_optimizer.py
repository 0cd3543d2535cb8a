"""Property tests for the compiled MFMOBO hot path (DESIGN.md §10).

Every jitted/vectorized program is checked against the retained NumPy
reference it replaced:

    GP.fit / predict / condition_on   vs  gp_ref.NumpyGP (eager loop)
    ehvi_2d (padded jit kernel)       vs  ehvi_2d_ref (strip integration)
    _acquire_batch (lax.scan greedy)  vs  gp_ref.acquire_batch_ref
    validate_batch                    vs  scalar validate (exact)
    row_redundancy_yield (exact DP)   vs  brute force + MC oracle
    min_spares_for_target_batch       vs  scalar min_spares_for_target
    reticle_yields_batch (unpadded)   vs  scalar reticle_yield

plus checkpoint-purity regressions: a LoopState pickle must never contain
device arrays, and re-running the compiled acquire on warmed buckets must
not retrace.
"""
from __future__ import annotations

import itertools
import pickle

import numpy as np
import pytest

import repro.core.mfmobo as M
from repro import telemetry as tm
from repro.core.design_space import DIMS, decode_batch, sample
from repro.core.ehvi import ehvi_2d, ehvi_2d_ref
from repro.core.gp import GP, bucket_size
from repro.core.gp_ref import NumpyGP, acquire_batch_ref
from repro.core.pareto import pareto_front
from repro.core.validator import validate, validate_batch
from repro.core.yield_model import (mc_row_redundancy_yield,
                                    min_spares_for_target,
                                    min_spares_for_target_batch,
                                    reticle_yield, reticle_yields_batch,
                                    row_redundancy_yield)


def _toy(rng, n, d=4):
    X = rng.random((n, d))
    y = np.sin(3.0 * X[:, 0]) + 0.5 * X[:, 1] + 0.05 * rng.standard_normal(n)
    return X, y


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------


def test_bucket_size_pow2_and_monotone():
    assert [bucket_size(n) for n in (1, 2, 8, 9, 16, 17, 100)] == \
        [8, 8, 8, 16, 16, 32, 128]
    assert bucket_size(3, minimum=4) == 4
    sizes = [bucket_size(n) for n in range(1, 200)]
    assert all(b >= a for a, b in zip(sizes, sizes[1:]))
    assert all(s & (s - 1) == 0 for s in sizes)


# ---------------------------------------------------------------------------
# GP vs NumPy reference
# ---------------------------------------------------------------------------


def test_gp_fit_predict_matches_reference():
    rng = np.random.default_rng(0)
    for n in (3, 8, 13):
        X, y = _toy(rng, n)
        Xs = rng.random((17, X.shape[1]))
        gp = GP.fit(X, y)
        ref = NumpyGP.fit(X, y)
        mu, sd = gp.predict(Xs)
        mu_r, sd_r = ref.predict(Xs)
        # fp32 padded-jit fit vs fp64 eager fit: same optimizer trajectory
        np.testing.assert_allclose(mu, mu_r, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(sd, sd_r, rtol=2e-3, atol=2e-3)


def test_gp_fit_pair_matches_separate_fits():
    rng = np.random.default_rng(1)
    X, y1 = _toy(rng, 9)
    y2 = -y1 + 0.1 * rng.standard_normal(len(y1))
    g1, g2 = GP.fit_pair(X, (y1, y2))
    s1, s2 = GP.fit(X, y1), GP.fit(X, y2)
    Xs = rng.random((11, X.shape[1]))
    for g, s in ((g1, s1), (g2, s2)):
        np.testing.assert_allclose(g.predict(Xs)[0], s.predict(Xs)[0],
                                   rtol=1e-5, atol=1e-5)


def test_gp_condition_on_matches_reference():
    rng = np.random.default_rng(2)
    X, y = _toy(rng, 7)
    Xs = rng.random((9, X.shape[1]))
    gp, ref = GP.fit(X, y), NumpyGP.fit(X, y)
    # chain several rank-1 updates across a bucket boundary (7 -> 12 obs)
    for k in range(5):
        x_new = rng.random(X.shape[1])
        y_new = float(np.sin(3.0 * x_new[0]))
        gp = gp.condition_on(x_new, y_new)
        ref = ref.condition_on(x_new, y_new)
        mu, sd = gp.predict(Xs)
        mu_r, sd_r = ref.predict(Xs)
        np.testing.assert_allclose(mu, mu_r, rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(sd, sd_r, rtol=5e-3, atol=5e-3)
    assert gp.n == 12


def test_gp_dtype_argument_controls_buffers():
    rng = np.random.default_rng(3)
    X, y = _toy(rng, 6)
    assert GP.fit(X, y).dtype == np.float32
    assert GP.fit(X, y, dtype=np.float32).X.dtype == np.float32


def test_gp_with_capacity_is_exact():
    rng = np.random.default_rng(4)
    X, y = _toy(rng, 6)
    gp = GP.fit(X, y)
    big = gp.with_capacity(32)
    Xs = rng.random((5, X.shape[1]))
    np.testing.assert_array_equal(np.asarray(gp.predict(Xs)),
                                  np.asarray(big.predict(Xs)))


# ---------------------------------------------------------------------------
# EHVI vs NumPy reference
# ---------------------------------------------------------------------------


def test_ehvi_matches_reference_random_fronts():
    rng = np.random.default_rng(5)
    for trial in range(10):
        n, f = int(rng.integers(1, 40)), int(rng.integers(0, 9))
        mu = rng.normal(0, 2, (n, 2))
        sg = rng.uniform(0.05, 1.5, (n, 2))
        front = rng.normal(0, 2, (f, 2))
        ref = np.array([-3.0, -3.0])
        got = ehvi_2d(mu, sg, front, ref)
        # the jit kernel Pareto-filters internally; the reference expects a
        # clean front
        want = ehvi_2d_ref(mu, sg, pareto_front(front) if f else front, ref)
        scale = np.maximum(np.abs(want), 1.0)
        np.testing.assert_allclose(got / scale, want / scale, atol=5e-5)
        assert (got >= 0).all()


def test_ehvi_pareto_filter_internal():
    """The jit kernel filters dominated points itself — feeding it a raw
    (unfiltered) set must equal feeding the reference the filtered front,
    so the acquisition scan can hand it its raw fantasy buffer."""
    rng = np.random.default_rng(6)
    pts = rng.normal(0, 1, (12, 2))
    mu, sg = rng.normal(0, 1, (5, 2)), rng.uniform(0.1, 1.0, (5, 2))
    ref = np.array([-4.0, -4.0])
    np.testing.assert_allclose(ehvi_2d(mu, sg, pts, ref),
                               ehvi_2d_ref(mu, sg, pareto_front(pts), ref),
                               rtol=1e-4, atol=1e-5)


def test_ehvi_output_is_writable_float64():
    out = ehvi_2d(np.zeros((2, 2)), np.ones((2, 2)),
                  np.zeros((0, 2)), np.array([-1.0, -1.0]))
    assert out.dtype == np.float64
    out[0] = -1.0   # _acquire mutates scores in place; must not raise


# ---------------------------------------------------------------------------
# greedy q-EHVI acquisition vs NumPy reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q", [1, 2, 4])
def test_acquire_batch_matches_reference(q):
    rng = np.random.default_rng(7 + q)
    n, d, c = 9, len(DIMS), 40
    X = rng.random((n, d))
    Y = np.stack([1e3 * (1 + rng.random(n)), 1e3 * (2 + rng.random(n))], 1)
    models = M._fit_models(X, Y)
    ref_models = [NumpyGP.fit(X, np.log1p(np.maximum(Y[:, 0], 0.0))),
                  NumpyGP.fit(X, -np.log(np.maximum(Y[:, 1], 1.0)))]
    ev = M.obj_space([tuple(y) for y in Y])
    cand = rng.random((c, d))
    ref = M.hv_ref(15000.0)
    js = M._acquire_batch(models, cand, ev, ref, q=q)
    js_ref = acquire_batch_ref(ref_models, cand, ev, ref, q=q)
    assert js == js_ref
    assert len(set(js)) == q


def test_acquire_batch_no_retrace_within_bucket():
    """Repeated proposals inside one capacity bucket reuse one compiled
    program (the ≥10x fig8 win depends on it)."""
    cache_size = getattr(M._acquire_scan_jit, "_cache_size", None)
    if cache_size is None:
        pytest.skip("jax version does not expose _cache_size")
    rng = np.random.default_rng(8)
    d = len(DIMS)
    ref = M.hv_ref(15000.0)
    for n in (5, 6, 7):   # all land in the same pow2 bucket
        X = rng.random((n, d))
        Y = np.stack([1e3 * (1 + rng.random(n)),
                      1e3 * (2 + rng.random(n))], 1)
        models = M._fit_models(X, Y)
        ev = M.obj_space([tuple(y) for y in Y])
        M._acquire_batch(models, rng.random((16, d)), ev, ref, q=2)
        if n == 5:
            first = cache_size()
    assert cache_size() == first


def test_warm_optimizer_kernels_covers_campaign_buckets():
    n_buckets = M.warm_optimizer_kernels(18, n_candidates=16, q=2)
    assert n_buckets >= 2   # at least the 8- and 16-obs buckets


# ---------------------------------------------------------------------------
# batched validator / yield vs scalar references
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,n", [(9, 160), (12, 256), (13, 256)],
                         ids=["160-draws", "256-draws-a", "256-draws-b"])
def test_validate_batch_matches_scalar_exactly(seed, n):
    """256 draws is the trace cell's round: its live designs' core arrays
    span every height and width band of 1..32."""
    rng = np.random.default_rng(seed)
    designs = decode_batch(sample(rng, n))
    batch = validate_batch(designs)
    for d, rb in zip(designs, batch):
        rs = validate(d)
        assert rb.ok == rs.ok
        assert rb.reason == rs.reason
        assert rb.design == rs.design            # includes resolved spares
        if rb.ok:
            assert rb.wafer_yield == rs.wafer_yield   # bitwise


def test_row_redundancy_yield_exact_and_matches_mc():
    rng = np.random.default_rng(10)
    ys = rng.uniform(0.6, 0.99, (4, 5))
    # exact Poisson-binomial by brute-force enumeration over fail patterns
    for spares in (0, 1, 2):
        want = 1.0
        for row in ys:
            p_ok = 0.0
            for fails in itertools.product([0, 1], repeat=len(row)):
                if sum(fails) <= spares:
                    p = np.prod([1 - y if f else y
                                 for f, y in zip(fails, row)])
                    p_ok += p
            want *= p_ok
        got = row_redundancy_yield(ys, spares)
        np.testing.assert_allclose(got, want, rtol=1e-12)
        mc = mc_row_redundancy_yield(ys, spares, n_samples=4000, seed=0)
        assert abs(got - mc) < 0.05


def _yield_batch(case, rng):
    """Designs for the yield-resolution tests: core sizes, arrays,
    reticles, TSV fields (some off), reticle counts and integrations."""
    if case == "square-2-8":         # the original case: one small shape band
        n = 24
        ch = rng.uniform(1.0, 6.0, n)
        cw = rng.uniform(1.0, 6.0, n)
        ah = aw = rng.integers(2, 9, n)
        nret = rng.integers(1, 30, n)
        infosow = rng.random(n) < 0.5
        tsv = rng.uniform(0.0, 5.0, n)
        return ch, cw, ah, aw, ch * ah, cw * aw, tsv, nret, infosow
    n = 40
    ch = rng.uniform(1.0, 6.0, n)
    cw = rng.uniform(1.0, 6.0, n)
    if case == "mixed-1-32":         # every band, both sides of 16/17
        ah = rng.integers(1, 33, n)
        aw = rng.integers(1, 33, n)
        ah[:6] = [16, 17, 16, 17, 1, 32]
        aw[:6] = [16, 16, 17, 17, 32, 1]
    elif case == "one-band":         # all arrays within 1..16 on each side
        ah = rng.integers(1, 17, n)
        aw = rng.integers(1, 17, n)
        ah[:2] = aw[:2] = 16
    else:                            # "two-bands": short rows, any width
        ah = rng.integers(1, 17, n)
        aw = rng.integers(1, 33, n)
        aw[:2] = [16, 17]
    nret = rng.integers(1, 30, n)
    infosow = np.arange(n) % 2 == 0
    tsv = np.where(np.arange(n) % 3 == 0, 0.0, rng.uniform(0.0, 5.0, n))
    return ch, cw, ah, aw, ch * ah, cw * aw, tsv, nret, infosow


@pytest.mark.parametrize(
    "case", ["square-2-8", "mixed-1-32", "one-band", "two-bands"])
def test_min_spares_batch_matches_scalar(case):
    """The batch resolves spares and wafer yield bit for bit as a batch of
    one per design (what the scalar validator runs), whatever mix of
    array shapes shares the batch."""
    ch, cw, ah, aw, rh, rw, tsv, nret, infosow = _yield_batch(
        case, np.random.default_rng(11))
    spares_b, wy_b = min_spares_for_target_batch(
        ch, cw, ah, aw, rh, rw, tsv, nret, infosow)
    for i in range(len(ch)):
        s, wy = min_spares_for_target(
            float(ch[i]), float(cw[i]), (int(ah[i]), int(aw[i])),
            (float(rh[i]), float(rw[i])), float(tsv[i]), int(nret[i]),
            "infosow" if infosow[i] else "die_stitching")
        assert spares_b[i] == s
        assert wy_b[i] == wy   # bitwise: scalar delegates to the batch path


@pytest.mark.parametrize("case", ["mixed-1-32", "two-bands"])
def test_reticle_yields_batch_matches_scalar_grid(case):
    """Every spares level of the unpadded batch equals the scalar
    `reticle_yield` (its own rectangular grid, `core_yield_grid`) bit for
    bit."""
    ch, cw, ah, aw, rh, rw, tsv, _, _ = _yield_batch(
        case, np.random.default_rng(12))
    rys = reticle_yields_batch(ch, cw, ah, aw, rh, rw, tsv)
    for i in range(len(ch)):
        for s in range(rys.shape[1]):
            assert rys[i, s] == reticle_yield(
                float(ch[i]), float(cw[i]), (int(ah[i]), int(aw[i])),
                (float(rh[i]), float(rw[i])), float(tsv[i]), s)


@pytest.mark.parametrize("case", ["mixed-1-32", "one-shape"])
def test_yield_cell_counters(case):
    """`validate.yield_cells` (grid cells built) against
    `validate.yield_cells_live` (the designs' own cells): no padding, on a
    mix of shapes as on one shape."""
    if case == "one-shape":
        ch, cw, _, _, _, _, tsv, nret, infosow = _yield_batch(
            "mixed-1-32", np.random.default_rng(13))
        ah = np.full(len(ch), 16)
        aw = np.full(len(ch), 17)
    else:
        ch, cw, ah, aw, _, _, tsv, nret, infosow = _yield_batch(
            case, np.random.default_rng(13))
    tel = tm.Telemetry()
    with tm.activate(tel):
        min_spares_for_target_batch(ch, cw, ah, aw, ch * ah, cw * aw, tsv,
                                    nret, infosow)
    live = int(np.sum(ah * aw))
    assert tel.counters["validate.yield_cells_live"] == live
    assert tel.counters["validate.yield_cells"] == live


# ---------------------------------------------------------------------------
# checkpoint purity
# ---------------------------------------------------------------------------


def test_loop_state_pickle_is_host_side():
    """LoopState checkpoints must hold only host types — never jax device
    arrays (they poison pickles and break resume across backends)."""
    import jax

    from repro.explore.runner import ExplorationLoop, LoopConfig

    def f(d):
        return (1e3 + d.mac_num, 5e2 + d.buffer_kb)

    cfg = LoopConfig(strategy="mobo", N0=6, d0=3, q=2, n_candidates=12,
                     seed=0)
    loop = ExplorationLoop(cfg, f)
    for _ in range(2):
        loop.step()
    blob = pickle.dumps(loop.state)

    def walk(o, seen=None):
        seen = seen if seen is not None else set()
        if id(o) in seen:
            return
        seen.add(id(o))
        assert not isinstance(o, jax.Array), f"device array in state: {o!r}"
        if isinstance(o, dict):
            for v in o.values():
                walk(v, seen)
        elif isinstance(o, (list, tuple, set)):
            for v in o:
                walk(v, seen)
        elif hasattr(o, "__dict__"):
            for v in vars(o).values():
                walk(v, seen)

    walk(pickle.loads(blob))
    assert len(loop.state.Y0) > 0
