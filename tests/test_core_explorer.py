"""GP surrogate, EHVI, Pareto/hypervolume, MFMOBO loop."""
import numpy as np
import pytest

from repro.core.ehvi import ehvi_2d
from repro.core.gp import GP
from repro.core.pareto import hypervolume_2d, pareto_front, pareto_mask


def test_gp_fits_smooth_function():
    rng = np.random.default_rng(0)
    X = rng.random((40, 3))
    y = np.sin(3 * X[:, 0]) + X[:, 1] ** 2
    gp = GP.fit(X, y, iters=60)
    Xs = rng.random((20, 3))
    ys = np.sin(3 * Xs[:, 0]) + Xs[:, 1] ** 2
    mu, sd = gp.predict(Xs)
    rmse = float(np.sqrt(np.mean((mu - ys) ** 2)))
    assert rmse < 0.25
    assert (sd > 0).all()


def test_gp_uncertainty_grows_off_data():
    X = np.random.default_rng(1).random((20, 2)) * 0.3   # data in a corner
    y = X.sum(1)
    gp = GP.fit(X, y, iters=60)
    _, sd_near = gp.predict(X[:5])
    _, sd_far = gp.predict(np.ones((5, 2)) * 0.95)
    assert sd_far.mean() > sd_near.mean()


def test_pareto_front_2d():
    pts = np.array([[1, 5], [2, 4], [3, 3], [2, 2], [0, 6], [3, 1]])
    mask = pareto_mask(pts)
    front = pts[mask]
    assert {tuple(p) for p in front} == {(0, 6), (1, 5), (2, 4), (3, 3)}


def test_hypervolume_known_case():
    ref = [0.0, 0.0]
    pts = np.array([[2.0, 1.0], [1.0, 2.0]])
    # union of 2x1 and 1x2 rectangles = 3
    assert hypervolume_2d(pts, ref) == pytest.approx(3.0)
    assert hypervolume_2d(np.zeros((0, 2)), ref) == 0.0
    # dominated point adds nothing
    pts2 = np.vstack([pts, [[1.0, 1.0]]])
    assert hypervolume_2d(pts2, ref) == pytest.approx(3.0)


def test_ehvi_monotone_in_mean():
    front = np.array([[2.0, 2.0]])
    ref = np.array([0.0, 0.0])
    sig = np.array([[0.3, 0.3]])
    lo = ehvi_2d(np.array([[1.0, 1.0]]), sig, front, ref)[0]
    hi = ehvi_2d(np.array([[3.0, 3.0]]), sig, front, ref)[0]
    assert hi > lo >= 0.0


def test_ehvi_zero_for_deeply_dominated():
    front = np.array([[5.0, 5.0]])
    ref = np.array([0.0, 0.0])
    v = ehvi_2d(np.array([[1.0, 1.0]]), np.array([[0.05, 0.05]]), front,
                ref)[0]
    assert v < 1e-6


def test_gp_condition_on_fantasy_update():
    """Rank-1 conditioning pins the posterior near the fantasized value and
    shrinks uncertainty there, without touching hyperparameters."""
    rng = np.random.default_rng(4)
    X = rng.random((25, 3))
    y = np.sin(3 * X[:, 0]) + X[:, 1]
    gp = GP.fit(X, y, iters=50)
    xs = rng.random(3)
    mu0, sd0 = gp.predict(xs[None])
    gp2 = gp.condition_on(xs, float(mu0[0]) + 0.3)
    mu1, sd1 = gp2.predict(xs[None])
    assert sd1[0] < sd0[0]
    assert mu1[0] > mu0[0]                      # pulled toward the fantasy
    assert gp2.params is gp.params              # no refit
    mu2, sd2 = gp2.predict(X[:5])
    assert np.isfinite(mu2).all() and (sd2 > 0).all()


def test_mobo_batched_proposals_with_batch_eval_fn():
    """q>1 proposals + a batch-aware objective: the loop evaluates whole
    batches in one call and still only spends the evaluation budget."""
    from repro.core.design_space import encode_batch
    from repro.explore.runner import ExplorationLoop, LoopConfig

    calls = {"n": 0, "sizes": []}

    def f(designs):
        calls["n"] += 1
        calls["sizes"].append(len(designs))
        U = encode_batch(designs)
        return [(float(1e5 * (1 + u[1] + u[4])),
                 float(5e3 * (0.5 + u[1] ** 2))) for u in U]
    f.batched = True

    tr = ExplorationLoop(LoopConfig(strategy="mobo", N0=9, d0=3, q=3,
                                    n_candidates=32, seed=0), f).run()
    assert len(tr.ys) == 9
    assert tr.hv[-1] >= tr.hv[0]
    assert max(calls["sizes"]) == 3             # proposals arrive as batches
    assert sum(calls["sizes"]) == 9


def test_mfmobo_loop_improves_hypervolume():
    """MFMOBO on a cheap synthetic 2-objective problem over the WSC space:
    maximize (throughput-proxy, -power-proxy) from the encoded vector."""
    from repro.core.design_space import encode
    from repro.explore.runner import ExplorationLoop, LoopConfig

    def f_hi(design):
        u = encode(design)
        thpt = 1e5 * (1 + u[1] + u[4] - 0.5 * abs(u[1] - 0.6))
        power = 5000 * (0.5 + u[1] ** 2 + 0.3 * u[3])
        return float(thpt), float(power)

    def f_lo(design):
        t, p = f_hi(design)
        return t * 1.1, p * 0.95               # biased-but-correlated

    tr = ExplorationLoop(LoopConfig(strategy="mfmobo", N0=7, N1=7, d0=2,
                                    d1=2, k=2, n_candidates=48, seed=0),
                         f_hi, f1=f_lo).run()
    assert len(tr.hv) >= 5
    assert tr.hv[-1] >= tr.hv[0]               # monotone non-decreasing
    # q=N: the whole sampled pool in one evaluation call
    rnd = ExplorationLoop(LoopConfig(strategy="random", N0=7, q=7, seed=0),
                          f_hi).run()
    assert tr.hv[-1] >= 0.8 * rnd.hv[-1]       # sanity: not catastrophically worse
