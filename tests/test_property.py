"""Property-based tests (hypothesis) on system invariants.

`hypothesis` is a dev-only dependency (pip install -e .[dev]); when it is
absent the whole module skips at collection instead of crashing tier-1."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core.ehvi import ehvi_2d
from repro.core.pareto import hypervolume_2d, pareto_front
from repro.kernels.flash_attention.ref import attention_ref, make_mask
from repro.models.attention import update_cache_layer

SETTINGS = dict(max_examples=25, deadline=None)


# --------------------------- attention masks --------------------------------


@given(sq=st.integers(1, 12), skv=st.integers(1, 16),
       window=st.one_of(st.none(), st.integers(1, 8)))
@settings(**SETTINGS)
def test_mask_causality(sq, skv, window):
    qp = jnp.broadcast_to(jnp.arange(sq), (1, sq))
    kp = jnp.broadcast_to(jnp.arange(skv), (1, skv))
    m = np.asarray(make_mask(qp, kp, causal=True, window=window))[0]
    ii, jj = np.meshgrid(np.arange(sq), np.arange(skv), indexing="ij")
    assert not (m & (jj > ii)).any()                     # no future peeking
    if window is not None:
        assert not (m & (jj <= ii - window)).any()       # window respected


@given(seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_attention_rows_are_convex_combinations(seed):
    """Each output is inside the convex hull of V rows: max |out| <= max |V|."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    B, S, H, hd = 1, 8, 2, 4
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    out = np.asarray(attention_ref(q, k, v, pos, pos, causal=True))
    assert np.abs(out).max() <= np.abs(np.asarray(v)).max() + 1e-5


@given(w=st.integers(2, 8), steps=st.integers(1, 20))
@settings(**SETTINGS)
def test_ring_cache_keeps_newest_positions(w, steps):
    """After writing positions 0..steps-1 into a ring of W slots, the cache
    holds exactly the newest min(steps, W) positions."""
    cache = {"k": jnp.zeros((1, w, 1, 2)), "v": jnp.zeros((1, w, 1, 2)),
             "kv_pos": jnp.full((1, w), -1, jnp.int32)}
    for t in range(steps):
        kn = jnp.full((1, 1, 1, 2), float(t))
        cache = update_cache_layer(cache, kn, kn, jnp.int32(t))
    held = set(np.asarray(cache["kv_pos"][0]).tolist()) - {-1}
    expect = set(range(max(0, steps - w), steps))
    assert held == expect
    # slot contents match their recorded position
    for slot, p in enumerate(np.asarray(cache["kv_pos"][0])):
        if p >= 0:
            assert float(cache["k"][0, slot, 0, 0]) == float(p)


# --------------------------- pareto / EHVI ----------------------------------


@given(n=st.integers(1, 20), seed=st.integers(0, 999))
@settings(**SETTINGS)
def test_pareto_front_is_mutually_nondominated(n, seed):
    pts = np.random.default_rng(seed).random((n, 2))
    front = pareto_front(pts)
    for i in range(len(front)):
        for j in range(len(front)):
            if i != j:
                assert not (np.all(front[j] >= front[i])
                            and np.any(front[j] > front[i]))


@given(n=st.integers(1, 15), seed=st.integers(0, 999))
@settings(**SETTINGS)
def test_hypervolume_monotone_under_adding_points(n, seed):
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 2))
    ref = np.array([0.0, 0.0])
    hv1 = hypervolume_2d(pts[:-1], ref) if n > 1 else 0.0
    hv2 = hypervolume_2d(pts, ref)
    assert hv2 >= hv1 - 1e-12
    assert hv2 <= 1.0 + 1e-9                    # points live in unit square


@given(seed=st.integers(0, 999))
@settings(**SETTINGS)
def test_ehvi_nonnegative_and_sigma_monotone_when_dominated(seed):
    rng = np.random.default_rng(seed)
    front = rng.random((4, 2)) + 1.0
    ref = np.array([0.0, 0.0])
    mu = rng.random((1, 2))                      # dominated region
    lo = ehvi_2d(mu, np.array([[0.05, 0.05]]), front, ref)[0]
    hi = ehvi_2d(mu, np.array([[1.0, 1.0]]), front, ref)[0]
    assert lo >= -1e-12 and hi >= -1e-12
    assert hi >= lo - 1e-9   # more uncertainty -> more improvement chance


# --------------------------- batched evaluation -----------------------------


@given(seed=st.integers(0, 10_000),
       wl_kind=st.sampled_from(["train", "prefill", "decode"]))
@settings(max_examples=12, deadline=None)
def test_evaluate_design_batch_matches_scalar(seed, wl_kind):
    """The vectorized (design, strategy) pipeline reproduces the scalar
    graph-based evaluator on random valid designs and workloads."""
    from repro.core.design_space import decode
    from repro.core.evaluator import (clear_eval_cache, evaluate_design,
                                      evaluate_design_batch)
    from repro.core.validator import validate
    from repro.core.workload import GPT_BENCHMARKS, inference_workload
    from hypothesis import assume

    rng = np.random.default_rng(seed)
    r = validate(decode(rng.random(13)))
    assume(r.ok)
    d = r.design
    wl = GPT_BENCHMARKS[0]
    if wl_kind != "train":
        wl = inference_workload(wl, wl_kind, batch=64)
    clear_eval_cache()
    a = evaluate_design(d, wl, max_strategies=12)
    clear_eval_cache()
    b = evaluate_design_batch([d], wl, max_strategies=12)[0]
    assert a.feasible == b.feasible
    assert a.n_wafers == b.n_wafers
    if a.feasible:
        assert a.strategy == b.strategy
        assert np.isclose(a.throughput, b.throughput, rtol=1e-6)
        assert np.isclose(a.power_w, b.power_w, rtol=1e-6)
        assert np.isclose(a.step.step_time_s, b.step.step_time_s, rtol=1e-6)


@given(seed=st.integers(0, 10_000),
       fidelity=st.sampled_from(["gnn", "sim"]))
@settings(max_examples=8, deadline=None)
def test_graph_fidelity_batch_matches_scalar(seed, fidelity):
    """The pattern-space batched gnn/sim backends reproduce the scalar
    graph-walking evaluator on random valid designs — same winning strategy,
    objectives equal to float tolerance."""
    from repro.core.design_space import decode
    from repro.core.evaluator import (clear_eval_cache, evaluate_design,
                                      evaluate_design_batch)
    from repro.core.noc_gnn import init_gnn
    from repro.core.validator import validate
    from repro.core.workload import GPT_BENCHMARKS
    from hypothesis import assume

    rng = np.random.default_rng(seed)
    r = validate(decode(rng.random(13)))
    assume(r.ok)
    d = r.design
    wl = GPT_BENCHMARKS[0]
    params = init_gnn(jax.random.PRNGKey(0)) if fidelity == "gnn" else None
    clear_eval_cache()
    a = evaluate_design(d, wl, fidelity=fidelity, gnn_params=params,
                        max_strategies=4)
    clear_eval_cache()
    b = evaluate_design_batch([d], wl, fidelity=fidelity, gnn_params=params,
                              max_strategies=4)[0]
    assert a.feasible == b.feasible
    assert a.n_wafers == b.n_wafers
    if a.feasible:
        assert a.strategy == b.strategy
        assert np.isclose(a.throughput, b.throughput, rtol=1e-5)
        assert np.isclose(a.power_w, b.power_w, rtol=1e-5)


@given(seed=st.integers(0, 10_000), w=st.integers(2, 6),
       n=st.integers(1, 24))
@settings(**SETTINGS)
def test_simulate_batch_matches_scalar_bitwise(seed, w, n):
    """The lockstep multi-lane simulator is bit-identical to the scalar
    event-ordered simulator on random packet sets (small grids)."""
    from repro.core.noc_sim import Packet, simulate, simulate_many

    rng = np.random.default_rng(seed)
    pkts = [Packet(src=int(rng.integers(0, w * w)),
                   dst=int(rng.integers(0, w * w)),
                   flits=int(rng.integers(1, 12)),
                   inject=float(rng.integers(0, 6)))
            for _ in range(n)]
    ref = simulate(pkts, w)
    got = simulate_many([pkts], [w])[0]
    assert got.makespan == ref.makespan
    assert got.avg_latency == ref.avg_latency
    assert got.link_wait == ref.link_wait
    assert got.link_util == ref.link_util


@given(seed=st.integers(0, 10_000), n_graphs=st.integers(1, 4))
@settings(max_examples=10, deadline=None)
def test_gnn_forward_batch_matches_scalar_forward(seed, n_graphs):
    """Padded vmapped forward == per-graph forward on heterogeneous graphs
    (masked segment sums make the padding inert)."""
    from repro.core.noc_gnn import (gnn_forward, gnn_forward_batch, init_gnn,
                                    pad_link_graphs)
    from repro.core.compiler import compile_chunk
    from repro.core.design_space import decode
    from repro.core.noc_gnn import featurize_transfer
    from repro.core.validator import validate
    from repro.core.workload import GPT_BENCHMARKS
    from hypothesis import assume

    rng = np.random.default_rng(seed)
    r = validate(decode(rng.random(13)))
    assume(r.ok)
    d = r.design
    wl = GPT_BENCHMARKS[0]
    graphs = []
    for cores in rng.choice([4, 8, 16, 32, 64], size=n_graphs):
        g = compile_chunk(d, wl, tp=16, mb_tokens=1024,
                          cores_per_chunk=int(cores))
        for t in range(len(g.transfers)):
            if g.transfers[t].pairs:
                graphs.append(featurize_transfer(g, d, t))
                break
    assume(graphs)
    params = init_gnn(jax.random.PRNGKey(1))
    batch = pad_link_graphs(graphs)
    out = gnn_forward_batch(params, batch)
    for i, g in enumerate(graphs):
        ref = np.asarray(gnn_forward(
            jax.tree.map(jnp.asarray, params), jnp.asarray(g.node_x),
            jnp.asarray(g.edge_x), jnp.asarray(g.senders),
            jnp.asarray(g.receivers), int(g.n_nodes)))
        np.testing.assert_allclose(out[i, :len(g.links)], ref,
                                   rtol=1e-5, atol=1e-5)


@given(seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_qehvi_q1_matches_scalar_ehvi_argmax(seed):
    """Greedy q-EHVI with q=1 is exactly the scalar EHVI acquisition."""
    from repro.core.mfmobo import (_acquire_batch, _fit_models, hv_ref,
                                   obj_space)

    rng = np.random.default_rng(seed)
    X = rng.random((12, 5))
    Y = np.stack([1e5 * (1 + X[:, 1] + 0.3 * rng.random(12)),
                  5e3 * (0.5 + X[:, 3])], 1)
    models = _fit_models(X, Y)
    ev = obj_space([tuple(r) for r in Y])
    ref = hv_ref(15000.0)
    cand = rng.random((32, 5))
    # scalar reference: argmax of the plain EHVI scores
    from repro.core.pareto import pareto_front
    g_t, g_p = models
    mu = np.stack([g_t.predict(cand)[0], g_p.predict(cand)[0]], 1)
    sg = np.stack([g_t.predict(cand)[1], g_p.predict(cand)[1]], 1)
    scores = ehvi_2d(mu, sg, pareto_front(ev), ref)
    j_ref = int(np.argmax(scores))
    js = _acquire_batch(models, cand, ev, ref, q=1)
    assert js == [j_ref]
    # q>1 extends (not replaces) the q=1 choice with distinct indices
    js4 = _acquire_batch(models, cand, ev, ref, q=4)
    assert js4[0] == j_ref and len(set(js4)) == 4


# --------------------------- optimizer --------------------------------------


@given(seed=st.integers(0, 999), clip=st.floats(0.1, 5.0))
@settings(**SETTINGS)
def test_clip_never_increases_norm(seed, clip):
    from repro.train.optimizer import clip_by_global_norm, global_norm
    rng = np.random.default_rng(seed)
    tree = {"a": jnp.asarray(rng.normal(size=7)),
            "b": {"c": jnp.asarray(rng.normal(size=(3, 2)))}}
    clipped, norm = clip_by_global_norm(tree, clip)
    assert float(global_norm(clipped)) <= max(clip, float(norm)) + 1e-5
