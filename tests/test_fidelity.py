"""Fidelity backend registry + batched graph-fidelity equivalence, and the
online-calibration loop."""
import jax
import numpy as np
import pytest

from repro.core import fidelity as F
from repro.core.compiler import compile_chunk, row_allgather_pattern
from repro.core.design_space import WSCDesign, decode
from repro.core.evaluator import (
    clear_eval_cache,
    evaluate_design,
    evaluate_design_batch,
    gnn_params_token,
)
from repro.core.noc_gnn import featurize_transfer, init_gnn
from repro.core.noc_sim import packets_for_transfer, simulate, simulate_many
from repro.core.validator import validate
from repro.core.workload import GPT_BENCHMARKS


# --------------------------- registry ---------------------------------------


def test_unknown_fidelity_raises_with_registered_list():
    with pytest.raises(ValueError) as ei:
        F.get_backend("cycle_exact")
    msg = str(ei.value)
    assert "cycle_exact" in msg
    for name in ("analytical", "gnn", "sim"):
        assert name in msg
    # the same failure surfaces through the public entry points
    d = validate(WSCDesign()).design
    with pytest.raises(ValueError):
        evaluate_design(d, GPT_BENCHMARKS[0], fidelity="anaytical")
    with pytest.raises(ValueError):
        evaluate_design_batch([d], GPT_BENCHMARKS[0], fidelity="")


def test_builtins_registered_and_instances_pass_through():
    assert F.registered_backends() == ("analytical", "gnn", "sim")
    backend = F.get_backend("sim")
    assert F.get_backend(backend) is backend


def test_register_custom_backend_roundtrip():
    class Fixed:
        name = "fixed-latency"

        def chunk_latency(self, graph, design, gnn_params=None):
            return 123.0

        def evaluate_batch(self, geom, wl, n_wafers, max_strategies=24,
                           gnn_params=None):
            ax = F.build_candidate_axis(geom, wl, n_wafers, max_strategies)
            return F._finish(ax, wl, np.full(len(ax.didx), 123.0))

    try:
        F.register_backend(Fixed())
        assert "fixed-latency" in F.registered_backends()
        d = validate(WSCDesign()).design
        clear_eval_cache()
        r = evaluate_design_batch([d], GPT_BENCHMARKS[0],
                                  fidelity="fixed-latency",
                                  max_strategies=4)[0]
        assert r.feasible
    finally:
        F._REGISTRY.pop("fixed-latency", None)


# --------------------------- params-version token ---------------------------


def test_params_token_is_monotonic_and_never_aliases():
    clear_eval_cache()
    assert gnn_params_token(None) is None
    p1 = {"w": np.zeros(3)}
    t1 = gnn_params_token(p1)
    assert gnn_params_token(p1) == t1          # stable while pinned
    # overflow the pin table: p1's pin is evicted, its token retired
    extras = [{"w": np.zeros(1)} for _ in range(40)]
    tokens = [gnn_params_token(p) for p in extras]
    assert len(set(tokens)) == len(tokens)     # all distinct
    t1b = gnn_params_token(p1)
    assert t1b != t1                           # re-pinned => fresh token
    assert t1b > max(tokens)                   # strictly monotonic counter


# --------------------------- pattern tables ---------------------------------


def test_row_allgather_pattern_matches_compiled_featurization():
    """The memoized pattern tables reproduce featurize_transfer /
    packets_for_transfer structure bit-for-bit on a compiled chunk."""
    d = validate(WSCDesign()).design
    wl = GPT_BENCHMARKS[0]
    g = compile_chunk(d, wl, tp=16, mb_tokens=2048, cores_per_chunk=64)
    gh, gw = g.array
    pat = row_allgather_pattern(gh, gw)
    for t_idx in range(len(g.transfers)):
        if not g.transfers[t_idx].pairs:
            continue
        ref = featurize_transfer(g, d, t_idx)
        np.testing.assert_array_equal(pat.senders, ref.senders)
        np.testing.assert_array_equal(pat.receivers, ref.receivers)
        pkts = packets_for_transfer(g, d, t_idx)
        flits = {p.flits for p in pkts}
        assert len(flits) == 1                 # uniform per transfer
        fl = flits.pop()
        interval = g.ops[g.transfers[t_idx].src_op].tile.out_interval_cycles
        np.testing.assert_array_equal(pat.src, [p.src for p in pkts])
        np.testing.assert_array_equal(pat.dst, [p.dst for p in pkts])
        np.testing.assert_allclose(pat.seq * interval,
                                   [p.inject for p in pkts])
        dur = max(g.ops[g.transfers[t_idx].src_op].tile.cycles, 1.0)
        lanes = F._GridLanes(pattern=pat, u_lane=np.zeros(1, np.int64),
                             flits=np.array([float(fl)]),
                             interval=np.array([interval]),
                             dur=np.array([dur]),
                             noc_bw=np.array([float(d.noc_bw)]))
        node_x, edge_x = F._pattern_features(lanes)
        np.testing.assert_array_equal(node_x[0], ref.node_x)
        np.testing.assert_array_equal(edge_x[0], ref.edge_x)


def test_row_decomposition_makespan_invariant():
    """A transfer's sim makespan on the (gh, gw) grid equals the (1, gw)
    single-row makespan — the invariant the batched graph backends use."""
    d = validate(WSCDesign()).design
    wl = GPT_BENCHMARKS[0]
    g = compile_chunk(d, wl, tp=16, mb_tokens=2048, cores_per_chunk=64)
    gh, gw = g.array
    assert gh > 1
    for t_idx in (0, len(g.transfers) - 1):
        if not g.transfers[t_idx].pairs:
            continue
        pkts = packets_for_transfer(g, d, t_idx)
        full = simulate(pkts, gw).makespan
        row = [p for p in pkts if p.src < gw]          # row 0 only
        assert np.isclose(simulate(row, gw).makespan, full)


# --------------------------- batched vs scalar ------------------------------


@pytest.mark.parametrize("fidelity", ["gnn", "sim"])
def test_graph_fidelity_batch_matches_scalar(fidelity):
    wl = GPT_BENCHMARKS[0]
    rng = np.random.default_rng(42)
    designs = []
    while len(designs) < 4:
        r = validate(decode(rng.random(13)))
        if r.ok:
            designs.append(r.design)
    params = init_gnn(jax.random.PRNGKey(0)) if fidelity == "gnn" else None
    clear_eval_cache()
    serial = [evaluate_design(d, wl, fidelity=fidelity, gnn_params=params,
                              max_strategies=6) for d in designs]
    clear_eval_cache()
    batch = evaluate_design_batch(designs, wl, fidelity=fidelity,
                                  gnn_params=params, max_strategies=6)
    for a, b in zip(serial, batch):
        assert a.feasible == b.feasible
        assert a.n_wafers == b.n_wafers
        if a.feasible:
            assert a.strategy == b.strategy
            assert np.isclose(a.throughput, b.throughput, rtol=1e-5)
            assert np.isclose(a.power_w, b.power_w, rtol=1e-5)


def test_gnn_without_params_degrades_to_analytical():
    d = validate(WSCDesign()).design
    wl = GPT_BENCHMARKS[0]
    clear_eval_cache()
    a = evaluate_design_batch([d], wl, fidelity="analytical",
                              max_strategies=6)[0]
    g = evaluate_design_batch([d], wl, fidelity="gnn", max_strategies=6)[0]
    assert np.isclose(a.throughput, g.throughput)


# --------------------------- calibration ------------------------------------


def test_pareto_neighborhood_prefers_front():
    from repro.core.calibration import pareto_neighborhood
    designs = [validate(WSCDesign(mac_num=2 ** i)).design
               for i in (6, 7, 8, 9)]
    # design 1 dominates 0; 2 and 3 trade off
    ys = [(100.0, 5000.0), (200.0, 4000.0), (300.0, 6000.0), (50.0, 1000.0)]
    picked = pareto_neighborhood(designs, ys, 2)
    assert designs[0] not in picked
    assert len(picked) == 2


def test_calibrator_on_handover_finetunes_params():
    from repro.core.calibration import GNNCalibrator
    from repro.explore.objectives import EvaluatorObjective
    wl = GPT_BENCHMARKS[0]
    designs = [validate(WSCDesign()).design,
               validate(WSCDesign(mac_num=256)).design]
    ys = [(100.0, 5000.0), (120.0, 6000.0)]
    p0 = init_gnn(jax.random.PRNGKey(1))
    cal = GNNCalibrator(p0, wl, n_designs=1, epochs=2, patience=None)
    f0 = EvaluatorObjective(wl, "gnn", params_fn=lambda: cal.params)
    assert getattr(f0, "batched", False) and f0.fidelity == "gnn"
    cal.on_handover(designs, ys)
    assert len(cal.records) == 1
    rec = cal.records[0]
    assert rec.n_graphs > 0 and len(rec.history.train_loss) > 0
    assert rec.history.val_loss and rec.history.val_kendall_tau
    assert cal.params is not p0               # fine-tuned copy
    # fresh params => fresh cache namespace
    assert gnn_params_token(cal.params) != gnn_params_token(p0)


def test_simulate_many_matches_scalar_bitwise():
    from repro.core.noc_sim import Packet
    rng = np.random.default_rng(3)
    lanes, Ws = [], []
    for _ in range(5):
        W = int(rng.integers(2, 6))
        n = int(rng.integers(1, 30))
        pkts = [Packet(src=int(rng.integers(0, W * 3)),
                       dst=int(rng.integers(0, W * 3)),
                       flits=int(rng.integers(1, 9)),
                       inject=float(rng.integers(0, 5)))
                for _ in range(n)]
        lanes.append(pkts)
        Ws.append(W)
    batch = simulate_many(lanes, Ws)
    for pkts, W, got in zip(lanes, Ws, batch):
        ref = simulate(pkts, W)
        assert got.makespan == ref.makespan
        assert got.avg_latency == ref.avg_latency
        assert set(got.link_wait) == set(ref.link_wait)
        for k in ref.link_wait:
            assert got.link_wait[k] == ref.link_wait[k]
