"""Campaign API (DESIGN.md §9): spec JSON round-trip, checkpoint/resume
bit-identity, constraint handling, budget exactness, candidate-sampling
failure modes, per-stage cache accounting, CLI."""
import dataclasses
import glob
import json
import os
import types

import numpy as np
import pytest

from repro.core import components as C
from repro.core.design_space import encode_batch
from repro.core.evaluator import clear_eval_cache, eval_cache_stats
from repro.core.workload import GPT_BENCHMARKS
from repro.explore import (
    Campaign,
    CampaignSpec,
    ConstraintSpec,
    EvaluatorObjective,
    ExplorationLoop,
    FidelitySchedule,
    LoopConfig,
    ObjectiveSpec,
    ServingSpec,
    as_objective,
    resolve_workload,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def quick_spec(**over) -> CampaignSpec:
    kw = dict(
        name="t-quick", workload="GPT-1.7B", scenario="train",
        strategy="mfmobo",
        fidelity=FidelitySchedule(f1="analytical", f0="analytical",
                                  d1=2, d0=2, k=2),
        n_evals_f0=5, n_evals_f1=6, q=2, n_candidates=16,
        max_strategies=6, seed=7)
    kw.update(over)
    return CampaignSpec(**kw)


# --------------------------- spec serialization -----------------------------


def test_spec_json_roundtrip_exact():
    spec = quick_spec(
        constraints=(ConstraintSpec("power_per_wafer", "<=", 4000.0),),
        objectives=(ObjectiveSpec("throughput", "max", "log1p"),
                    ObjectiveSpec("power_per_wafer", "min", "neg_log")),
        workload_overrides={"batch": 256},
        serving=None)
    blob = spec.to_json()
    again = CampaignSpec.from_json(blob)
    assert again == spec
    # and through a dict cycle with json in the middle
    assert CampaignSpec.from_dict(json.loads(
        json.dumps(spec.to_dict()))) == spec


def test_spec_file_roundtrip(tmp_path):
    spec = quick_spec(serving=ServingSpec(n_requests=4, out_len=8),
                      scenario="serving", strategy="mobo")
    p = tmp_path / "c.json"
    spec.to_json(str(p))
    assert CampaignSpec.from_json(str(p)) == spec


def test_spec_rejects_unknowns_and_bad_refs():
    with pytest.raises(ValueError, match="unknown campaign spec fields"):
        CampaignSpec.from_dict({"name": "x", "workload": "GPT-1.7B",
                                "frobnicate": 1})
    with pytest.raises(ValueError, match="unknown workload ref"):
        quick_spec(workload="GPT-9999B").validate()
    with pytest.raises(ValueError, match="unknown scenario"):
        quick_spec(scenario="overclock").validate()
    with pytest.raises(ValueError, match="needs a `serving` spec"):
        quick_spec(scenario="serving", strategy="mobo").validate()
    with pytest.raises(ValueError, match="constraint metric"):
        quick_spec(constraints=(
            ConstraintSpec("ttft", "<=", 1.0),)).validate()


def test_shipped_example_specs_parse_and_validate():
    from repro.explore import FleetSpec
    paths = sorted(glob.glob(os.path.join(REPO, "examples", "campaigns",
                                          "*.json")))
    assert len(paths) >= 4, "expected shipped example campaign specs"
    for p in paths:
        with open(p) as f:
            raw = json.load(f)
        if "campaigns" in raw or "grid" in raw:       # fleet grid spec
            fleet = FleetSpec.from_json(p).validate()
            assert len(fleet.campaigns) > 0
            continue
        spec = CampaignSpec.from_json(p).validate()
        assert spec.loop_config().total_evals() > 0


def test_resolve_workload_config_ref_and_overrides():
    spec = quick_spec(workload="smollm-135m@decode_32k",
                      scenario="inference",
                      workload_overrides={"batch": 8, "seq": 512})
    wl = resolve_workload(spec)
    assert wl.phase == "decode" and wl.batch == 8 and wl.seq == 512
    # train scenario pins the phase
    assert resolve_workload(quick_spec()).phase == "train"


# --------------------------- campaign execution -----------------------------


@pytest.fixture(scope="module")
def quick_run():
    clear_eval_cache()
    return Campaign(quick_spec()).run()


def test_campaign_budget_and_trace(quick_run):
    spec = quick_spec()
    assert quick_run.finished
    # exact budgets: N0 f0-points recorded, N0+N1 total evaluations
    assert len(quick_run.trace.ys) == spec.n_evals_f0
    assert quick_run.n_evals == spec.n_evals_f0 + spec.n_evals_f1
    assert quick_run.hv_final >= quick_run.trace.hv[0]
    assert quick_run.candidates_per_sec > 0


def test_campaign_stage_cache_recorded(quick_run):
    sc = quick_run.stage_cache
    assert set(sc) == {"f0", "f1"}
    for stage in ("f0", "f1"):
        assert sc[stage]["hits"] + sc[stage]["misses"] > 0
        assert 0.0 <= sc[stage]["hit_rate"] <= 1.0
        assert sc[stage]["entries_added"] >= 0
    # trace carries the same accounting (satellite: handover cost visible)
    assert quick_run.trace.stage_cache["f1"]["misses"] > 0
    assert set(quick_run.trace.cache_hit_rates()) == {"f0", "f1"}


def test_checkpoint_resume_bit_identical(tmp_path):
    """A campaign interrupted mid-run and resumed from its checkpoint
    reproduces the uninterrupted trace bit-for-bit at the same seed."""
    full = Campaign(quick_spec()).run()
    ck = str(tmp_path / "c.ckpt.pkl")
    partial = Campaign(quick_spec()).run(checkpoint_path=ck, max_steps=2)
    assert not partial.finished
    assert len(partial.trace.ys) < len(full.trace.ys)
    resumed = Campaign.resume(ck).run(checkpoint_path=ck)
    assert resumed.finished
    assert [tuple(y) for y in resumed.trace.ys] == \
        [tuple(y) for y in full.trace.ys]
    assert resumed.trace.hv == full.trace.hv
    assert all(np.array_equal(a, b)
               for a, b in zip(resumed.trace.xs, full.trace.xs))
    assert resumed.trace.designs == full.trace.designs


def test_serving_campaign_constraints_exclude_from_front():
    """SLO-violating candidates are mapped to the penalty point and never
    enter the Pareto front."""
    spec = quick_spec(
        scenario="serving", strategy="random", n_evals_f0=6, q=6,
        serving=ServingSpec(n_requests=4, prompt_len=256, out_len=8,
                            slots=2, ttft_s=1e9, tpot_s=1e9),
        max_strategies=6, seed=1)
    base = Campaign(spec).run()
    goods = [y for y in base.trace.ys if y[0] > 0]
    assert len(goods) >= 2, "need some feasible serving designs"
    # bind on the median power so some candidates violate
    cap = float(np.median([y[1] for y in goods]))
    spec_c = dataclasses.replace(
        spec, constraints=(ConstraintSpec("power_per_wafer", "<=", cap),))
    cam = Campaign(spec_c)
    res = cam.run()
    assert cam.f0.n_violations > 0
    assert res.objective_stats["f0"]["n_constraint_violations"] > 0
    # violating candidates land on the penalty point...
    for y in res.trace.ys:
        assert y[0] == 0.0 or y[1] <= cap
    # ...and the reported front only contains constraint-satisfying points
    assert res.front, "front should not be empty"
    for p in res.front:
        assert p["power_per_wafer"] <= cap


def test_resume_restores_objective_counters(tmp_path):
    """Counters (violations/infeasible) survive checkpoint/resume, so a
    resumed campaign reports the same cumulative stats as an uninterrupted
    one."""
    spec = quick_spec(
        constraints=(ConstraintSpec("power_per_wafer", "<=", 1000.0),))
    full = Campaign(spec)
    full_res = full.run()
    assert full.f0.n_violations + full.f0.n_infeasible > 0, \
        "cap should bind for this seed"
    ck = str(tmp_path / "c.ckpt.pkl")
    Campaign(spec).run(checkpoint_path=ck, max_steps=3)
    resumed = Campaign.resume(ck).run(checkpoint_path=ck)
    assert resumed.objective_stats == full_res.objective_stats


def test_validate_rejects_swapped_objective_directions():
    spec = quick_spec(objectives=(
        ObjectiveSpec("power_per_wafer", "min", "neg_log"),
        ObjectiveSpec("throughput", "max", "log1p")))
    with pytest.raises(ValueError, match="must be .max, min."):
        spec.validate()
    # transforms the loop would silently not apply must not validate
    spec = quick_spec(objectives=(
        ObjectiveSpec("throughput", "max", "identity"),
        ObjectiveSpec("power_per_wafer", "min", "neg_log")))
    with pytest.raises(ValueError, match="transforms must be"):
        spec.validate()


def test_hetero_objective_reads_live_params():
    """Hetero campaigns must see calibrated params: the objective
    dereferences params_fn at call time, not a construction-time
    snapshot."""
    from repro.explore import HeteroServingObjective

    box = {"params": None}
    sv = ServingSpec(n_requests=2, prompt_len=128, out_len=4, slots=2,
                     ttft_s=1e9, tpot_s=1e9)
    obj = HeteroServingObjective(
        GPT_BENCHMARKS[0], sv.mix(), sv.slo(), granularity="reticle",
        params_fn=lambda: box["params"])
    assert obj.gnn_params() is None
    box["params"] = {"w": 1}
    assert obj.gnn_params() == {"w": 1}      # live, not a snapshot


def test_periodic_checkpoint_carries_wall_time(tmp_path):
    """wall_s is flushed into the state before each periodic checkpoint, so
    a crash-resume doesn't under-report wall time (and overstate
    candidates/sec)."""
    from repro.explore.runner import ExplorationLoop, LoopConfig

    f = synthetic_fns()
    cfg = LoopConfig(strategy="mobo", N0=6, d0=2, q=2, n_candidates=12,
                     seed=0)
    loop = ExplorationLoop(cfg, f)
    ck = str(tmp_path / "w.ckpt")
    seen = []
    loop.run(checkpoint_every=1,
             checkpoint_cb=lambda: seen.append(
                 (loop.save_state(ck), loop.state.wall_s)))
    walls = [w for _, w in seen]
    assert walls[0] > 0.0                    # first periodic ckpt, not 0
    assert all(b >= a for a, b in zip(walls, walls[1:]))
    _, state, _ = ExplorationLoop.load_state(ck)
    assert state.wall_s == pytest.approx(loop.state.wall_s)


def test_hetero_objective_emits_every_advertised_metric():
    """Every metric known_metrics() advertises for a scenario must exist in
    the objective's metrics dicts (constraints on them must not KeyError)."""
    from benchmarks.common import sample_valid_designs
    from repro.explore import HeteroServingObjective, ServingObjective

    sv = ServingSpec(n_requests=2, prompt_len=128, out_len=4, slots=2,
                     ttft_s=1e9, tpot_s=1e9)
    wl = GPT_BENCHMARKS[0]
    cases = {
        "hetero": HeteroServingObjective(
            wl, sv.mix(), sv.slo(), granularity="reticle", n_wafers=4),
        "serving": ServingObjective(wl, sv.mix(), sv.slo(), slots=2,
                                    max_strategies=4),
        "train": EvaluatorObjective(wl, max_strategies=4),
    }
    d = sample_valid_designs(1, seed=6)
    for scenario, obj in cases.items():
        known = quick_spec(scenario=scenario, serving=sv).known_metrics()
        m = obj.metrics(d)[0]
        missing = set(known) - set(m)
        assert not missing, f"{scenario}: metrics missing {missing}"


def test_constraint_spec_semantics():
    c = ConstraintSpec("ttft", "<=", 2.0)
    assert c.ok({"ttft": 1.5}) and not c.ok({"ttft": 2.5})
    with pytest.raises(KeyError, match="not produced"):
        c.ok({"goodput": 1.0})
    with pytest.raises(ValueError, match="constraint op"):
        ConstraintSpec("ttft", "==", 2.0)


def test_evaluator_objective_metrics_and_penalty():
    wl = GPT_BENCHMARKS[0]
    from benchmarks.common import sample_valid_designs
    designs = sample_valid_designs(4, seed=2)
    free = EvaluatorObjective(wl, "analytical", max_strategies=6)
    ys = free.eval_many(designs)
    capped = EvaluatorObjective(
        wl, "analytical", max_strategies=6,
        constraints=(ConstraintSpec("power_per_wafer", "<=", -1.0),))
    ys_c = capped.eval_many(designs)
    # everything violates an impossible cap -> all penalty points
    assert all(y == (0.0, C.WAFER_POWER_W) for y in ys_c)
    assert capped.n_violations == sum(1 for y in ys if y[0] > 0)
    # legacy calling conventions survive on the protocol object
    assert free.batched and free.fidelity == "analytical"
    assert free(designs[0]) == ys[0]


# --------------------------- loop regressions -------------------------------


def synthetic_fns():
    def f(designs):
        U = encode_batch(designs)
        return [(float(1e5 * (1 + u[1] + u[4])),
                 float(5e3 * (0.5 + u[1] ** 2))) for u in U]
    f.batched = True
    return f


def test_budget_never_overshoots_with_q():
    """Regression (ISSUE 5): with q > 1 and budgets not divisible by q,
    the final batch is clamped so traces honor N0/N1 exactly."""
    f = synthetic_fns()
    tr = ExplorationLoop(LoopConfig(strategy="mobo", N0=10, d0=3, q=4,
                                    n_candidates=24, seed=0), f).run()
    assert len(tr.ys) == 10 and tr.n_evals == 10
    tr = ExplorationLoop(LoopConfig(strategy="mfmobo", N0=5, N1=6, d0=2,
                                    d1=2, k=2, q=4, n_candidates=24,
                                    seed=0), f, f1=f).run()
    assert len(tr.ys) == 5          # exactly the f0 budget
    assert tr.n_evals == 11         # N0 + N1, not a q-multiple overshoot


def test_priors_exceeding_budget_raise():
    f = synthetic_fns()
    with pytest.raises(ValueError, match="priors"):
        ExplorationLoop(LoopConfig(strategy="mobo", N0=4, d0=8), f)
    with pytest.raises(ValueError, match="priors"):
        ExplorationLoop(LoopConfig(strategy="mfmobo", N0=4, d0=9), f, f1=f)
    with pytest.raises(ValueError, match="unknown strategy"):
        LoopConfig(strategy="anneal").validate()


def test_valid_candidates_raises_when_space_rejects(monkeypatch):
    """Regression (ISSUE 5): `_valid_candidates` must not silently return a
    short (or empty) candidate set — it tops up across rounds and raises a
    clear error when the validator rejects (nearly) everything."""
    import repro.core.mfmobo as M

    rng = np.random.default_rng(0)
    monkeypatch.setattr(M, "validate_batch", lambda ds: [
        types.SimpleNamespace(ok=False, design=d) for d in ds])
    with pytest.raises(RuntimeError, match="valid candidates") as ei:
        M._valid_candidates(rng, 8, max_tries=2)
    assert "acceptance rate" in str(ei.value)    # satellite: rate surfaced

    # sparse acceptance still tops up to exactly n
    calls = {"n": 0}

    def sparse_batch(ds):
        out = []
        for d in ds:
            calls["n"] += 1
            out.append(types.SimpleNamespace(ok=calls["n"] % 3 == 0, design=d))
        return out
    monkeypatch.setattr(M, "validate_batch", sparse_batch)
    xs, ds = M._valid_candidates(np.random.default_rng(1), 8, max_tries=8)
    assert len(xs) == len(ds) == 8


def test_eval_cache_stats_entries():
    """Satellite: `eval_cache_stats()` exposes a live entry count."""
    from repro.core.evaluator import evaluate_design
    clear_eval_cache()
    s0 = eval_cache_stats()
    assert s0["entries"] == 0 and s0["size"] == 0
    from benchmarks.common import sample_valid_designs
    d = sample_valid_designs(1, seed=3)[0]
    evaluate_design(d, GPT_BENCHMARKS[0], max_strategies=4)
    s1 = eval_cache_stats()
    assert s1["entries"] == s1["size"] == 1
    assert s1["misses"] == 1


def test_as_objective_coercions():
    scalar_calls = []

    def scalar(d):
        scalar_calls.append(d)
        return 1.0, 2.0

    from benchmarks.common import sample_valid_designs
    designs = sample_valid_designs(3, seed=4)
    obj = as_objective(scalar)
    assert obj.eval_many(designs) == [(1.0, 2.0)] * 3
    assert len(scalar_calls) == 3            # scalar loop
    batched = synthetic_fns()
    obj_b = as_objective(batched)
    assert len(obj_b.eval_many(designs)) == 3
    assert as_objective(obj_b) is obj_b      # idempotent
    with pytest.raises(TypeError):
        as_objective(42)


def test_cli_validate_and_run(tmp_path):
    from repro.explore.__main__ import main

    spec = quick_spec(n_evals_f0=4, n_evals_f1=5, q=2,
                      fidelity=FidelitySchedule(d1=2, d0=2, k=1))
    p = tmp_path / "spec.json"
    spec.to_json(str(p))
    assert main(["--validate", str(p)]) == 0
    out = tmp_path / "r.json"
    ck = tmp_path / "c.pkl"
    assert main([str(p), "--out", str(out), "--checkpoint", str(ck)]) == 0
    res = json.loads(out.read_text())
    assert res["finished"] and res["n_evals"] == 9
    assert res["spec"]["name"] == "t-quick"
    assert "stage_cache" in res and "hv" in res
    # resume path: run 1 step elsewhere, then --resume completes it
    ck2 = tmp_path / "c2.pkl"
    out2 = tmp_path / "r2.json"
    assert main([str(p), "--out", str(out2), "--checkpoint", str(ck2),
                 "--max-steps", "1"]) == 0
    assert not json.loads(out2.read_text())["finished"]
    assert main(["--resume", str(ck2), "--out", str(out2)]) == 0
    res2 = json.loads(out2.read_text())
    assert res2["finished"]
    assert res2["hv"] == res["hv"]           # same spec, same seed
