"""Benchmark harness: one benchmark per paper table/figure + the roofline
table from the dry-run artifacts.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig7,...]

Every invocation also measures the batched-vs-serial evaluator speedup and
writes `BENCH_dse.json` at the repo root (per-benchmark wall time, explorer
candidates/sec, key result metrics) so the perf trajectory is tracked
across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

BENCHES = ("fig7", "fig8", "fig9", "fig10", "fig11", "fig11b", "fig11c",
           "fig12", "fig13", "roofline")

_MODULES = {
    "fig7": "benchmarks.fig7_eval_models",
    "fig8": "benchmarks.fig8_explorer",
    "fig9": "benchmarks.fig9_core_granularity",
    "fig10": "benchmarks.fig10_reticle_granularity",
    "fig11": "benchmarks.fig11_inference",
    "fig11b": "benchmarks.fig11b_serving",
    "fig11c": "benchmarks.fig11c_trace_serving",
    "fig12": "benchmarks.fig12_heterogeneity",
    "fig13": "benchmarks.fig13_dse",
    "roofline": "benchmarks.roofline_table",
}

# result keys worth tracking across PRs (when a benchmark reports them).
# "campaigns" / "stage_cache" carry per-campaign wall-clock, candidates/sec
# and per-fidelity-stage eval-cache hit-rates (DESIGN.md §9) so campaign
# cost — including the f1->f0 handover — is visible in BENCH_dse.json.
_TRACKED_KEYS = ("candidates_per_sec", "n_evaluations", "wall_s", "q",
                 "convergence_speedup_vs_mobo", "hv_improvement_at_equal_iters",
                 "hv_sim_final", "calibration", "batched_candidates_per_sec",
                 "n_points", "workload", "eval_cache",
                 "serving_front", "goodput_best", "slo", "explorer",
                 "hetero_serving", "campaigns", "stage_cache", "fleet",
                 "eval_lanes", "trace_serving", "chat_slo")

BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_dse.json")


def measure_batch_speedup(n_designs: int = 64, max_strategies: int = 24,
                          serial_subset: int = 8):
    """Acceptance probe, one record per registered fidelity backend:
    evaluate_design_batch on n_designs candidates vs serial evaluate_design
    calls (cold caches for both), on the quick GPT-1.7B workload.

    The analytical serial loop runs all n_designs; the gnn/sim serial loops
    are slow enough that they run a `serial_subset` prefix and extrapolate
    candidates/sec (recorded as n_designs_serial). Agreement is always
    checked on the designs both paths evaluated."""
    import jax

    from benchmarks.common import sample_valid_designs
    from repro.core.evaluator import (clear_eval_cache, evaluate_design,
                                      evaluate_design_batch)
    from repro.core.noc_gnn import init_gnn
    from repro.core.workload import GPT_BENCHMARKS

    from repro.core import eval_compiled

    wl = GPT_BENCHMARKS[0]
    designs = sample_valid_designs(n_designs, seed=1234)
    gnn_params = init_gnn(jax.random.PRNGKey(0))
    # pre-compile the analytical evaluator buckets (DESIGN.md §12) so the
    # timed analytical batch measures the jitted pipeline, not its compile
    eval_compiled.warm_evaluator_kernels(wl, n_designs_max=n_designs,
                                         max_strategies=max_strategies)
    # warm the jitted GNN kernels so the probe times steady-state math, not
    # one-off XLA compilation (which the serial path amortizes too). The
    # warm-up must run the FULL design batch: smaller prefixes miss the
    # larger pow-2 feature buckets / grid patterns the timed batch hits,
    # leaving recompilation inside the timed region.
    evaluate_design_batch(designs, wl, fidelity="gnn",
                          gnn_params=gnn_params,
                          max_strategies=max_strategies)
    [evaluate_design(d, wl, fidelity="gnn", gnn_params=gnn_params,
                     max_strategies=max_strategies) for d in designs[:1]]

    out = {}
    for fidelity in ("analytical", "gnn", "sim"):
        kw = {"gnn_params": gnn_params} if fidelity == "gnn" else {}
        n_serial = n_designs if fidelity == "analytical" else serial_subset
        clear_eval_cache()
        t0 = time.perf_counter()
        serial = [evaluate_design(d, wl, fidelity=fidelity,
                                  max_strategies=max_strategies, **kw)
                  for d in designs[:n_serial]]
        serial_s = time.perf_counter() - t0
        clear_eval_cache()
        t0 = time.perf_counter()
        batch = evaluate_design_batch(designs, wl, fidelity=fidelity,
                                      max_strategies=max_strategies, **kw)
        batch_s = time.perf_counter() - t0
        agree = all(
            a.feasible == b.feasible
            and (not a.feasible
                 or abs(a.throughput - b.throughput)
                 <= 1e-5 * abs(a.throughput))
            for a, b in zip(serial, batch))
        cps_serial = n_serial / max(serial_s, 1e-9)
        cps_batch = n_designs / max(batch_s, 1e-9)
        out[fidelity] = {
            "n_designs": n_designs,
            "n_designs_serial": n_serial,
            "workload": wl.name,
            "serial_s": serial_s,
            "batch_s": batch_s,
            "speedup": cps_batch / max(cps_serial, 1e-9),
            "candidates_per_sec_batch": cps_batch,
            "candidates_per_sec_serial": cps_serial,
            "scalar_batch_agree": agree,
        }
    return out


def measure_proposal_rate(n_obs: int = 16, n_candidates: int = 96,
                          q: int = 4, iters: int = 20):
    """Optimizer-only acceptance probe: one full MFMOBO proposal iteration
    = GP pair refit on the observation set + greedy q-EHVI acquisition over
    the candidate pool (posterior predict + EHVI + q rank-1 fantasizations),
    with evaluation excluded — i.e. the jitted hot path of DESIGN.md §10.
    Kernels are warmed first so the probe times steady-state proposals, not
    XLA compilation."""
    import numpy as np

    from repro.core.design_space import DIMS
    from repro.core.mfmobo import (_acquire_batch, _fit_models, hv_ref,
                                   obj_space, warm_optimizer_kernels)

    warm_optimizer_kernels(n_obs, n_candidates=n_candidates, q=q)
    rng = np.random.default_rng(7)
    X = rng.random((n_obs, len(DIMS)))
    Y = np.stack([1e3 * (1.0 + rng.random(n_obs)),
                  1e3 * (2.0 + rng.random(n_obs))], 1)
    ev = obj_space([tuple(y) for y in Y])
    ref = hv_ref(15000.0)
    cands = rng.random((iters, n_candidates, len(DIMS)))
    t0 = time.perf_counter()
    for i in range(iters):
        models = _fit_models(X, Y)
        _acquire_batch(models, cands[i], ev, ref, q=q)
    wall = time.perf_counter() - t0
    return {
        "n_obs": n_obs,
        "n_candidates": n_candidates,
        "q": q,
        "iters": iters,
        "wall_s": wall,
        "proposals_per_sec": iters / max(wall, 1e-9),
    }


def measure_fused_iteration_rate(n_obs: int = 16, n_candidates: int = 96,
                                 q: int = 4, iters: int = 20):
    """Fused-iteration acceptance probe (DESIGN.md §12): one synchronous
    MFMOBO f1 iteration end to end — GP pair refit, scanned q-EHVI acquire,
    candidate decode, and compiled analytical evaluation of the q picks
    gathered by device-resident indices (no host sync between proposal and
    evaluation). Kernels (optimizer AND evaluator) are warmed first, so the
    probe times the steady-state fused loop."""
    import numpy as np

    from repro.core import eval_compiled
    from repro.core.design_space import DIMS, decode_batch
    from repro.core.evaluator import clear_eval_cache, evaluate_pool_fused
    from repro.core.mfmobo import (_acquire_batch_device, _fit_models,
                                   hv_ref, obj_space, warm_optimizer_kernels)
    from repro.core.workload import GPT_BENCHMARKS

    if not eval_compiled.enabled():
        return {"status": "disabled"}
    wl = GPT_BENCHMARKS[0]
    warm_optimizer_kernels(n_obs, n_candidates=n_candidates, q=q,
                           workload=wl, n_designs_max=q)
    rng = np.random.default_rng(7)
    X = rng.random((n_obs, len(DIMS)))
    Y = np.stack([1e3 * (1.0 + rng.random(n_obs)),
                  1e3 * (2.0 + rng.random(n_obs))], 1)
    ev = obj_space([tuple(y) for y in Y])
    ref = hv_ref(15000.0)
    cands = rng.random((iters, n_candidates, len(DIMS)))
    clear_eval_cache()
    t0 = time.perf_counter()
    for i in range(iters):
        models = _fit_models(X, Y)
        cand_d = decode_batch(cands[i])
        js_dev = _acquire_batch_device(models, cands[i], ev, ref, q=q)
        evaluate_pool_fused(cand_d, wl, js_dev, q)
    wall = time.perf_counter() - t0
    return {
        "n_obs": n_obs,
        "n_candidates": n_candidates,
        "q": q,
        "iters": iters,
        "wall_s": wall,
        "iterations_per_sec": iters / max(wall, 1e-9),
        "candidates_per_sec": iters * q / max(wall, 1e-9),
        "eval_lanes": eval_compiled.lane_stats(),
    }


def measure_joint_vs_grid(seed: int = 2, n0: int = 8, n1: int = 10,
                          q: int = 2, n_candidates: int = 32):
    """Strategy-architecture co-exploration acceptance probe (DESIGN.md
    §13): two MFMOBO campaigns on the GPT-175B train workload with the
    same seed and budget — one scoring each design at the argmin of the
    frozen per-design strategy grid (`strategy_mode="grid"`), one
    searching the joint (architecture, Strategy) space
    (`strategy_mode="joint"`). Records both final hypervolumes, plus a
    bit-exactness check that the joint pinned-evaluation path replays the
    grid run's winning strategies to identical objectives (the contract
    that makes the two hypervolumes comparable at all)."""
    from repro.core.design_space import JointDesign
    from repro.core.evaluator import (clear_eval_cache, evaluate_design_batch,
                                      evaluate_joint_batch)
    from repro.explore import Campaign, CampaignSpec, FidelitySchedule
    from repro.explore.campaign import resolve_workload

    def mk(mode):
        return CampaignSpec(
            name=f"joint-vs-grid-{mode}", workload="GPT-175B",
            scenario="train", strategy="mfmobo",
            fidelity=FidelitySchedule(f1="analytical", f0="analytical",
                                      d1=2, d0=2, k=2),
            n_evals_f0=n0, n_evals_f1=n1, q=q, n_candidates=n_candidates,
            seed=seed, strategy_mode=mode)

    out = {"workload": "GPT-175B", "seed": seed,
           "n_evals_f0": n0, "n_evals_f1": n1, "q": q}
    runs = {}
    for mode in ("grid", "joint"):
        clear_eval_cache()
        t0 = time.perf_counter()
        runs[mode] = Campaign(mk(mode)).run()
        out[f"hv_{mode}"] = float(runs[mode].hv_final)
        out[f"wall_s_{mode}"] = time.perf_counter() - t0
    # replay contract: pinning each grid-evaluated design to its own grid
    # argmin strategy through the joint path must reproduce the grid
    # objectives bit-for-bit
    wl = resolve_workload(mk("grid"))
    designs = list(runs["grid"].trace.designs)
    grid_r = evaluate_design_batch(designs, wl)
    pts = [JointDesign(d, r.strategy)
           for d, r in zip(designs, grid_r) if r.feasible]
    joint_r = evaluate_joint_batch(pts, wl)
    out["pinned_matches_grid"] = bool(pts) and all(
        b.feasible and a.throughput == b.throughput
        for a, b in zip([r for r in grid_r if r.feasible], joint_r))
    out["n_replayed"] = len(pts)
    return out


def write_bench_json(records, quick: bool, speedup, optimizer=None,
                     fused=None, joint_vs_grid=None):
    # merge into the existing file so an `--only` subset run refreshes its
    # own records without wiping the other benchmarks' tracked history
    merged = {}
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                merged = json.load(f).get("benchmarks", {})
        except (json.JSONDecodeError, OSError):
            merged = {}
    merged.update(records)
    data = {
        "generated_unix_s": time.time(),
        "quick": quick,
        "batch_eval": speedup,
        "optimizer": optimizer or {"status": "failed"},
        "fused_iteration": fused or {"status": "failed"},
        "joint_vs_grid": joint_vs_grid or {"status": "failed"},
        "benchmarks": merged,
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(data, f, indent=1, default=float)
    return BENCH_JSON


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sample counts (CI-speed)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(BENCHES)
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()

    failures = []
    records = {}
    for name in names:
        mod_name = _MODULES[name.strip()]
        print(f"\n{'='*70}\nRunning {mod_name} (quick={args.quick})\n{'='*70}",
              flush=True)
        t0 = time.time()
        try:
            import importlib
            mod = importlib.import_module(mod_name)
            result = mod.run(quick=args.quick)
            wall = time.time() - t0
            rec = {"wall_s": wall, "status": "ok", "quick": args.quick}
            if isinstance(result, dict):
                rec["metrics"] = {k: result[k] for k in _TRACKED_KEYS
                                  if k in result}
            records[name] = rec
            print(f"[{name}] done in {wall:.0f}s", flush=True)
        except Exception:
            traceback.print_exc()
            records[name] = {"wall_s": time.time() - t0, "status": "failed",
                             "quick": args.quick}
            failures.append(name)

    print(f"\n{'='*70}\nMeasuring batched-evaluator speedup (all fidelities)"
          f"\n{'='*70}", flush=True)
    try:
        speedup = measure_batch_speedup()
        for fid, rec in speedup.items():
            print(f"{fid:12s}: {rec['n_designs']} designs in "
                  f"{rec['batch_s']:.3f}s batched -> {rec['speedup']:.0f}x "
                  f"vs serial ({rec['candidates_per_sec_batch']:.0f} "
                  f"candidates/sec batched, "
                  f"{rec['candidates_per_sec_serial']:.1f} serial)")
            if not rec["scalar_batch_agree"]:
                print(f"{fid} batch eval DISAGREES with serial evaluation")
                failures.append(f"batch_vs_serial_agreement_{fid}")
        if speedup["gnn"]["speedup"] < 20.0:
            print("gnn batched speedup below the 20x acceptance floor")
            failures.append("gnn_batch_speedup_floor")
    except Exception:
        traceback.print_exc()
        speedup = {"status": "failed"}
        failures.append("batch_speedup")

    print(f"\n{'='*70}\nMeasuring compiled-optimizer proposal rate"
          f"\n{'='*70}", flush=True)
    try:
        optimizer = measure_proposal_rate()
        print(f"optimizer   : {optimizer['iters']} proposal iterations "
              f"(refit + q={optimizer['q']} acquire over "
              f"{optimizer['n_candidates']} candidates) in "
              f"{optimizer['wall_s']:.3f}s -> "
              f"{optimizer['proposals_per_sec']:.1f} proposals/sec")
        if optimizer["proposals_per_sec"] < 2.0:
            print("optimizer proposal rate below the 2/sec acceptance floor")
            failures.append("optimizer_proposal_rate_floor")
    except Exception:
        traceback.print_exc()
        optimizer = {"status": "failed"}
        failures.append("proposal_rate")

    print(f"\n{'='*70}\nMeasuring fused propose->evaluate iteration rate"
          f"\n{'='*70}", flush=True)
    try:
        fused = measure_fused_iteration_rate()
        if fused.get("status") == "disabled":
            print("compiled evaluator disabled (REPRO_COMPILED_EVAL=0); "
                  "fused probe skipped")
        else:
            print(f"fused       : {fused['iters']} fused iterations "
                  f"(refit + q={fused['q']} acquire + compiled analytical "
                  f"eval) in {fused['wall_s']:.3f}s -> "
                  f"{fused['candidates_per_sec']:.1f} evaluated "
                  f"candidates/sec")
            if fused["candidates_per_sec"] < 8.0:
                print("fused-iteration candidates/sec below the 8/sec "
                      "acceptance floor")
                failures.append("fused_iteration_rate_floor")
    except Exception:
        traceback.print_exc()
        fused = {"status": "failed"}
        failures.append("fused_iteration_rate")

    print(f"\n{'='*70}\nMeasuring joint-vs-grid strategy co-exploration"
          f"\n{'='*70}", flush=True)
    try:
        jvg = measure_joint_vs_grid()
        print(f"joint-vs-grid [{jvg['workload']}, seed {jvg['seed']}]: "
              f"grid hv={jvg['hv_grid']:.2f} "
              f"({jvg['wall_s_grid']:.0f}s)  joint hv={jvg['hv_joint']:.2f} "
              f"({jvg['wall_s_joint']:.0f}s)  pinned replay "
              f"{'matches' if jvg['pinned_matches_grid'] else 'DIVERGES'} "
              f"({jvg['n_replayed']} points)")
        if not jvg["pinned_matches_grid"]:
            print("joint pinned path does not replay the grid argmin "
                  "strategies bit-exactly")
            failures.append("joint_pinned_replay_mismatch")
        if jvg["hv_joint"] < jvg["hv_grid"]:
            print("joint-campaign hypervolume below the grid-campaign floor")
            failures.append("joint_vs_grid_hv_floor")
    except Exception:
        traceback.print_exc()
        jvg = {"status": "failed"}
        failures.append("joint_vs_grid")

    # fleet acceptance floors (DESIGN.md §11): the fig8 fleet probe must
    # sustain a minimum evaluated-candidate rate and the warm second pass
    # over the persistent eval cache must actually hit it
    fleet = (records.get("fig8", {}).get("metrics", {}) or {}).get("fleet")
    if fleet and "skipped" not in fleet:
        if fleet["fleet_candidates_per_sec"] < 0.2:
            print("fleet candidates/sec below the 0.2/sec acceptance floor")
            failures.append("fleet_candidates_per_sec_floor")
        if fleet["warm_f0_hit_rate"] <= 0.5:
            print("warm-fleet f0 cache hit-rate below the 50% floor "
                  f"({100 * fleet['warm_f0_hit_rate']:.0f}%)")
            failures.append("fleet_warm_cache_hit_rate_floor")

    # trace-serving acceptance floors (DESIGN.md §14): the spike trace must
    # produce positive worst-window interactive goodput somewhere, and some
    # non-FIFO policy must beat FIFO at equal power (same design)
    tsv = (records.get("fig11c", {}).get("metrics", {}) or {}) \
        .get("trace_serving")
    if tsv:
        if tsv["worst_window_goodput_best"] <= 0.0:
            print("trace-serving worst-window goodput floor violated "
                  "(no design/policy sustains chat goodput through the spike)")
            failures.append("trace_serving_goodput_floor")
        if not tsv["policy_beats_fifo"]:
            print("no non-FIFO policy beats FIFO on worst-window goodput "
                  "at equal power")
            failures.append("trace_serving_policy_vs_fifo_floor")

    path = write_bench_json(records, args.quick, speedup, optimizer, fused,
                            jvg)
    print(f"wrote {path}")

    if failures:
        print("\nFAILED:", failures)
        sys.exit(1)
    print("\nAll benchmarks completed.")


if __name__ == "__main__":
    main()
