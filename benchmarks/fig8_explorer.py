"""Fig. 8 reproduction: explorer efficiency — random search vs MOBO vs
MFMOBO (hypervolume vs iteration, averaged over seeds), expressed as
declarative campaigns (repro.explore, DESIGN.md §9): each method/seed cell
is a `CampaignSpec` — workload, strategy, fidelity schedule, budget — run
through the `Campaign` runner. f1 = analytical, f0 = GNN-based evaluation,
exactly as the paper runs its loop, on the batched fidelity backends with
q-point greedy q-EHVI proposals and the cross-call eval cache. The MFMOBO
campaign declares `calibrate_on_handover`: simulator traces from the
current Pareto neighborhood fine-tune the pre-trained GNN checkpoint
before f0 spends the rest of the budget. Reports candidates/sec and
per-fidelity-stage cache hit-rates.
"""
from __future__ import annotations

import dataclasses
import os
import pickle
import shutil
import tempfile
import time
from typing import Dict

import numpy as np

from benchmarks.common import save_artifact, trained_gnn
from repro.core.evaluator import evaluate_objectives_batch
from repro.core.mfmobo import hv_ref, obj_space, warm_optimizer_kernels
from repro.core.pareto import hypervolume_2d
from repro.core.workload import GPT_BENCHMARKS
from repro.explore import Campaign, CampaignSpec, FidelitySchedule


def method_specs(workload: str, seed: int, *, N0: int, N1: int, cand: int,
                 q: int, quick: bool) -> Dict[str, CampaignSpec]:
    """The three Fig. 8 method cells as campaign specs (same budgets and
    seeds as the pre-campaign hand-wired loops)."""
    gnn_f0 = FidelitySchedule(f1="analytical", f0="gnn", d1=3, d0=3, k=0)
    return {
        "random": CampaignSpec(
            name=f"fig8-random-s{seed}", workload=workload,
            scenario="train", strategy="random", fidelity=gnn_f0,
            n_evals_f0=N0, q=N0, seed=seed),   # q=N0: one batched GNN pass
        "mobo": CampaignSpec(
            name=f"fig8-mobo-s{seed}", workload=workload, scenario="train",
            strategy="mobo", fidelity=gnn_f0, n_evals_f0=N0, q=q,
            n_candidates=cand, seed=seed),
        "mfmobo": CampaignSpec(
            name=f"fig8-mfmobo-s{seed}", workload=workload,
            scenario="train", strategy="mfmobo",
            fidelity=FidelitySchedule(
                f1="analytical", f0="gnn", d1=3, d0=2, k=3,
                calibrate_on_handover=True,
                calibration={"n_designs": 3 if quick else 6,
                             "epochs": 5 if quick else 15}),
            n_evals_f0=N0, n_evals_f1=N1, q=q, n_candidates=cand,
            seed=seed),
    }


def fleet_probe(quick: bool, gnn_params) -> Dict:
    """Fleet-scale grid execution probe (DESIGN.md §11): the fig8
    method×seed grid run three ways —

        serial-cold  one fresh worker process per campaign, no shared
                     eval cache: what the grid costs when each campaign is
                     an independent job (the pre-fleet deployment shape);
        fleet-cold   `workers` persistent processes sharing the on-disk
                     eval cache;
        fleet-warm   the same fleet re-run against the now-populated
                     persistent caches (fresh checkpoints, so every
                     campaign genuinely re-evaluates) — measures the
                     cross-campaign eval-cache hit-rate.

    Every process keeps its XLA compiles in the persistent compilation
    cache (`repro.compile_cache`). Fleet workers need a parent that holds
    no accelerator: this process trains the GNN first, so on a TPU host the
    probe is not run and says so.
    """
    from repro.explore.fleet import FleetSpec, parent_platform, run_fleet

    held = parent_platform()
    if held not in (None, "cpu"):
        return {"skipped": f"this process holds the {held} devices; fleet "
                           "workers spawned from it could not get them"}

    wl = GPT_BENCHMARKS[0]
    seeds = (0,) if quick else (0, 1, 2)
    N0 = 8 if quick else 14
    N1 = 10 if quick else 18
    cand = 48 if quick else 96
    q = 2 if quick else 4
    workers = 2 if quick else 4

    root = tempfile.mkdtemp(prefix="fig8fleet-")
    params_path = os.path.join(root, "gnn_params.pkl")
    with open(params_path, "wb") as f:
        pickle.dump(gnn_params, f)
    campaigns = []
    for seed in seeds:
        for spec in method_specs(wl.name, seed, N0=N0, N1=N1, cand=cand,
                                 q=q, quick=quick).values():
            fid = dataclasses.replace(spec.fidelity,
                                      params_path=params_path)
            campaigns.append(dataclasses.replace(
                spec, name="fleet-" + spec.name, fidelity=fid))
    try:
        # serial-cold baseline: a fresh spawned process per campaign,
        # nothing shared — every campaign pays imports + XLA compiles
        t0 = time.time()
        serial_evals = 0
        for i, c in enumerate(campaigns):
            r = run_fleet(FleetSpec(name=f"serial-{i}", campaigns=(c,),
                                    workers=1))
            if r.errors:
                raise RuntimeError(f"serial baseline failed: {r.errors}")
            serial_evals += r.n_evals
        serial_wall = time.time() - t0

        fs = FleetSpec(
            name="fig8-fleet", campaigns=tuple(campaigns), workers=workers,
            host_devices=2,          # shard eval batches across 2 XLA lanes
            cache_dir=os.path.join(root, "evalcache"),
            checkpoint_dir=os.path.join(root, "ck"), checkpoint_every=2)
        cold = run_fleet(fs)
        if cold.errors:
            raise RuntimeError(f"fleet run failed: {cold.errors}")
        # fresh checkpoint dir: same campaigns recompute their evaluations
        # against the persistent eval cache the cold pass populated
        warm = run_fleet(dataclasses.replace(
            fs, name="fig8-fleet-warm",
            checkpoint_dir=os.path.join(root, "ck-warm")))
        if warm.errors:
            raise RuntimeError(f"warm fleet run failed: {warm.errors}")
    finally:
        shutil.rmtree(root, ignore_errors=True)

    f0 = {"hits": 0, "misses": 0}
    for c in warm.campaigns:
        sc = (c or {}).get("stage_cache", {}).get("f0", {})
        f0["hits"] += sc.get("hits", 0)
        f0["misses"] += sc.get("misses", 0)
    warm_hit = f0["hits"] / max(f0["hits"] + f0["misses"], 1)
    # per-lane evaluator utilization, aggregated over every worker's
    # campaigns (each worker reports its process-local lane counters)
    lanes = {"n_lanes": 0, "sharded_calls": 0, "rows_sharded": 0,
             "jit_calls": 0, "rows_jit": 0}
    for c in list(cold.campaigns) + list(warm.campaigns):
        el = (c or {}).get("eval_lanes") or {}
        lanes["n_lanes"] = max(lanes["n_lanes"], el.get("n_lanes", 0))
        for k in ("sharded_calls", "rows_sharded", "jit_calls", "rows_jit"):
            lanes[k] += el.get(k, 0)
    return {
        "workers": workers,
        "host_devices": 2,
        "eval_lanes": lanes,
        "n_campaigns": len(campaigns),
        "n_evals": cold.n_evals,
        "serial_cold_wall_s": serial_wall,
        "fleet_wall_s": cold.wall_s,
        "fleet_warm_wall_s": warm.wall_s,
        "fleet_speedup": serial_wall / max(cold.wall_s, 1e-9),
        "fleet_warm_speedup": serial_wall / max(warm.wall_s, 1e-9),
        "fleet_candidates_per_sec": cold.fleet_candidates_per_sec,
        "fleet_warm_candidates_per_sec": warm.fleet_candidates_per_sec,
        "warm_f0_hit_rate": warm_hit,
        "crashes": cold.crashes + warm.crashes,
    }


def run(quick: bool = False) -> Dict:
    gnn, _ = trained_gnn(quick=quick)
    wl = GPT_BENCHMARKS[0]            # GPT-1.7B (paper also shows 175B/530B)
    seeds = (0,) if quick else (0, 1, 2)
    N0 = 8 if quick else 14
    N1 = 10 if quick else 18
    cand = 48 if quick else 96
    q = 2 if quick else 4
    curves = {"random": [], "mobo": [], "mfmobo": []}
    sim_hv = {"random": [], "mobo": [], "mfmobo": []}
    n_evals = 0
    calib_records = []
    stage_cache = {"f0": {"hits": 0, "misses": 0, "entries_added": 0},
                   "f1": {"hits": 0, "misses": 0, "entries_added": 0}}
    # compile the jitted optimizer programs (GP pair fit, scanned q-EHVI
    # acquire) for every pow2 capacity bucket the campaigns will touch, so
    # the timed wall below measures proposal throughput, not XLA compiles
    t0 = time.time()
    n_buckets = warm_optimizer_kernels(max(N0, N1), n_candidates=cand, q=q,
                                       workload=wl,
                                       n_designs_max=max(N0, N1))
    print(f"  optimizer+evaluator warmup: {n_buckets} shape buckets "
          f"compiled in {time.time()-t0:.1f}s")
    t_all = time.time()

    def hv_under_sim(trace):
        """Ground-truth final hypervolume: re-score every design the method
        evaluated with the (batched) simulator. mfmobo's own hv curve is
        measured by a GNN that calibration changes mid-run, so cross-method
        comparisons need one common instrument."""
        ys = evaluate_objectives_batch(trace.designs, wl, "sim")
        return hypervolume_2d(obj_space(ys), hv_ref(15000.0))

    for seed in seeds:
        t0 = time.time()
        specs = method_specs(wl.name, seed, N0=N0, N1=N1, cand=cand, q=q,
                             quick=quick)
        results = {m: Campaign(spec, gnn_params=gnn).run()
                   for m, spec in specs.items()}
        for m, r in results.items():
            curves[m].append(r.trace.hv)
            sim_hv[m].append(hv_under_sim(r.trace))
            n_evals += r.n_evals
            for stage, sc in r.stage_cache.items():
                for k in ("hits", "misses", "entries_added"):
                    stage_cache[stage][k] += sc.get(k, 0)
        for rec in results["mfmobo"].calibration:
            calib_records.append(dict(rec, seed=seed))
        print(f"  seed {seed}: {time.time()-t0:.0f}s  final hv "
              + " ".join(f"{m}={r.trace.hv[-1]:.2f}"
                         for m, r in results.items()))
    wall_s = time.time() - t_all

    def avg(tag):
        n = min(len(c) for c in curves[tag])
        return np.mean([c[:n] for c in curves[tag]], axis=0).tolist()

    out = {k: avg(k) for k in curves}
    # convergence speed: iterations for mobo to reach mfmobo's mid hv
    tgt = out["mfmobo"][len(out["mfmobo"]) // 2]
    it_f = next((i for i, h in enumerate(out["mfmobo"]) if h >= tgt),
                len(out["mfmobo"]))
    it_m = next((i for i, h in enumerate(out["mobo"]) if h >= tgt),
                len(out["mobo"]))
    out["convergence_speedup_vs_mobo"] = (it_m + 1) / (it_f + 1)
    hv_gain = (out["mfmobo"][min(len(out["mobo"]), len(out["mfmobo"])) - 1]
               / max(out["mobo"][-1], 1e-9) - 1.0)
    out["hv_improvement_at_equal_iters"] = hv_gain
    out["q"] = q
    out["n_evaluations"] = n_evals
    out["calibration"] = calib_records
    out["hv_sim_final"] = {k: float(np.mean(v)) for k, v in sim_hv.items()}
    out["wall_s"] = wall_s
    out["candidates_per_sec"] = n_evals / max(wall_s, 1e-9)
    out["eval_cache"] = {
        k: stage_cache["f0"][k] + stage_cache["f1"][k]
        for k in ("hits", "misses")}
    out["stage_cache"] = {
        stage: dict(sc, hit_rate=sc["hits"] / max(sc["hits"] + sc["misses"],
                                                  1))
        for stage, sc in stage_cache.items()}
    out["campaigns"] = sorted(s.name for s in method_specs(
        wl.name, seeds[0], N0=N0, N1=N1, cand=cand, q=q,
        quick=quick).values())
    print("\n  fleet probe: serial-cold vs shared-cache workers "
          "(repro.explore.fleet)...")
    out["fleet"] = fleet_probe(quick, gnn)
    save_artifact("fig8_explorer", out)
    print("\n=== Fig.8: explorer efficiency (avg hypervolume) ===")
    for k in ("random", "mobo", "mfmobo"):
        print(f"{k:8s} " + " ".join(f"{h:7.2f}" for h in out[k]))
    print(f"MFMOBO convergence speedup vs MOBO: "
          f"{out['convergence_speedup_vs_mobo']:.2f}x; "
          f"HV improvement at equal iterations: {100*hv_gain:.0f}%")
    print("final hv re-scored under sim (common instrument): "
          + "  ".join(f"{k}={v:.2f}" for k, v in out["hv_sim_final"].items()))
    print(f"explorer throughput: {out['candidates_per_sec']:.2f} "
          f"evaluated candidates/sec (q={q}, {n_evals} evals in "
          f"{wall_s:.0f}s)")
    for stage, sc in out["stage_cache"].items():
        print(f"eval cache [{stage}]: {sc['hits']}/{sc['hits']+sc['misses']}"
              f" hits ({100*sc['hit_rate']:.0f}%)")
    fl = out["fleet"]
    if "skipped" in fl:
        print(f"fleet probe not run: {fl['skipped']}")
        return out
    print(f"fleet [{fl['workers']} workers, {fl['n_campaigns']} campaigns]: "
          f"serial-cold {fl['serial_cold_wall_s']:.0f}s -> fleet "
          f"{fl['fleet_wall_s']:.0f}s ({fl['fleet_speedup']:.1f}x, "
          f"{fl['fleet_candidates_per_sec']:.2f} candidates/sec); warm "
          f"re-run {fl['fleet_warm_wall_s']:.0f}s "
          f"({fl['fleet_warm_speedup']:.1f}x) with "
          f"{100*fl['warm_f0_hit_rate']:.0f}% f0 cache hits")
    return out


if __name__ == "__main__":
    run()
