"""Fig. 11(b) extension: request-level serving evaluation for GPT-175B.

The paper's headline inference numbers come from serving workloads, but the
per-figure benchmarks score isolated prefill/decode steps. This benchmark
runs the request-level continuous-batching model (repro.core.serving: the
request mix as the degenerate trace of repro.core.traces, DESIGN.md §8)
end to end:

  (1) a design sweep scored on (SLO goodput, power) — the serving Pareto
      front, with the SLO calibrated from the sampled designs' median
      TTFT/TPOT so it binds for roughly half the pool;
  (2) an SLO-constrained MOBO exploration as a declarative serving
      campaign (repro.explore, DESIGN.md §9): the calibrated SLO becomes
      `ConstraintSpec`s on TTFT/TPOT, so violating candidates are mapped to
      the penalty point and excluded from the front;
  (3) the heterogeneity re-score: the same prefill/decode disaggregation as
      Fig. 12, under the coupled request model instead of rate matching.

Artifacts land in benchmarks/artifacts/fig11b_serving.json; the goodput
front + explorer stats are tracked in BENCH_dse.json.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks.common import sample_valid_designs, save_artifact
from repro.core.design_space import WSCDesign
from repro.core.heterogeneity import evaluate_hetero_serving_batch
from repro.core.pareto import pareto_front, to_max_space
from repro.core.serving import ServingSLO
from repro.core.traces import (
    RequestTrace,
    TenantClass,
    evaluate_pool_serving_batch,
)
from repro.core.validator import validate
from repro.core.workload import GPT_BENCHMARKS, RequestMix
from repro.explore import (
    Campaign,
    CampaignSpec,
    ConstraintSpec,
    FidelitySchedule,
    ServingSpec,
)


def explorer_spec(workload: str, mix: RequestMix, slo: ServingSLO,
                  slots: int, quick: bool) -> CampaignSpec:
    """The SLO-constrained exploration as a campaign: the probe-calibrated
    SLO lands both in the goodput objective (via the serving spec) and as
    hard TTFT/TPOT constraints."""
    return CampaignSpec(
        name="fig11b-serving-slo", workload=workload, scenario="serving",
        strategy="mobo",
        constraints=(ConstraintSpec("ttft", "<=", slo.ttft_s),
                     ConstraintSpec("tpot", "<=", slo.tpot_s)),
        fidelity=FidelitySchedule(f0="analytical", d0=4, k=0),
        n_evals_f0=8 if quick else 20, q=4, seed=3,
        max_strategies=8,
        serving=ServingSpec(
            n_requests=mix.n_requests,
            prompt_len=int(mix.prompt_lens[0]),
            out_len=int(mix.out_lens[0]), slots=slots,
            ttft_s=slo.ttft_s, tpot_s=slo.tpot_s))


def run(quick: bool = False) -> Dict:
    wl = GPT_BENCHMARKS[7]                          # GPT-175B
    n_req, out_len = (16, 64) if quick else (32, 256)
    mix = RequestMix.uniform(n_req, prompt_len=2048, out_len=out_len)
    slots = 8

    # ---- (1) design sweep + SLO calibration ----------------------------
    designs = sample_valid_designs(12 if quick else 48, seed=11)

    def score(slo):
        trace = RequestTrace.from_mix(mix, TenantClass.from_slo(slo))
        return evaluate_pool_serving_batch(designs, wl, trace, slots=slots,
                                           max_strategies=8)

    probe = score(ServingSLO(1e9, 1e9))
    feas = [r for r in probe if r.feasible]
    if not feas:
        raise RuntimeError("no feasible serving design in the probe pool")
    slo = ServingSLO(
        ttft_s=float(np.median([r.ttft_s for r in feas])),
        tpot_s=float(np.median([r.tpot_s for r in feas])))
    scored = score(slo)
    rows = [{"goodput_tok_s": r.goodput_tok_s, "power_w": r.power_w,
             "ttft_s": r.ttft_s, "tpot_s": r.tpot_s,
             "slo_attainment": r.slo_attainment, "n_wafers": r.n_wafers}
            for r in scored if r.feasible]
    # zero-goodput designs are feasible but serve nothing within the SLO —
    # they would pad the front with useless lowest-power points
    good = np.array([r["goodput_tok_s"] for r in rows
                     if r["goodput_tok_s"] > 0])
    pw = np.array([max(r["power_w"], 1.0) for r in rows
                   if r["goodput_tok_s"] > 0])
    front_pts = pareto_front(to_max_space(good, pw))   # (goodput, -power)
    front = [{"goodput_tok_s": float(t), "power_w": float(-p)}
             for t, p in front_pts]

    # ---- (2) SLO-constrained exploration (campaign) --------------------
    spec = explorer_spec(wl.name, mix, slo, slots, quick)
    res = Campaign(spec).run()
    tr = res.trace
    explored_best = max((y[0] for y in tr.ys), default=0.0)

    # ---- (3) heterogeneity, coupled request model ----------------------
    d_prefill = validate(WSCDesign(
        dataflow="WS", mac_num=1024, buffer_kb=256, buffer_bw=1024,
        noc_bw=512, core_array=(10, 10), inter_reticle_bw_ratio=1.0,
        use_stacked_dram=True, dram_bw_tbps_per_100mm2=0.5,
        reticle_array=(8, 8), integration="infosow")).design
    d_decode = validate(WSCDesign(
        dataflow="WS", mac_num=256, buffer_kb=128, buffer_bw=1024,
        noc_bw=512, core_array=(9, 9), inter_reticle_bw_ratio=1.0,
        use_stacked_dram=True, dram_bw_tbps_per_100mm2=2.0,
        reticle_array=(8, 8), integration="infosow")).design
    hetero = []
    for gran in ("core", "reticle", "wafer"):
        dp = d_decode if gran == "core" else d_prefill
        (h,) = evaluate_hetero_serving_batch([dp], [d_decode], wl, gran, 0.5,
                                             mix, slo, slots=slots,
                                             n_wafers=8)
        hetero.append({"granularity": gran,
                       "goodput_tok_s": h.goodput_tok_s,
                       "ttft_s": h.ttft_s, "tpot_s": h.tpot_s,
                       "slo_attainment": h.slo_attainment,
                       "kv_transfer_s": h.kv_transfer_s})

    out = {
        "workload": wl.name,
        "mix": {"n_requests": mix.n_requests, "prompt_len": 2048,
                "out_len": out_len, "slots": slots},
        "slo": {"ttft_s": slo.ttft_s, "tpot_s": slo.tpot_s},
        "sweep": rows,
        "serving_front": front,
        "goodput_best": float(good.max()) if len(good) else 0.0,
        "explorer": {"n_evals": tr.n_evals, "hv_final":
                     tr.hv[-1] if tr.hv else 0.0,
                     "goodput_best": explored_best,
                     "campaign": spec.name,
                     "candidates_per_sec": res.candidates_per_sec,
                     "wall_s": res.wall_s,
                     "n_constraint_violations":
                     res.objective_stats["f0"]["n_constraint_violations"],
                     "front_size": len(res.front)},
        "stage_cache": res.stage_cache,
        "hetero_serving": hetero,
    }
    save_artifact("fig11b_serving", out)

    print("\n=== Fig.11b: request-level serving (GPT-175B) ===")
    print(f"mix: {mix.n_requests} req x (prompt 2048 -> {out_len} tokens), "
          f"{slots} slots; SLO ttft<={slo.ttft_s:.3f}s tpot<={slo.tpot_s:.4f}s")
    print(f"sweep: {len(rows)} feasible, goodput/power front "
          f"({len(front)} points), best goodput {out['goodput_best']:.0f} tok/s")
    for p in front:
        print(f"  front: goodput={p['goodput_tok_s']:10.1f} tok/s  "
              f"power={p['power_w']:10.0f} W")
    print(f"explorer: {tr.n_evals} SLO-constrained evals, "
          f"best goodput {explored_best:.0f} tok/s")
    for h in hetero:
        print(f"hetero {h['granularity']:8s}: goodput={h['goodput_tok_s']:9.1f}"
              f" ttft={h['ttft_s']:7.3f}s tpot={h['tpot_s']:.4f}s "
              f"att={h['slo_attainment']:.2f}")
    return out


if __name__ == "__main__":
    run()
