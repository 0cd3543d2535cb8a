"""Heterogeneous WSC modeling for LLM inference (paper §V-B, §IX-E).

prefill_ratio splits compute resources between the prefill and decode
stages; `hetero` granularity sets where the split lives and what the
KV-cache transfer between stages costs:

    core     same reticle, software-scheduled      -> NoC bisection
    reticle  different reticles, one wafer          -> inter-reticle links
    wafer    different wafers                       -> inter-wafer NIs

One stage scorer serves three models of the split (DESIGN.md §14):
`score_stages` places the two stages (`stage_split`: whole wafers at wafer
granularity, resource shares otherwise; the core-level efficiency
`STAGE_EFF`) and scores every prefill stage in one batched evaluator call
and every decode stage in another.

  * `evaluate_hetero` — the paper's model: a matched-rate pipeline of the
    two stages including the KV transfer; each stage's design can tune its
    stacking-DRAM bandwidth independently (reticle/wafer granularity).
  * `evaluate_hetero_serving_batch` — the coupled request model on a
    `RequestMix` (`serving.disaggregated_metrics`): prefills run on their
    own stage so decode never stalls, but each request's admission to the
    decode pool is gated by its prefill completion plus the KV-cache
    transfer, so TTFT/TPOT/SLO goodput are first-class instead of
    rate-matched stage throughputs.
  * `evaluate_hetero_trace_serving_batch` — the same on a timed,
    multi-tenant `RequestTrace` (`traces.trace_disaggregated_metrics`):
    the "disaggregated" policy of a trace-serving campaign.

The two coupled models share `_request_costs`: per-request prefill and
KV-transfer seconds, the decode step and the split's power.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.core import components as C
from repro.core.design_space import WSCDesign
from repro.core.evaluator import (
    EvalResult,
    Fidelity,
    evaluate_design_batch,
    get_backend,
)
from repro.core.serving import ServingSLO, disaggregated_metrics
from repro.core.traces import (
    RequestTrace,
    TraceServingResult,
    _infeasible,
    _per_tenant,
    serving_workloads,
    trace_disaggregated_metrics,
)
from repro.core.workload import LLMWorkload, RequestMix, inference_workload


@dataclasses.dataclass
class HeteroResult:
    throughput: float           # tokens/s end-to-end
    power_w: float
    prefill_tps: float
    decode_tps: float
    kv_transfer_s: float
    granularity: str


def wafer_split(n_wafers: int, prefill_ratio: float) -> Tuple[int, int]:
    """Wafer-granularity resource split with the area budget respected:
    nw_p + nw_d == n_wafers always. (The old `max(1, n_wafers - nw_p)`
    fallback let the two stages claim n_wafers + 1 wafers at extreme
    prefill ratios — silently granting extra silicon vs the area-matched
    budget.) Each stage needs at least one whole wafer."""
    if n_wafers < 2:
        raise ValueError(
            "wafer-granularity heterogeneity needs n_wafers >= 2 "
            f"(got {n_wafers}); use core/reticle granularity instead")
    nw_p = min(max(1, round(n_wafers * prefill_ratio)), n_wafers - 1)
    return nw_p, n_wafers - nw_p


def _kv_transfer_bw(design: WSCDesign, granularity: str) -> float:
    if granularity == "core":
        return design.reticle_bisection_Bps()
    if granularity == "reticle":
        # stage boundary crosses the wafer's inter-reticle bisection
        return design.inter_reticle_bw_Bps() * min(design.reticle_array)
    # wafer-level: KV leaves through the facing edge's network interfaces
    # at protocol-achievable utilization — the paper's inter-wafer
    # bottleneck (§IX-E)
    n_ni = design.reticle_array[0]
    return 0.5 * n_ni * C.INTER_WAFER_BW_PER_NI


#: what a table of layer kinds is refused by: the stage scoring below sizes
#: the KV hand-off and the stage steps from one uniform layer
_STAGES = "heterogeneous prefill/decode stage scoring"

#: Core-level scheduling flexibility costs intra-reticle traffic and
#: control overhead (paper §IX-E): a rate discount in `evaluate_hetero`, a
#: time inflation in the coupled request models.
STAGE_EFF = {"core": 0.92, "reticle": 1.0, "wafer": 1.0}

#: Strategy cap of every stage evaluation: the scalar `evaluate_design`
#: default. A campaign's `max_strategies` does not reach the stages
#: (DESIGN.md §14).
STAGE_MAX_STRATEGIES = 24


class StageSplit(NamedTuple):
    """Where a prefill/decode split puts its stages: the wafers each stage
    is scored on (None: the workload's budget), the share of resources
    each holds, and the core-level efficiency."""
    nw_p: Optional[int]
    nw_d: Optional[int]
    scale_p: float
    scale_d: float
    eff: float


def stage_split(granularity: str, prefill_ratio: float,
                n_wafers: Optional[int]) -> StageSplit:
    """At wafer granularity each stage gets whole wafers of `n_wafers`;
    at core/reticle granularity both stages share the system and hold
    `prefill_ratio` and the rest of its resources."""
    eff = STAGE_EFF[granularity]
    if granularity == "wafer":
        nw_p, nw_d = wafer_split(n_wafers, prefill_ratio)
        return StageSplit(nw_p, nw_d, 1.0, 1.0, eff)
    return StageSplit(n_wafers, n_wafers, prefill_ratio,
                      1.0 - prefill_ratio, eff)


def score_stages(designs_prefill: Sequence[WSCDesign],
                 designs_decode: Sequence[WSCDesign], wl_p: LLMWorkload,
                 wl_d: LLMWorkload, granularity: str, prefill_ratio: float,
                 n_wafers: Optional[int], fidelity: Fidelity,
                 gnn_params: Optional[Dict]
                 ) -> Tuple[List[EvalResult], List[EvalResult], StageSplit]:
    """Score N (prefill, decode) stage pairs of a split: every prefill
    stage on `wl_p` in one `evaluate_design_batch` call, every decode
    stage on `wl_d` in another, on the wafers `stage_split` gives them, at
    `STAGE_MAX_STRATEGIES`."""
    split = stage_split(granularity, prefill_ratio, n_wafers)
    kw = dict(fidelity=fidelity, gnn_params=gnn_params,
              max_strategies=STAGE_MAX_STRATEGIES)
    rps = evaluate_design_batch(designs_prefill, wl_p, n_wafers=split.nw_p,
                                **kw)
    rds = evaluate_design_batch(designs_decode, wl_d, n_wafers=split.nw_d,
                                **kw)
    return rps, rds, split


def _request_costs(rp: EvalResult, rd: EvalResult, design_decode: WSCDesign,
                   wl_base: LLMWorkload, granularity: str, split: StageSplit,
                   prompt_lens: Sequence[int], p_ref: int):
    """What the coupled request models read from one scored stage pair:
    per-request prefill seconds on the prefill stage's share (scaled from
    the reference prompt `p_ref`), per-request KV-cache shipping seconds
    across the stage boundary, the decode step on the decode stage's share
    (core-level efficiency as time inflation), and the split's power."""
    t_p_ref = rp.step.step_time_s / max(split.scale_p, 1e-9) / split.eff
    t_d = rd.step.step_time_s / max(split.scale_d, 1e-9) / split.eff
    plens = np.asarray(prompt_lens, np.float64)
    t_prefill = t_p_ref * plens / max(p_ref, 1)
    # per-request K+V cache: the canonical per-layer formula, rescaled from
    # the workload's (batch, seq) footprint to one prompt of plens tokens
    kv_per_token = (wl_base.kv_bytes_per_layer() * wl_base.n_layers
                    / max(wl_base.batch * wl_base.seq, 1))
    kv_s = kv_per_token * plens / max(
        _kv_transfer_bw(design_decode, granularity), 1.0)
    power = rp.power_w * split.scale_p + rd.power_w * split.scale_d
    return t_prefill, kv_s, t_d, power


def _stage_pairs(designs_prefill, designs_decode):
    designs_prefill = list(designs_prefill)
    designs_decode = list(designs_decode)
    if len(designs_prefill) != len(designs_decode):
        raise ValueError("one decode design per prefill design")
    return designs_prefill, designs_decode


def _stage_reason(rp: EvalResult) -> str:
    return "prefill_infeasible" if not rp.feasible else "decode_infeasible"


# ---------------------------------------------------------------------------
# rate-matched stage pipeline (paper Fig. 12)
# ---------------------------------------------------------------------------


def evaluate_hetero(design_prefill: WSCDesign, design_decode: WSCDesign,
                    wl_base: LLMWorkload, granularity: str,
                    prefill_ratio: float, out_tokens: int = 2048,
                    n_wafers: int = 1, fidelity: Fidelity = "analytical",
                    gnn_params: Optional[Dict] = None) -> HeteroResult:
    """Evaluate a prefill/decode split as a matched-rate pipeline. At
    core/reticle granularity both stages share the wafer (resource
    fractions); at wafer granularity each stage gets whole wafers.
    `fidelity` is a registered backend name (or a FidelityBackend
    instance) — resolved up front so typos fail loudly."""
    wl_base.require_uniform(_STAGES)
    fidelity = get_backend(fidelity)
    wl_p = inference_workload(wl_base, "prefill", batch=wl_base.batch,
                              seq=wl_base.seq)
    wl_d = inference_workload(wl_base, "decode", batch=wl_base.batch,
                              seq=wl_base.seq)
    (rp,), (rd,), split = score_stages(
        [design_prefill], [design_decode], wl_p, wl_d, granularity,
        prefill_ratio, n_wafers, fidelity, gnn_params)

    # prefill produces prompts (seq tokens each); decode consumes them,
    # emitting out_tokens per prompt
    prefill_prompts_s = rp.throughput * split.scale_p / max(wl_base.seq, 1)
    decode_tokens_s = rd.throughput * split.scale_d
    decode_prompts_s = decode_tokens_s / max(out_tokens, 1)

    # KV transfer between stages per prompt
    kv_bytes = wl_base.kv_bytes_per_layer() * wl_base.n_layers / max(
        wl_base.batch, 1)
    bw = _kv_transfer_bw(design_decode, granularity)
    kv_s_per_prompt = kv_bytes / max(bw, 1.0)
    kv_prompts_s = 1.0 / max(kv_s_per_prompt, 1e-12)

    prompts_s = split.eff * min(prefill_prompts_s, decode_prompts_s,
                                kv_prompts_s)
    thpt = prompts_s * out_tokens
    power = rp.power_w * split.scale_p + rd.power_w * split.scale_d
    return HeteroResult(
        throughput=thpt, power_w=power,
        prefill_tps=rp.throughput * split.scale_p,
        decode_tps=decode_tokens_s,
        kv_transfer_s=kv_s_per_prompt,
        granularity=granularity)


# ---------------------------------------------------------------------------
# coupled request model on a request mix
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HeteroServingResult:
    feasible: bool
    goodput_tok_s: float
    throughput_tok_s: float
    ttft_s: float                  # mean over the mix's requests
    tpot_s: float
    slo_attainment: float
    power_w: float
    kv_transfer_s: float           # mean per-request stage transfer
    n_decode_steps: int
    granularity: str
    reason: str = ""


def evaluate_hetero_serving_batch(
        designs_prefill: Sequence[WSCDesign],
        designs_decode: Sequence[WSCDesign], wl_base: LLMWorkload,
        granularity: str, prefill_ratio: float, mix: RequestMix,
        slo: ServingSLO, slots: int = 8, n_wafers: int = 1,
        fidelity: Fidelity = "analytical",
        gnn_params: Optional[Dict] = None) -> List[HeteroServingResult]:
    """Score N (prefill design, decode design) disaggregations with the
    coupled request model instead of independent rate-matched stage
    throughputs: per-request prefill times on the prefill stage's resource
    share, per-request KV-cache shipping across the stage boundary, and a
    decode pool that only admits a request once its KV has landed and a
    slot is free (`serving.disaggregated_metrics`). Results keep the input
    order."""
    wl_base.require_uniform(_STAGES)
    designs_prefill, designs_decode = _stage_pairs(designs_prefill,
                                                   designs_decode)
    if not designs_prefill:
        return []
    fidelity = get_backend(fidelity)
    wl_p, wl_d, p_ref = serving_workloads(wl_base, mix, slots)
    rps, rds, split = score_stages(designs_prefill, designs_decode, wl_p,
                                   wl_d, granularity, prefill_ratio,
                                   n_wafers, fidelity, gnn_params)
    out = []
    for rp, rd, dd in zip(rps, rds, designs_decode):
        if not (rp.feasible and rd.feasible):
            out.append(HeteroServingResult(
                feasible=False, goodput_tok_s=0.0, throughput_tok_s=0.0,
                ttft_s=float("inf"), tpot_s=float("inf"),
                slo_attainment=0.0, power_w=float("inf"),
                kv_transfer_s=float("inf"), n_decode_steps=0,
                granularity=granularity, reason=_stage_reason(rp)))
            continue
        t_prefill, kv_s, t_d, power = _request_costs(
            rp, rd, dd, wl_base, granularity, split, mix.prompt_lens, p_ref)
        m = disaggregated_metrics(mix, slo, slots, t_prefill, kv_s, t_d)
        out.append(HeteroServingResult(
            feasible=True,
            goodput_tok_s=m["goodput_tok_s"],
            throughput_tok_s=m["throughput_tok_s"],
            ttft_s=m["ttft_s"], tpot_s=m["tpot_s"],
            slo_attainment=m["slo_attainment"],
            power_w=power,
            kv_transfer_s=float(np.mean(kv_s)),
            n_decode_steps=m["n_decode_steps"],
            granularity=granularity))
    return out


# ---------------------------------------------------------------------------
# coupled request model on a timed, multi-tenant trace
# ---------------------------------------------------------------------------


def evaluate_hetero_trace_serving_batch(
        designs_prefill: Sequence[WSCDesign],
        designs_decode: Sequence[WSCDesign], wl_base: LLMWorkload,
        granularity: str, prefill_ratio: float, trace: RequestTrace,
        slots: int = 8, window_steps: int = 64,
        n_wafers: Optional[int] = None, fidelity: Fidelity = "analytical",
        gnn_params: Optional[Dict] = None) -> List[TraceServingResult]:
    """Timed-arrival, multi-tenant counterpart of
    `evaluate_hetero_serving_batch`: the "disaggregated" routing policy of
    a trace-serving campaign (DESIGN.md §14). Stages are scored by
    `score_stages` (`n_wafers` None: 2 at wafer granularity, the
    workload's budget otherwise). The coupled request model then runs per
    pair: prompts prefill on their own stage in priority-then-arrival
    order as they *arrive*, KV ships across the stage boundary, and the
    decode pool admits by priority once the KV lands
    (`traces.trace_disaggregated_metrics`). Returns
    `traces.TraceServingResult`s in input order, so disaggregated points
    score in the same frame as the shared-pool policies."""
    wl_base.require_uniform(_STAGES)
    designs_prefill, designs_decode = _stage_pairs(designs_prefill,
                                                   designs_decode)
    if not designs_prefill:
        return []
    fidelity = get_backend(fidelity)
    wl_p, wl_d, p_ref = serving_workloads(wl_base, trace, slots)
    if granularity == "wafer" and n_wafers is None:
        n_wafers = 2
    rps, rds, split = score_stages(designs_prefill, designs_decode, wl_p,
                                   wl_d, granularity, prefill_ratio,
                                   n_wafers, fidelity, gnn_params)
    return [_trace_coupled(rp, rd, dd, wl_base, granularity, split, trace,
                           slots, window_steps, p_ref)
            for rp, rd, dd in zip(rps, rds, designs_decode)]


def evaluate_hetero_trace_serving(design_prefill: WSCDesign,
                                  design_decode: WSCDesign,
                                  wl_base: LLMWorkload, granularity: str,
                                  prefill_ratio: float, trace: RequestTrace,
                                  **kw) -> TraceServingResult:
    """Scalar wrapper: `evaluate_hetero_trace_serving_batch` with one
    pair."""
    return evaluate_hetero_trace_serving_batch(
        [design_prefill], [design_decode], wl_base, granularity,
        prefill_ratio, trace, **kw)[0]


def _trace_coupled(rp: EvalResult, rd: EvalResult, design_decode: WSCDesign,
                   wl_base: LLMWorkload, granularity: str, split: StageSplit,
                   trace: RequestTrace, slots: int, window_steps: int,
                   p_ref: int) -> TraceServingResult:
    """The trace-coupled model of one disaggregated pair, from its two
    stage `EvalResult`s."""
    if not (rp.feasible and rd.feasible):
        return _infeasible("disaggregated", rd.n_wafers, _stage_reason(rp))
    t_prefill, kv_s, t_d, power = _request_costs(
        rp, rd, design_decode, wl_base, granularity, split,
        trace.prompt_lens, p_ref)
    m = trace_disaggregated_metrics(trace, slots, t_prefill, kv_s, t_d,
                                    window_steps=window_steps)
    energy = power * m["total_time_s"]
    return TraceServingResult(
        feasible=True, policy="disaggregated",
        goodput_tok_s=m["goodput_tok_s"],
        interactive_goodput_tok_s=m["interactive_goodput_tok_s"],
        worst_window_goodput_tok_s=m["worst_window_goodput_tok_s"],
        throughput_tok_s=m["throughput_tok_s"],
        ttft_s=m["ttft_s"], ttft_max_s=m["ttft_max_s"],
        tpot_s=m["tpot_s"], tpot_max_s=m["tpot_max_s"],
        slo_attainment=m["slo_attainment"],
        total_time_s=m["total_time_s"],
        n_steps=m["n_steps"], n_decode_steps=m["n_decode_steps"],
        n_preemptions=0, power_w=power, energy_j=energy,
        n_wafers=rd.n_wafers,
        per_tenant=_per_tenant(trace, m["met"], m["ttft"], m["tpot"],
                               m["total_time_s"]))
