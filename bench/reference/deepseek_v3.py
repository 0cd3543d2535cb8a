"""Plain reference for configurations with a table of layer kinds
(DeepSeek-V3): the analytical evaluator in NumPy, one design at a time.

`bench.reference.for_config` picks this module for a configuration file
that carries `"reference": "deepseek_v3"`. It exports the four names the
package exports. Like the frozen modules beside it, it imports nothing of
the program: the GEMMs of each layer kind, the strategy grid with its
expert-parallel column, the pipeline stages and the step model are written
out here, and only the tile model (`tile_eval`), the NoC closed forms
(`noc_analytical`), the core grid (`compiler.grid_for_batch`), the design
geometry (`design_space`), the area-matched system size
(`evaluator._wafers_for_budget_batch`) and the constants (`components`)
come from the frozen modules.

The model, as the configuration states it:

  * layers in published order: `n_dense_layers` dense (MLA + SwiGLU of
    width d_ff), then MoE (MLA + router + top-k routed SwiGLU experts of
    width d_ff_expert + `moe_shared` shared ones); in training
    `mtp_layers` multi-token-prediction blocks (eh_proj, MLA, MoE) in the
    last pipeline stage;
  * MLA in its published (non-absorbed) training form; the compressions
    replicated under TP, everything after them split by heads;
  * the strategy grid: pp, dp, tp, microbatches, and ep (powers of two up
    to the expert count, ep <= dp: the expert-parallel group is drawn
    from a stage's data-parallel replicas, so a replica holds 1/ep of the
    stage's routed experts), the first `max_strategies` feasible rows of
    the sorted grid;
  * stages: a balanced contiguous split of the layers; a microbatch's stage
    time is the slowest stage's sum of per-layer compute, TP collectives,
    and, on MoE layers, the expert all-to-all.

Only the train scenario with the analytical fidelity is modelled: no cell
of this configuration runs another.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from bench.reference import components as C
from bench.reference import design, fold
from bench.reference.compiler import grid_for_batch
from bench.reference.design_space import DesignBatch
from bench.reference.evaluator import _wafers_for_budget_batch
from bench.reference.noc_analytical import (
    chunk_latency_cycles_closed,
    row_allgather_byte_hops,
)
from bench.reference.tile_eval import evaluate_tile_batch

BYTES = 2
WORKLOAD_KEYS = ("n_layers", "d_model", "n_heads", "n_kv", "d_ff", "vocab",
                 "seq", "batch", "phase", "moe_experts", "moe_topk",
                 "gpu_budget", "n_dense_layers", "d_ff_expert",
                 "moe_shared", "q_lora_rank", "kv_lora_rank",
                 "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                 "mtp_layers")


@dataclasses.dataclass(frozen=True)
class Workload:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    seq: int
    batch: int
    phase: str
    moe_experts: int
    moe_topk: int
    gpu_budget: int
    n_dense_layers: int
    d_ff_expert: int
    moe_shared: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    mtp_layers: int


def workload(config: Mapping) -> Workload:
    """The modelled job, from the configuration file's published widths."""
    return Workload(name=config["workload_ref"],
                    **{k: config[k] for k in WORKLOAD_KEYS})


# ---------------------------------------------------------------------------
# the layer kinds, their GEMMs and their parameters
# ---------------------------------------------------------------------------

# (name, count, moe, mtp)
Kind = Tuple[str, int, bool, bool]


def kinds(wl: Workload) -> List[Kind]:
    out = [("dense", wl.n_dense_layers, False, False),
           ("moe", wl.n_layers - wl.n_dense_layers, True, False)]
    if wl.mtp_layers and wl.phase == "train":
        out.append(("mtp", wl.mtp_layers, True, True))
    return [k for k in out if k[1]]


def gemms(wl: Workload, kind: Kind, tp: np.ndarray, M: np.ndarray
          ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """(M, K, N) of each GEMM of one layer of `kind`, in chain order, for
    candidate arrays `tp` and `M` (tokens a microbatch)."""
    D, H = wl.d_model, wl.n_heads
    nope, rope, v = wl.qk_nope_head_dim, wl.qk_rope_head_dim, wl.v_head_dim
    c, ql = wl.kv_lora_rank, wl.q_lora_rank
    one = np.ones_like(M)
    rows_h = M * np.maximum(H // tp, 1)
    ops = []
    if kind[3]:
        ops.append((M, one * (2 * D), one * D))              # eh_proj
    ops += [(M, one * D, one * ql),                          # q_a
            (M, one * ql, H * (nope + rope) // tp),          # q_b
            (M, one * D, one * (c + rope)),                  # kv_a
            (M, one * c, H * (nope + v) // tp),              # kv_b
            (rows_h, one * (nope + rope), one * wl.seq),     # q.k
            (rows_h, one * wl.seq, one * v),                 # p.v
            (M, H * v // tp, one * D)]                       # o
    if not kind[2]:
        F = wl.d_ff
        return ops + [(M, one * D, 2 * F // tp), (M, F // tp, one * D)]
    fe, k = wl.d_ff_expert, wl.moe_topk
    ops += [(M, one * D, one * wl.moe_experts),              # router
            (M * k, one * D, 2 * fe // tp),                  # routed in
            (M * k, fe // tp, one * D)]                      # routed out
    if wl.moe_shared:
        fs = fe * wl.moe_shared
        ops += [(M, one * D, 2 * fs // tp), (M, fs // tp, one * D)]
    return ops


def layer_params(wl: Workload, kind: Kind, active: bool) -> int:
    D, H = wl.d_model, wl.n_heads
    nope, rope, v = wl.qk_nope_head_dim, wl.qk_rope_head_dim, wl.v_head_dim
    c, ql = wl.kv_lora_rank, wl.q_lora_rank
    p = (D * ql + ql * H * (nope + rope) + D * (c + rope)
         + c * H * (nope + v) + H * v * D)
    if kind[3]:
        p += 2 * D * D
    if not kind[2]:
        return p + 3 * D * wl.d_ff
    experts = wl.moe_topk if active else wl.moe_experts
    return (p + D * wl.moe_experts
            + (experts + wl.moe_shared) * 3 * D * wl.d_ff_expert)


def params_bytes(wl: Workload) -> int:
    return (sum(k[1] * layer_params(wl, k, False) for k in kinds(wl))
            + 2 * wl.vocab * wl.d_model) * BYTES


def expert_bytes(wl: Workload) -> int:
    n_moe = sum(k[1] for k in kinds(wl) if k[2])
    return n_moe * 3 * wl.d_model * wl.d_ff_expert * wl.moe_experts * BYTES


def flops_per_step(wl: Workload) -> float:
    active = (sum(k[1] * layer_params(wl, k, True) for k in kinds(wl))
              + wl.vocab * wl.d_model)
    return 2.0 * active * (wl.batch * wl.seq) * 3.0


def stage_counts(wl: Workload, pp: int) -> np.ndarray:
    """(pp, n_kinds) layers of each kind a stage: stage s holds layers
    floor(s L / pp) .. floor((s+1) L / pp) - 1; MTP in the last stage."""
    ks = kinds(wl)
    order = [i for i, k in enumerate(ks) if not k[3] for _ in range(k[1])]
    L = len(order)
    out = np.zeros((pp, len(ks)), np.float64)
    for s in range(pp):
        for layer in range(s * L // pp, (s + 1) * L // pp):
            out[s, order[layer]] += 1.0
    for i, k in enumerate(ks):
        if k[3]:
            out[pp - 1, i] += k[1]
    return out


# ---------------------------------------------------------------------------
# the strategy grid
# ---------------------------------------------------------------------------


def strategy_grid(wl: Workload) -> List[Tuple[int, int, int, int, int, float]]:
    """(tp, pp, dp, mb, ep, need) rows, sorted: modest tp first
    (|log2 tp - 5|), then shallow pipelines, then more microbatches; ties
    in enumeration order (pp, dp, tp, mb, ep ascending). `need` is the
    weights-only memory check, routed-expert bytes divided by ep."""
    p_bytes, p_exp = params_bytes(wl), expert_bytes(wl)
    pows = [2 ** i for i in range(17)]
    rows = []
    for pp in [p for p in pows if p <= wl.n_layers]:
        for dp in [d for d in pows if d <= wl.batch]:
            for tp in pows:
                for mb in (1, 2, 4, 8, 16, 32):
                    if wl.batch % (dp * mb):
                        continue
                    ep = 1
                    while True:
                        w = p_bytes if ep == 1 else (p_bytes - p_exp) \
                            + p_exp / ep
                        rows.append((tp, pp, dp, mb, ep,
                                     dp * w * 6.0 / pp))
                        if ep * 2 > min(wl.moe_experts, dp):
                            break
                        ep *= 2
    return sorted(rows, key=lambda r: (abs(math.log2(r[0]) - 5.0), r[1],
                                       -r[3]))


def strategies_for(grid, total_cores: int, budget: float,
                   max_strategies: int) -> np.ndarray:
    picked = []
    for r in grid:
        if (r[1] * r[2] * r[0] <= total_cores and r[0] <= total_cores
                and r[5] <= budget):
            picked.append(r[:5])
            if len(picked) == max_strategies:
                break
    return np.array(picked or [(1, 1, 1, 1, 1)], np.int64)


# ---------------------------------------------------------------------------
# one design: every candidate strategy, then the best
# ---------------------------------------------------------------------------


def best_point(g: DesignBatch, i: int, nw: int, wl: Workload, grid,
               max_strategies: int) -> Tuple[bool, float, float]:
    """(feasible, throughput, power) of design i of `g` on `nw` wafers:
    the best-throughput feasible strategy of its first `max_strategies`."""
    tc = int(g.total_cores[i]) * nw
    budget = float(g.buffer_kb[i] * 1024.0 * g.total_cores[i] * nw
                   + g.dram_gb_per_reticle[i] * 1e9 * g.n_reticles[i] * nw)
    st = strategies_for(grid, tc, budget, max_strategies)
    tp, pp, dp, mb, ep = (st[:, j] for j in range(5))
    n = len(st)
    geo = {k: np.full(n, getattr(g, k)[i]) for k in (
        "total_cores", "cores_per_reticle", "reticle_bisection_Bps",
        "inter_reticle_bw_Bps", "buffer_kb", "n_reticles",
        "dram_bw_Bps_per_reticle", "ret_h", "ret_w", "dram_on",
        "static_power_w", "ir_energy_pj_per_bit", "mac", "buffer_bw",
        "dataflow_code", "noc_bw")}
    nwc = np.full(n, nw, np.int64)
    tokens = wl.batch * wl.seq
    chunks = pp * dp
    M = np.maximum(tokens // (dp * mb), 1)
    cpc = np.maximum(geo["total_cores"] * nwc // chunks, 1)
    gh_t, gw_t = grid_for_batch(cpc)
    gh, gw = grid_for_batch(np.minimum(cpc, 64))
    n_cores = gh * gw

    # per kind: one layer's latency (cycles), SRAM bits and NoC byte-hops
    ks = kinds(wl)
    lat, sram, noc = [], [], []
    for k in ks:
        ops = gemms(wl, k, tp, M)
        Ms = np.stack([o[0] for o in ops])
        Ks = np.stack([o[1] for o in ops])
        Ns = np.stack([o[2] for o in ops])
        t = evaluate_tile_batch(np.maximum(Ms // gh_t, 1), Ks,
                                np.maximum(Ns // gw_t, 1),
                                geo["mac"][None, :],
                                geo["buffer_kb"][None, :],
                                geo["buffer_bw"][None, :],
                                geo["dataflow_code"][None, :])
        out_b = (Ms * Ns).astype(np.float64) * BYTES
        lat.append(chunk_latency_cycles_closed(t["cycles"], out_b, gh, gw,
                                               geo["noc_bw"]))
        sram.append((t["sram_read_bits"] + t["sram_write_bits"])
                    .sum(axis=0) * n_cores)
        noc.append(row_allgather_byte_hops(out_b[:-1], gh, gw))

    # one microbatch through a stage
    bwd = 3.0
    act = (M * wl.d_model).astype(np.float64) * BYTES
    ir_bw = geo["inter_reticle_bw_Bps"]
    tp_vol = 2.0 * (tp - 1) / tp * act * 2.0
    tp_bw = np.where(geo["total_cores"] * nwc // np.maximum(chunks, 1)
                     <= geo["cores_per_reticle"],
                     geo["reticle_bisection_Bps"], ir_bw)
    tp_layer = np.where(tp <= 1, 0.0, tp_vol / np.maximum(tp_bw, 1.0)) * bwd
    a2a = np.where(ep > 1, 4.0 * (ep - 1) / ep * act * wl.moe_topk, 0.0)
    a2a_layer = a2a / np.maximum(ir_bw, 1.0) * bwd
    per_layer = []
    for j, k in enumerate(ks):
        u = lat[j] / C.CLOCK_HZ * bwd + tp_layer
        per_layer.append(u + a2a_layer if k[2] else u)
    slow = np.empty(n)
    for c in range(n):
        counts = stage_counts(wl, int(pp[c]))
        slow[c] = max(sum(counts[s, j] * per_layer[j][c]
                          for j in range(len(ks)))
                      for s in range(int(pp[c])))
    pp_s = np.where(pp <= 1, 0.0, act / np.maximum(ir_bw, 1.0)) * bwd

    # weights beyond the chunk's SRAM stream from DRAM
    p_bytes, p_exp = params_bytes(wl), expert_bytes(wl)
    sram_chunk = (geo["buffer_kb"] * 1024.0 * geo["total_cores"] * nwc
                  / np.maximum(chunks, 1))
    w = np.where(ep > 1, ((p_bytes - p_exp) + p_exp / ep)
                 / np.maximum(pp, 1), p_bytes / np.maximum(pp, 1))
    spill = np.maximum(w - sram_chunk, 0.0)
    n_edge = 2 * (geo["ret_h"] + geo["ret_w"])
    dram_bw = np.where(
        geo["dram_on"].astype(bool),
        geo["dram_bw_Bps_per_reticle"]
        * np.maximum(geo["n_reticles"] * nwc / np.maximum(chunks, 1), 1e-9),
        np.minimum(n_edge * C.OFFCHIP_BW_PER_CTRL / np.maximum(chunks, 1),
                   ir_bw * np.minimum(geo["ret_h"], geo["ret_w"])
                   / np.maximum(chunks, 1)))
    dram_s = np.where(spill <= 0, 0.0, spill / np.maximum(dram_bw, 1.0))

    # the step: pipeline, then the DP gradient all-reduce
    stage = slow + pp_s + dram_s
    eff = mb / (mb + pp - 1.0)
    step = stage * mb / eff
    dp_bw = np.where(np.maximum(nwc / dp, 1e-9) >= 1.0,
                     n_edge * C.INTER_WAFER_BW_PER_NI,
                     ir_bw * np.minimum(geo["ret_h"], geo["ret_w"]))
    step = step + np.where(dp <= 1, 0.0, 2.0 * (dp - 1) / dp * w
                           / np.maximum(dp_bw, 1.0))
    thpt = tokens / np.maximum(step, 1e-12)

    # energy: MACs, SRAM, NoC, inter-reticle, DRAM, static
    E = C.ENERGY
    n_all = sum(k[1] for k in ks)
    n_moe = sum(k[1] for k in ks if k[2])
    sram_all = sum(sram[j] * float(k[1]) for j, k in enumerate(ks))
    noc_all = sum(noc[j] * float(k[1]) for j, k in enumerate(ks))
    ir = (2.0 * (tp - 1) / np.maximum(tp, 1) * M * wl.d_model * BYTES * 2
          * n_all * mb * dp * bwd) + p_bytes * 2 * (dp > 1) \
        + a2a * n_moe * mb * dp
    energy = (flops_per_step(wl) / 2.0 * E.mac * 1e-12
              + sram_all * mb * dp * bwd * E.sram_read_bit * 1e-12
              + noc_all * 8 * mb * dp * bwd * E.noc_bit_hop * 1e-12
              + ir * 8 * geo["ir_energy_pj_per_bit"] * 1e-12
              + spill * mb * dp * 8
              * np.where(geo["dram_on"].astype(bool), E.dram_bit,
                         E.offchip_bit) * 1e-12
              + geo["static_power_w"] * nwc * step)
    bad = ~(np.isfinite(step) & np.isfinite(energy))
    power = np.where(bad, np.inf, energy / np.maximum(step, 1e-12))
    ok = ~bad & (power <= C.WAFER_POWER_W * nwc) & np.isfinite(power)
    if not ok.any():
        return False, 0.0, math.inf
    c = int(np.argmax(np.where(ok, thpt, -1.0)))
    return True, float(thpt[c]), float(power[c])


def train_objectives(designs: Sequence[Mapping], config: Mapping,
                     spec: Mapping, fidelity: str = "analytical",
                     gnn_params: Optional[Dict] = None
                     ) -> List[Tuple[float, float]]:
    """(y0, y1) of each design under the train scenario."""
    if fidelity != "analytical":
        raise ValueError(f"{config['name']}: the reference models the "
                         f"analytical fidelity only, not {fidelity!r}")
    wl = dataclasses.replace(workload(config), phase="train")
    ds = [design(d) for d in designs]
    if not ds:
        return []
    g = DesignBatch.from_designs(ds)
    nw = _wafers_for_budget_batch(g, wl)
    grid = strategy_grid(wl)
    ms = []
    for i in range(len(ds)):
        ok, thpt, power = best_point(g, i, int(nw[i]), wl, grid,
                                     spec["max_strategies"])
        ms.append({"throughput": thpt, "power": power,
                   "power_per_wafer": power / max(int(nw[i]), 1),
                   "n_wafers": float(nw[i]), "feasible": ok})
    return fold(ms, spec["objectives"], spec.get("constraints", ()))


def trace_objectives(designs: Sequence[Mapping], config: Mapping,
                     spec: Mapping) -> List[Tuple[float, float]]:
    raise ValueError(f"{config['name']}: no cell of this configuration "
                     f"runs the trace_serving scenario; its reference "
                     f"models training only")
