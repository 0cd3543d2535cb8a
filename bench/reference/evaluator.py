# Frozen copy of src/repro/core/evaluator.py (commit eba02b4), part of the
# benchmark's plain reference: it imports nothing of the program, so a
# change to the program cannot move the yardstick. Edits from the
# original are marked "bench reference:".
"""Hierarchical Evaluation Engine (paper §VI, Fig. 6).

evaluate_design(design, workload, fidelity) walks tile -> op -> chunk level
and searches the parallel-strategy space (TP x DP x PP x micro-batch),
returning the best-throughput feasible (throughput, power) point. It is the
scalar *reference* path: explicit ChunkGraphs, per-graph latency through the
fidelity backend's `chunk_latency`.

evaluate_design_batch(designs, workload, fidelity) dispatches to the
fidelity backend registry (bench.reference.fidelity, DESIGN.md §4b): every
registered fidelity — analytical closed form, padded-graph GNN, lockstep
simulator — scores the whole flattened (design, strategy) candidate axis in
one array pass. There is no scalar per-design fallback; an unknown fidelity
raises with the registered list.

Fidelities (paper §VII: f1 = analytical, f0 = GNN; CA-sim for validation):
    "analytical"  fast equivalent-bandwidth NoC model
    "gnn"         GNN congestion model (needs trained params)
    "sim"         cycle-approximate NoC simulator (ground truth)

bench reference: the cross-call eval cache, the fused and strategy-pinned
paths (which drive the program's compiled evaluator) and the serving
forwarders are left out; every call evaluates afresh.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from bench.reference.chunk_eval import evaluate_step
from bench.reference.compiler import (
    ChunkGraph,
    compile_chunk,
    enumerate_strategies,
    strategy_sort_key,
)
from bench.reference.design_space import DesignBatch, WSCDesign
from bench.reference.fidelity import EvalResult, FidelityBackend, get_backend
from bench.reference.workload import LLMWorkload

H100_AREA_MM2 = 814.0

_strategy_order = strategy_sort_key        # kept name: search-order heuristic

Fidelity = Union[str, FidelityBackend]


def wafers_for_budget(design: WSCDesign, wl: LLMWorkload) -> int:
    """Area-matched system size: same total silicon as the GPU baseline
    (paper: 'total area of the WSCs consistent with the corresponding number
    of GPUs')."""
    total = wl.gpu_budget * H100_AREA_MM2
    return max(1, round(total / max(design.wafer_area_mm2(), 1.0)))


def _wafers_for_budget_batch(geom: DesignBatch, wl: LLMWorkload) -> np.ndarray:
    total = wl.gpu_budget * H100_AREA_MM2
    return np.maximum(
        1, np.round(total / np.maximum(geom.wafer_area_mm2, 1.0))
    ).astype(np.int64)


# ---------------------------------------------------------------------------
# scalar reference path (graph-based)
# ---------------------------------------------------------------------------


def evaluate_design(design: WSCDesign, wl: LLMWorkload,
                    fidelity: Fidelity = "analytical",
                    gnn_params: Optional[Dict] = None,
                    n_wafers: Optional[int] = None,
                    max_strategies: int = 24) -> EvalResult:
    backend = get_backend(fidelity)
    nw = n_wafers if n_wafers is not None else wafers_for_budget(design, wl)

    # memory_model="grid": the scalar path must stay element-identical to
    # the batched grid (`feasible_strategy_arrays`), which bakes the frozen
    # legacy memory check; the recompute-aware v2 model is the joint path.
    strategies = enumerate_strategies(design, wl, n_wafers=nw,
                                      memory_model="grid")
    strategies = sorted(strategies, key=_strategy_order)[:max_strategies]

    graph_cache: Dict[Tuple[int, int, int], Tuple[ChunkGraph, float]] = {}
    best: Optional[EvalResult] = None
    for s in strategies:
        mb_count = s.microbatches if wl.phase == "train" else 1
        mb_tokens = max(wl.tokens_per_step() // (s.dp * mb_count), 1)
        cores_per_chunk = max(design.total_cores() * nw // s.chunks(), 1)
        gkey = (s.tp, mb_tokens, cores_per_chunk)
        if gkey not in graph_cache:
            graph = compile_chunk(design, wl, s.tp, mb_tokens,
                                  cores_per_chunk)
            lat = backend.chunk_latency(graph, design, gnn_params)
            graph_cache[gkey] = (graph, lat)
        graph, lat = graph_cache[gkey]
        step = evaluate_step(design, wl, s, lat, graph, nw)
        if not step.feasible:
            continue
        cand = EvalResult(step.throughput, step.power_w, s, step, nw, True)
        if best is None or cand.throughput > best.throughput:
            best = cand
    if best is None:
        best = EvalResult(0.0, float("inf"), None, None, nw, False,
                          "no_feasible_strategy")
    return best


# ---------------------------------------------------------------------------
# batched path: registry dispatch (DESIGN.md §4/§4b)
# ---------------------------------------------------------------------------


def evaluate_design_batch(designs: Sequence[WSCDesign], wl: LLMWorkload,
                          fidelity: Fidelity = "analytical",
                          gnn_params: Optional[Dict] = None,
                          n_wafers: Optional[Union[int, np.ndarray]] = None,
                          max_strategies: int = 24) -> List[EvalResult]:
    """Evaluate N designs at once through the fidelity backend registry:
    every fidelity runs its vectorized pipeline over the flattened
    (design, strategy) candidate axis."""
    backend = get_backend(fidelity)
    designs = list(designs)
    if not designs:
        return []

    geom0 = DesignBatch.from_designs(designs)
    if n_wafers is None:
        nw = _wafers_for_budget_batch(geom0, wl)
    else:
        nw = np.broadcast_to(np.asarray(n_wafers, np.int64),
                             (len(designs),)).copy()
    return backend.evaluate_batch(geom0, wl, nw, max_strategies, gnn_params)
