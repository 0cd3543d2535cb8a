"""Compiled analytical evaluation pipeline (DESIGN.md §12).

The analytical f1 backend used to run as vectorized NumPy: strategy-grid
enumeration (`compiler.feasible_strategy_arrays`), the tile model
(`tile_eval.evaluate_tile_batch`), the closed-form row-all-gather NoC costs
(`noc_analytical`), and the chunk-level step model
(`chunk_eval.evaluate_step_batch`), with a host round-trip between the
compiled MFMOBO proposal program and every evaluation. This module ports
that whole pipeline to jitted JAX with static shapes so analytical
`FidelityBackend.evaluate_batch` is ONE compiled program per
(workload, max_strategies) — and exposes a fused gather+evaluate entry
point that consumes the device-resident candidate indices
`mfmobo._acquire_scan_jit` produces, so a synchronous MFMOBO f1 iteration
never leaves XLA between proposal and evaluation.

Bit-exactness contract: every jnp expression mirrors its NumPy oracle
(`evaluate_tile_batch`, `evaluate_step_batch`,
`row_allgather_comm_cycles`, `row_allgather_byte_hops`,
`feasible_strategy_arrays` — retained verbatim and re-exported as `*_ref`)
operation for operation, in the same association order, in float64 under a
scoped `jax.enable_x64` (the rest of the process stays f32 —
the GP/EHVI programs are untouched). The analytical path uses only
exactly-rounded ops (+ - * / min max and integer arithmetic; the one log2
is the ±1-ulp-corrected exact `floor_log2`), so XLA CPU reproduces the
NumPy results bit for bit; `tests/test_eval_compiled.py` property-tests
equality, including bit-exact feasibility masks and strategy rows.

Static-shape conventions (the PR 6 capacity-bucket idiom):
  * the design axis is padded to a pow2 bucket (edge-replicated rows,
    sliced off on extraction), so a campaign touches a handful of
    programs, all pre-compilable via `warm_evaluator_kernels`;
  * the strategy axis is the per-workload sorted strategy grid, padded to
    pow2 with never-feasible rows; per-design selection of the first
    `max_strategies` feasible rows runs in-program as a cumsum +
    vmapped-searchsorted gather (identical rows, identical order, same
    Strategy(1,1,1,1) fallback as `feasible_strategy_arrays`).

Placement: every program here runs on the host CPU devices, whatever the
process's default backend is (DESIGN.md §12). A TPU has no native float64,
and the emulated-f64 program takes minutes per bucket to compile for a
v5e; on XLA:CPU it compiles in seconds and keeps the NumPy bit-exactness.
Inputs are committed to `host_devices()[0]`, so the jitted programs follow
them there; the fused entry points copy the acquire scan's pick indices
from the accelerator to the host before the gather.

When several XLA host-platform lanes are exposed
(`--xla_force_host_platform_device_count`, see explore/fleet.py), the
padded design axis is sharded across them with `pmap`; per-design
math is embarrassingly parallel, so sharding cannot change results.
`lane_stats()` reports per-lane row counts for the fleet probe.

Set REPRO_COMPILED_EVAL=0 to fall back to the NumPy reference pipeline.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import components as C
from repro.core.chunk_eval import StepResult
from repro.core.compiler import Strategy, _strategy_grid
from repro.core.design_space import DesignBatch
from repro.core.workload import BYTES, LLMWorkload

_ENV = "REPRO_COMPILED_EVAL"


def enabled() -> bool:
    return os.environ.get(_ENV, "1").lower() not in ("0", "false", "off")


def host_devices() -> list:
    """The CPU devices the evaluator's programs run on: its lanes."""
    import jax
    return jax.local_devices(backend="cpu")


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


# per-lane dispatch accounting for the fleet probe (DESIGN.md §12)
_LANE_STATS = {"n_lanes": 0, "sharded_calls": 0, "rows_sharded": 0,
               "jit_calls": 0, "rows_jit": 0}


def lane_stats() -> Dict[str, int]:
    """XLA host-lane utilization counters: how many evaluator dispatches
    ran pmap-sharded vs single-lane, and the design rows each mode moved
    (sharded rows split evenly across `n_lanes` by construction)."""
    return dict(_LANE_STATS)


# ---------------------------------------------------------------------------
# exact integer helpers (jnp mirrors of design_space.floor_log2 /
# compiler.grid_for_batch / tile_eval._ceil_div — same correction steps,
# so the results are integer-exact, not merely close)
# ---------------------------------------------------------------------------


def _jnp():
    import jax.numpy as jnp
    return jnp


def _floor_log2_j(n):
    jnp = _jnp()
    n = jnp.maximum(n.astype(jnp.int64), 1)
    e = jnp.floor(jnp.log2(n.astype(jnp.float64))).astype(jnp.int64)
    e = jnp.where((jnp.int64(1) << jnp.minimum(e + 1, 62)) <= n, e + 1, e)
    e = jnp.where((jnp.int64(1) << jnp.minimum(e, 62)) > n, e - 1, e)
    return e


def _grid_for_j(n):
    jnp = _jnp()
    n = jnp.maximum(n.astype(jnp.int64), 1)
    gh = jnp.int64(1) << (_floor_log2_j(n) // 2)
    return gh, jnp.maximum(n // gh, 1)


def _ceil_div_j(a, b):
    return -(-a // b)


# ---------------------------------------------------------------------------
# compiled program per (workload, max_strategies, lanes)
# ---------------------------------------------------------------------------

# geometry fields the pipeline consumes, in DesignBatch attribute order
_GEOM_FIELDS = (
    "dataflow_code", "mac", "buffer_kb", "buffer_bw", "noc_bw",
    "total_cores", "cores_per_reticle", "n_reticles", "ret_h", "ret_w",
    "reticle_bisection_Bps", "inter_reticle_bw_Bps",
    "dram_bw_Bps_per_reticle", "dram_gb_per_reticle", "dram_on",
    "static_power_w", "ir_energy_pj_per_bit",
)

_PROGRAMS: Dict[Tuple, "_EvalProgram"] = {}
_PROGRAMS_MAX = 16


def _program_for(wl: LLMWorkload, max_strategies: int) -> "_EvalProgram":
    lanes = len(host_devices())
    key = (wl, max_strategies, lanes)
    prog = _PROGRAMS.get(key)
    if prog is None:
        if len(_PROGRAMS) >= _PROGRAMS_MAX:
            _PROGRAMS.pop(next(iter(_PROGRAMS)))
        prog = _EvalProgram(wl, max_strategies, lanes)
        _PROGRAMS[key] = prog
    return prog


def clear_compiled_programs() -> None:
    _PROGRAMS.clear()
    _WARMED.clear()


class _EvalProgram:
    """One workload's compiled analytical pipeline: the sorted strategy
    grid (pow2-padded with never-feasible rows) baked in as constants,
    plus the jitted batch / fused-gather / pmap entry points."""

    def __init__(self, wl: LLMWorkload, max_strategies: int, lanes: int):
        import jax

        self.wl = wl
        self.K = int(max_strategies)
        self.lanes = int(lanes)
        self.devices = host_devices()[:self.lanes]
        self.host = self.devices[0]

        g = _strategy_grid(wl)
        order = g["order"]
        tp_o = g["tp"][order]
        pp_o = g["pp"][order]
        dp_o = g["dp"][order]
        mb_o = g["mb"][order]
        need_o = g["need"][order]
        chunks_o = g["chunks"][order]
        G = len(order)
        Gp = _pow2(max(G, 1))
        pad = Gp - G
        big = np.int64(1) << 31          # pad rows: product stays < 2^63
        self._tp_o = np.concatenate([tp_o, np.full(pad, big)])
        self._pp_o = np.concatenate([pp_o, np.full(pad, big)])
        self._dp_o = np.concatenate([dp_o, np.full(pad, 1, np.int64)])
        self._mb_o = np.concatenate([mb_o, np.full(pad, 1, np.int64)])
        self._need_o = np.concatenate([need_o, np.full(pad, np.inf)])
        self._chunks_o = np.concatenate([chunks_o, np.full(pad, big)])
        fb = np.flatnonzero((tp_o == 1) & (pp_o == 1) & (dp_o == 1)
                            & (mb_o == 1))
        self._fb_idx = int(fb[0])        # Strategy(1,1,1,1) always exists

        # workload scalars (python numbers -> exact f64 constants)
        self._train = wl.phase == "train"
        self._bwd = 3.0 if self._train else 1.0
        self._tokens = wl.tokens_per_step()
        self._p_bytes = wl.params_bytes()
        self._p_exp = wl.expert_params_bytes()
        self._kvtot_num = wl.kv_bytes_per_layer() * wl.n_layers
        self._e_mac = wl.flops_per_step() / 2.0 * C.ENERGY.mac * 1e-12
        self._zc_np = tuple(np.float64(c) for c in (
            0.0, self._bwd, C.CLOCK_HZ, wl.d_model, 1e-12, wl.n_layers))

        self._jit = jax.jit(self._body)
        self._pfn = (jax.pmap(self._body, in_axes=(0, 0, None),
                              devices=self.devices)
                     if lanes > 1 else None)

        def fused(arrs, nw, zc, js):
            sub = {k: v[js] for k, v in arrs.items()}
            return self._body(sub, nw[js], zc)

        self._fused_jit = jax.jit(fused)

        # pinned-strategy (joint mode, ISSUE 9): same `_eval_core` trace,
        # no grid selection — the strategy arrays come in as inputs
        self._jit_pinned = jax.jit(self._body_pinned)
        self._pfn_pinned = (jax.pmap(self._body_pinned,
                                     in_axes=(0, 0, None, 0),
                                     devices=self.devices)
                            if lanes > 1 else None)

        def fused_pinned(arrs, nw, zc, strat, js):
            sub = {k: v[js] for k, v in arrs.items()}
            st = tuple(s[js] for s in strat)
            return self._body_pinned(sub, nw[js], zc, st)

        self._fused_pinned_jit = jax.jit(fused_pinned)

    def _zc(self):
        """Traced scalars for `_body`: the FMA-guard zero plus the inexact
        float constants whose multiplication order must stay fixed (XLA's
        algebraic simplifier folds adjacent constant factors into one —
        e.g. `/ CLOCK_HZ * bwd` into `* (bwd/CLOCK_HZ)` — which rounds
        once where the NumPy oracle rounds twice). Passing them as runtime
        values pins the op-for-op association. Host-lane constants: built
        once, reused across dispatches (the pmap paths take the NumPy
        scalars of `_zc_np`)."""
        zc = getattr(self, "_zc_cached", None)
        if zc is None:
            zc = self._zc_cached = tuple(self._put(c) for c in self._zc_np)
        return zc

    # -- the pipeline body (traced under enable_x64) ------------------------

    def _body(self, arrs, nw, zc):
        jnp = _jnp()
        K = self.K
        z = zc[0]

        # `z` is a traced f64 zero. XLA CPU contracts `a*b + c` into an FMA
        # (skipping the product's rounding step), which NumPy never does;
        # neither --xla_cpu_enable_fast_math=false nor optimization_barrier
        # suppresses it (LLVM fuses below HLO). `fp(x) = x + z` pins a
        # product to its correctly rounded value: either the add contracts
        # to fma(a, b, 0) == round(a*b), or it runs as round(a*b) + 0 —
        # bit-identical either way (operands here are never -0.0). Apply it
        # to every float product whose result NumPy rounds before an
        # addition or subtraction.
        def fp(x):
            return x + z

        buffer_kb = arrs["buffer_kb"]
        total_cores = arrs["total_cores"].astype(jnp.int64)
        nw = nw.astype(jnp.int64)

        # --- strategy selection: first K feasible rows of the sorted grid
        # (mirrors feasible_strategy_arrays' mask + order + cap + fallback)
        tp_o = jnp.asarray(self._tp_o)
        pp_o = jnp.asarray(self._pp_o)
        dp_o = jnp.asarray(self._dp_o)
        mb_o = jnp.asarray(self._mb_o)
        need_o = jnp.asarray(self._need_o)
        chunks_o = jnp.asarray(self._chunks_o)
        Gp = tp_o.shape[0]

        tc = total_cores * nw                              # (N,) int64
        sram_total = buffer_kb * 1024.0 * total_cores * nw
        dram_total = (arrs["dram_gb_per_reticle"] * 1e9
                      * arrs["n_reticles"].astype(jnp.int64) * nw)
        budget = fp(sram_total) + fp(dram_total)           # (N,) f64

        mask = ((chunks_o[None, :] * tp_o[None, :] <= tc[:, None])
                & (tp_o[None, :] <= tc[:, None])
                & (need_o[None, :] <= budget[:, None]))    # (N, Gp)
        csum = jnp.cumsum(mask.astype(jnp.int32), axis=1)
        count = csum[:, -1]
        targets = jnp.arange(1, K + 1, dtype=jnp.int32)
        import jax
        pos = jax.vmap(
            lambda c: jnp.searchsorted(c, targets, side="left"))(csum)
        sel = jnp.minimum(pos, Gp - 1)                     # (N, K)
        ks = jnp.arange(K)
        selmask = ks[None, :] < count[:, None]
        nofeas = count == 0
        first = nofeas[:, None] & (ks[None, :] == 0)
        sel = jnp.where(first, self._fb_idx, sel)
        selmask = selmask | first

        cand = self._eval_core(arrs, nw, zc, tp_o[sel], pp_o[sel],
                               dp_o[sel], mb_o[sel], None)

        # --- per-design winner (first max wins, like np.argmax) ----------
        live = cand["feasible"] & selmask
        thpt_rank = jnp.where(live, cand["throughput"], -1.0)
        jw = jnp.argmax(thpt_rank, axis=1)

        def at(a):
            return jnp.take_along_axis(a, jw[:, None], axis=1)[:, 0]

        out = {"any_feasible": live.any(axis=1), "sel_g": at(sel)}
        for k in ("throughput", "power_w", "step_time_s", "pipeline_eff",
                  "energy_j", "compute_s", "tp_s", "pp_s", "dram_s",
                  "dp_s", "mb_count"):
            out[k] = at(cand[k])
        return out

    def _body_pinned(self, arrs, nw, zc, strat):
        """Joint-mode body: one pinned strategy per design, no grid argmin.
        `strat` = (tp, pp, dp, mb, ep, recompute) as (N,) arrays. Shares
        `_eval_core` with the grid body, so a pinned (tp, pp, dp, mb) with
        ep=1/recompute=False reproduces that grid row bit for bit."""
        jnp = _jnp()

        def col(a):
            return a.astype(jnp.int64)[:, None]

        tp, pp, dp, mb, ep, rc = strat
        cand = self._eval_core(arrs, nw.astype(jnp.int64), zc, col(tp),
                               col(pp), col(dp), col(mb),
                               (col(ep), rc.astype(bool)[:, None]))
        return {k: v[:, 0] for k, v in cand.items()}

    def _eval_core(self, arrs, nw, zc, tp, pp, dp, mb, extras):
        """Candidate axis + tile/NoC/chunk-step model for (N, K) strategy
        columns — the shared trace of the grid and pinned bodies. `extras`
        is None (grid mode: byte-identical trace to the pre-refactor body)
        or (ep, recompute) columns, every extra term `where`-guarded so
        ep=1/recompute=False lanes keep the legacy bits (the same guard
        discipline as `chunk_eval.evaluate_step_batch`)."""
        jnp = _jnp()
        wl = self.wl
        z, bwd_t, clock_t, dmod_t, p12, nl_t = zc

        def fp(x):
            return x + z

        code = arrs["dataflow_code"].astype(jnp.int64)
        mac = arrs["mac"].astype(jnp.int64)
        buffer_kb = arrs["buffer_kb"]
        buffer_bw = arrs["buffer_bw"].astype(jnp.int64)
        noc_bw = arrs["noc_bw"]
        total_cores = arrs["total_cores"].astype(jnp.int64)

        # --- candidate axis (build_candidate_axis mirror), shapes (N, K)
        chunks = pp * dp
        mb_count = mb if self._train else jnp.ones_like(mb)
        mb_tokens = jnp.maximum(self._tokens // (dp * mb_count), 1)
        tcn = (total_cores * nw)[:, None]
        cores_per_chunk = jnp.maximum(tcn // chunks, 1)
        gh_t, gw_t = _grid_for_j(cores_per_chunk)
        gh, gw = _grid_for_j(jnp.minimum(cores_per_chunk, 64))
        n_cores = gh * gw

        # layer_ops_batch mirror: the 6 GEMMs of one layer under tp
        D, F = wl.d_model, wl.d_ff
        hd = D // max(wl.n_heads, 1)
        e = wl.moe_topk if wl.moe_experts else 1
        heads_tp = jnp.maximum(wl.n_heads // tp, 1)
        M = mb_tokens
        m_attn = M * heads_tp // max(wl.n_heads, 1)
        kv_len = wl.seq
        zi = jnp.zeros_like(M)           # int broadcast helper (NOT `z`)
        ops = (
            (M, zi + D, (wl.n_heads + 2 * wl.n_kv) * hd // tp),
            (m_attn, zi + hd, zi + kv_len),
            (m_attn, zi + kv_len, zi + hd),
            (M, wl.n_heads * hd // tp, zi + D),
            (M * e, zi + D, 2 * F // tp),
            (M * e, F // tp, zi + D),
        )

        # tile stage per op (evaluate_tile_batch mirror), accumulated in
        # the same sequential order as the NumPy axis-0 sums
        bkb = buffer_kb[:, None]
        bbw = buffer_bw[:, None]
        nbw = noc_bw[:, None]
        mac2 = mac[:, None]
        code2 = code[:, None]
        ws = code2 == 0
        os_ = code2 == 2
        pr = jnp.int64(1) << (_floor_log2_j(mac2) // 2)
        pc = jnp.maximum(mac2, 1) // pr
        bkb_f = bkb.astype(jnp.float64)
        buf_bits = bkb_f * 1024 * 8

        def sel3(a, b, c):
            return jnp.where(ws, a, jnp.where(os_, b, c))

        cycles_sum = None
        sram_sum = None
        comm_sum = None
        hops_sum = None

        # NoC closed form shared terms (row_allgather_* mirrors)
        bw_bytes = nbw.astype(jnp.float64) / 8.0
        n_transfers = len(ops) - 1
        maxflow = (jnp.float64(n_transfers) * (gw // 2) * ((gw + 1) // 2))
        eq_bw = bw_bytes / jnp.maximum(maxflow, 1.0)
        hop_fac = gh * (gw * (gw * gw - 1)) / 3.0

        for oi, (Mo, Ko, No) in enumerate(ops):
            tM = jnp.maximum(jnp.maximum(Mo // gh_t, 1), 1)
            tK = jnp.maximum(Ko, 1)
            tN = jnp.maximum(jnp.maximum(No // gw_t, 1), 1)
            u1 = sel3(tK, tM, tM)
            u2 = sel3(tN, tN, tK)
            stream = sel3(tM, tK, tN)
            t1 = _ceil_div_j(u1, pr)
            t2 = _ceil_div_j(u2, pc)
            compute = (t1 * t2).astype(jnp.float64) * stream
            Mf = tM.astype(jnp.float64)
            Kf = tK.astype(jnp.float64)
            Nf = tN.astype(jnp.float64)
            reads = sel3(fp(Kf * Nf) + fp(Mf * Kf * t2),
                         fp(Mf * Kf * t2) + fp(Kf * Nf * t1),
                         fp(Mf * Kf) + fp(Kf * Nf * t1))
            writes = sel3(Mf * Nf * t1, Mf * Nf, Mf * Nf * t2)
            stat1 = sel3(jnp.minimum(tK, pr), jnp.minimum(tM, pr),
                         jnp.minimum(tM, pr))
            stat2 = sel3(jnp.minimum(tN, pc), jnp.minimum(tN, pc),
                         jnp.minimum(tK, pc))
            stat_bits = (stat1 * stat2).astype(jnp.float64) * BYTES * 8
            cap_factor = jnp.maximum(1.0, stat_bits
                                     / jnp.maximum(buf_bits, 1))
            read_bits = reads * BYTES * 8 * cap_factor
            write_bits = writes * BYTES * 8
            rw = fp(read_bits) + fp(write_bits)
            mem_cycles = rw / jnp.maximum(bbw, 1)
            cyc = jnp.maximum(compute, mem_cycles)
            cycles_sum = cyc if cycles_sum is None else cycles_sum + cyc
            sram_sum = rw if sram_sum is None else sram_sum + rw
            if oi < n_transfers:         # producer feeds a transfer
                out_b = (Mo * No).astype(jnp.float64) * BYTES
                per_pair = out_b / n_cores
                comm = per_pair / jnp.maximum(eq_bw, 1e-9) + (gw - 1)
                comm = jnp.where(gw > 1, comm, 0.0)
                comm_sum = comm if comm_sum is None else comm_sum + comm
                pph = jnp.where(gw > 1, out_b / (gh * gw), 0.0)
                hop = fp(pph * hop_fac)
                hops_sum = hop if hops_sum is None else hops_sum + hop

        lat = cycles_sum + comm_sum
        sram_bits_layer = sram_sum * n_cores
        noc_bytes_layer = hops_sum

        # --- chunk-level step model (evaluate_step_batch mirror) ---------
        nw2 = nw[:, None]
        bwd = bwd_t
        ep2 = rc2 = None
        if extras is not None:
            ep2 = jnp.maximum(extras[0], 1)
            rc2 = extras[1]
            if self._train:
                # recompute re-runs the forward in the backward: 3x -> 4x
                bwd = jnp.where(rc2, jnp.float64(4.0), bwd_t)
        layers_per_stage = jnp.maximum(wl.n_layers // pp, 1)
        act_bytes = (mb_tokens * wl.d_model).astype(jnp.float64) * BYTES
        p_bytes = self._p_bytes

        compute_s = lat * layers_per_stage / clock_t * bwd
        cpc_step = total_cores[:, None] * nw2 // jnp.maximum(chunks, 1)
        tp_vol = 2.0 * (tp - 1) / tp * act_bytes * 2.0
        tp_bw = jnp.where(cpc_step <= arrs["cores_per_reticle"][:, None],
                          arrs["reticle_bisection_Bps"][:, None],
                          arrs["inter_reticle_bw_Bps"][:, None])
        tp_s = jnp.where(tp <= 1, 0.0, tp_vol / jnp.maximum(tp_bw, 1.0)) \
            * layers_per_stage * bwd
        ir_bw = arrs["inter_reticle_bw_Bps"][:, None]
        pp_s = jnp.where(pp <= 1, 0.0,
                         act_bytes / jnp.maximum(ir_bw, 1.0)) * bwd

        sram_per_chunk = (buffer_kb[:, None] * 1024.0
                          * total_cores[:, None] * nw2
                          / jnp.maximum(chunks, 1))
        w_bytes = p_bytes / jnp.maximum(pp, 1)
        if ep2 is not None:
            p_exp = self._p_exp
            w_bytes = jnp.where(ep2 > 1,
                                ((p_bytes - p_exp) + p_exp / ep2)
                                / jnp.maximum(pp, 1), w_bytes)
        kv_total = self._kvtot_num / jnp.maximum(pp, 1)
        if wl.phase == "decode":
            kv_read, kv_write = kv_total, kv_total / max(wl.seq, 1)
        elif wl.phase == "prefill":
            kv_read, kv_write = 0.0, kv_total
        else:
            kv_read = kv_write = 0.0
        spill = jnp.maximum(w_bytes + kv_read - sram_per_chunk, 0.0)
        reticles_per_chunk = jnp.maximum(
            arrs["n_reticles"].astype(jnp.int64)[:, None] * nw2
            / jnp.maximum(chunks, 1), 1e-9)
        stacked_bw = (arrs["dram_bw_Bps_per_reticle"][:, None]
                      * reticles_per_chunk)
        ret_h = arrs["ret_h"].astype(jnp.int64)[:, None]
        ret_w = arrs["ret_w"].astype(jnp.int64)[:, None]
        n_edge = 2 * (ret_h + ret_w)
        offchip_bw = (n_edge * C.OFFCHIP_BW_PER_CTRL
                      / jnp.maximum(chunks, 1))
        transit = ir_bw * jnp.minimum(ret_h, ret_w) \
            / jnp.maximum(chunks, 1)
        dram_on = arrs["dram_on"][:, None].astype(bool)
        dram_bw = jnp.where(dram_on, stacked_bw,
                            jnp.minimum(offchip_bw, transit))
        kv_in_dram = (w_bytes + kv_total) > sram_per_chunk
        dram_traffic = spill + jnp.where(kv_in_dram, kv_write, 0.0)
        dram_s = jnp.where(dram_traffic <= 0, 0.0,
                           dram_traffic / jnp.maximum(dram_bw, 1.0))

        _s1 = fp(compute_s) + fp(tp_s)
        _s2 = _s1 + fp(pp_s)
        stage_s = _s2 + fp(dram_s)
        a2a_vol = None
        if ep2 is not None:
            # MoE dispatch+combine all-to-all (chunk_eval mirror); the
            # where-guard zeroes ep=1 lanes so stage_s + fp(0.0) keeps
            # the legacy bits
            topk = max(wl.moe_topk, 1)
            a2a_vol = jnp.where(ep2 > 1,
                                4.0 * (ep2 - 1) / ep2 * act_bytes * topk,
                                0.0)
            ep_s = (a2a_vol / jnp.maximum(ir_bw, 1.0) * layers_per_stage
                    * bwd)
            stage_s = stage_s + fp(ep_s)
        # fp() also blocks the `x / (a/b) -> x * (b/a)` divide rewrite on
        # iter_s below, which re-rounds against the NumPy association.
        eff = fp(mb_count / (mb_count + pp - 1.0))
        iter_s = stage_s * mb_count / eff
        grad_vol = 2.0 * (dp - 1) / dp * w_bytes
        wafers_per_replica = jnp.maximum(nw2 / dp, 1e-9)
        dp_bw = jnp.where(wafers_per_replica >= 1.0,
                          n_edge * C.INTER_WAFER_BW_PER_NI,
                          ir_bw * jnp.minimum(ret_h, ret_w))
        dp_s = jnp.where((dp <= 1) | (not self._train), 0.0,
                         grad_vol / jnp.maximum(dp_bw, 1.0))
        step_s = iter_s + dp_s
        throughput = self._tokens / jnp.maximum(step_s, 1e-12)

        E = C.ENERGY
        # `p12` (traced 1e-12) keeps the simplifier from folding the pJ
        # constants with the unit scale into one single-rounded factor.
        # pin every intermediate product: these bare mul chains get
        # reassociated under jit (each fp is fma(a, b, 0) == round(a*b),
        # i.e. exactly the NumPy left-to-right per-op rounding)
        e_sram = fp(fp(fp(fp(fp(fp(sram_bits_layer * nl_t) * mb_count)
                            * dp) * bwd) * E.sram_read_bit) * p12)
        e_noc = fp(fp(fp(fp(fp(fp(fp(noc_bytes_layer * 8) * nl_t)
                             * mb_count) * dp) * bwd) * E.noc_bit_hop)
                   * p12)
        ir_bytes = (2.0 * (tp - 1) / jnp.maximum(tp, 1) * mb_tokens
                    * dmod_t * BYTES * 2 * wl.n_layers * mb_count * dp
                    * bwd)
        ir_bytes = fp(ir_bytes) + fp(p_bytes * 2 * (dp > 1))
        if a2a_vol is not None:
            ir_bytes = ir_bytes + fp(fp(fp(a2a_vol * nl_t) * mb_count)
                                     * dp)
        e_ir = (ir_bytes * 8 * arrs["ir_energy_pj_per_bit"][:, None]
                * p12)
        dram_bytes = dram_traffic * mb_count * dp
        e_dram = dram_bytes * 8 * jnp.where(dram_on, E.dram_bit,
                                            E.offchip_bit) * p12
        static_w = arrs["static_power_w"][:, None] * nw2
        energy = (self._e_mac + fp(e_sram) + fp(e_noc) + fp(e_ir)
                  + fp(e_dram) + fp(static_w * step_s))

        bad = ~(jnp.isfinite(step_s) & jnp.isfinite(energy))
        power = jnp.where(bad, jnp.inf,
                          energy / jnp.maximum(step_s, 1e-12))
        limit = C.WAFER_POWER_W * nw2
        feasible = ~bad & (power <= limit) & jnp.isfinite(power)

        step_time_s = jnp.where(bad, jnp.inf, step_s)
        thpt_out = jnp.where(bad, 0.0, throughput)
        energy_out = jnp.where(bad, 0.0, energy)

        cand = {
            "feasible": feasible,
            "throughput": thpt_out,
            "power_w": power,
            "step_time_s": step_time_s,
            "pipeline_eff": eff,
            "energy_j": energy_out,
            "compute_s": compute_s,
            "tp_s": tp_s,
            "pp_s": pp_s,
            "dram_s": dram_s,
            "dp_s": dp_s,
            "mb_count": mb_count,
        }
        if extras is not None:
            cand["ep_s"] = ep_s
        return cand

    # -- host-side entry points --------------------------------------------

    def _put(self, v):
        """Commit `v` to host lane 0 in its traced dtype (call under
        `jax.enable_x64`)."""
        import jax
        return jax.device_put(_np64(v), self.host)

    def _pad_rows(self, arrs: Dict[str, np.ndarray], nw: np.ndarray,
                  npad: int):
        n = len(nw)
        if npad == n:
            return arrs, nw
        width = [(0, npad - n)]
        return ({k: np.pad(v, width, mode="edge") for k, v in arrs.items()},
                np.pad(nw, width, mode="edge"))

    def _bucket(self, n: int) -> int:
        npad = _pow2(max(n, 4))
        if self.lanes > 1:
            npad = -(-npad // self.lanes) * self.lanes
        return npad

    def run_batch(self, arrs: Dict[str, np.ndarray], nw: np.ndarray
                  ) -> Dict[str, np.ndarray]:
        """Evaluate N designs; returns winner arrays sliced back to N."""
        import jax

        n = len(nw)
        npad = self._bucket(n)
        arrs, nwp = self._pad_rows(arrs, nw, npad)
        with jax.enable_x64(True):
            if self.lanes > 1 and npad % self.lanes == 0:
                shp = (self.lanes, npad // self.lanes)
                out = self._pfn(
                    {k: _np64(v).reshape(shp + v.shape[1:])
                     for k, v in arrs.items()},
                    _np64(nwp).reshape(shp), self._zc_np)
                out = {k: np.asarray(v).reshape(npad) for k, v in out.items()}
                _LANE_STATS["n_lanes"] = self.lanes
                _LANE_STATS["sharded_calls"] += 1
                _LANE_STATS["rows_sharded"] += npad
            else:
                out = self._jit({k: self._put(v) for k, v in arrs.items()},
                                self._put(nwp), self._zc())
                out = {k: np.asarray(v) for k, v in out.items()}
                _LANE_STATS.setdefault("n_lanes", 1)
                _LANE_STATS["n_lanes"] = max(_LANE_STATS["n_lanes"], 1)
                _LANE_STATS["jit_calls"] += 1
                _LANE_STATS["rows_jit"] += npad
        return {k: v[:n] for k, v in out.items()}

    def dispatch_fused(self, arrs: Dict[str, np.ndarray], nw: np.ndarray,
                       js_dev) -> "_PendingEval":
        """Gather + evaluate the candidate-pool rows the device-resident
        `js_dev` indices name, without waiting for the indices to reach the
        host (the acquire scan's output feeds the evaluator inside XLA).
        Returns a pending handle; extraction is one host transfer."""
        import jax

        n = len(nw)
        npad = _pow2(max(n, 4))
        arrs, nwp = self._pad_rows(arrs, nw, npad)
        with jax.enable_x64(True):
            ja = {k: self._put(v) for k, v in arrs.items()}
            jn = self._put(nwp)
            js = jax.device_put(js_dev, self.host)
            out = self._fused_jit(ja, jn, self._zc(), js)
        _LANE_STATS["jit_calls"] += 1
        _LANE_STATS["rows_jit"] += int(js_dev.shape[0])
        return _PendingEval(self, out)

    def results_from(self, out: Dict[str, np.ndarray], nw: np.ndarray
                     ) -> List["EvalResult"]:
        """Materialize EvalResult/StepResult rows from extracted winner
        arrays — the same construction `_finish` + `step_result_at` do."""
        from repro.core.fidelity import EvalResult
        res: List[EvalResult] = []
        for i in range(len(nw)):
            if not bool(out["any_feasible"][i]):
                res.append(EvalResult(0.0, float("inf"), None, None,
                                      int(nw[i]), False,
                                      "no_feasible_strategy"))
                continue
            g = int(out["sel_g"][i])
            eff = float(out["pipeline_eff"][i])
            mbc = float(out["mb_count"][i])
            sr = StepResult(
                step_time_s=float(out["step_time_s"][i]),
                throughput=float(out["throughput"][i]),
                power_w=float(out["power_w"][i]),
                pipeline_eff=eff,
                breakdown={
                    "compute": float(out["compute_s"][i]) * mbc / eff,
                    "tp": float(out["tp_s"][i]) * mbc / eff,
                    "pp": float(out["pp_s"][i]) * mbc / eff,
                    "dram": float(out["dram_s"][i]) * mbc / eff,
                    "dp": float(out["dp_s"][i])},
                energy_j=float(out["energy_j"][i]),
                feasible=True, reason="")
            res.append(EvalResult(
                sr.throughput, sr.power_w,
                Strategy(int(self._tp_o[g]), int(self._pp_o[g]),
                         int(self._dp_o[g]), int(self._mb_o[g])),
                sr, int(nw[i]), True))
        return res

    # -- pinned-strategy (joint mode) entry points -------------------------

    def _pad_strat(self, strat, npad: int):
        n = len(strat[0])
        if npad == n:
            return strat
        return tuple(np.pad(s, [(0, npad - n)], mode="edge") for s in strat)

    def run_batch_pinned(self, arrs: Dict[str, np.ndarray], nw: np.ndarray,
                         strat) -> Dict[str, np.ndarray]:
        """Evaluate N (design, strategy) pairs; `strat` is the
        (tp, pp, dp, mb, ep, recompute) array tuple."""
        import jax

        n = len(nw)
        npad = self._bucket(n)
        arrs, nwp = self._pad_rows(arrs, nw, npad)
        strat = self._pad_strat(strat, npad)
        with jax.enable_x64(True):
            if self.lanes > 1 and npad % self.lanes == 0:
                shp = (self.lanes, npad // self.lanes)
                out = self._pfn_pinned(
                    {k: _np64(v).reshape(shp + v.shape[1:])
                     for k, v in arrs.items()},
                    _np64(nwp).reshape(shp), self._zc_np,
                    tuple(_np64(s).reshape(shp) for s in strat))
                out = {k: np.asarray(v).reshape(npad) for k, v in out.items()}
                _LANE_STATS["n_lanes"] = self.lanes
                _LANE_STATS["sharded_calls"] += 1
                _LANE_STATS["rows_sharded"] += npad
            else:
                out = self._jit_pinned(
                    {k: self._put(v) for k, v in arrs.items()},
                    self._put(nwp), self._zc(),
                    tuple(self._put(s) for s in strat))
                out = {k: np.asarray(v) for k, v in out.items()}
                _LANE_STATS["jit_calls"] += 1
                _LANE_STATS["rows_jit"] += npad
        return {k: v[:n] for k, v in out.items()}

    def dispatch_fused_pinned(self, arrs: Dict[str, np.ndarray],
                              nw: np.ndarray, strat, js_dev
                              ) -> "_PendingPinnedEval":
        """Fused gather + pinned evaluation of the joint-pool rows named by
        the device-resident `js_dev` indices (joint-mode counterpart of
        `dispatch_fused`)."""
        import jax

        n = len(nw)
        npad = _pow2(max(n, 4))
        arrs, nwp = self._pad_rows(arrs, nw, npad)
        strat = self._pad_strat(strat, npad)
        with jax.enable_x64(True):
            ja = {k: self._put(v) for k, v in arrs.items()}
            jn = self._put(nwp)
            js = tuple(self._put(s) for s in strat)
            picks = jax.device_put(js_dev, self.host)
            out = self._fused_pinned_jit(ja, jn, self._zc(), js, picks)
        _LANE_STATS["jit_calls"] += 1
        _LANE_STATS["rows_jit"] += int(js_dev.shape[0])
        return _PendingPinnedEval(self, out)

    def results_from_pinned(self, out: Dict[str, np.ndarray],
                            nw: np.ndarray, strategies,
                            res_ok: Optional[np.ndarray] = None
                            ) -> List["EvalResult"]:
        """Materialize pinned-mode EvalResults — the same construction the
        NumPy `_finish` does in pinned mode (strategy_resources when the
        host-computed grid resource-fit mask `res_ok` rejects the point,
        strategy_infeasible on a power/finiteness failure, breakdown gains
        "ep" only when the all-to-all term is nonzero, matching
        `step_result_at`)."""
        from repro.core.fidelity import EvalResult
        res: List[EvalResult] = []
        for i, s in enumerate(strategies):
            fit = res_ok is None or bool(res_ok[i])
            if not (fit and bool(out["feasible"][i])):
                res.append(EvalResult(0.0, float("inf"), s, None,
                                      int(nw[i]), False,
                                      "strategy_resources" if not fit
                                      else "strategy_infeasible"))
                continue
            eff = float(out["pipeline_eff"][i])
            mbc = float(out["mb_count"][i])
            bd = {"compute": float(out["compute_s"][i]) * mbc / eff,
                  "tp": float(out["tp_s"][i]) * mbc / eff,
                  "pp": float(out["pp_s"][i]) * mbc / eff,
                  "dram": float(out["dram_s"][i]) * mbc / eff,
                  "dp": float(out["dp_s"][i])}
            ep_v = float(out["ep_s"][i])
            if ep_v:
                bd["ep"] = ep_v * mbc / eff
            sr = StepResult(
                step_time_s=float(out["step_time_s"][i]),
                throughput=float(out["throughput"][i]),
                power_w=float(out["power_w"][i]),
                pipeline_eff=eff, breakdown=bd,
                energy_j=float(out["energy_j"][i]),
                feasible=True, reason="")
            res.append(EvalResult(sr.throughput, sr.power_w, s, sr,
                                  int(nw[i]), True))
        return res


def _np64(v) -> np.ndarray:
    """`v` as the int64 / float64 / bool array the programs trace."""
    a = np.asarray(v)
    if np.issubdtype(a.dtype, np.integer):
        return a.astype(np.int64)
    if a.dtype == np.bool_:
        return a
    return a.astype(np.float64)


@dataclasses.dataclass
class _PendingEval:
    """In-flight fused evaluation: the program is dispatched; `finish`
    blocks on the single batched host extraction and builds EvalResults
    for the first q picks (position-aligned with the pick indices)."""
    prog: _EvalProgram
    out: Dict

    def finish(self, nw_picks: np.ndarray, q: int) -> List["EvalResult"]:
        host = {k: np.asarray(v)[:q] for k, v in self.out.items()}
        return self.prog.results_from(host, nw_picks[:q])


@dataclasses.dataclass
class _PendingPinnedEval:
    """In-flight fused pinned-strategy evaluation (joint mode)."""
    prog: _EvalProgram
    out: Dict

    def finish(self, nw_picks: np.ndarray, strategies, q: int,
               res_ok: Optional[np.ndarray] = None) -> List["EvalResult"]:
        host = {k: np.asarray(v)[:q] for k, v in self.out.items()}
        return self.prog.results_from_pinned(
            host, nw_picks[:q], strategies[:q],
            res_ok if res_ok is None else res_ok[:q])


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def geom_arrays(geom: DesignBatch) -> Dict[str, np.ndarray]:
    return {k: getattr(geom, k) for k in _GEOM_FIELDS}


def evaluate_batch_compiled(geom: DesignBatch, wl: LLMWorkload,
                            n_wafers: np.ndarray, max_strategies: int = 24
                            ) -> List["EvalResult"]:
    """Compiled analytical `evaluate_batch`: one jitted program over the
    pow2-padded design axis, bit-identical to the NumPy reference
    (`AnalyticalBackend.evaluate_batch_ref`)."""
    prog = _program_for(wl, max_strategies)
    nw = np.asarray(n_wafers, np.int64)
    out = prog.run_batch(geom_arrays(geom), nw)
    return prog.results_from(out, nw)


def strategy_arrays(strategies) -> Tuple[np.ndarray, ...]:
    """Columnize a list of Strategy into the (tp, pp, dp, mb, ep, recompute)
    array tuple the pinned program consumes."""
    return (np.array([s.tp for s in strategies], np.int64),
            np.array([s.pp for s in strategies], np.int64),
            np.array([s.dp for s in strategies], np.int64),
            np.array([s.microbatches for s in strategies], np.int64),
            np.array([s.ep for s in strategies], np.int64),
            np.array([s.recompute for s in strategies], np.bool_))


def evaluate_pinned_compiled(geom: DesignBatch, wl: LLMWorkload,
                             n_wafers: np.ndarray, strategies,
                             max_strategies: int = 24) -> List["EvalResult"]:
    """Compiled joint-mode `evaluate_batch`: each design is evaluated under
    its pinned Strategy (no grid argmin), bit-identical to the NumPy pinned
    reference path in `AnalyticalBackend.evaluate_batch_ref` — including
    the host-side grid resource-fit gate (`compiler.pinned_resource_ok`),
    computed by the same NumPy code both paths share."""
    from repro.core.compiler import pinned_resource_ok

    prog = _program_for(wl, max_strategies)
    nw = np.asarray(n_wafers, np.int64)
    cols = strategy_arrays(strategies)
    out = prog.run_batch_pinned(geom_arrays(geom), nw, cols)
    res_ok = pinned_resource_ok(wl, geom, nw, cols[0], cols[1], cols[2],
                                cols[3])
    return prog.results_from_pinned(out, nw, strategies, res_ok)


def dispatch_fused_eval_pinned(pool_geom: DesignBatch, wl: LLMWorkload,
                               nw_pool: np.ndarray, strategies, js_dev,
                               max_strategies: int = 24
                               ) -> _PendingPinnedEval:
    """Joint-mode fused propose→evaluate: gather the pool rows named by
    the device-resident `js_dev` indices together with their pinned
    strategy columns, evaluate without a host round-trip."""
    prog = _program_for(wl, max_strategies)
    return prog.dispatch_fused_pinned(geom_arrays(pool_geom),
                                      np.asarray(nw_pool, np.int64),
                                      strategy_arrays(strategies), js_dev)


def dispatch_fused_eval(pool_geom: DesignBatch, wl: LLMWorkload,
                        nw_pool: np.ndarray, js_dev,
                        max_strategies: int = 24) -> _PendingEval:
    """Fused propose→evaluate: evaluate the pool rows selected by the
    device-resident indices `js_dev` (the `_acquire_scan_jit` output)
    without a host round-trip between acquisition and evaluation."""
    prog = _program_for(wl, max_strategies)
    return prog.dispatch_fused(geom_arrays(pool_geom),
                               np.asarray(nw_pool, np.int64), js_dev)


# ---------------------------------------------------------------------------
# warm-up (satellite: evaluator programs join warm_optimizer_kernels)
# ---------------------------------------------------------------------------

_WARMED: set = set()


def warm_evaluator_kernels(wl: LLMWorkload, n_designs_max: int = 4,
                           max_strategies: int = 24,
                           pool_sizes: Tuple[int, ...] = (),
                           force: bool = False) -> int:
    """Pre-compile the analytical evaluator programs for every pow2 design
    bucket up to `n_designs_max`, plus the fused gather program for each
    candidate-pool size in `pool_sizes` (per (bucket, workload-shape)
    memoization; `force=True` re-warms). Returns buckets newly warmed."""
    if not enabled():
        return 0
    from repro.core.design_space import decode_batch

    prog = _program_for(wl, max_strategies)
    d0 = decode_batch(np.full((1, 13), 0.5))[0]
    geom1 = DesignBatch.from_designs([d0])
    arrs1 = geom_arrays(geom1)
    warmed = 0
    n = 4
    buckets = []
    while n <= _pow2(max(int(n_designs_max), 4)):
        buckets.append(("batch", n))
        n *= 2
    for p in pool_sizes:
        for qp in (4,):                  # bucket_size(q<=4, minimum=4)
            buckets.append(("fused", _pow2(max(int(p), 4)), qp))
    for b in buckets:
        key = (wl, max_strategies, prog.lanes, b)
        if key in _WARMED and not force:
            continue
        _WARMED.add(key)
        warmed += 1
        if b[0] == "batch":
            npad = b[1]
            arrs = {k: np.repeat(v, npad, axis=0) for k, v in arrs1.items()}
            nw = np.ones(npad, np.int64)
            prog.run_batch(arrs, nw)
        else:
            npad, qp = b[1], b[2]
            arrs = {k: np.repeat(v, npad, axis=0) for k, v in arrs1.items()}
            nw = np.ones(npad, np.int64)
            # int32, the dtype of the acquire scan's pick indices
            js = np.arange(qp, dtype=np.int32) % npad
            prog.dispatch_fused(arrs, nw, js).finish(nw, min(qp, npad))
    return warmed


__all__ = [
    "clear_compiled_programs", "dispatch_fused_eval",
    "dispatch_fused_eval_pinned", "enabled", "evaluate_batch_compiled",
    "evaluate_pinned_compiled", "geom_arrays", "lane_stats",
    "strategy_arrays", "warm_evaluator_kernels",
]
