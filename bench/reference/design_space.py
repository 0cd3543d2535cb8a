# Frozen copy of src/repro/core/design_space.py (commit eba02b4), part of the
# benchmark's plain reference: it imports nothing of the program, so a
# change to the program cannot move the yardstick. Edits from the
# original are marked "bench reference:".
"""WSC design-space construction (paper §V, Table I).

Candidate values (Table I):
    dataflow          WS | IS | OS
    mac_num           8 .. 4096            (per core)
    buffer_size       32 .. 2048 KB
    buffer_bw         32 .. 4096 bit/cycle
    noc_bw            32 .. 4096 bit/cycle
    inter_reticle_bw  0.2 .. 2.0 x reticle bisection bw
    stacking_DRAM_bw  0.25 .. 4 TB/s/100mm^2 (optional)
    stacking_DRAM sz  8 .. 40 GB (linear trade with bw)
    integration       die_stitching | InFO-SoW
    inter_wafer_bw    100 GB/s per network interface
    off_chip_mem_bw   160 GB/s per memory controller
    core/reticle arrays: 1 .. max under area constraints
Heterogeneous params (§V-B): prefill_ratio, hetero granularity.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.reference import components as C

DATAFLOWS = ("WS", "IS", "OS")
INTEGRATIONS = ("die_stitching", "infosow")

MAC_RANGE = (8, 4096)
BUF_KB_RANGE = (32, 2048)
BUF_BW_RANGE = (32, 4096)
NOC_BW_RANGE = (32, 4096)
IR_RATIO_RANGE = (0.2, 2.0)
DRAM_BW_RANGE = C.DRAM_BW_RANGE


@dataclasses.dataclass(frozen=True)
class WSCDesign:
    # core level
    dataflow: str = "WS"
    mac_num: int = 512
    buffer_kb: int = 256
    buffer_bw: int = 1024          # bits/cycle
    noc_bw: int = 512              # bits/cycle
    # reticle level
    core_array: Tuple[int, int] = (8, 8)
    inter_reticle_bw_ratio: float = 1.0
    use_stacked_dram: bool = True
    dram_bw_tbps_per_100mm2: float = 1.0
    # wafer level
    reticle_array: Tuple[int, int] = (8, 8)
    integration: str = "infosow"
    # heterogeneity (inference only; §V-B)
    prefill_ratio: float = 0.5
    hetero: str = "none"           # none | core | reticle | wafer
    # resolved by the validator (spares needed for the yield target)
    spares_per_row: int = 1

    # ---------------- derived geometry ------------------------------------

    def core_area_mm2(self) -> float:
        return C.core_area_mm2(self.mac_num, self.buffer_kb, self.buffer_bw,
                               self.noc_bw)

    def core_dims_mm(self) -> Tuple[float, float]:
        a = self.core_area_mm2()
        s = math.sqrt(a)
        return (s, s)

    def cores_per_reticle(self) -> int:
        return self.core_array[0] * self.core_array[1]

    def reticle_bisection_Bps(self) -> float:
        """Bisection bandwidth of the core-array NoC (bits/cycle -> B/s)."""
        w = min(self.core_array)
        return w * self.noc_bw / 8.0 * C.CLOCK_HZ

    def inter_reticle_bw_Bps(self) -> float:
        return self.inter_reticle_bw_ratio * self.reticle_bisection_Bps()

    def reticle_compute_area_mm2(self) -> float:
        h, w = self.core_array
        spare_cols = self.spares_per_row
        return (w + spare_cols) * h * self.core_area_mm2()

    def dram_bw_Bps_per_reticle(self) -> float:
        if not self.use_stacked_dram:
            return 0.0
        return (self.dram_bw_tbps_per_100mm2 * 1e12
                * self.reticle_area_mm2() / 100.0)

    def dram_gb_per_reticle(self) -> float:
        if not self.use_stacked_dram:
            return 0.0
        return (C.dram_gb_at_bw(self.dram_bw_tbps_per_100mm2)
                * self.reticle_area_mm2() / 100.0)

    def tsv_area_mm2(self) -> float:
        if not self.use_stacked_dram:
            return 0.0
        return C.tsv_area_mm2(self.dram_bw_Bps_per_reticle())

    def reticle_area_mm2(self) -> float:
        """Compute + inter-reticle PHY + TSV keep-out."""
        phy = C.inter_reticle_area_mm2(
            4 * self.inter_reticle_bw_Bps(), self.integration)
        # TSV area depends on reticle area (bw per mm^2): solve fixed point
        base = self.reticle_compute_area_mm2() + phy
        if not self.use_stacked_dram:
            return base
        ratio = C.tsv_area_ratio(self.dram_bw_tbps_per_100mm2)
        return base / max(1.0 - ratio, 1e-3)

    def n_reticles(self) -> int:
        return self.reticle_array[0] * self.reticle_array[1]

    def wafer_area_mm2(self) -> float:
        return self.n_reticles() * self.reticle_area_mm2()

    def total_cores(self) -> int:
        return self.cores_per_reticle() * self.n_reticles()

    def core_flops(self) -> float:
        return C.core_peak_flops(self.mac_num)

    def reticle_flops(self) -> float:
        return self.core_flops() * self.cores_per_reticle()

    def wafer_flops(self) -> float:
        return self.reticle_flops() * self.n_reticles()

    def sram_per_reticle_bytes(self) -> float:
        return self.cores_per_reticle() * self.buffer_kb * 1024.0

    def static_power_w(self) -> float:
        per_core = C.core_static_w(self.mac_num, self.buffer_kb)
        dram = (C.DRAM_STATIC_W_PER_GB * self.dram_gb_per_reticle()
                * self.n_reticles())
        return per_core * self.total_cores() + dram

    def describe(self) -> str:
        return (f"{self.dataflow} mac={self.mac_num} buf={self.buffer_kb}KB "
                f"bw={self.buffer_bw}/{self.noc_bw}b "
                f"cores={self.core_array} ret={self.reticle_array} "
                f"ir={self.inter_reticle_bw_ratio:.2f}x "
                f"dram={'%.2fTB/s' % self.dram_bw_tbps_per_100mm2 if self.use_stacked_dram else 'off'} "
                f"{self.integration}")


# ---------------------------------------------------------------------------
# sampling / encoding for the explorer
# ---------------------------------------------------------------------------

# normalized [0,1]^d encoding: log-scaled for the exponential-range knobs
DIMS = ("dataflow", "mac", "buf_kb", "buf_bw", "noc_bw", "core_h", "core_w",
        "ir_ratio", "dram_on", "dram_bw", "ret_h", "ret_w", "integration")


# ---------------------------------------------------------------------------
# batched (struct-of-arrays) backend — see DESIGN.md §4
# ---------------------------------------------------------------------------


def floor_log2(n: np.ndarray) -> np.ndarray:
    """Exact floor(log2(n)) for positive int arrays (float-log corrected)."""
    n = np.maximum(np.asarray(n, dtype=np.int64), 1)
    e = np.floor(np.log2(n.astype(np.float64))).astype(np.int64)
    # one ulp of float error can push e off by one either way
    e = np.where((np.int64(1) << np.minimum(e + 1, 62)) <= n, e + 1, e)
    e = np.where((np.int64(1) << np.minimum(e, 62)) > n, e - 1, e)
    return e


@dataclasses.dataclass
class DesignBatch:
    """Struct-of-arrays view of N designs: the vector encoding plus every
    derived geometry quantity the evaluation stack needs, all computed with
    vectorized NumPy so downstream kernels broadcast over a leading batch
    axis instead of calling per-design methods (DESIGN.md §4)."""
    designs: List[WSCDesign]
    # raw knobs
    dataflow_code: np.ndarray      # (N,) 0=WS 1=IS 2=OS
    mac: np.ndarray                # (N,) int64
    buffer_kb: np.ndarray
    buffer_bw: np.ndarray
    noc_bw: np.ndarray
    core_h: np.ndarray
    core_w: np.ndarray
    ir_ratio: np.ndarray
    dram_on: np.ndarray            # (N,) bool
    dram_bw_tbps: np.ndarray
    ret_h: np.ndarray
    ret_w: np.ndarray
    integ_code: np.ndarray         # 0=die_stitching 1=infosow
    spares_per_row: np.ndarray
    # derived geometry (all float64 unless noted)
    core_area_mm2: np.ndarray
    cores_per_reticle: np.ndarray  # int64
    n_reticles: np.ndarray         # int64
    total_cores: np.ndarray        # int64
    reticle_bisection_Bps: np.ndarray
    inter_reticle_bw_Bps: np.ndarray
    reticle_area_mm2: np.ndarray
    wafer_area_mm2: np.ndarray
    dram_bw_Bps_per_reticle: np.ndarray
    dram_gb_per_reticle: np.ndarray
    static_power_w: np.ndarray
    ir_energy_pj_per_bit: np.ndarray

    def __len__(self) -> int:
        return len(self.designs)

    @staticmethod
    def from_designs(designs: Sequence[WSCDesign]) -> "DesignBatch":
        designs = list(designs)
        df = np.array([DATAFLOWS.index(d.dataflow) for d in designs], np.int64)
        mac = np.array([d.mac_num for d in designs], np.int64)
        buf_kb = np.array([d.buffer_kb for d in designs], np.int64)
        buf_bw = np.array([d.buffer_bw for d in designs], np.int64)
        noc_bw = np.array([d.noc_bw for d in designs], np.int64)
        ch = np.array([d.core_array[0] for d in designs], np.int64)
        cw = np.array([d.core_array[1] for d in designs], np.int64)
        ir = np.array([d.inter_reticle_bw_ratio for d in designs], np.float64)
        don = np.array([d.use_stacked_dram for d in designs], bool)
        dbw = np.array([d.dram_bw_tbps_per_100mm2 for d in designs], np.float64)
        rh = np.array([d.reticle_array[0] for d in designs], np.int64)
        rw = np.array([d.reticle_array[1] for d in designs], np.int64)
        ig = np.array([INTEGRATIONS.index(d.integration) for d in designs],
                      np.int64)
        spares = np.array([d.spares_per_row for d in designs], np.int64)

        # components helpers are dtype-polymorphic: same formulas/constants
        # as the scalar WSCDesign methods, applied to the whole batch
        core_area = C.core_area_mm2(mac, buf_kb, buf_bw, noc_bw)

        cpr = ch * cw
        nret = rh * rw
        total = cpr * nret
        bisect = np.minimum(ch, cw) * noc_bw / 8.0 * C.CLOCK_HZ
        ir_bw = ir * bisect

        # --- reticle area fixed point (WSCDesign.reticle_area_mm2) ---------
        phy = (4.0 * ir_bw) * 8e-9 * np.where(
            ig == 1, C.IR_AREA_UM2_PER_GBPS["infosow"],
            C.IR_AREA_UM2_PER_GBPS["die_stitching"]) * 1e-6
        compute_a = (cw + spares) * ch * core_area
        base = compute_a + phy
        tsv_ratio = C.tsv_area_ratio(dbw)
        r_area = np.where(don, base / np.maximum(1.0 - tsv_ratio, 1e-3), base)

        dram_bw_Bps = np.where(don, dbw * 1e12 * r_area / 100.0, 0.0)
        dram_gb = np.where(don, C.dram_gb_at_bw(dbw) * r_area / 100.0, 0.0)

        per_core_w = C.core_static_w(mac, buf_kb)
        static_w = per_core_w * total + C.DRAM_STATIC_W_PER_GB * dram_gb * nret

        ir_pj = np.where(ig == 1, C.IR_ENERGY_PJ_PER_BIT["infosow"],
                         C.IR_ENERGY_PJ_PER_BIT["die_stitching"])

        return DesignBatch(
            designs=designs, dataflow_code=df, mac=mac, buffer_kb=buf_kb,
            buffer_bw=buf_bw, noc_bw=noc_bw, core_h=ch, core_w=cw,
            ir_ratio=ir, dram_on=don, dram_bw_tbps=dbw, ret_h=rh, ret_w=rw,
            integ_code=ig, spares_per_row=spares, core_area_mm2=core_area,
            cores_per_reticle=cpr, n_reticles=nret, total_cores=total,
            reticle_bisection_Bps=bisect, inter_reticle_bw_Bps=ir_bw,
            reticle_area_mm2=r_area, wafer_area_mm2=nret * r_area,
            dram_bw_Bps_per_reticle=dram_bw_Bps, dram_gb_per_reticle=dram_gb,
            static_power_w=static_w, ir_energy_pj_per_bit=ir_pj)

    def take(self, idx: np.ndarray) -> "DesignBatch":
        """Gather rows (with repetition) — used to expand designs to the
        flattened (design, strategy) candidate axis."""
        idx = np.asarray(idx, np.int64)
        kw = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name == "designs":
                kw[f.name] = [self.designs[i] for i in idx]
            else:
                kw[f.name] = v[idx]
        return DesignBatch(**kw)


# ---------------------------------------------------------------------------
# joint (architecture, strategy) search space.
#
# The parallelization strategy stops being a dense grid scored inside the
# evaluator and becomes extra normalized dimensions appended to the 13-dim
# architecture encoding, so MFMOBO proposes joint points directly.
# Power-of-two axes (tp/pp/dp/ep) encode as exponent fractions of a
# workload-derived cap; microbatch count indexes the discrete choice list;
# recompute and the pipeline schedule are threshold bits.
# ---------------------------------------------------------------------------

STRATEGY_DIMS = ("tp", "pp", "dp", "ep", "microbatches", "recompute",
                 "schedule")
MB_CHOICES = (1, 2, 4, 8, 16, 32)


