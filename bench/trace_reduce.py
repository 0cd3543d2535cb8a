"""Reduce a JAX profiler trace (`.xplane.pb`) to device busy and idle time.

    reduce_trace(path) -> Reduction

Device planes are those named `/device:<TPU|GPU>:<n>`. An operation is an
event on a device plane's "XLA Ops" line (every line but the module and
step lines where a plane has none by that name). Busy time is the union
of the operations' intervals inside the window, per device, averaged over
the devices used. The window is the host span `bench:window` when the
trace has one, otherwise the extent of all events. Idle gaps are the
window less the busy intervals, each labelled by the innermost `bench:`
host span that covers its midpoint ("none" when no span does).
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINE = "XLA Ops"
NON_OP_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Ops",
                "Framework Name Scope", "Source code")
SPAN_PREFIX = "bench:"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                         # mean over devices
    n_devices: int
    top_ops: List[Tuple[str, float]]      # (op name, seconds), all devices
    gaps: List[Tuple[str, float]]         # (enclosing span, seconds)
    gap_totals: Dict[str, float]          # span -> idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def complement(busy: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """The parts of [lo, hi] not covered by the sorted disjoint `busy`."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_gaps(gaps: Sequence[Interval], spans: Sequence[Tuple[str, float,
                                                               float]]
               ) -> List[Tuple[str, float]]:
    """(innermost covering span name, gap length) for each gap."""
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        out.append((min(cover)[1] if cover else "none", b - a))
    return out


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  spans: List[Tuple[str, float, float]]) -> Reduction:
    """The reduction on plain data: per device a list of (op, start, end),
    and the host spans (name without prefix, start, end), in seconds."""
    win = [(s, e) for n, s, e in spans if n == "window"]
    everything = [(s, e) for ops in device_ops.values() for _, s, e in ops]
    everything += [(s, e) for _, s, e in spans]
    if win:
        lo, hi = win[0]
    elif everything:
        lo, hi = min(s for s, _ in everything), max(e for _, e in everything)
    else:
        lo = hi = 0.0
    per_op: Dict[str, float] = collections.defaultdict(float)
    busy_total = 0.0
    all_gaps: List[Tuple[str, float]] = []
    inner = [sp for sp in spans if sp[0] != "window"]
    for ops in device_ops.values():
        ivs = clip([(s, e) for _, s, e in ops], lo, hi)
        for name, s, e in ops:
            a, b = max(s, lo), min(e, hi)
            if b > a:
                per_op[name] += b - a
        busy = union(ivs)
        busy_total += sum(b - a for a, b in busy)
        all_gaps += label_gaps(complement(busy, lo, hi), inner)
    n = max(len(device_ops), 1)
    totals: Dict[str, float] = collections.defaultdict(float)
    for name, g in all_gaps:
        totals[name] += g / n
    return Reduction(
        window_s=hi - lo, busy_s=busy_total / n, n_devices=len(device_ops),
        top_ops=sorted(per_op.items(), key=lambda kv: -kv[1])[:10],
        gaps=sorted(all_gaps, key=lambda kv: -kv[1])[:10],
        gap_totals=dict(sorted(totals.items(), key=lambda kv: -kv[1])))


def _short(name: str) -> str:
    """`%while.5 = (...) while(...)` -> `while.5`; `jit_f(123)` -> `jit_f`."""
    name = name.split(" = ")[0].lstrip("%")
    return name.split("(")[0] if name.endswith(")") else name


def _module_of(modules: List[Tuple[str, float, float]], t: float) -> str:
    """The module event covering time t (modules sorted by start)."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][1] <= t <= modules[lo - 1][2]:
        return modules[lo - 1][0]
    return ""


def read_xplane(path: str):
    """(device ops per device plane, bench host spans), times in seconds.
    An op is named `<module>/<op>`, both as short as the trace allows."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if DEVICE_PLANE.match(plane.name):
            names = [ln.name for ln in lines]
            pick = ([ln for ln in lines if ln.name == OP_LINE]
                    if OP_LINE in names else
                    [ln for ln in lines if ln.name not in NON_OP_LINES])
            modules = sorted(
                (_short(ev.name), ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ln in lines if ln.name == "XLA Modules"
                for ev in ln.events)
            ops = []
            for ln in pick:
                for ev in ln.events:
                    s = ev.start_ns * 1e-9
                    mod = _module_of(modules, s)
                    op = _short(ev.name)
                    ops.append((f"{mod}/{op}" if mod else op, s,
                                s + ev.duration_ns * 1e-9))
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    return device_ops, spans


def profile_options():
    """Profiler options for a traced run: device activity and the bench
    annotations, without the Python-call tracer (which slowed the
    Python-heavy host layers threefold) or XLA:CPU op events."""
    from jax.profiler import ProfileOptions
    o = ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    o.enable_hlo_proto = False
    return o


def reduce_trace(path: str) -> Reduction:
    device_ops, spans = read_xplane(path)
    return reduce_events(device_ops, spans)
