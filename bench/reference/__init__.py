"""The benchmark's plain reference: what every timed campaign's answers are
compared with after the window.

The modules beside this file are frozen copies of the program's NumPy
reference paths (the analytical evaluator's `evaluate_batch_ref`, the
per-step trace scheduler `_trace_schedule_ref`, the eager `NumpyGP`, and the
GNN run one unpadded graph at a time). They import nothing of the program
and take nothing it made except the question itself: the designs a campaign
evaluated, and for the GNN fidelity the parameters it evaluated them with.
The workload comes from the configuration file, the trace from the traffic
file.

This package is the default reference, for a configuration whose layers
are the one uniform layer `workload.LLMWorkload` models. A configuration
whose layers differ brings its own: its file carries `"reference":
"<name>"`, and a new module `bench/reference/<name>.py` exports the four
names this package exports (`WORKLOAD_KEYS`, `workload(config)`,
`train_objectives(...)`, `trace_objectives(...)`) with the same signatures
and return shapes. It may import the frozen modules beside it and replace
what its layers change, and changes none of them; like them, it imports
nothing of the program. `for_config` finds it.
"""
from __future__ import annotations

import dataclasses
import importlib
import math
import sys
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from bench.reference import components as C
from bench.reference.design_space import WSCDesign
from bench.reference.evaluator import evaluate_design_batch
from bench.reference.traces import (
    PolicyDesign,
    TenantClass,
    evaluate_trace_serving_batch,
    synth_trace,
)
from bench.reference.workload import LLMWorkload

PENALTY = (0.0, C.WAFER_POWER_W)
WORKLOAD_KEYS = ("n_layers", "d_model", "n_heads", "n_kv", "d_ff", "vocab",
                 "seq", "batch", "phase", "moe_experts", "moe_topk",
                 "gpu_budget")
EXPORTS = ("WORKLOAD_KEYS", "workload", "train_objectives",
           "trace_objectives")


def for_config(config: Mapping):
    """The reference a configuration is compared with: the module
    `bench.reference.<name>` where its file carries `"reference": "<name>"`,
    this package otherwise. A name that is not a plain module name under
    `bench/reference/` exporting `EXPORTS` raises; there is no fall-back to
    this package."""
    name = config.get("reference")
    if name is None:
        return sys.modules[__name__]
    if not (isinstance(name, str) and name.isidentifier()
            and name.isascii()):
        raise ValueError(f"reference {name!r}: not a plain module name "
                         f"under bench/reference/")
    full = f"{__name__}.{name}"
    try:
        mod = importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
        raise ValueError(f"reference {name!r}: no module "
                         f"bench/reference/{name}.py") from None
    missing = [k for k in EXPORTS if not hasattr(mod, k)]
    if missing:
        raise ValueError(f"reference {name!r}: bench/reference/{name}.py "
                         f"does not export {missing}")
    return mod


def workload(config: Mapping) -> LLMWorkload:
    """The modelled job, from the configuration file's published widths."""
    return LLMWorkload(name=config["workload_ref"],
                       **{k: config[k] for k in WORKLOAD_KEYS})


def design(d: Mapping):
    """A design (or a (design, policy) point) from its plain-dict form."""
    if "policy" in d and "design" in d:
        return PolicyDesign(design(d["design"]), d["policy"])
    kw = dict(d)
    for k in ("core_array", "reticle_array"):
        kw[k] = tuple(kw[k])
    return WSCDesign(**kw)


def request_trace(t: Mapping):
    """The campaign's request trace, from the traffic file's `trace` block
    (same generator arguments as the spec's trace)."""
    kw: Dict = {"rate": t["rate"]}
    if t["kind"] == "spike":
        kw.update(spike_factor=t["spike_factor"], spike_len=t["spike_len"],
                  gap_len=t["gap_len"])
    elif t["kind"] == "diurnal":
        kw.update(period=t["period"], amplitude=t["amplitude"])
    if t.get("tenants"):
        ts = t["tenants"]
        kw.update(
            tenants=tuple(TenantClass(
                name=x["name"], ttft_s=float(x["ttft_s"]),
                tpot_s=float(x["tpot_s"]), priority=int(x.get("priority", 0)),
                interactive=bool(x.get("interactive", True))) for x in ts),
            shares=tuple(float(x.get("share", 1.0)) for x in ts),
            prompt_ranges=tuple(tuple(x.get("prompt_range", (256, 1024)))
                                for x in ts),
            out_ranges=tuple(tuple(x.get("out_range", (32, 128)))
                             for x in ts))
    return synth_trace(t["kind"], t["n_requests"], seed=t["seed"], **kw)


def fold(metrics: Sequence[Mapping], objectives: Sequence[Mapping],
         constraints: Sequence = ()) -> List[Tuple[float, float]]:
    """Metric dicts to the campaign's (y0, y1) pairs: an infeasible or
    constraint-violating point, or a non-finite one, is the penalty point."""
    ops = {"<=": lambda v, b: v <= b, ">=": lambda v, b: v >= b}
    out = []
    for m in metrics:
        if not bool(m.get("feasible", True)):
            out.append(PENALTY)
            continue
        cs = [c if isinstance(c, Mapping) else dict(zip(
            ("metric", "op", "bound"), c)) for c in constraints]
        if not all(ops[c["op"]](float(m[c["metric"]]), float(c["bound"]))
                   for c in cs):
            out.append(PENALTY)
            continue
        y = (float(m[objectives[0]["name"]]), float(m[objectives[1]["name"]]))
        out.append(y if math.isfinite(y[0]) and math.isfinite(y[1])
                   else PENALTY)
    return out


def train_objectives(designs: Sequence[Mapping], config: Mapping,
                     spec: Mapping, fidelity: str = "analytical",
                     gnn_params: Optional[Dict] = None
                     ) -> List[Tuple[float, float]]:
    """(y0, y1) of each design under the train scenario."""
    wl = dataclasses.replace(workload(config), phase="train")
    rs = evaluate_design_batch([design(d) for d in designs], wl,
                               fidelity=fidelity, gnn_params=gnn_params,
                               max_strategies=spec["max_strategies"])
    ms = [{"throughput": r.throughput, "power": r.power_w,
           "power_per_wafer": r.power_w / max(r.n_wafers, 1),
           "n_wafers": float(r.n_wafers), "feasible": r.feasible}
          for r in rs]
    return fold(ms, spec["objectives"], spec.get("constraints", ()))


def trace_objectives(designs: Sequence[Mapping], config: Mapping,
                     spec: Mapping) -> List[Tuple[float, float]]:
    """(y0, y1) of each (design, policy) point under the trace_serving
    scenario, scheduled by the per-step reference scheduler."""
    t = spec["trace"]
    rs = evaluate_trace_serving_batch(
        [design(d) for d in designs], workload(config), request_trace(t),
        slots=t["slots"],
        policy="fifo" if t["policy"] == "search" else t["policy"],
        window_steps=t["window_steps"], prefill_ratio=t["prefill_ratio"],
        max_strategies=spec["max_strategies"])
    ms = []
    for r in rs:
        m = {"goodput": r.goodput_tok_s,
             "interactive_goodput": r.interactive_goodput_tok_s,
             "worst_window_goodput": r.worst_window_goodput_tok_s,
             "throughput": r.throughput_tok_s,
             "ttft": r.ttft_s, "ttft_max": r.ttft_max_s,
             "tpot": r.tpot_s, "tpot_max": r.tpot_max_s,
             "slo_attainment": r.slo_attainment,
             "n_preemptions": float(r.n_preemptions),
             "power": r.power_w,
             "power_per_wafer": r.power_w / max(r.n_wafers, 1),
             "n_wafers": float(r.n_wafers),
             "feasible": r.feasible and np.isfinite(r.power_w)}
        for name, tm in r.per_tenant.items():
            m[f"tenant:{name}:goodput"] = tm["goodput_tok_s"]
            m[f"tenant:{name}:slo_attainment"] = tm["slo_attainment"]
        ms.append(m)
    return fold(ms, spec["objectives"], spec.get("constraints", ()))
