"""The comparison that decides `correct`: what the timed campaigns produced
against the plain reference in `bench/reference/`, after the window.

Numbers compared, each against its limit (PERF.md gives the readings each
limit was set from):

  analytical_mismatch  designs scored by the analytical evaluator (float64
                       on XLA:CPU) whose objective pair differs in any bit
                       from the NumPy reference. Limit 0: the evaluator's
                       contract is bit-exactness.
  trace_mismatch       the same for the trace-serving objective: analytical
                       step latencies, the trace schedule under the
                       design's admission policy, the windowed metrics,
                       for every design of a seeded sample of the window's
                       campaigns (the reference scores a disaggregated
                       design through the scalar graph path, about a second
                       each). Limit 0.
  pick_gap             the proposal's answer: the picks the timed q-EHVI
                       program (the chip's GP pair fit, then the scanned
                       greedy q-EHVI with rank-1 fantasies) returned, for
                       a seeded sample of the window's proposals. The
                       reference (the eager float32 `NumpyGP` pair fitted
                       on the same data on the host, the NumPy EHVI) is led
                       through the same picks and reads, at each pick, the
                       share of its best EHVI score that the pick gives up
                       (0 where the pick is its own first choice). The
                       worst share over the sample.
  gnn_gap              the GNN-fidelity objectives of a seeded sample of
                       campaigns against the reference run one unpadded
                       graph at a time on the host in float32, with the
                       calibrated parameters the campaign evaluated them
                       with, as max |got - want| / |want|.

The control (`control=True`) puts the reference itself, computed one
precision lower, in the program's place: objective pairs rounded to
float32 for the float64 paths; the GNN in bfloat16 (weights, features,
arithmetic); the GP pair and the EHVI scores in bfloat16 arithmetic, whose
first choice at each pick is read by the float32 reference.

The reference is the one the configuration names (`bench.reference
.for_config`): the `bench/reference/` package by default, or the module
`bench/reference/<name>.py` that a configuration file's `"reference":
"<name>"` key brings (exporting `WORKLOAD_KEYS`, `workload`,
`train_objectives` and `trace_objectives`; it may import the frozen modules
beside it and changes none of them). Every number is read through it. The
GNN control lowers `bench.reference.noc_gnn.DTYPE`, so a per-configuration
module with a GNN path must score it through that module, or the control
would not bite.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

LIMITS: Dict[str, float] = {
    "analytical_mismatch": 0,
    "trace_mismatch": 0,
    "pick_gap": 1e-3,
    "gnn_gap": 1e-3,
}
PICK_SAMPLE = 4        # proposals compared per run
TRACE_SAMPLE = 4       # trace-serving campaigns compared per run
GNN_SAMPLE = 2         # campaigns whose GNN-fidelity designs are compared


def design_dict(d) -> Dict:
    if hasattr(d, "policy") and hasattr(d, "design"):
        return {"design": dataclasses.asdict(d.design), "policy": d.policy}
    return dataclasses.asdict(d)


def stage_pairs(camp) -> Tuple[List, List]:
    """(f1 pairs, f0 pairs) of (design, ys) a campaign evaluated: the trace
    records the f0 evaluations, `hist_*` every mfmobo evaluation."""
    st = camp.loop.state
    f0 = list(zip(st.trace.designs, st.trace.ys))
    f0_ids = {id(d) for d in st.trace.designs}
    f1 = [(d, y) for d, y in zip(st.hist_d, st.hist_y) if id(d) not in f0_ids]
    return f1, f0


def _f32(ys):
    return [(float(np.float32(a)), float(np.float32(b))) for a, b in ys]


def _mismatch(got: Sequence, want: Sequence) -> int:
    return sum(tuple(map(float, g)) != tuple(map(float, w))
               for g, w in zip(got, want)) + abs(len(got) - len(want))


def _rel_gap(got: Sequence, want: Sequence) -> float:
    worst = 0.0
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            if a == b:
                continue
            if not (math.isfinite(a) and math.isfinite(b)) or b == 0:
                return math.inf
            worst = max(worst, abs(a - b) / abs(b))
    return worst


def proposals(run) -> List[Dict]:
    """The window's proposals: fit data, question and picks, as host
    arrays."""
    out = []
    for e in run.gp_log:
        if e.get("picks") is None:
            continue
        out.append(dict(e, picks=[int(j) for j in
                                  np.asarray(e["picks"])[:e["q"]]]))
    return out


def pick_gap(entries: Sequence[Dict], control: bool) -> float:
    """Worst share of the reference's best q-EHVI score that a pick gave
    up, over the sampled proposals."""
    import jax

    from bench.reference.gp_ref import NumpyGP, pick_gaps
    worst = 0.0
    for e in entries:
        X = np.asarray(e["X"], np.float64)
        Y = np.asarray(e["Y"], np.float64)
        cols = (np.log1p(np.maximum(Y[:, 0], 0.0)),
                -np.log(np.maximum(Y[:, 1], 1.0)))
        with jax.default_device(jax.devices("cpu")[0]):
            ref = tuple(NumpyGP.fit(X, c) for c in cols)
            low = (tuple(NumpyGP.fit(X, c, low=True) for c in cols)
                   if control else None)
            gaps = pick_gaps(ref, np.asarray(e["cand"], np.float64),
                             np.asarray(e["evaluated"], float), e["ref"],
                             e["picks"], control=low)
        worst = max([worst] + gaps)
    return worst


def readings(run, control: bool = False) -> Dict[str, float]:
    """Every number this cell compares, for the program's campaigns (or
    for the control in the program's place)."""
    import jax
    import jax.numpy as jnp

    from bench import reference
    from bench.reference import noc_gnn as ref_gnn

    spec, config = run.traffic["spec"], run.config
    R = reference.for_config(config)
    scenario = spec["scenario"]
    out: Dict[str, float] = {}
    ana: Tuple[List, List] = ([], [])
    trace: Tuple[List, List] = ([], [])
    camps = run.campaigns
    if scenario == "trace_serving" and len(camps) > TRACE_SAMPLE:
        rng = np.random.default_rng([run.seed, 3])
        camps = [camps[i] for i in sorted(rng.choice(
            len(camps), TRACE_SAMPLE, replace=False))]
    for camp in camps:
        f1, f0 = stage_pairs(camp)
        fid = spec["fidelity"]
        stages = [(f1, fid["f1"])] if spec["strategy"] == "mfmobo" else []
        stages.append((f0, fid["f0"]))
        for pairs, fidelity in stages:
            if not pairs or fidelity != "analytical":
                continue
            ds = [design_dict(d) for d, _ in pairs]
            if scenario == "trace_serving":
                want = R.trace_objectives(ds, config, spec)
                acc = trace
            else:
                want = R.train_objectives(ds, config, spec)
                acc = ana
            acc[0].extend(_f32(want) if control else [y for _, y in pairs])
            acc[1].extend(want)
    if ana[1]:
        out["analytical_mismatch"] = _mismatch(*ana)
    if trace[1]:
        out["trace_mismatch"] = _mismatch(*trace)

    gnn_recs = [r for r in run.campaigns if spec["fidelity"]["f0"] == "gnn"]
    if gnn_recs:
        rng = np.random.default_rng([run.seed, 1])
        pick = sorted(rng.choice(len(gnn_recs), min(GNN_SAMPLE,
                                                    len(gnn_recs)),
                                 replace=False))
        worst = 0.0
        for i in pick:
            camp = gnn_recs[i]
            _, f0 = stage_pairs(camp)
            params = (camp.calibrator.params if camp.calibrator is not None
                      else camp.gnn_params)
            params = jax.tree.map(np.asarray, params)
            ds = [design_dict(d) for d, _ in f0]
            want = R.train_objectives(ds, config, spec, "gnn", params)
            if control:
                ref_gnn.DTYPE = jnp.bfloat16
                try:
                    got = R.train_objectives(ds, config, spec, "gnn", params)
                finally:
                    ref_gnn.DTYPE = jnp.float32
            else:
                got = [y for _, y in f0]
            worst = max(worst, _rel_gap(got, want))
        out["gnn_gap"] = worst

    props = proposals(run)
    if props:
        rng = np.random.default_rng([run.seed, 2])
        pick = sorted(rng.choice(len(props), min(PICK_SAMPLE, len(props)),
                                 replace=False))
        out["pick_gap"] = pick_gap([props[i] for i in pick], control)
    return out


def verdict(values: Dict[str, float]) -> bool:
    """True when every number compared is within its limit (and there is at
    least one)."""
    return bool(values) and all(
        math.isfinite(v) and v <= LIMITS[k] for k, v in values.items())
