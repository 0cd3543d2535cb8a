"""Request-level serving on one arrival batch: continuous batching.

The per-step evaluators score isolated prefill/decode `LLMWorkload`s; real
serving interleaves them: a fixed pool of decode slots runs batched decode
steps, finished slots are immediately refilled from the request queue, and
each admission runs a single-prompt prefill that stalls decode — exactly
`repro.serve.engine.ServeEngine`'s loop (DESIGN.md §8).

A `RequestMix` scored under a `ServingSLO` is the degenerate case of the
trace scenario (`repro.core.traces`): every request arrives at step 0 under
one tenant, admitted first-in first-out. Its designs are scored by
`traces.evaluate_pool_serving_batch` on
``RequestTrace.from_mix(mix, TenantClass.from_slo(slo))`` under "fifo"
(`explore.objectives.ServingObjective`), and its discrete schedule,
`continuous_batch_schedule`, is `traces.trace_schedule` on that trace. The
original per-step loop stays as `_continuous_batch_schedule_ref`, the
bitwise oracle the trace scheduler is tested against.

`disaggregated_metrics` is the coupled-request counterpart for
prefill/decode disaggregation (`heterogeneity.evaluate_hetero_serving_batch`):
prefills run on their own stage so decode never stalls, but admission is
gated by prefill completion plus the KV-cache transfer between stages.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from repro.core.traces import RequestTrace, trace_schedule
from repro.core.workload import RequestMix


@dataclasses.dataclass(frozen=True)
class ServingSLO:
    """Service-level objective: a request counts toward goodput only if its
    time-to-first-token and time-per-output-token both meet the bound."""
    ttft_s: float
    tpot_s: float


@dataclasses.dataclass
class BatchSchedule:
    """Design-independent discrete schedule of one arrival batch under
    continuous batching with `slots` decode slots (ServeEngine semantics:
    admissions fill free slots in queue order at the start of each step;
    the admitted request's first token comes from its prefill; each decode
    step then generates one token per live slot)."""
    slots: int
    n_decode_steps: int
    admit_step: np.ndarray        # (R,) step at whose start r is admitted
    finish_step: np.ndarray       # (R,) step at whose end r completes
    decode_tokens: np.ndarray     # (R,) decode steps r occupies: max(out-1,1)


def continuous_batch_schedule(mix: RequestMix, slots: int) -> BatchSchedule:
    """Mirror `ServeEngine.step`/`_admit` on the request mix. The decode
    step count is the quantity cross-validated against a real engine run.

    The degenerate case of `core.traces.trace_schedule` — every request
    arrives at step 0, one tenant, FIFO admission — and delegates to it
    (property-tested bitwise equal to the original loop, kept as
    `_continuous_batch_schedule_ref`)."""
    if slots < 1:
        raise ValueError("slots must be >= 1")
    ts = trace_schedule(RequestTrace.from_mix(mix), slots, "fifo")
    return BatchSchedule(slots=slots, n_decode_steps=ts.n_decode_steps,
                         admit_step=ts.admit_step,
                         finish_step=ts.finish_step,
                         decode_tokens=ts.decode_tokens)


def _continuous_batch_schedule_ref(mix: RequestMix,
                                   slots: int) -> BatchSchedule:
    """The original PR 4 per-step loop, kept as the reference for the
    degenerate-case bitwise property test in tests/test_traces.py."""
    if slots < 1:
        raise ValueError("slots must be >= 1")
    R = mix.n_requests
    decode_tokens = np.maximum(np.asarray(mix.out_lens, np.int64) - 1, 1)
    admit_step = np.zeros(R, np.int64)
    finish_step = np.zeros(R, np.int64)
    active: Dict[int, List[int]] = {}      # slot -> [rid, remaining]
    nxt = 0
    step = 0
    while nxt < R or active:
        for slot in range(slots):
            if slot not in active and nxt < R:
                admit_step[nxt] = step
                active[slot] = [nxt, int(decode_tokens[nxt])]
                nxt += 1
        for slot in list(active):
            active[slot][1] -= 1
            if active[slot][1] == 0:
                finish_step[active[slot][0]] = step
                del active[slot]
        step += 1
    return BatchSchedule(slots=slots, n_decode_steps=step,
                         admit_step=admit_step, finish_step=finish_step,
                         decode_tokens=decode_tokens)


# ---------------------------------------------------------------------------
# disaggregated (prefill/decode split) coupled request model
# ---------------------------------------------------------------------------


def disaggregated_metrics(mix: RequestMix, slo: ServingSLO, slots: int,
                          t_prefill: np.ndarray, kv_s: np.ndarray,
                          t_decode: float) -> Dict[str, float]:
    """Coupled request model for prefill/decode disaggregation: prompts
    prefill serially on the prefill stage (no decode stall), then the KV
    cache ships to the decode stage, and the request joins the decode pool
    when a slot frees. Admission stays in queue order (head-blocking, like
    the engine). `t_prefill`/`kv_s` are per-request seconds on the stages'
    actual resource shares; `t_decode` is the batched decode step time.

    Not the degenerate case of `traces.trace_disaggregated_metrics`, which
    admits the earliest-ready request among those whose KV has landed:
    the two differ whenever a long prompt's KV lands after a shorter
    successor's, which here waits behind it."""
    if slots < 1:
        raise ValueError("slots must be >= 1")
    R = mix.n_requests
    t_p = np.asarray(t_prefill, np.float64)
    kv = np.broadcast_to(np.asarray(kv_s, np.float64), (R,))
    ttft = np.cumsum(t_p)                  # first token from prefill stage
    ready = ttft + kv                      # decode-eligible time
    dtoks = np.maximum(np.asarray(mix.out_lens, np.int64) - 1, 1)
    completion = np.zeros(R)
    active: Dict[int, List[int]] = {}
    nxt = 0
    t = 0.0
    n_steps = 0
    while nxt < R or active:
        while (nxt < R and len(active) < slots
               and ready[nxt] <= t + 1e-12):
            slot = next(s for s in range(slots) if s not in active)
            active[slot] = [nxt, int(dtoks[nxt])]
            nxt += 1
        if not active:
            t = float(ready[nxt])
            continue
        t += t_decode
        n_steps += 1
        for slot in list(active):
            active[slot][1] -= 1
            if active[slot][1] == 0:
                completion[active[slot][0]] = t
                del active[slot]
    tpot = (completion - ttft) / dtoks
    total_time = float(max(completion.max(), ttft[-1]))
    out_toks = np.asarray(mix.out_lens, np.float64)
    met = (ttft <= slo.ttft_s) & (tpot <= slo.tpot_s)
    return {
        "ttft_s": float(ttft.mean()), "ttft_max_s": float(ttft.max()),
        "tpot_s": float(tpot.mean()), "tpot_max_s": float(tpot.max()),
        "total_time_s": total_time,
        "n_decode_steps": n_steps,
        "throughput_tok_s": float(out_toks.sum() / max(total_time, 1e-12)),
        "goodput_tok_s": float((out_toks * met).sum()
                               / max(total_time, 1e-12)),
        "slo_attainment": float(met.mean()),
    }


__all__ = [
    "BatchSchedule", "RequestMix", "ServingSLO", "continuous_batch_schedule",
    "disaggregated_metrics",
]
