"""Benchmark-side spans around the calls into each layer of the explorer.

The program has no spans of its own yet, so the benchmark wraps the calls
the exploration loop makes into each layer, at the place the loop looks
them up (a module attribute or a class attribute), keyed by dotted name.
A target that has gone is left alone and its spans are missing: the
metrics that read them report nothing rather than a wrong number.

Two modes:
  timed   (--trace 0) only `ExplorationLoop.step` is timed, for the step
          tail, plus the bookkeeping the correctness check needs (each
          proposal's data, candidate pool and picks). No clock reads
          elsewhere.
  traced  (--trace 1) every target is timed and wrapped in a
          `jax.profiler.TraceAnnotation("bench:<span>")`, so the spans
          share the device trace's clock. The proposal's device pick
          indices are waited for inside the propose span, which splits
          proposal from evaluation in the fused iteration (the untraced
          loop lets them overlap the host work that follows).
"""
from __future__ import annotations

import dataclasses
import importlib
import time
from typing import Callable, Dict, List, Optional, Tuple

#: span name -> dotted target the exploration loop calls
TARGETS: Dict[str, str] = {
    "step": "repro.explore.runner.ExplorationLoop.step",
    "candidates": "repro.explore.runner.ExplorationLoop._candidates",
    "propose.fit": "repro.explore.runner._fit_models",
    "propose.acquire": "repro.explore.runner._acquire_batch",
    "propose.acquire_device": "repro.explore.runner._acquire_batch_device",
    "evaluate": "repro.explore.runner._eval_attributed",
    "evaluate.fused":
        "repro.explore.objectives.EvaluatorObjective.eval_many_fused",
    "calibrate": "repro.core.calibration.GNNCalibrator.on_handover",
}
#: the targets the correctness check needs in every run
ALWAYS = ("step", "propose.fit", "propose.acquire", "propose.acquire_device")


@dataclasses.dataclass
class Span:
    name: str          # layer span: candidates | propose | evaluate | calibrate
    t0: float
    t1: float
    tag: str = ""      # evaluate: the fidelity layer (analytical|gnn|trace)
    n: int = 0         # evaluate: designs evaluated


@dataclasses.dataclass
class Step:
    campaign: int
    kind: str          # init | f1 | handover | f0
    t0: float
    t1: float


def _resolve(dotted: str):
    """(owner, attribute name) of a dotted target, or None if it is gone."""
    parts = dotted.split(".")
    for i in range(len(parts) - 1, 0, -1):
        try:
            owner = importlib.import_module(".".join(parts[:i]))
        except ImportError:
            continue
        for p in parts[i:-1]:
            owner = getattr(owner, p, None)
            if owner is None:
                return None
        return (owner, parts[-1]) if hasattr(owner, parts[-1]) else None
    return None


def step_kind(loop) -> str:
    """Which kind of step the loop is about to take."""
    st, cfg = loop.state, loop.cfg
    if not st.initialized:
        return "init"
    if cfg.strategy != "mfmobo":
        return "f0"
    if st.done < cfg.N1 - cfg.d1:
        return "f1"
    if st.done < cfg.N1 - cfg.d1 + cfg.k:
        return "handover"
    return "f0"


def evaluate_layer(obj) -> str:
    """The layer an objective's evaluation runs in."""
    if type(obj).__name__ == "TraceServingObjective":
        return "trace"
    return str(getattr(obj, "fidelity", "") or "other")


class Recorder:
    """Installs the wrappers, collects spans, steps and the GP log, and
    restores every target on `uninstall`."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.spans: List[Span] = []
        self.steps: List[Step] = []
        # one entry per GP-pair fit: X, Y, the fitted models, and the
        # proposal made with them (its question and its picks)
        self.gp_log: List[Dict] = []
        self.campaign = -1
        self.missing: List[str] = []
        self._saved: List[Tuple[object, str, object]] = []
        self._annotate = None
        if traced:
            import jax
            self._annotate = jax.profiler.TraceAnnotation

    # -- installation ------------------------------------------------------

    def install(self) -> "Recorder":
        for name, dotted in TARGETS.items():
            if not self.traced and name not in ALWAYS:
                continue
            where = _resolve(dotted)
            if where is None:
                self.missing.append(dotted)
                continue
            owner, attr = where
            orig = owner.__dict__.get(attr, getattr(owner, attr))
            self._saved.append((owner, attr, orig))
            setattr(owner, attr, getattr(self, "_wrap_" + name.replace(
                ".", "_"))(orig))
        return self

    def clear(self) -> None:
        """Forget what was recorded (the warm-up's spans and steps)."""
        self.spans.clear()
        self.steps.clear()
        self.gp_log.clear()

    def uninstall(self) -> None:
        for owner, attr, orig in reversed(self._saved):
            setattr(owner, attr, orig)
        self._saved.clear()

    def annotation(self, name: str):
        """A profiler annotation in traced runs; a no-op otherwise."""
        if self._annotate is None:
            return _NULL
        return self._annotate("bench:" + name)

    # -- wrappers ----------------------------------------------------------

    def _timed(self, name: str, fn: Callable, tag_fn=None, sync=False):
        rec = self

        def wrapper(*a, **kw):
            if not rec.traced:
                return fn(*a, **kw)
            tag, n = tag_fn(a, kw) if tag_fn else ("", 0)
            with rec._annotate("bench:" + name):
                t0 = time.perf_counter()
                out = fn(*a, **kw)
                if sync:
                    import jax
                    jax.block_until_ready(out)
                t1 = time.perf_counter()
            rec.spans.append(Span(name, t0, t1, tag, n))
            return out
        return wrapper

    def _wrap_step(self, fn):
        rec = self

        def step(loop, *a, **kw):
            kind = step_kind(loop)
            with rec.annotation("step"):
                t0 = time.perf_counter()
                out = fn(loop, *a, **kw)
                t1 = time.perf_counter()
            if out:
                rec.steps.append(Step(rec.campaign, kind, t0, t1))
            return out
        return step

    def _wrap_candidates(self, fn):
        return self._timed("candidates", fn)

    def _wrap_propose_fit(self, fn):
        rec, timed = self, self._timed("propose", fn)

        def fit(X, Y, *a, **kw):
            models = timed(X, Y, *a, **kw)
            rec.gp_log.append({"campaign": rec.campaign, "X": X, "Y": Y,
                               "models": models, "cand": None})
            return models
        return fit

    def _log_acquire(self, models, cand_x, a, kw, picks) -> None:
        """Complete the fit's entry with the proposal's question (pool,
        evaluated points, reference point, batch size) and its answer:
        the picks as the program returned them (for the fused iteration
        the device vector, whose first q entries are the picks)."""
        e = self.gp_log[-1] if self.gp_log else None
        if e is None or e["models"] is not models or e["cand"] is not None:
            return
        names = ("evaluated", "ref", "q")
        args = dict(zip(names, a), **{k: kw[k] for k in names if k in kw})
        e.update(cand=cand_x, evaluated=args["evaluated"], ref=args["ref"],
                 q=int(args.get("q", 1)), picks=picks)

    def _wrap_propose_acquire(self, fn):
        rec, timed = self, self._timed("propose", fn)

        def acquire(models, cand_x, *a, **kw):
            out = timed(models, cand_x, *a, **kw)
            rec._log_acquire(models, cand_x, a, kw, out)
            return out
        return acquire

    def _wrap_propose_acquire_device(self, fn):
        rec, timed = self, self._timed("propose", fn, sync=True)

        def acquire(models, cand_x, *a, **kw):
            out = timed(models, cand_x, *a, **kw)
            rec._log_acquire(models, cand_x, a, kw, out)
            return out
        return acquire

    def _wrap_evaluate(self, fn):
        return self._timed(
            "evaluate", fn,
            lambda a, kw: (evaluate_layer(a[0]), len(a[1])))

    def _wrap_evaluate_fused(self, fn):
        return self._timed(
            "evaluate", fn,
            lambda a, kw: (evaluate_layer(a[0]), int(a[3])))

    def _wrap_calibrate(self, fn):
        return self._timed("calibrate", fn)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


def total(spans: List[Span], name: str, tag: Optional[str] = None
          ) -> Tuple[float, int, int]:
    """(seconds, count, designs) of the spans of one layer."""
    sel = [s for s in spans if s.name == name and (tag is None or
                                                    s.tag == tag)]
    return (sum(s.t1 - s.t0 for s in sel), len(sel), sum(s.n for s in sel))
