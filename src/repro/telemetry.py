"""Spans and counters inside the explorer, one telemetry per campaign.

    with telemetry.span("propose.fit", items=len(X)):
        ...
    telemetry.count("schedule.hit")
    picks = telemetry.to_host(js_dev, "picks")

A span is a `jax.profiler.TraceAnnotation("repro:<name>", id=<id>)` plus
two reads of `time.perf_counter_ns()`. While a `Telemetry` is active on
the thread (`activate`), the span appends a `SpanRecord` to it: its id,
its parent (the span open around it on the same thread), name, tag,
start and end, items and thread. A profiler trace taken meanwhile holds
one `repro:` host event per record carrying the same id, so a record is
joined to its event by id (the trace's times are relative to its own
start). With no telemetry active only the annotation is made.

`to_host` is the one place the program blocks on the chip for a value:
it reads a pytree to NumPy inside a `sync.<what>` span, counts it under
`host_syncs`, and allows the read under a disallowing
`jax.transfer_guard_device_to_host`. A `jax.monitoring` listener counts
compiles (backend compiles and persistent-cache loads) and their seconds
under the innermost open span, so a summary says which step compiled.

The campaign's exploration loop owns its `Telemetry` (it lives on the
loop state and is checkpointed with it) and activates it for each step;
threads that evaluate for it are handed it explicitly.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

PREFIX = "repro:"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")

_local = threading.local()


class SpanRecord(NamedTuple):
    id: int
    parent: int              # -1: no span open around it on its thread
    name: str
    tag: str
    t0_ns: int               # time.perf_counter_ns()
    t1_ns: int
    items: int
    thread: str

    @property
    def seconds(self) -> float:
        return 1e-9 * (self.t1_ns - self.t0_ns)


class Telemetry:
    """The span records and counters of one campaign."""

    def __init__(self):
        self.records: List[SpanRecord] = []
        self.counters: Dict[str, float] = {}
        self._next_id = 0
        self._lock = threading.Lock()

    def __getstate__(self):
        return {"records": list(self.records),
                "counters": dict(self.counters), "_next_id": self._next_id}

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()

    def _new_id(self) -> int:
        with self._lock:
            i = self._next_id
            self._next_id += 1
        return i

    def add(self, name: str, n: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def summary(self) -> Dict[str, Dict[str, Any]]:
        """Per span name: count, seconds, self seconds (less the spans
        opened inside it) and items; a tagged name also splits by tag."""
        child_s: Dict[int, float] = collections.defaultdict(float)
        for r in self.records:
            if r.parent >= 0:
                child_s[r.parent] += r.seconds
        out: Dict[str, Dict[str, Any]] = {}
        for r in self.records:
            e = out.setdefault(r.name, _empty())
            _fold(e, r, r.seconds - child_s[r.id])
            if r.tag:
                _fold(e.setdefault("tags", {}).setdefault(r.tag, _empty()),
                      r, r.seconds - child_s[r.id])
        return out

    def to_dict(self) -> Dict[str, Any]:
        return {"spans": self.summary(), "counters": dict(self.counters)}


def _empty() -> Dict[str, Any]:
    return {"count": 0, "s": 0.0, "self_s": 0.0, "items": 0}


def _fold(e: Dict[str, Any], r: SpanRecord, self_s: float) -> None:
    e["count"] += 1
    e["s"] += r.seconds
    e["self_s"] += self_s
    e["items"] += r.items


def current() -> Optional[Telemetry]:
    """The telemetry active on this thread, if any."""
    return getattr(_local, "tel", None)


class activate:
    """Make `tel` this thread's active telemetry for a `with` block."""
    __slots__ = ("tel", "_prev")

    def __init__(self, tel: Optional[Telemetry]):
        self.tel = tel

    def __enter__(self) -> Optional[Telemetry]:
        self._prev = getattr(_local, "tel", None)
        _local.tel = self.tel
        return self.tel

    def __exit__(self, *exc) -> bool:
        _local.tel = self._prev
        return False


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


class span:
    """A named span; `items` may be set inside the block. After the block
    `seconds` holds its length, whether or not a telemetry is active."""
    __slots__ = ("name", "items", "tag", "t0_ns", "t1_ns", "_tel", "_id",
                 "_parent", "_ann")

    def __init__(self, name: str, items: int = 0, tag: str = ""):
        self.name = name
        self.items = items
        self.tag = tag

    def __enter__(self) -> "span":
        tel = self._tel = getattr(_local, "tel", None)
        if tel is None:
            self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        else:
            self._id = tel._new_id()
            stack = _stack()
            self._parent = stack[-1][0] if stack else -1
            stack.append((self._id, self.name))
            self._ann = jax.profiler.TraceAnnotation(
                PREFIX + self.name, id=self._id, tag=self.tag)
        self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        tel = self._tel
        if tel is not None:
            _stack().pop()
            tel.records.append(SpanRecord(
                self._id, self._parent, self.name, self.tag, self.t0_ns,
                self.t1_ns, int(self.items),
                threading.current_thread().name))
        return False

    @property
    def seconds(self) -> float:
        return 1e-9 * (self.t1_ns - self.t0_ns)


def count(name: str, n: float = 1) -> None:
    """Add `n` to a counter of the active telemetry (none: no-op)."""
    tel = getattr(_local, "tel", None)
    if tel is not None:
        tel.add(name, n)


def to_host(x, what: str):
    """Block on the device for the pytree `x` and return it as NumPy,
    inside a `sync.<what>` span, counted under `host_syncs`."""
    with span("sync." + what), jax.transfer_guard_device_to_host("allow"):
        out = jax.device_get(x)
    count("host_syncs")
    return out


def _on_compile(event: str, duration: float, **kw) -> None:
    if event not in COMPILE_EVENTS:
        return
    tel = getattr(_local, "tel", None)
    if tel is None:
        return
    stack = getattr(_local, "stack", None)
    where = stack[-1][1] if stack else "none"
    tel.add("compiles")
    tel.add("compile_s", duration)
    tel.add(f"compiles[{where}]")
    tel.add(f"compile_s[{where}]", duration)


jax.monitoring.register_event_duration_secs_listener(_on_compile)

__all__ = ["PREFIX", "SpanRecord", "Telemetry", "activate", "count",
           "current", "span", "to_host"]
