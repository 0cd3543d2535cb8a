"""The program's own spans and counters (`repro.telemetry`), as the
window's campaigns report them.

Each campaign's `result().telemetry` holds its spans by name (count,
seconds, self seconds, items) and its counters, for that campaign alone:
the warm-up campaigns are not in `run.campaigns`. A campaign without
telemetry (a program that records none) makes every reading None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def summaries(run) -> Optional[List[Dict]]:
    """The telemetry of each campaign of the window; None when there is
    no campaign or one of them has none."""
    out = []
    for c in run.campaigns:
        tel = getattr(c.result(), "telemetry", None)
        if not tel or "spans" not in tel:
            return None
        out.append(tel)
    return out or None


def total(tels: List[Dict], name: str) -> Tuple[float, int, int]:
    """(seconds, count, items) of the spans of one name."""
    es = [t["spans"][name] for t in tels if name in t["spans"]]
    return (sum(e["s"] for e in es), sum(e["count"] for e in es),
            sum(e["items"] for e in es))


def steps(tels: List[Dict]) -> int:
    """The program's `step` spans: steps that advanced a campaign."""
    return total(tels, "step")[1]


def ms_per_step(run, name: str) -> Optional[float]:
    tels = summaries(run)
    if tels is None or not steps(tels):
        return None
    s, n, _ = total(tels, name)
    return 1e3 * s / steps(tels) if n else None


def ms_per_item(run, name: str) -> Optional[float]:
    tels = summaries(run)
    if tels is None:
        return None
    s, _, items = total(tels, name)
    return 1e3 * s / items if items else None
