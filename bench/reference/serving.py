# Frozen copy of src/repro/core/serving.py (commit eba02b4), part of the
# benchmark's plain reference: it imports nothing of the program, so a
# change to the program cannot move the yardstick. Edits from the
# original are marked "bench reference:".
"""Request-level serving evaluation: analytical continuous batching.

bench reference: only `ServingSLO`, which the trace-serving reference
(`traces.py`) builds on, is kept; the all-arrived serving model is not part
of any cell.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ServingSLO:
    """Service-level objective: a request counts toward goodput only if its
    time-to-first-token and time-per-output-token both meet the bound."""
    ttft_s: float
    tpot_s: float
