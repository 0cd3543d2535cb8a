"""Times the host blocked on the chip for a value (`sync.*` spans, the
program's `telemetry.to_host`), per step."""
from bench.program_spans import steps, summaries


def read(run):
    tels = summaries(run)
    if tels is None or not steps(tels):
        return None
    n = sum(e["count"] for t in tels for name, e in t["spans"].items()
            if name.startswith("sync."))
    return n / steps(tels)
