"""Candidate sampling (`mfmobo._valid_candidates` through the loop's
`_candidates`: decode and validate a pool), milliseconds per step."""
from bench.spans import total


def read(run):
    s, n, _ = total(run.spans, "candidates")
    return 1e3 * s / len(run.steps) if n and run.steps else None
