"""Proposal (GP pair fit and q-EHVI scan on the chip, until the picks are
on the host), milliseconds per step."""
from bench.spans import total


def read(run):
    s, n, _ = total(run.spans, "propose")
    return 1e3 * s / len(run.steps) if n and run.steps else None
