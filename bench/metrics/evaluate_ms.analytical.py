"""Evaluation in the analytical layer, milliseconds per design evaluated."""
from bench.spans import total


def read(run):
    s, _, designs = total(run.spans, "evaluate", "analytical")
    return 1e3 * s / designs if designs else None
