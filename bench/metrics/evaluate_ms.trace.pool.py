"""Trace-serving evaluation of the pool-policy designs of a batch
(`evaluate.trace.pool`: step evaluation, shared schedule, broadcast
metrics), milliseconds per design, from the program's spans."""
from bench.program_spans import ms_per_item


def read(run):
    return ms_per_item(run, "evaluate.trace.pool")
