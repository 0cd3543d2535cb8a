"""Trace-serving evaluation of a disaggregated design (one
`evaluate.trace.disaggregated` span per design), milliseconds per design,
from the program's spans."""
from bench.program_spans import ms_per_item


def read(run):
    return ms_per_item(run, "evaluate.trace.disaggregated")
