"""Decoding and validating sampled designs (`candidates.validate`, one
span per round of draws), milliseconds per step, from the program's
spans."""
from bench.program_spans import ms_per_step


def read(run):
    return ms_per_step(run, "candidates.validate")
