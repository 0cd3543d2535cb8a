"""The GP pair fit (`propose.fit`: the fit on the chip and the read of
its parameters), milliseconds per step, from the program's spans."""
from bench.program_spans import ms_per_step


def read(run):
    return ms_per_step(run, "propose.fit")
