"""Share of the traced window in which no operation ran on the chip, in
percent, from the profiler trace (`bench/trace_reduce.py`)."""


def read(run):
    r = run.reduction
    if r is None or r.n_devices == 0 or r.window_s <= 0:
        return None
    return 100.0 * r.idle_share
