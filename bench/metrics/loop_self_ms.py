"""Campaign loop self time: the window's wall clock less every layer span
(candidates, propose, evaluate, calibrate), milliseconds per step. It holds
the fold, the hypervolume, objective builds and campaign summaries."""
from bench.spans import total

LAYERS = ("candidates", "propose", "evaluate", "calibrate")


def read(run):
    if not run.steps or not any(total(run.spans, n)[1] for n in LAYERS):
        return None
    covered = sum(total(run.spans, n)[0] for n in LAYERS)
    return 1e3 * (run.window_s - covered) / len(run.steps)
