"""GNN calibration at the fidelity handover (`noc_sim` labelling and the
fine-tune), seconds per campaign."""
from bench.spans import total


def read(run):
    s, n, _ = total(run.spans, "calibrate")
    return s / run.n_campaigns if n and run.n_campaigns else None
