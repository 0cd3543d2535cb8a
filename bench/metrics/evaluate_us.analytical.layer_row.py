"""The analytical evaluator's program time per layer row it scored:
microseconds of the program's `evaluate.analytical.run` spans per
`evaluate.layer_rows` (design rows x strategy columns x layer kinds of
each call's compiled program), over the window's campaigns whose
objective is the analytical evaluator (`evaluate` spans tagged
`analytical`). None where the program counts no layer rows."""
from bench.program_spans import summaries, total


def read(run):
    tels = summaries(run)
    if tels is None:
        return None
    tels = [t for t in tels
            if set(t["spans"].get("evaluate", {}).get("tags", {}))
            == {"analytical"}]
    rows = sum(t.get("counters", {}).get("evaluate.layer_rows", 0)
               for t in tels)
    if not rows:
        return None
    s, _, _ = total(tels, "evaluate.analytical.run")
    return 1e6 * s / rows
