"""Readings the correctness limits are set from, on the chip.

    python -m bench.limits --workload <cell> --seeds 11,12,13 [--out F]

One process: the cell's set-up once, then for each seed one round of the
cell's campaign pool (the GNN weights drawn from that seed), and for each
round the numbers `bench/check.py` compares, twice: for the program's
campaigns, and for the control (the reference one precision lower) in the
program's place. For the seeds listed in `--all`, also the proposal's
number over every proposal of the round, not only the run's seeded sample
(`pick_gap_all`). One JSON line per seed on standard output, and in
`--out`. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run_cell as rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    ap.add_argument("--all", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    every = {int(s) for s in args.all.split(",") if s}

    _, cell, config, traffic, _ = rc.start(args.workload)
    from bench import check
    run, _, _ = rc.prepare(cell, config, traffic, seeds[0], traced=False)
    recorder = run.recorder
    lines = []
    for seed in seeds:
        run = rc.Run(cell, config, traffic, seed)
        run.recorder = recorder
        recorder.clear()
        gnn = (rc.gnn_weights(seed) if traffic["spec"]["fidelity"]["f0"] ==
               "gnn" else None)
        t0 = time.perf_counter()
        rc.window(run, 0.0, gnn)
        t1 = time.perf_counter()
        line = {"seed": seed, "campaigns": run.n_campaigns,
                "failed": run.failed, "window_s": t1 - t0,
                "program": check.readings(run),
                "control": check.readings(run, control=True),
                "check_s": time.perf_counter() - t1}
        if seed in every:
            props = check.proposals(run)
            line["pick_gap_all"] = {
                "n": len(props),
                "program": check.pick_gap(props, False),
                "control": check.pick_gap(props, True)}
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write("".join(json.dumps(x) + "\n" for x in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
