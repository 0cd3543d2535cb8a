"""Theseus DSE case study: explore WSC designs for GPT-175B training with
MFMOBO (analytical + GNN fidelities), print the Pareto set and compare
against the H100-like / WSE2-like / Dojo-like baselines.

    PYTHONPATH=src python examples/dse_case_study.py [--quick] \
        [--fidelity analytical|gnn|sim]

With `--fidelity gnn` the high-fidelity stage runs the batched GNN backend
with *online calibration*: the model starts untrained and is fine-tuned on
simulator traces from the Pareto neighborhood at the f1 -> f0 handover
(repro.core.calibration). `--fidelity sim` runs the cycle-approximate
simulator itself as f0 through its batched backend.
"""
import argparse

from repro.core.baselines import DOJO_LIKE, WSE2_LIKE, gpu_cluster_eval
from repro.core.evaluator import evaluate_design, registered_backends
from repro.core.validator import validate
from repro.core.workload import GPT_BENCHMARKS
from repro.explore import EvaluatorObjective, ExplorationLoop, LoopConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--benchmark", type=int, default=7,
                    help="index into the GPT benchmark table (7 = 175B)")
    ap.add_argument("--fidelity", default="analytical",
                    choices=registered_backends(),
                    help="fidelity backend for the f0 (high-fidelity) stage")
    args = ap.parse_args()

    wl = GPT_BENCHMARKS[1 if args.quick else args.benchmark]
    print(f"workload: {wl.name} training, batch {wl.batch} x seq {wl.seq}, "
          f"GPU budget {wl.gpu_budget}, f0 fidelity: {args.fidelity}")

    f1 = EvaluatorObjective(wl, "analytical")
    on_handover = None
    if args.fidelity == "gnn":
        import jax

        from repro.core.calibration import GNNCalibrator
        from repro.core.noc_gnn import init_gnn

        cal = GNNCalibrator(init_gnn(jax.random.PRNGKey(0)), wl,
                            n_designs=3 if args.quick else 6,
                            epochs=5 if args.quick else 20)
        f0 = EvaluatorObjective(wl, "gnn", params_fn=lambda: cal.params)
        on_handover = cal.on_handover
    else:
        f0 = EvaluatorObjective(wl, args.fidelity)
    cfg = LoopConfig(strategy="mfmobo", d0=2, d1=3, k=3,
                     N0=6 if args.quick else 14,
                     N1=8 if args.quick else 18,
                     n_candidates=64, q=2 if args.quick else 4, seed=0)
    tr = ExplorationLoop(cfg, f0, f1=f1, on_handover=on_handover).run()
    front = tr.pareto()
    print(f"\nexplored {len(tr.ys)} high-fidelity designs; "
          f"hypervolume {tr.hv[0]:.2f} -> {tr.hv[-1]:.2f}")
    best_i = max(range(len(tr.ys)), key=lambda i: tr.ys[i][0])
    print(f"best design: {tr.designs[best_i].describe()}")
    print(f"  throughput {tr.ys[best_i][0]:.0f} tok/s, "
          f"power {tr.ys[best_i][1]/1e3:.1f} kW/wafer")

    gpu_t, gpu_p = gpu_cluster_eval(wl)
    print(f"\nbaselines at matched total area:")
    print(f"  H100-like cluster: {gpu_t:.0f} tok/s, {gpu_p/1e3:.0f} kW")
    for name, d in (("WSE2-like", WSE2_LIKE), ("Dojo-like", DOJO_LIKE)):
        v = validate(d)
        r = evaluate_design(v.design if v.ok else d, wl, max_strategies=8)
        print(f"  {name}: {r.throughput:.0f} tok/s, {r.power_w/1e3:.1f} kW "
              f"(strategy {r.strategy})")


if __name__ == "__main__":
    main()
