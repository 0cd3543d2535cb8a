"""Chip smoke: the explorer and its model runtime, end to end on a TPU.

    python chip_smoke.py             # one chip: campaigns, trainer, server
    python chip_smoke.py --chips 4   # four chips: sharded trainer vs 1x1

One process, no child processes. Every phase checks its output and any
failure exits non-zero; there is no fall-back to the CPU, to interpret mode
or to the NumPy evaluator. The float64 analytical evaluator is placed on the
host CPU by design (DESIGN.md §12); everything else runs on the chip.

Phases (one chip):
  campaigns  `examples/campaigns/gpt175b_train_dse.json` through
             `Campaign(spec).run()`, then a GPT-1.7B MFMOBO campaign with a
             GNN f0 stage (GNN trained here from a seed) and online
             calibration. Every analytically evaluated design is re-scored
             by the NumPy reference; the GP posterior on the chip is compared
             with `gp_ref.NumpyGP` fitted on the host.
  trainer    `repro.launch.train.main` on smollm-135m at full width, then
             the loss of one batch with the Pallas kernels on and off.
  server     `repro.launch.serve.main` on smollm-135m at full width, then
             the prefill logits of one request with the kernels on and off.

Timings printed on the way are set-up and smoke timings, not benchmark
metrics. The last line of standard output is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(ROOT, ".smoke")

# Tolerances (why each is what it is: PERF.md, "chip_smoke tolerances").
GP_TOL = 2e-3          # mu, sd vs NumpyGP: the CPU property test's bound
LOSS_RTOL = 1e-3       # f32 loss, Pallas vs jnp attention
LOGITS_RTOL = 2e-2     # bf16 prefill logits, relative L2, Pallas vs jnp
SHARDED_RTOL = 5e-3    # bf16 per-step loss, 2x2 mesh vs 1x1

ARCH = "smollm-135m"
TRAIN = dict(steps=5, batch=8, seq=1024)
SERVE = dict(requests=8, slots=4, max_new=16, max_len=128)


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found — JAX's devices are "
            f"{len(devs)} x {devs[0].platform}; this smoke runs on the chip "
            "only")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX found {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# campaigns
# ---------------------------------------------------------------------------


def _split_stages(campaign):
    """(design, y) pairs of the loop, split into f1 and f0 evaluations (the
    trace records the f0 ones; `hist_*` holds every evaluation)."""
    st = campaign.loop.state
    f0_ids = {id(d) for d in st.trace.designs}
    pairs = list(zip(st.hist_d, st.hist_y))
    return ([p for p in pairs if id(p[0]) not in f0_ids],
            list(zip(st.trace.designs, st.trace.ys)))


def rescore_numpy(spec, pairs, label: str) -> None:
    """Re-score analytically evaluated designs with the NumPy reference and
    require bit-identical (throughput, power) objective pairs: the
    evaluator runs on the host CPU, where it is bit-exact by contract."""
    import numpy as np

    from repro.core.design_space import DesignBatch
    from repro.core.evaluator import _wafers_for_budget_batch
    from repro.core.fidelity import AnalyticalBackend
    from repro.explore.campaign import resolve_workload
    from repro.explore.objectives import EvaluatorObjective

    wl = resolve_workload(spec)
    designs = [d for d, _ in pairs]
    geom = DesignBatch.from_designs(designs)
    nw = _wafers_for_budget_batch(geom, wl)
    ref = AnalyticalBackend().evaluate_batch_ref(geom, wl, nw,
                                                 spec.max_strategies)
    obj = EvaluatorObjective(wl, objectives=spec.objectives,
                             max_strategies=spec.max_strategies)
    want = obj.fold_metrics(obj.metrics_from_results(ref))
    bad = [i for i, ((_, got), w) in enumerate(zip(pairs, want))
           if tuple(got) != tuple(w)]
    feas = sum(1 for r in ref if r.feasible)
    log(f"{label}: NumPy re-score of {len(pairs)} designs ({feas} feasible):"
        f" {len(pairs) - len(bad)} bit-identical")
    check(not bad, f"{label}: designs {bad[:5]} differ from the NumPy "
                   f"reference, e.g. {pairs[bad[0]][1] if bad else ''} vs "
                   f"{want[bad[0]] if bad else ''}")
    check(np.isfinite([y for _, ys in pairs for y in ys]).all(),
          f"{label}: non-finite objective value")


def check_front(result, label: str) -> None:
    log(f"{label}: {result.n_evals} evaluations, hv_final "
        f"{result.hv_final!r}, front {len(result.front)} designs, "
        f"wall {result.wall_s:.1f}s (smoke timing)")
    check(math.isfinite(result.hv_final) and result.hv_final > 0,
          f"{label}: hv_final {result.hv_final!r} is not finite and > 0")
    check(len(result.front) > 0, f"{label}: empty Pareto front")


def check_gp(X, Y, Xs, label: str) -> None:
    """GP posterior on the default device (the chip) vs `NumpyGP` fitted on
    the host CPU, on the campaign's own data."""
    import jax
    import numpy as np

    from repro.core.gp import GP
    from repro.core.gp_ref import NumpyGP
    from repro.core.mfmobo import obj_space

    ys = obj_space([tuple(y) for y in Y])
    worst = 0.0
    for i, name in enumerate(("log throughput", "-log power")):
        gp = GP.fit(X, ys[:, i])
        with jax.default_device(jax.devices("cpu")[0]):
            ref = NumpyGP.fit(X, ys[:, i])
        for got, want in zip(gp.predict(Xs), ref.predict(Xs)):
            got, want = np.asarray(got, float), np.asarray(want, float)
            err = np.max(np.abs(got - want) / (GP_TOL + GP_TOL * np.abs(want)))
            worst = max(worst, float(err))
            np.testing.assert_allclose(got, want, rtol=GP_TOL, atol=GP_TOL,
                                       err_msg=f"{label}: GP {name}")
    log(f"{label}: GP mean/sd vs NumpyGP on {len(X)} points: worst error "
        f"{worst:.3f} of the tolerance ({GP_TOL} rel + abs)")


def phase_campaigns() -> None:
    import numpy as np

    from benchmarks.common import trained_gnn
    from repro.core import eval_compiled
    from repro.explore import Campaign, CampaignSpec, FidelitySchedule

    spec = CampaignSpec.from_json(
        os.path.join(ROOT, "examples", "campaigns", "gpt175b_train_dse.json"))
    t0 = time.time()
    camp = Campaign(spec)
    res = camp.run()
    log(f"campaign {spec.name}: {time.time() - t0:.1f}s incl. compiles "
        "(smoke timing)")
    check_front(res, spec.name)
    f1, f0 = _split_stages(camp)
    rescore_numpy(spec, f1 + f0, spec.name)
    st = camp.loop.state
    cand = np.random.default_rng(0).random((64, len(st.X1[0])))
    check_gp(np.array(st.X0 + st.X1), st.Y0 + st.Y1, cand, spec.name)

    t0 = time.time()
    gnn, info = trained_gnn(seed=0)
    log(f"set-up: GNN trained from seed 0 in {time.time() - t0:.1f}s "
        f"({info['n_graphs']} graphs, val Kendall tau "
        f"{info['val_kendall_tau']:.3f})")
    spec2 = CampaignSpec(
        name="smoke-gpt1.7b-mfmobo-gnn", workload="GPT-1.7B",
        scenario="train", strategy="mfmobo",
        fidelity=FidelitySchedule(
            f1="analytical", f0="gnn", d1=3, d0=2, k=3,
            calibrate_on_handover=True,
            calibration={"n_designs": 6, "epochs": 15}),
        n_evals_f0=14, n_evals_f1=18, q=4, n_candidates=96, seed=0)
    t0 = time.time()
    camp2 = Campaign(spec2, gnn_params=gnn)
    res2 = camp2.run()
    log(f"campaign {spec2.name}: {time.time() - t0:.1f}s incl. compiles "
        "(smoke timing)")
    check_front(res2, spec2.name)
    check(len(res2.calibration) > 0, "GNN calibration did not run")
    log(f"{spec2.name}: calibration {res2.calibration[0]}")
    f1, f0 = _split_stages(camp2)
    check(len(f1) == spec2.n_evals_f1 and len(f0) == spec2.n_evals_f0,
          f"stage counts f1={len(f1)} f0={len(f0)}")
    rescore_numpy(spec2, f1, spec2.name + " f1")
    check(np.isfinite([y for _, ys in f0 for y in ys]).all(),
          "non-finite GNN objective")

    lanes = eval_compiled.lane_stats()
    log(f"evaluator dispatches on the host CPU: {lanes}")
    check(lanes["jit_calls"] + lanes["sharded_calls"] > 0,
          "the compiled evaluator never ran")


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------


def _train(extra, tag: str):
    from repro.launch import train

    ck = os.path.join(SCRATCH, f"ckpt-{tag}")
    shutil.rmtree(ck, ignore_errors=True)
    t0 = time.time()
    out = train.main(["--arch", ARCH, "--steps", str(TRAIN["steps"]),
                      "--batch", str(TRAIN["batch"]),
                      "--seq", str(TRAIN["seq"]), "--log-every", "1",
                      "--ckpt-every", str(TRAIN["steps"]), "--ckpt-dir", ck]
                     + list(extra))
    wall = time.time() - t0
    shutil.rmtree(ck, ignore_errors=True)
    losses = [m["loss"] for m in out["metrics"]]
    log(f"train {tag}: losses {losses}, restarts {out['restarts']}, "
        f"{wall:.1f}s incl. compile (smoke timing)")
    check(len(losses) == TRAIN["steps"], f"{tag}: {len(losses)} steps")
    check(all(math.isfinite(x) for x in losses), f"{tag}: non-finite loss")
    check(out["restarts"] == 0, f"{tag}: {out['restarts']} restarts")
    check(out["runtime"].use_pallas, f"{tag}: Pallas kernels were off")
    return losses


def phase_trainer() -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import model as M
    from repro.models.runtime import Runtime
    from repro.train.data import MarkovLMDataset

    _train([], "1x1")

    cfg = get_config(ARCH)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = MarkovLMDataset(vocab=cfg.vocab, seq_len=TRAIN["seq"],
                            batch=TRAIN["batch"], seed=0).batch_at(0)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    loss = {}
    for pallas in (True, False):
        rt = Runtime(compute_dtype=jnp.float32, use_pallas=pallas)
        f = jax.jit(lambda p, b, rt=rt: M.loss_fn(p, cfg, rt, b)[0])
        loss[pallas] = float(f(params, batch))
    rel = abs(loss[True] - loss[False]) / abs(loss[False])
    log(f"f32 loss on one batch: Pallas {loss[True]!r} vs jnp "
        f"{loss[False]!r}: relative difference {rel:.3e} "
        f"(tolerance {LOSS_RTOL})")
    check(rel <= LOSS_RTOL, "Pallas and jnp losses disagree")


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------


def phase_server() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch import serve
    from repro.models import model as M
    from repro.models.runtime import Runtime

    t0 = time.time()
    outs = serve.main(["--arch", ARCH, "--requests", str(SERVE["requests"]),
                       "--slots", str(SERVE["slots"]),
                       "--max-new", str(SERVE["max_new"]),
                       "--max-len", str(SERVE["max_len"])])
    log(f"serve: {len(outs)} requests in {time.time() - t0:.1f}s incl. "
        "compile (smoke timing)")
    check(sorted(outs) == list(range(SERVE["requests"])),
          f"served requests {sorted(outs)}")
    short = {r: len(t) for r, t in outs.items() if len(t) != SERVE["max_new"]}
    check(not short, f"requests without {SERVE['max_new']} tokens: {short}")

    cfg = get_config(ARCH)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, size=(1, 100)), jnp.int32)
    logits = {}
    for pallas in (True, False):
        rt = Runtime(use_pallas=pallas)          # the server's runtime
        f = jax.jit(lambda p, t, rt=rt: M.prefill(
            p, cfg, rt, {"tokens": t},
            M.init_cache(cfg, rt, 1, SERVE["max_len"]))[0])
        logits[pallas] = np.asarray(f(params, tokens), np.float64)
    rel = (np.linalg.norm(logits[True] - logits[False])
           / np.linalg.norm(logits[False]))
    log(f"bf16 prefill logits (100 tokens): Pallas vs jnp relative L2 "
        f"{rel:.3e} (tolerance {LOGITS_RTOL}), max |diff| "
        f"{np.abs(logits[True] - logits[False]).max():.3e}")
    check(np.isfinite(logits[True]).all(), "non-finite Pallas logits")
    check(rel <= LOGITS_RTOL, "Pallas and jnp prefill logits disagree")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_sharded_trainer() -> None:
    sharded = _train(["--data", "2", "--model", "2"], "2x2")
    single = _train(["--data", "1", "--model", "1"], "1x1")
    rel = [abs(a - b) / abs(b) for a, b in zip(sharded, single)]
    log(f"per-step loss, 2x2 mesh vs 1x1 on device 0: relative differences "
        f"{[f'{r:.3e}' for r in rel]} (tolerance {SHARDED_RTOL})")
    check(max(rel) <= SHARDED_RTOL, "sharded and 1x1 losses disagree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-trainer comparison")
    args = ap.parse_args(argv)

    t_start = time.time()
    import jax
    devs = require_tpu(args.chips)
    log(f"jax {jax.__version__}, device {devs[0].device_kind}, "
        f"{len(devs)} devices")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.compile_cache import configure_compile_cache
    from repro.core import eval_compiled

    log(f"compile cache: {configure_compile_cache()}")
    if not eval_compiled.enabled():
        raise SystemExit("chip_smoke: REPRO_COMPILED_EVAL disables the "
                         "compiled evaluator; unset it")
    log(f"evaluator placement: float64 programs on the host CPU "
        f"({[str(d) for d in eval_compiled.host_devices()]}); GP, EHVI, GNN "
        f"and the model runtime on {devs[0].device_kind}")
    log(f"set-up: imports and device in {time.time() - t_start:.1f}s")
    os.makedirs(SCRATCH, exist_ok=True)

    phases = ([phase_sharded_trainer] if args.chips == 4
              else [phase_campaigns, phase_trainer, phase_server])
    for phase in phases:
        t0 = time.time()
        phase()
        log(f"{phase.__name__} passed in {time.time() - t0:.1f}s "
            "(smoke timing)")
    shutil.rmtree(SCRATCH, ignore_errors=True)
    log(f"all phases passed in {time.time() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
