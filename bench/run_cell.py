"""One benchmark run of one cell: set-up, a timed window of design-space
campaigns on the chip, then the check against the plain reference.

    python -m bench.run_cell --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (`BENCHMARK.json` `workloads`) names a configuration
(`bench/configs/<config>.json`: the modelled job's published widths) and a
traffic mix (`bench/traffic/<traffic>.json`: a campaign spec without its
workload and seed, the pool of campaign seeds the window runs, and the
seeds of the warm-up campaigns). Per-layer metrics are read by
`bench/metrics/<metric>.py`, found by the metric's name. The plain
reference that decides `correct` is `bench/reference/` unless the
configuration file names its own with `"reference": "<name>"`: a new
module `bench/reference/<name>.py` exporting `WORKLOAD_KEYS`, `workload`,
`train_objectives` and `trace_objectives`, which may import the frozen
modules beside it and changes none of them (`bench.reference.for_config`).
Set-up holds the program's workload to the configuration file on every
key of that reference's `WORKLOAD_KEYS`.

Set-up (`setup_s`): imports and the device, the compile cache, the GNN
weights made on the device from `--seed` (cells with a GNN fidelity), and
the warm-up campaigns, which compile or load every program the window's
campaigns use: one campaign outside the pool where that is enough, the
pool itself where a program's shapes follow the designs evaluated (the
GNN fidelity compiles per NoC row pattern and lane count). The window runs whole rounds of the
pool, each round in an order drawn from `--seed`, back to back (a closed
loop: one architect waits on each campaign), and starts a round while
`--seconds` have not passed. The evaluation cache is cleared before each
campaign, as a campaign in a fresh process starts cold.

One process, no child. Without an accelerator, or with fewer chips than the
cell asks for, it exits non-zero and prints no result. The last line of
standard output is one JSON object; the numbers compared for `correct`
close standard error and the result line.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import statistics
import sys
import tempfile
import time
import traceback
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_cell(name: str):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return manifest, cell, config, traffic


def require_accelerator(chips: int):
    """The devices of the accelerator; exits when there is none or too
    few. There is no fall-back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        log(f"no accelerator: JAX's devices are {len(devs)} x "
            f"{devs[0].platform}; the benchmark runs on the chip only")
        raise SystemExit(3)
    if len(devs) < chips:
        log(f"the cell needs {chips} chips, JAX found {len(devs)}")
        raise SystemExit(3)
    return devs[:chips]


class Run:
    """What one run produced: the view the metric readers and the check
    read."""

    def __init__(self, cell, config, traffic, seed):
        self.cell, self.config, self.traffic, self.seed = (cell, config,
                                                           traffic, seed)
        self.campaigns = []          # Campaign objects, in window order
        self.attempted = 0
        self.failed = 0
        self.empty_fronts = 0
        self.window_s = 0.0
        self.rounds = 0
        self.reduction = None
        self.recorder = None

    @property
    def spans(self):
        return self.recorder.spans

    @property
    def steps(self):
        return self.recorder.steps

    @property
    def gp_log(self):
        return self.recorder.gp_log

    @property
    def n_campaigns(self):
        return len(self.campaigns)


def campaign_spec(traffic, config, seed: int):
    from repro.explore import CampaignSpec
    d = dict(traffic["spec"], workload=config["workload_ref"], seed=int(seed))
    return CampaignSpec.from_dict(d)


def check_widths(spec, config) -> None:
    """The program's workload must be the configuration file's job, on
    every key its reference declares (`WORKLOAD_KEYS` of
    `bench.reference.for_config(config)`)."""
    from repro.explore.campaign import resolve_workload

    from bench.reference import for_config
    keys = for_config(config).WORKLOAD_KEYS
    wl = resolve_workload(spec)
    lacking = [k for k in keys if not hasattr(wl, k)]
    if lacking:
        raise SystemExit(f"the program's workload {spec.workload} has no "
                         f"{lacking}, which its reference declares")
    got = {k: getattr(wl, k) for k in keys}
    want = {k: config.get(k) for k in keys}
    if got != want:
        raise SystemExit(f"the program resolves {spec.workload} to {got}, "
                         f"the configuration file says {want}")


def gnn_weights(seed: int):
    """GNN parameters made on the device from the seed, in one call."""
    import jax
    import numpy as np

    from repro.core import noc_gnn
    k = int(np.random.SeedSequence(seed).generate_state(1)[0]) & 0x7FFFFFFF
    return jax.block_until_ready(jax.jit(noc_gnn.init_gnn)(
        jax.random.PRNGKey(k)))


def run_campaign(run, cseed: int, gnn, index: int):
    """One campaign, as a user runs it; None when it raised."""
    import numpy as np

    from repro.core.evaluator import clear_eval_cache
    from repro.explore import Campaign
    spec = campaign_spec(run.traffic, run.config, cseed)
    run.recorder.campaign = index
    run.attempted += 1
    clear_eval_cache()
    try:
        camp = Campaign(spec, gnn_params=gnn)
        res = camp.run()
    except Exception:
        run.failed += 1
        log(f"campaign seed {cseed} raised:\n{traceback.format_exc()}")
        return None
    ys = list(camp.loop.state.trace.ys) + list(camp.loop.state.hist_y)
    if not np.isfinite(np.asarray(ys, float)).all() or not math.isfinite(
            res.hv_final):
        run.failed += 1
    if not res.front:
        run.empty_fronts += 1
    return camp


class CompileCounter:
    """Counts XLA compilations (backend compiles and persistent-cache
    loads alike: both mean a program was not yet in this process)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.n += 1


def window(run, seconds: float, gnn) -> None:
    import numpy as np
    pool = list(run.traffic["campaign_seeds"])
    t0 = time.perf_counter()
    rnd = 0
    with run.recorder.annotation("window"):
        while rnd == 0 or time.perf_counter() - t0 < seconds:
            order = np.random.default_rng([run.seed, rnd]).permutation(pool)
            for cs in order:
                camp = run_campaign(run, int(cs), gnn, len(run.campaigns))
                if camp is not None:
                    run.campaigns.append(camp)
            rnd += 1
    run.window_s = time.perf_counter() - t0
    run.rounds = rnd


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric, cell) -> bool:
    return "workloads" not in metric or cell["name"] in metric["workloads"]


def percentile(values, p: int) -> float:
    """The p-th percentile, `statistics.quantiles` (exclusive method)."""
    return statistics.quantiles(values, n=100)[p - 1]


def end_to_end(manifest, cell, run, setup_s):
    steps_ms = [1e3 * (s.t1 - s.t0) for s in run.steps]
    values = {"setup_s": setup_s,
              "campaign_s": run.window_s / max(run.n_campaigns, 1)}
    if len(steps_ms) >= 2:
        values["step_p90_ms"] = percentile(steps_ms, 90)
    out = {}
    for m in manifest["end_to_end"]:
        if applies(m, cell) and m["name"] in values:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def per_layer(manifest, cell, run):
    out = {}
    for m in manifest["per_layer"]:
        if not applies(m, cell):
            continue
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def tail_population(run) -> str:
    """Which kinds of step make up the slowest tenth."""
    steps = sorted(run.steps, key=lambda s: s.t1 - s.t0)
    top = steps[len(steps) - max(len(steps) // 10, 1):]
    kinds = {}
    for s in top:
        kinds[s.kind] = kinds.get(s.kind, 0) + 1
    return ", ".join(f"{k} {n}" for k, n in sorted(kinds.items()))


def start(workload: str):
    """Load the cell, take the chip, point JAX at its compile cache."""
    manifest, cell, config, traffic = load_cell(workload)
    devs = require_accelerator(cell["chips"])
    import jax
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import configure_compile_cache
    cache = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']}, device {devs[0].device_kind} x {len(devs)}, "
        f"compile cache {cache}")
    return manifest, cell, config, traffic, devs


def prepare(cell, config, traffic, seed: int, traced: bool):
    """Set-up after the imports: the workload check, the GNN weights, the
    spans, and the warm-up campaigns. Returns the run, the weights and the
    compile counter."""
    from bench.spans import Recorder
    run = Run(cell, config, traffic, seed)
    spec0 = campaign_spec(traffic, config, traffic["warmup_seeds"][0])
    check_widths(spec0, config)
    gnn = gnn_weights(seed) if spec0.fidelity.needs_gnn_params() else None
    run.recorder = Recorder(traced=traced).install()
    if run.recorder.missing:
        log(f"span targets gone (their metrics are left out): "
            f"{run.recorder.missing}")
    compiles = CompileCounter()
    warm = Run(cell, config, traffic, seed)
    warm.recorder = run.recorder
    t_w = time.perf_counter()
    for s in traffic["warmup_seeds"]:
        if run_campaign(warm, s, gnn, -1) is None:
            raise SystemExit(f"the warm-up campaign at seed {s} raised")
    log(f"warm-up: {len(traffic['warmup_seeds'])} campaigns in "
        f"{time.perf_counter() - t_w:.3f}s, {compiles.n} compiles")
    run.recorder.clear()
    gc.collect()
    return run, gnn, compiles


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    seed = args.seed % (1 << 63)

    manifest, cell, config, traffic, devs = start(args.workload)
    import jax

    from bench import check
    run, gnn, compiles = prepare(cell, config, traffic, seed,
                                 bool(args.trace))
    setup_s = time.perf_counter() - T_START

    tracedir = None
    if args.trace:
        tracedir = tempfile.mkdtemp(prefix="bench-trace-")
        from bench.trace_reduce import profile_options
        jax.profiler.start_trace(tracedir, profiler_options=profile_options())
    n_compiles = compiles.n
    window(run, args.seconds, gnn)
    n_compiles = compiles.n - n_compiles
    if args.trace:
        jax.profiler.stop_trace()
    run.recorder.uninstall()

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": run.attempted,
              "failed": run.failed}
    if args.trace:
        import shutil

        from bench.trace_reduce import reduce_trace
        paths = sorted(Path(tracedir).rglob("*.xplane.pb"))
        if paths:
            run.reduction = reduce_trace(str(paths[-1]))
        shutil.rmtree(tracedir, ignore_errors=True)
        metrics = per_layer(manifest, cell, run)
        if run.reduction is not None:
            device.update(busy_s=run.reduction.busy_s,
                          window_s=run.reduction.window_s)
            log(f"idle by host span (s): {run.reduction.gap_totals}")
    else:
        metrics = end_to_end(manifest, cell, run, setup_s)

    log(f"window {run.window_s:.3f}s, {run.rounds} rounds, "
        f"{run.n_campaigns} campaigns, {len(run.steps)} steps, "
        f"{n_compiles} compiles in the window, {run.empty_fronts} empty "
        f"fronts, {run.failed} failed")
    if run.steps:
        log(f"slowest tenth of steps by kind: {tail_population(run)}")
    import repro.core.eval_compiled as ec
    log(f"evaluator lanes: {ec.lane_stats()}")

    t_c = time.perf_counter()
    values = check.readings(run)
    log(f"check against the reference in {time.perf_counter() - t_c:.1f}s")
    result["correct"] = check.verdict(values) and run.failed == 0
    result["metrics"] = metrics
    result["device"] = device
    if args.trace and run.reduction is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.reduction.top_ops],
            "idle_gaps": [[n, s] for n, s in run.reduction.gaps]}
    result["checks"] = {k: {"value": v, "limit": check.LIMITS[k]}
                        for k, v in values.items()}
    for k, v in values.items():
        log(f"check {k}: {v!r} (limit {check.LIMITS[k]})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
