"""Tests of the comparison that decides `correct`, on the CPU at a small
size: each cell with its pool cut to two campaigns.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_correct.py

- the program's own campaigns pass, and the control (the reference one
  precision lower, in the program's place) fails;
- a whole run of the harness, with its look for a chip skipped and an
  answer altered where the program produces it, prints `correct` false.
"""
from __future__ import annotations

import io
import json
from contextlib import redirect_stdout

import pytest

from bench import check
from bench import run_cell as rc

#: cells whose files are in bench/ but which BENCHMARK.json leaves out
#: (PERF.md, Open questions); their check is tested all the same
SPARE = {"gpt1.7b-train-gnn": {"config": "gpt-1.7b",
                               "traffic": "train-gnn-calibrated"}}
#: every cell of BENCHMARK.json, then the spare ones
CELLS = tuple(c["name"] for c in json.loads(
    (rc.ROOT / "BENCHMARK.json").read_text())["workloads"]) + tuple(SPARE)


def shrink(monkeypatch):
    """The harness without its look for a chip, each pool cut to two
    campaigns and one warm-up campaign."""
    import jax
    load = rc.load_cell

    def load_small(name):
        if name in SPARE:            # built, not in BENCHMARK.json yet
            manifest = json.loads((rc.ROOT / "BENCHMARK.json").read_text())
            cell = dict(SPARE[name], name=name, chips=1)
            config = json.loads((rc.BENCH / "configs" /
                                 f"{cell['config']}.json").read_text())
            traffic = json.loads((rc.BENCH / "traffic" /
                                  f"{cell['traffic']}.json").read_text())
        else:
            manifest, cell, config, traffic = load(name)
        traffic = dict(traffic, campaign_seeds=traffic["campaign_seeds"][:2],
                       warmup_seeds=traffic["warmup_seeds"][:1])
        return manifest, cell, config, traffic

    monkeypatch.setattr(rc, "load_cell", load_small)
    monkeypatch.setattr(rc, "require_accelerator",
                        lambda chips: jax.devices()[:chips])


@pytest.fixture
def small(monkeypatch):
    shrink(monkeypatch)


def one_round(name: str, seed: int = 5):
    _, cell, config, traffic, _ = rc.start(name)
    run, gnn, _ = rc.prepare(cell, config, traffic, seed, traced=False)
    try:
        rc.window(run, 0.0, gnn)
    finally:
        run.recorder.uninstall()
    return run


@pytest.mark.parametrize("name", CELLS)
def test_program_passes_and_control_fails(small, name):
    run = one_round(name)
    program = check.readings(run)
    control = check.readings(run, control=True)
    assert check.verdict(program), program
    assert not check.verdict(control), control
    assert set(program) == set(control)


def _alter_first(rows, key):
    rows = [dict(r) for r in rows]
    for r in rows:
        if r.get("feasible", True) and r[key] > 0:
            r[key] = r[key] * (1 + 1e-9)
            break
    return rows


def fault_analytical(monkeypatch):
    from repro.explore.objectives import EvaluatorObjective
    orig = EvaluatorObjective.metrics_from_results
    monkeypatch.setattr(EvaluatorObjective, "metrics_from_results",
                        staticmethod(lambda rs: _alter_first(
                            orig(rs), "throughput")))


def fault_gnn(monkeypatch):
    from repro.core import fidelity
    orig = fidelity.gnn_forward_batch
    monkeypatch.setattr(fidelity, "gnn_forward_batch",
                        lambda p, b: orig(p, b) * 1.01)


def fault_trace(monkeypatch):
    from repro.explore.objectives import TraceServingObjective
    orig = TraceServingObjective.metrics
    monkeypatch.setattr(TraceServingObjective, "metrics",
                        lambda self, ds: _alter_first(
                            orig(self, ds), "power_per_wafer"))


def fault_pick(monkeypatch):
    """The q-EHVI program's first pick moved to another candidate."""
    from repro.core import mfmobo
    orig = mfmobo._acquire_scan_jit

    def scan(*a, **kw):
        js = orig(*a, **kw)
        n = a[19].shape[0]               # the candidate pool
        return js.at[0].set((js[0] + n // 2) % n)
    monkeypatch.setattr(mfmobo, "_acquire_scan_jit", scan)


FAULTS = [
    ("gpt175b-train-analytical", fault_analytical, "analytical_mismatch"),
    ("gpt175b-train-analytical", fault_pick, "pick_gap"),
    ("gpt175b-serve-trace", fault_pick, "pick_gap"),
    ("gpt1.7b-train-gnn", fault_gnn, "gnn_gap"),
    ("gpt175b-serve-trace", fault_trace, "trace_mismatch"),
]


@pytest.mark.parametrize("name,fault,number", FAULTS,
                         ids=[f"{f[1].__name__}-{f[0]}" for f in FAULTS])
def test_altered_answer_fails_the_run(small, monkeypatch, name, fault,
                                      number):
    import sys
    sys.path.insert(0, str(rc.ROOT / "src"))
    fault(monkeypatch)
    out = io.StringIO()
    with redirect_stdout(out):
        assert rc.main(["--workload", name, "--seed", "9", "--seconds", "0",
                        "--trace", "0"]) == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is False, result["checks"]
    got = result["checks"][number]
    assert got["value"] > got["limit"], result["checks"]
    assert list(result)[-1] == "checks"
