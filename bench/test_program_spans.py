"""Tests of the per-layer metrics read from the program's own spans
(`repro.telemetry`, `bench/program_spans.py`), on the CPU at a small size.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_program_spans.py

- a traced harness run reports the metrics read from the program's spans
  in their cells, and every accepted per-layer metric but `device_idle`
  (the CPU has no device plane) as before;
- the wrappers of `bench/spans.py` find every target;
- the program's spans agree with the wrappers' timing of the same layers.
"""
from __future__ import annotations

import io
import json
from contextlib import redirect_stdout

import pytest

from bench import run_cell as rc
from bench.program_spans import summaries, total
from bench.spans import Recorder
from bench.spans import total as bench_total
from bench.test_correct import small  # noqa: F401  (fixture)

PROGRAM = {"propose_ms.fit", "candidates_ms.validate", "candidate_yield",
           "host_syncs", "evaluate_ms.trace.disaggregated",
           "evaluate_ms.trace.pool"}
CELLS = ("gpt175b-train-analytical", "gpt175b-serve-trace")


def expected(cell: str):
    manifest = json.loads((rc.ROOT / "BENCHMARK.json").read_text())
    return {m["name"] for m in manifest["per_layer"]
            if ("workloads" not in m or cell in m["workloads"])
            and m["name"] != "device_idle"}


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_program_metrics(small, name):  # noqa: F811
    out = io.StringIO()
    with redirect_stdout(out):
        assert rc.main(["--workload", name, "--seed", "4000000011",
                        "--seconds", "0", "--trace", "1"]) == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    want = expected(name)
    assert PROGRAM & want, want
    assert want <= set(result["metrics"]), (want, result["metrics"])
    m = result["metrics"]
    assert 0 < m["candidate_yield"]["value"] <= 100
    assert m["host_syncs"]["value"] >= 1
    assert result["correct"] is True, result["checks"]


def test_span_targets_resolve(monkeypatch):
    monkeypatch.syspath_prepend(str(rc.ROOT / "src"))
    rec = Recorder(traced=True).install()
    try:
        assert rec.missing == []
    finally:
        rec.uninstall()


@pytest.mark.parametrize("name", CELLS)
def test_program_spans_match_the_wrappers(small, name):  # noqa: F811
    _, cell, config, traffic, _ = rc.start(name)
    run, gnn, _ = rc.prepare(cell, config, traffic, 21, traced=True)
    try:
        rc.window(run, 0.0, gnn)
    finally:
        run.recorder.uninstall()
    tels = summaries(run)
    assert tels is not None
    fit = total(tels, "propose.fit")[0]
    propose = bench_total(run.spans, "propose")[0]
    assert 0 < fit <= propose
    steps = total(tels, "step")
    assert steps[1] == len(run.steps)
    # the traced run waits for the fused iteration's picks inside its
    # propose wrapper, outside the program's spans: that wait lands in the
    # step's self time, the rest of the step is in the layers' spans
    wait = propose - fit - total(tels, "propose.acquire")[0]
    self_s = sum(t["spans"]["step"]["self_s"] for t in tels)
    assert self_s - wait <= 0.05 * steps[0], (self_s, wait, steps)
    if name == "gpt175b-serve-trace":
        inner = (total(tels, "evaluate.trace.disaggregated")[0]
                 + total(tels, "evaluate.trace.pool")[0])
        outer = bench_total(run.spans, "evaluate", "trace")[0]
        assert inner == pytest.approx(outer, rel=0.05)
