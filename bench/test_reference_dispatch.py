"""Tests of the per-configuration reference (`bench.reference.for_config`),
on the CPU at a small size: each cell of `BENCHMARK.json` with its pool cut
to two campaigns.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_reference_dispatch.py

- a reference a configuration names is the one compared: a stand-in
  module that delegates to the cell's own reference reads what that
  reference reads, and the control through it still fails; moved by one
  ulp, it makes the check fail;
- a name that is not a reference module, or a key a reference declares and
  the program's workload lacks or disagrees on, stops set-up.

Stand-in modules are put in `sys.modules` for the test's length; no file
is written under `bench/reference/`.
"""
from __future__ import annotations

import copy
import json
import sys
import types

import numpy as np
import pytest

from bench import check
from bench import reference
from bench import run_cell as rc
from bench.test_correct import one_round, shrink

if str(rc.ROOT / "src") not in sys.path:
    sys.path.insert(0, str(rc.ROOT / "src"))
MANIFEST = json.loads((rc.ROOT / "BENCHMARK.json").read_text())
CELLS = tuple(c["name"] for c in MANIFEST["workloads"])
STUB = "stub_for_test"


def stub(monkeypatch, base, keys=(), ulp=False):
    """`bench.reference.<STUB>`: delegates to the module `base`, counting
    its calls, declares `keys` beside `base`'s, and with `ulp` moves each
    call's first objective up by one ulp."""
    mod = types.ModuleType(f"bench.reference.{STUB}")
    mod.calls = 0
    mod.WORKLOAD_KEYS = tuple(base.WORKLOAD_KEYS) + tuple(keys)
    mod.workload = base.workload

    def delegate(f):
        def scored(*a, **kw):
            mod.calls += 1
            ys = list(f(*a, **kw))
            if ulp and ys:
                ys[0] = (float(np.nextafter(ys[0][0], np.inf)), ys[0][1])
            return ys
        return scored
    mod.train_objectives = delegate(base.train_objectives)
    mod.trace_objectives = delegate(base.trace_objectives)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def naming(run, name):
    """The same run, its configuration naming the reference `name`."""
    other = copy.copy(run)
    other.config = dict(run.config, reference=name)
    return other


@pytest.fixture(scope="module")
def rounds():
    """One small round of each cell, with the cell's own configuration."""
    with pytest.MonkeyPatch.context() as mp:
        shrink(mp)
        return {name: one_round(name) for name in CELLS}


@pytest.mark.parametrize("name", [c["name"] for c in MANIFEST["configs"]])
def test_configuration_reference(name):
    entry = {c["name"]: c for c in MANIFEST["configs"]}[name]
    config = json.loads((rc.ROOT / entry["file"]).read_text())
    got = reference.for_config(config)
    if "reference" in config:
        assert got.__name__ == f"bench.reference.{config['reference']}"
    else:
        assert got is reference
    assert all(hasattr(got, k) for k in reference.EXPORTS)


@pytest.mark.parametrize("name", CELLS)
def test_named_reference_is_the_one_compared(rounds, monkeypatch, name):
    run = rounds[name]
    base = reference.for_config(run.config)
    mod = stub(monkeypatch, base)
    direct = check.readings(run)
    via = check.readings(naming(run, STUB))
    assert mod.calls > 0
    assert via == direct, (via, direct)
    assert check.verdict(via), via
    control = check.readings(naming(run, STUB), control=True)
    assert set(control) == set(via)
    assert not check.verdict(control), control


@pytest.mark.parametrize("name", CELLS)
def test_one_ulp_off_in_the_named_reference_fails(rounds, monkeypatch,
                                                  name):
    run = rounds[name]
    stub(monkeypatch, reference.for_config(run.config), ulp=True)
    values = check.readings(naming(run, STUB))
    exact = {k: v for k, v in values.items() if k.endswith("_mismatch")}
    assert exact and all(v >= 1 for v in exact.values()), values
    assert not check.verdict(values), values


def setup_of(config):
    """Set-up of the train cell up to its warm-up, with `config`."""
    _, cell, _, traffic = rc.load_cell(CELLS[0])
    rc.prepare(cell, config, traffic, 5, traced=False)


def base_config():
    return rc.load_cell(CELLS[0])[2]


@pytest.mark.parametrize("bad", ["no_such_reference", "../workload",
                                 "workload.LLMWorkload", "noc_gnn", "", 3])
def test_bad_reference_name_stops_setup(bad):
    config = dict(base_config(), reference=bad)
    with pytest.raises(ValueError, match="reference"):
        reference.for_config(config)
    with pytest.raises(ValueError, match="reference"):
        setup_of(config)


def test_declared_key_the_program_lacks_stops_setup(monkeypatch):
    config = dict(base_config(), reference=STUB, dense_layers=3)
    stub(monkeypatch, reference, keys=("dense_layers",))
    with pytest.raises(SystemExit, match="dense_layers"):
        setup_of(config)


@pytest.mark.parametrize("held", [True, False])
def test_declared_keys_are_held(monkeypatch, held):
    """A key the reference adds is compared like the default ones: here
    the workload's `name`, against the program's."""
    from repro.explore.campaign import resolve_workload
    traffic = rc.load_cell(CELLS[0])[3]
    have = resolve_workload(rc.campaign_spec(traffic, base_config(), 5)).name
    config = dict(base_config(), reference=STUB,
                  name=have if held else have + "-other")
    stub(monkeypatch, reference, keys=("name",))
    spec = rc.campaign_spec(traffic, config, 5)
    if held:
        rc.check_widths(spec, config)
    else:
        with pytest.raises(SystemExit, match="configuration file says"):
            rc.check_widths(spec, config)
