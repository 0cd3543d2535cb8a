"""Compiled analytical evaluator (repro.core.eval_compiled, DESIGN.md §12).

The jitted pipeline must be *bit-identical* to the retained NumPy oracles
(`AnalyticalBackend.evaluate_batch_ref`, `feasible_strategy_arrays_ref`):
the fused propose→evaluate iteration feeds the same eval cache and the
same campaign traces as the unfused path, so any drift — even 1 ulp —
forks the checkpoint/resume history. The fixture
tests/data/fig8_trace_pr7_baseline.json was generated at the pre-change
HEAD (PR 7, pure NumPy evaluation); the campaign test replays it through
the fused compiled loop and demands hex equality.
"""
import dataclasses
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from repro.core import eval_compiled
from repro.core.compiler import feasible_strategy_arrays_ref
from repro.core.design_space import DesignBatch, decode_batch
from repro.core.fidelity import AnalyticalBackend
from repro.core.workload import (
    DEEPSEEK_V3,
    GPT_BENCHMARKS,
    LLMWorkload,
    inference_workload,
)

DATA = os.path.join(os.path.dirname(__file__), "data")


def _designs(seed: int, n: int):
    rng = np.random.default_rng(seed)
    designs = decode_batch(rng.random((n, 13)))
    nw = rng.integers(1, 9, size=n).astype(np.int64)
    return designs, DesignBatch.from_designs(designs), nw


def _hex(v) -> str:
    return float(np.float64(v)).hex()


def _result_fingerprint(r):
    """Every float hex-exact, plus the discrete fields."""
    out = {"feasible": r.feasible, "n_wafers": r.n_wafers,
           "reason": r.reason,
           "strategy": None if r.strategy is None else list(
               dataclasses.astuple(r.strategy))}
    if r.feasible:
        out.update(throughput=_hex(r.throughput), power_w=_hex(r.power_w),
                   step_time_s=_hex(r.step.step_time_s),
                   pipeline_eff=_hex(r.step.pipeline_eff),
                   energy_j=_hex(r.step.energy_j),
                   breakdown={k: _hex(v)
                              for k, v in r.step.breakdown.items()})
    return out


#: a small table of two layer kinds: one dense layer, then MoE layers
#: with shared experts, MLA attention at small ranks
SMALL_MOE = LLMWorkload(
    name="moe-2kind", n_layers=6, d_model=512, n_heads=8, n_kv=8,
    d_ff=1536, vocab=32000, seq=1024, batch=64, phase="train",
    moe_experts=16, moe_topk=2, gpu_budget=8, n_dense_layers=1,
    d_ff_expert=512, moe_shared=2, q_lora_rank=192, kv_lora_rank=64,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32)
WORKLOADS = {"GPT-1.7B": GPT_BENCHMARKS[0], "DeepSeek-V3": DEEPSEEK_V3,
             "moe-2kind": SMALL_MOE}


@pytest.mark.parametrize("wl_name,wl_case", [
    pytest.param(w, c, id=c if w == "GPT-1.7B" else f"{w}-{c}")
    for w in WORKLOADS for c in ("train", "prefill", "decode")])
def test_compiled_matches_numpy_ref_bit_exact(wl_name, wl_case):
    wl = WORKLOADS[wl_name]
    if wl_case != "train":
        wl = inference_workload(wl, wl_case, 8, 2048)
    designs, geom, nw = _designs(7, 16)
    be = AnalyticalBackend()
    ref = be.evaluate_batch_ref(geom, wl, nw, max_strategies=24)
    got = eval_compiled.evaluate_batch_compiled(geom, wl, nw,
                                                max_strategies=24)
    assert len(ref) == len(got) == 16
    for i, (r, g) in enumerate(zip(ref, got)):
        assert _result_fingerprint(r) == _result_fingerprint(g), f"row {i}"


def test_compiled_matches_ref_across_strategy_caps():
    wl = GPT_BENCHMARKS[4]
    designs, geom, nw = _designs(3, 8)
    be = AnalyticalBackend()
    for ms in (8, 24):
        ref = be.evaluate_batch_ref(geom, wl, nw, max_strategies=ms)
        got = eval_compiled.evaluate_batch_compiled(geom, wl, nw,
                                                    max_strategies=ms)
        for r, g in zip(ref, got):
            assert _result_fingerprint(r) == _result_fingerprint(g)


def test_strategy_grid_selection_matches_ref():
    """The baked pow2-padded grid + in-program mask reproduce
    `feasible_strategy_arrays_ref` exactly: same mask, same sorted order,
    same cap, same (1,1,1,1) fallback. Pad rows must never be selectable."""
    wl = GPT_BENCHMARKS[0]
    prog = eval_compiled._program_for(wl, 24)
    # pad rows are engineered infeasible under any budget
    g = len(feasible_strategy_arrays_ref(wl, 2 ** 62, np.inf, 10 ** 9))
    assert prog._tp_o.shape[0] >= g
    assert (prog._need_o[g:] == np.inf).all()
    designs, geom, nw = _designs(11, 8)
    for i in range(8):
        tc = int(geom.total_cores[i]) * int(nw[i])
        sram = float(geom.buffer_kb[i]) * 1024.0 * geom.total_cores[i] * nw[i]
        dram = (float(geom.dram_gb_per_reticle[i]) * 1e9
                * int(geom.n_reticles[i]) * int(nw[i]))
        budget = sram + dram
        ref = feasible_strategy_arrays_ref(wl, tc, budget, 24)
        # host-side replay of the in-program mask over the baked grid
        mask = ((prog._chunks_o * prog._tp_o <= tc) & (prog._tp_o <= tc)
                & (prog._need_o <= budget))
        sel = np.flatnonzero(mask)[:24]
        if len(sel) == 0:
            got = np.array([[1, 1, 1, 1]], np.int64)
        else:
            got = np.stack([prog._tp_o[sel], prog._pp_o[sel],
                            prog._dp_o[sel], prog._mb_o[sel]], axis=1)
        assert (ref == got).all(), f"design {i}"


def test_warm_no_retrace_within_bucket():
    """`warm_optimizer_kernels(workload=...)` pre-compiles the evaluator
    buckets; any batch size inside a warmed bucket must then run without
    a single new trace (the PR 6 no-retrace contract, extended to the
    evaluator)."""
    from repro.core.mfmobo import warm_optimizer_kernels

    wl = GPT_BENCHMARKS[0]
    warmed = warm_optimizer_kernels(8, n_candidates=16, q=2, workload=wl,
                                    n_designs_max=16, max_strategies=24)
    assert warmed >= 1
    # memoized: a second warm compiles nothing new
    assert warm_optimizer_kernels(8, n_candidates=16, q=2, workload=wl,
                                  n_designs_max=16, max_strategies=24) == 0
    # force= re-warms through the memo
    assert warm_optimizer_kernels(8, n_candidates=16, q=2, workload=wl,
                                  n_designs_max=16, max_strategies=24,
                                  force=True) > 0
    prog = eval_compiled._program_for(wl, 24)
    before = prog._jit._cache_size()
    for n in (3, 5, 8, 11, 16):            # buckets 4/8/8/16/16 — all warm
        designs, geom, nw = _designs(n, n)
        eval_compiled.evaluate_batch_compiled(geom, wl, nw)
    assert prog._jit._cache_size() == before, "retrace inside warmed bucket"
    # the fused program was warmed with the acquire scan's own index dtype
    import jax.numpy as jnp

    fused_before = prog._fused_jit._cache_size()
    designs, geom, nw = _designs(16, 16)
    eval_compiled.dispatch_fused_eval(
        geom, wl, nw, jnp.arange(4, dtype=jnp.int32), 24).finish(nw, 2)
    assert prog._fused_jit._cache_size() == fused_before, "fused retrace"


def test_fused_dispatch_matches_batch_path():
    """dispatch_fused_eval (device-resident gather of pool rows) returns
    the same EvalResults as evaluating the gathered designs directly."""
    import jax.numpy as jnp

    wl = GPT_BENCHMARKS[0]
    designs, geom, nw = _designs(5, 12)
    js = np.array([7, 2, 9, 2], np.int64)
    pend = eval_compiled.dispatch_fused_eval(geom, wl, nw,
                                             jnp.asarray(js), 24)
    fused = pend.finish(nw[js], q=4)
    direct = eval_compiled.evaluate_batch_compiled(
        DesignBatch.from_designs([designs[j] for j in js]), wl, nw[js], 24)
    assert len(fused) == 4
    for f, d in zip(fused, direct):
        assert _result_fingerprint(f) == _result_fingerprint(d)


def test_campaign_trace_identity_vs_pr7_baseline():
    """Fixed-seed fig8 campaigns through the fused compiled loop replay
    the PR 7 (NumPy, unfused) trace hex-for-hex: same proposals, same
    objective values, same hypervolume curve, same calibration metric."""
    import jax

    from benchmarks.fig8_explorer import method_specs
    from repro.core.evaluator import clear_eval_cache
    from repro.core.noc_gnn import init_gnn
    from repro.explore import Campaign

    with open(os.path.join(DATA, "fig8_trace_pr7_baseline.json")) as f:
        base = json.load(f)
    s = base["settings"]
    assert eval_compiled.enabled(), "compiled path must be on for this test"
    params = init_gnn(jax.random.PRNGKey(base["gnn_init_seed"]))
    specs = method_specs(base["workload"], base["seed"], N0=s["N0"],
                         N1=s["N1"], cand=s["cand"], q=s["q"],
                         quick=s["quick"])
    for m, spec in specs.items():
        clear_eval_cache()
        r = Campaign(spec, gnn_params=params).run()
        tr = r.trace
        exp = base["methods"][m]
        assert tr.n_evals == exp["n_evals"], m
        got_ys = [[_hex(a), _hex(b)] for a, b in tr.ys]
        assert got_ys == exp["ys_hex"], f"{m}: objective values drifted"
        assert [_hex(h) for h in tr.hv] == exp["hv_hex"], m
        assert [[_hex(v) for v in x] for x in tr.xs] == exp["xs_hex"], m
        got_tau = [_hex(c["val_kendall_tau"]) for c in r.calibration]
        assert got_tau == exp["calibration_val_kendall_tau"], m


def test_host_lane_sharding_identical_results():
    """With --xla_force_host_platform_device_count=2 the batch path runs
    pmap-sharded across 2 XLA host lanes — and must produce byte-identical
    results. Needs a subprocess: lane count is fixed at jax init."""
    designs, geom, nw = _designs(13, 8)
    be = AnalyticalBackend()
    wl = GPT_BENCHMARKS[0]
    ref = be.evaluate_batch_ref(geom, wl, nw, max_strategies=24)
    ref_fp = [_result_fingerprint(r) for r in ref]

    child = """
import json, sys
import numpy as np
from repro.core import eval_compiled
from tests.test_eval_compiled import _designs, _result_fingerprint
from repro.core.workload import GPT_BENCHMARKS
import jax
assert jax.local_device_count() == 2, jax.local_device_count()
designs, geom, nw = _designs(13, 8)
got = eval_compiled.evaluate_batch_compiled(geom, GPT_BENCHMARKS[0], nw, 24)
print(json.dumps({"fp": [_result_fingerprint(g) for g in got],
                  "lanes": eval_compiled.lane_stats()}))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2").strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [root, os.path.join(root, "src")]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    env["REPRO_COMPILED_EVAL"] = "1"
    out = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["fp"] == ref_fp, "sharded results drifted from oracle"
    assert payload["lanes"]["n_lanes"] == 2
    assert payload["lanes"]["sharded_calls"] >= 1
    assert payload["lanes"]["rows_sharded"] >= 8


def test_eval_cache_set_many():
    """Batch cache writes: one `set_many` call lands every entry, bumps
    the batched-write counters, and — for the disk backend — appends one
    segment record run that a fresh process replays."""
    from repro.core.evalcache import DiskSegmentEvalCache, InMemoryEvalCache

    from repro.core.evalcache import attribute_cache_traffic

    mem = InMemoryEvalCache()
    with attribute_cache_traffic() as traffic:
        n = mem.set_many([(f"k{i}", i * i) for i in range(5)])
    assert n == 5
    assert traffic["entries_added"] == 5
    st = mem.stats()
    assert st["set_many_calls"] == 1
    assert st["set_many_entries"] == 5
    assert mem.get("k3") == 9 and st["entries"] == 5

    with tempfile.TemporaryDirectory() as d:
        disk = DiskSegmentEvalCache(d)
        disk.set_many([(f"k{i}", {"v": i}) for i in range(4)])
        disk.put("extra", {"v": 99})
        st = disk.stats()
        assert st["set_many_calls"] == 1 and st["set_many_entries"] == 4
        disk.close()
        fresh = DiskSegmentEvalCache(d)      # replays the segment files
        assert fresh.get("k2") == {"v": 2}
        assert fresh.get("extra") == {"v": 99}
        fresh.close()
