"""Fleet-scale campaign execution (DESIGN.md §11): pluggable eval-cache
backends (LRU bounds, on-disk segment sharing, traffic attribution),
checkpoint retention + corrupt-head fallback, per-process kernel-warm
memoization, async proposal-mode determinism and mid-flight resume, and
the multiprocess fleet scheduler (shared persistent cache, crash-requeue
+ checkpoint-resume)."""
import dataclasses
import glob
import os
import pickle
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core.evalcache import (
    DiskSegmentEvalCache,
    InMemoryEvalCache,
    attribute_cache_traffic,
)
from repro.core import evaluator
from repro.explore import (
    Campaign,
    CampaignSpec,
    ExplorationLoop,
    FidelitySchedule,
    FleetSpec,
    LoopConfig,
    expand_grid,
    run_fleet,
)
from repro.explore.fleet import _CRASH_ENV


def quick_spec(**over) -> CampaignSpec:
    kw = dict(
        name="fleet-quick", workload="GPT-1.7B", scenario="train",
        strategy="mfmobo",
        fidelity=FidelitySchedule(f1="analytical", f0="analytical",
                                  d1=2, d0=2, k=2),
        n_evals_f0=5, n_evals_f1=6, q=2, n_candidates=16,
        max_strategies=6, seed=7)
    kw.update(over)
    return CampaignSpec(**kw)


# --------------------------- eval-cache backends ----------------------------


def test_inmemory_lru_eviction_and_stats():
    c = InMemoryEvalCache(max_entries=3)
    for i in range(3):
        c.put(("k", i), i)
    assert c.get(("k", 0)) == 0              # refreshes k0's recency
    c.put(("k", 3), 3)                       # evicts k1 (LRU), not k0
    assert c.get(("k", 1)) is None
    assert c.get(("k", 0)) == 0 and c.get(("k", 3)) == 3
    s = c.stats()
    assert s["entries"] == 3 and s["evictions"] == 1
    assert s["hits"] == 3 and s["misses"] == 1
    assert s["max_entries"] == 3
    with pytest.raises(ValueError):
        InMemoryEvalCache(max_entries=0)


def test_disk_segment_cache_shares_across_instances(tmp_path):
    d = str(tmp_path / "cache")
    a = DiskSegmentEvalCache(d)
    b = DiskSegmentEvalCache(d)              # a second "process"
    a.put(("design", 1, "f0"), (10.0, 20.0))
    # b misses in memory, merges a's segment on the miss path, then hits
    assert b.get(("design", 1, "f0")) == (10.0, 20.0)
    assert b.stats()["merged_in"] == 1
    b.put(("design", 2, "f0"), (30.0, 40.0))
    assert a.get(("design", 2, "f0")) == (30.0, 40.0)
    assert a.stats()["segments"] == 2
    # a cold third instance rebuilds the merged view from disk alone
    c = DiskSegmentEvalCache(d)
    assert c.get(("design", 1, "f0")) is not None
    assert c.get(("design", 2, "f0")) is not None
    for x in (a, b, c):
        x.close()


def test_disk_segment_cache_tolerates_torn_tail(tmp_path):
    d = str(tmp_path / "cache")
    a = DiskSegmentEvalCache(d)
    a.put(("k", 1), 1.0)
    a.put(("k", 2), 2.0)
    a.close()
    seg = glob.glob(os.path.join(d, "seg-*"))[0]
    with open(seg, "ab") as f:               # crashed writer mid-append
        f.write(b"\x80\x05torn")
    b = DiskSegmentEvalCache(d)
    assert b.get(("k", 1)) == 1.0 and b.get(("k", 2)) == 2.0
    b.close()


def test_disk_segment_cache_clear_keeps_disk_purge_deletes(tmp_path):
    d = str(tmp_path / "cache")
    a = DiskSegmentEvalCache(d)
    a.put(("k", 1), 1.0)
    a.clear()                                 # memory only
    assert glob.glob(os.path.join(d, "seg-*"))
    b = DiskSegmentEvalCache(d)               # peers still see the entry
    assert b.get(("k", 1)) == 1.0
    b.close()
    a.purge()                                 # explicit disk reset
    assert not glob.glob(os.path.join(d, "seg-*"))


def test_attribute_cache_traffic_is_thread_local():
    c = InMemoryEvalCache()
    c.put(("seed",), 0)
    accs = {}

    def worker(tag, hit_key, miss_key):
        with attribute_cache_traffic() as acc:
            c.get(hit_key)
            c.get(miss_key)
            c.put(("new", tag), 1)
            accs[tag] = acc

    ts = [threading.Thread(target=worker,
                           args=(t, ("seed",), ("nope", t)))
          for t in range(4)]
    with attribute_cache_traffic() as outer:
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    # each thread sees exactly its own traffic; the outer block none of it
    for t in range(4):
        assert accs[t] == {"hits": 1, "misses": 1, "entries_added": 1}
    assert outer == {"hits": 0, "misses": 0, "entries_added": 0}


def test_evaluator_backend_swap_and_stats(tmp_path):
    prev = evaluator.get_eval_cache_backend()
    try:
        be = evaluator.configure_eval_cache(max_entries=2)
        assert evaluator.get_eval_cache_backend() is be
        s = evaluator.eval_cache_stats()
        assert s["entries"] == 0 and "evictions" in s
        disk = evaluator.configure_eval_cache(
            cache_dir=str(tmp_path / "ec"), max_entries=8)
        assert isinstance(disk, DiskSegmentEvalCache)
        assert evaluator.eval_cache_stats()["segments"] == 0
    finally:
        evaluator.set_eval_cache_backend(prev)


def test_gnn_params_digest_is_content_stable():
    import jax
    from repro.core.noc_gnn import init_gnn
    p1 = init_gnn(jax.random.PRNGKey(0))
    p2 = init_gnn(jax.random.PRNGKey(0))
    p3 = init_gnn(jax.random.PRNGKey(1))
    # same content -> same digest even across distinct objects (unlike the
    # monotonic pin token, which is object-identity based)
    assert evaluator.gnn_params_digest(p1) == evaluator.gnn_params_digest(p2)
    assert evaluator.gnn_params_digest(p1) != evaluator.gnn_params_digest(p3)
    assert evaluator.gnn_params_token(p1) != evaluator.gnn_params_token(p2)


# --------------------------- checkpoint retention ---------------------------


def _tiny_loop(**over):
    cfg = dict(strategy="mobo", N0=6, d0=2, q=2, n_candidates=8, seed=3)
    cfg.update(over)

    def f(d):
        return (1000.0, 2000.0)

    return ExplorationLoop(LoopConfig(**cfg), f)


def test_save_state_retains_last_n_and_prunes(tmp_path):
    loop = _tiny_loop()
    ck = str(tmp_path / "w.ckpt")
    while loop.step():
        loop.save_state(ck, keep=3)
    hist = sorted(glob.glob(ck + ".step*"))
    assert len(hist) == 2                      # keep-1 history + the head
    assert os.path.exists(ck)
    # keep<=1 reverts to single-file behavior
    loop2 = _tiny_loop()
    ck2 = str(tmp_path / "s.ckpt")
    while loop2.step():
        loop2.save_state(ck2, keep=1)
    assert not glob.glob(ck2 + ".step*")


def test_load_state_falls_back_on_corrupt_head(tmp_path):
    loop = _tiny_loop()
    ck = str(tmp_path / "w.ckpt")
    while loop.step():
        loop.save_state(ck, keep=3)
    good_cfg, good_state, _ = ExplorationLoop.load_state(ck)
    with open(ck, "wb") as f:
        f.write(b"definitely not a pickle")
    cfg, state, _ = ExplorationLoop.load_state(ck)
    assert cfg == good_cfg
    # fallback is the newest retained history snapshot — one save behind
    # the (corrupt) head, and a strict prefix of its trace
    assert state.steps == good_state.steps - 1
    assert state.trace.ys == good_state.trace.ys[:len(state.trace.ys)]
    # nothing loadable at all -> the head's error propagates
    for p in glob.glob(ck + ".step*"):
        os.remove(p)
    with pytest.raises(Exception):
        ExplorationLoop.load_state(ck)


def test_load_state_reads_v1_checkpoints(tmp_path):
    loop = _tiny_loop()
    while loop.step():
        pass
    st = loop.state
    for f in ("inflight", "dispatch_seq"):    # simulate a pre-async state
        delattr(st, f)
    blob = {"version": 1, "cfg": dataclasses.asdict(loop.cfg),
            "state": st, "extra": {}}
    p = str(tmp_path / "v1.ckpt")
    with open(p, "wb") as f:
        pickle.dump(blob, f)
    _, state, _ = ExplorationLoop.load_state(p)
    assert state.inflight == [] and state.dispatch_seq == 0


# --------------------------- warm memoization -------------------------------


def test_warm_optimizer_kernels_memoized_per_process():
    from repro.core.mfmobo import warm_optimizer_kernels
    n1 = warm_optimizer_kernels(4, n_candidates=12, q=2)
    n2 = warm_optimizer_kernels(4, n_candidates=12, q=2)
    assert n1 >= 1 and n2 == 0                # second call skips everything
    assert warm_optimizer_kernels(4, n_candidates=12, q=2, force=True) == n1


# --------------------------- async proposal mode ----------------------------


def test_async_depth_validation():
    with pytest.raises(ValueError, match="async_depth"):
        LoopConfig(async_depth=-1).validate()


@pytest.mark.parametrize("strategy", ["mfmobo", "mobo"])
def test_async_mode_is_deterministic_and_exact(strategy):
    over = ({} if strategy == "mfmobo"
            else dict(strategy="mobo", n_evals_f0=6))
    spec = quick_spec(async_depth=2, **over)
    r1 = Campaign(spec).run()
    r2 = Campaign(spec).run()
    assert r1.finished and r2.finished
    # fixed seed + fixed (state-driven) interleaving replays the trace
    assert r1.trace.ys == r2.trace.ys
    assert r1.trace.hv == r2.trace.hv
    assert [x.tolist() for x in r1.trace.xs] == [x.tolist()
                                                 for x in r2.trace.xs]
    # async mode still honors the budgets exactly
    assert r1.n_evals == spec.loop_config().total_evals()
    assert len(r1.trace.ys) == spec.n_evals_f0


def test_async_resume_mid_flight_matches_uninterrupted(tmp_path):
    spec = quick_spec(async_depth=2)
    full = Campaign(spec).run()
    ck = str(tmp_path / "a.ckpt")
    c = Campaign(spec)
    c.run(checkpoint_path=ck, checkpoint_every=1, max_steps=4)
    assert not c.loop.finished
    # the checkpoint legitimately carries in-flight batches (futures are
    # process-local and not pickled; the resume path re-dispatches them)
    resumed = Campaign.resume(ck).run()
    assert resumed.trace.ys == full.trace.ys
    assert resumed.trace.hv == full.trace.hv
    assert resumed.n_evals == full.n_evals


def test_sync_mode_untouched_by_async_fields():
    # async_depth=0 must consume the identical rng stream as the loop did
    # before async mode existed: pin against the thin legacy wrapper
    from repro.core.mfmobo import run_mfmobo

    def f(d):
        return (float(d.mac_num) / 2.0, 1500.0)

    spec = quick_spec(async_depth=0)
    tr = run_mfmobo(f, f, d0=2, d1=2, k=2, N0=5, N1=6, q=2,
                    n_candidates=16, seed=7)
    res = Campaign(spec).run()      # different objective, same rng stream
    assert len(res.trace.ys) == len(tr.ys)


# --------------------------- fleet spec + scheduler -------------------------


def test_fleet_spec_roundtrip_grid_and_validation(tmp_path):
    fs = FleetSpec(name="t", campaigns=(quick_spec(),), workers=2,
                   cache_dir="x", checkpoint_every=4)
    again = FleetSpec.from_json(fs.to_json())
    assert again == fs
    grid = expand_grid({"base": quick_spec().to_dict(),
                        "strategies": ["mfmobo", "random"],
                        "seeds": [0, 1]})
    assert len(grid) == 4
    assert len({c.name for c in grid}) == 4
    with pytest.raises(ValueError, match="unique"):
        FleetSpec(name="d", campaigns=(quick_spec(), quick_spec())
                  ).validate()
    with pytest.raises(ValueError, match="no campaigns"):
        FleetSpec(name="e", campaigns=()).validate()
    with pytest.raises(ValueError, match="unknown fleet spec fields"):
        FleetSpec.from_dict({"name": "x", "campaigns": [], "bogus": 1})


def _fleet_campaigns():
    a = quick_spec(name="fa", seed=0, async_depth=1)
    b = quick_spec(name="fb", seed=0, strategy="random", n_evals_f0=4, q=4)
    return a, b


def test_fleet_runs_grid_with_shared_cache(tmp_path):
    a, b = _fleet_campaigns()
    fs = FleetSpec(name="t-fleet", campaigns=(a, b), workers=2,
                   cache_dir=str(tmp_path / "ec"),
                   checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2)
    res = run_fleet(fs)
    assert res.errors == [] and res.crashes == 0
    assert all(c is not None for c in res.campaigns)
    assert res.n_evals == (a.loop_config().total_evals()
                           + b.loop_config().total_evals())
    assert res.fleet_candidates_per_sec > 0
    # both workers wrote segments into the shared persistent cache
    assert len(glob.glob(str(tmp_path / "ec" / "seg-*"))) >= 1
    # result dicts are JSON-serializable artifacts
    out = str(tmp_path / "fleet.json")
    res.save(out)
    assert os.path.getsize(out) > 0


def test_fleet_warm_second_pass_hits_shared_cache(tmp_path):
    _, b = _fleet_campaigns()
    fs = FleetSpec(name="t-warm", campaigns=(b,), workers=1,
                   cache_dir=str(tmp_path / "ec"))
    cold = run_fleet(fs)
    warm = run_fleet(dataclasses.replace(fs, name="t-warm2"))
    sc_cold = cold.campaigns[0]["stage_cache"]["f0"]
    sc_warm = warm.campaigns[0]["stage_cache"]["f0"]
    assert sc_warm["hits"] > sc_cold["hits"]
    # the warm campaign re-evaluates the same candidates: >50% f0 hit-rate
    assert sc_warm["hit_rate"] > 0.5


def test_fleet_killed_worker_resumes_to_identical_front(tmp_path):
    a, _ = _fleet_campaigns()
    ref = Campaign(a).run()
    fs = FleetSpec(name="t-crash", campaigns=(a,), workers=1,
                   checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=1)
    marker = str(tmp_path / "crashed.marker")
    os.environ[_CRASH_ENV] = f"{a.name}:{marker}"
    try:
        res = run_fleet(fs)
    finally:
        del os.environ[_CRASH_ENV]
    assert os.path.exists(marker), "crash hook never fired"
    assert res.crashes == 1
    c = res.campaigns[0]
    assert c["resumed"] is True
    assert c["hv"] == list(ref.trace.hv)
    assert c["n_evals"] == ref.n_evals
    assert [f["throughput"] for f in c["front"]] == [
        f["throughput"] for f in ref.front]


def test_fleet_refuses_a_parent_holding_an_accelerator(monkeypatch):
    """A parent that already started a TPU backend holds the chip its
    workers would need: run_fleet refuses before spawning anything."""
    import jax

    import repro.explore.fleet as F

    jax.devices()                            # this process runs on the CPU
    assert F.parent_platform() == "cpu"
    monkeypatch.setattr(F, "parent_platform", lambda: "tpu")
    with pytest.raises(RuntimeError, match="already holds the tpu"):
        run_fleet(FleetSpec(name="t-held", campaigns=(quick_spec(),),
                            workers=1))


def test_fleet_worker_without_a_device_is_reported_not_respawned(
        monkeypatch):
    """A worker whose JAX backend cannot start reports it and the fleet
    stops with that error at once, instead of respawning the worker until
    the crash budget runs out."""
    monkeypatch.setenv("JAX_PLATFORMS", "no-such-platform")
    with pytest.raises(RuntimeError, match="worker 0 could not get a "
                                           "device"):
        run_fleet(FleetSpec(name="t-nodev", campaigns=(quick_spec(),),
                            workers=2))


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets no directory of
    its own; without it, the cache sits at the fixed path in the checkout."""
    import jax

    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(compile_cache.ENV)
    try:
        assert compile_cache.configure_compile_cache() == str(
            compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(
            compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.parent == Path(__file__).parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
