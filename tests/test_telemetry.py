"""The explorer's spans and counters (`repro.telemetry`): nesting and self
time, counters and host syncs, one telemetry per campaign (async pool
threads, checkpoint and resume, old checkpoints), how much of a step the
layer spans cover, and the join with a profiler trace by span id."""
import collections
import gc
import glob
import pickle
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry as tm
from repro.core.evaluator import clear_eval_cache
from repro.explore import Campaign, CampaignSpec, FidelitySchedule
from repro.explore.runner import ExplorationLoop


def quick_spec(**over) -> CampaignSpec:
    kw = dict(
        name="t-telemetry", workload="GPT-1.7B", scenario="train",
        strategy="mfmobo",
        fidelity=FidelitySchedule(f1="analytical", f0="analytical",
                                  d1=2, d0=2, k=2),
        n_evals_f0=5, n_evals_f1=6, q=2, n_candidates=16,
        max_strategies=6, seed=7)
    kw.update(over)
    return CampaignSpec(**kw)


def _counts(summary):
    return {k: (v["count"], v["items"]) for k, v in summary.items()}


# ------------------------------ the module ---------------------------------


def test_span_nesting_parents_and_self_time():
    tel = tm.Telemetry()
    with tm.activate(tel):
        with tm.span("outer", items=3, tag="a") as outer:
            time.sleep(0.01)
            with tm.span("outer.inner"):
                time.sleep(0.02)
            with tm.span("outer.inner") as sp:
                sp.items = 5
        with tm.span("outer", tag="b"):
            pass
    assert tm.current() is None
    first, second, third, fourth = sorted(tel.records, key=lambda r: r.id)
    assert (first.name, first.parent) == ("outer", -1)
    assert (second.parent, third.parent) == (first.id, first.id)
    assert fourth.parent == -1 and fourth.tag == "b"
    assert third.items == 5
    assert outer.seconds == pytest.approx(first.seconds)
    s = tel.summary()
    assert s["outer"]["count"] == 2 and s["outer"]["items"] == 3
    assert s["outer"]["tags"]["a"]["count"] == 1
    assert s["outer.inner"]["count"] == 2 and s["outer.inner"]["items"] == 5
    child = second.seconds + third.seconds
    assert s["outer"]["self_s"] == pytest.approx(
        first.seconds + fourth.seconds - child)
    assert s["outer"]["self_s"] >= 0.009
    assert s["outer.inner"]["self_s"] == pytest.approx(child)


def test_span_without_telemetry_times_and_records_nothing():
    with tm.span("alone") as sp:
        time.sleep(0.002)
    assert sp.seconds >= 0.002
    tm.count("nothing")                    # no active telemetry: no-op


def test_counters_and_host_syncs():
    tel = tm.Telemetry()
    x = jnp.arange(4) * 2
    with tm.activate(tel):
        tm.count("schedule.hit")
        tm.count("schedule.hit", 2)
        got = tm.to_host({"a": x, "b": (x, 3)}, "test")
    assert isinstance(got["a"], np.ndarray)
    assert got["a"].tolist() == [0, 2, 4, 6] and got["b"][1] == 3
    assert tel.counters["schedule.hit"] == 3
    assert tel.counters["host_syncs"] == 1
    assert [r.name for r in tel.records] == ["sync.test"]
    assert tel.to_dict()["counters"] == tel.counters


def test_compiles_are_counted_under_the_open_span():
    tel = tm.Telemetry()
    f = jax.jit(lambda v: v * 3 + 1)
    with tm.activate(tel), tm.span("fresh"):
        f(jnp.ones(7)).block_until_ready()
    assert tel.counters["compiles"] >= 1
    assert tel.counters["compiles[fresh]"] == tel.counters["compiles"]
    assert tel.counters["compile_s"] > 0


def test_threads_keep_their_own_telemetry_and_stack():
    tels = [tm.Telemetry() for _ in range(4)]

    def work(tel):
        with tm.activate(tel):
            for _ in range(50):
                with tm.span("a"):
                    with tm.span("a.b"):
                        tm.count("n")

    ts = [threading.Thread(target=work, args=(t,)) for t in tels]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for tel in tels:
        assert tel.counters["n"] == 50
        recs = {r.id: r for r in tel.records}
        assert len(recs) == 100
        assert all(recs[r.parent].name == "a" for r in recs.values()
                   if r.name == "a.b")


def test_telemetry_pickles():
    tel = tm.Telemetry()
    with tm.activate(tel), tm.span("x"):
        tm.count("c")
    again = pickle.loads(pickle.dumps(tel))
    assert again.records == tel.records and again.counters == tel.counters
    with tm.activate(again), tm.span("y"):
        pass
    assert again.records[-1].id == 1          # ids go on after a reload


def test_disaggregated_candidates_share_one_span():
    """A trace-serving call with k disaggregated candidates opens one
    `evaluate.trace.disaggregated` span with items k (no span a design);
    the stage evaluations' spans nest under it, and their `.run` items
    count the stage evaluations that missed the eval cache."""
    from benchmarks.common import sample_valid_designs
    from repro.core.traces import (
        PolicyDesign,
        evaluate_trace_serving_batch,
        spike_trace,
    )
    from repro.core.workload import GPT_BENCHMARKS

    designs = sample_valid_designs(3, seed=2)
    cands = ([PolicyDesign(d, "disaggregated") for d in designs]
             + [PolicyDesign(designs[0], "fifo")])
    t = spike_trace(12, seed=1)
    clear_eval_cache()
    tel = tm.Telemetry()
    with tm.activate(tel):
        evaluate_trace_serving_batch(cands, GPT_BENCHMARKS[0], t, slots=4,
                                     window_steps=16, max_strategies=6)
    dis = [r for r in tel.records if r.name == "evaluate.trace.disaggregated"]
    assert len(dis) == 1 and dis[0].items == len(designs)
    runs = [r for r in tel.records if r.name == "evaluate.analytical.run"
            and r.parent == dis[0].id]
    assert len(runs) == 2                     # the prefill and decode stages
    assert [r.items for r in runs] == [len(designs)] * 2
    assert tel.summary()["evaluate.trace.pool"]["items"] == 1


# ------------------------------- campaigns ---------------------------------


def test_campaign_reports_its_layers():
    clear_eval_cache()
    camp = Campaign(quick_spec())
    res = camp.run()
    spans = res.telemetry["spans"]
    assert spans["step"]["count"] == camp.loop.state.steps
    assert set(spans["step"]["tags"]) == {"init", "f1", "handover", "f0"}
    for name in ("candidates", "candidates.validate", "propose.fit",
                 "propose.acquire", "evaluate", "fold", "sync.gp_params",
                 "sync.picks", "evaluate.analytical.run"):
        assert spans[name]["count"] > 0, name
    assert spans["evaluate"]["items"] == res.n_evals
    assert spans["fold"]["items"] == res.n_evals
    syncs = sum(e["count"] for n, e in spans.items()
                if n.startswith("sync."))
    assert res.telemetry["counters"]["host_syncs"] == syncs
    assert "telemetry" in res.to_dict()


def test_layer_spans_cover_the_steps():
    """The spans opened inside a step (candidates, propose, evaluate,
    calibrate, fold) hold at least 90% of the summed step time."""
    clear_eval_cache()
    Campaign(quick_spec()).run()             # programs compiled
    clear_eval_cache()
    res = Campaign(quick_spec()).run()
    step = res.telemetry["spans"]["step"]
    assert step["self_s"] <= 0.10 * step["s"], step


def test_async_records_go_to_their_campaign():
    """Two async campaigns stepped in turn on one thread, each evaluating
    on its own pool threads: every batch's evaluate span lands in its own
    campaign's telemetry, none is lost."""
    clear_eval_cache()
    a = Campaign(quick_spec(name="ta", seed=3, async_depth=2))
    b = Campaign(quick_spec(name="tb", seed=4, async_depth=2))
    while True:
        moved = [c.loop.step() for c in (a, b)]
        if not any(moved):
            break
    for c in (a, b):
        c.loop.run()                         # shuts the pool down
        res = c.result()
        ev = res.telemetry["spans"]["evaluate"]
        assert ev["items"] == res.n_evals
        recs = c.loop.state.telemetry.records
        assert any(r.thread.startswith("eval") for r in recs
                   if r.name == "evaluate")
        assert len({r.id for r in recs}) == len(recs)


def test_resume_reports_the_same_counts(tmp_path):
    clear_eval_cache()
    Campaign(quick_spec()).run()             # programs compiled
    clear_eval_cache()
    full = Campaign(quick_spec()).run()
    ck = str(tmp_path / "c.ckpt.pkl")
    clear_eval_cache()
    Campaign(quick_spec()).run(checkpoint_path=ck, max_steps=3)
    resumed = Campaign.resume(ck).run(checkpoint_path=ck)
    assert resumed.finished
    assert _counts(resumed.telemetry["spans"]) == \
        _counts(full.telemetry["spans"])
    assert resumed.telemetry["counters"] == full.telemetry["counters"]


def test_checkpoint_without_telemetry_loads(tmp_path):
    camp = Campaign(quick_spec())
    camp.run(max_steps=2)
    ck = str(tmp_path / "old.ckpt.pkl")
    camp._checkpoint(ck)
    with open(ck, "rb") as f:
        blob = pickle.load(f)
    del blob["state"].telemetry              # as written before telemetry
    blob["state"].trace.wall_s = [0.0] * len(blob["state"].trace.ys)
    with open(ck, "wb") as f:
        pickle.dump(blob, f)
    _, state, _ = ExplorationLoop.load_state(ck)
    assert isinstance(state.telemetry, tm.Telemetry)
    res = Campaign.resume(ck).run()
    assert res.finished and res.telemetry["spans"]["step"]["count"] > 0


def test_profiler_trace_holds_every_record(tmp_path):
    """Each in-memory record has its `repro:` host event in a profiler
    trace, found by id, with the same name and a duration within 50 us."""
    from jax.profiler import ProfileData
    clear_eval_cache()
    Campaign(quick_spec()).run()             # programs compiled
    clear_eval_cache()
    camp = Campaign(quick_spec(n_evals_f0=3, n_evals_f1=4))
    # a garbage collection or a handoff of the GIL between an annotation's
    # edge and the span's clock read would stretch one of the two: keep
    # both out of the traced campaign
    gc.collect()
    gc.disable()
    switch = sys.getswitchinterval()
    sys.setswitchinterval(10.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        camp.run()
    finally:
        jax.profiler.stop_trace()
        sys.setswitchinterval(switch)
        gc.enable()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path[0])
    events = collections.defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tm.PREFIX):
                    stats = dict(ev.stats)
                    events[stats["id"]].append(
                        (ev.name[len(tm.PREFIX):], ev.duration_ns))
    recs = camp.loop.state.telemetry.records
    assert recs
    for r in recs:
        (name, dur_ns), = events[r.id]
        assert name == r.name
        assert abs(dur_ns - (r.t1_ns - r.t0_ns)) <= 50_000, (r, dur_ns)
