"""Trace-driven multi-tenant serving (repro.core.traces, DESIGN.md §14):
generators, the event-skip scheduler vs its per-step reference, policy
semantics, windowed goodput metrics, the searchable policy axis, and the
ServeEngine cross-validation (ISSUE 10 satellites S1-S4)."""
import dataclasses
import json
import time

import numpy as np
import pytest

from repro.core.serving import (
    _continuous_batch_schedule_ref,
    continuous_batch_schedule,
)
from repro.core.traces import (
    DEFAULT_TENANT,
    POLICIES,
    POOL_POLICIES,
    PolicyDesign,
    RequestTrace,
    TenantClass,
    _trace_schedule_ref,
    diurnal_trace,
    evaluate_trace_serving_batch,
    poisson_trace,
    sample_policy_candidates,
    spike_trace,
    synth_trace,
    trace_schedule,
    trace_serving_metrics,
)
from repro.core.workload import GPT_BENCHMARKS, RequestMix

TWO_TENANTS = (
    TenantClass("chat", ttft_s=5.0, tpot_s=0.1, priority=2,
                interactive=True),
    TenantClass("batch", ttft_s=1e4, tpot_s=1e3, priority=0,
                interactive=False),
)


def _sched_equal(a, b):
    assert a.n_steps == b.n_steps
    assert a.n_decode_steps == b.n_decode_steps
    assert a.n_preemptions == b.n_preemptions
    for f in ("admit_step", "finish_step", "decode_tokens",
              "event_step", "event_req", "event_ctx", "first_event"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f), f)


# ---------------------------------------------------------------------------
# trace generators + serialization
# ---------------------------------------------------------------------------


def test_generators_deterministic_and_sorted():
    for kind in ("poisson", "spike", "diurnal"):
        t1 = synth_trace(kind, 40, seed=3, tenants=TWO_TENANTS)
        t2 = synth_trace(kind, 40, seed=3, tenants=TWO_TENANTS)
        assert t1 == t2
        assert t1.n_requests == 40
        arr = np.asarray(t1.arrival_steps)
        assert (np.diff(arr) >= 0).all()
        assert synth_trace(kind, 40, seed=4, tenants=TWO_TENANTS) != t1


def test_trace_json_round_trip(tmp_path):
    t = spike_trace(24, tenants=TWO_TENANTS, shares=(0.5, 0.5), seed=9)
    rt = RequestTrace.from_json(t.to_json())
    assert rt == t
    p = tmp_path / "trace.json"
    t.to_json(str(p))
    assert RequestTrace.from_json(str(p)) == t
    # payload is plain JSON with tenant classes embedded
    d = json.loads(t.to_json())
    assert {tc["name"] for tc in d["tenants"]} == {"chat", "batch"}


def test_trace_tenant_views():
    t = spike_trace(30, tenants=TWO_TENANTS, shares=(0.5, 0.5), seed=1)
    prio = t.priorities()
    inter = t.interactive_mask()
    for r in range(t.n_requests):
        tc = t.tenant_of(r)
        assert prio[r] == tc.priority
        assert inter[r] == tc.interactive
    # single-tenant default: everyone interactive at priority 0
    u = poisson_trace(10, seed=0)
    assert u.interactive_mask().all() and (u.priorities() == 0).all()


def test_from_mix_is_all_arrived_at_zero():
    mix = RequestMix.sampled(np.random.default_rng(0), 12, (4, 64), (2, 9))
    t = RequestTrace.from_mix(mix)
    assert (np.asarray(t.arrival_steps) == 0).all()
    assert t.mix() == mix
    assert t.tenants == (DEFAULT_TENANT,)
    assert mix.as_trace() == t


def test_bad_traces_rejected():
    with pytest.raises(ValueError):
        RequestTrace((1, 0), (4, 4), (2, 2), (0, 0), (DEFAULT_TENANT,))
    with pytest.raises(ValueError):
        synth_trace("lognormal", 8)
    with pytest.raises(ValueError):
        poisson_trace(8, rate=0.0)


# ---------------------------------------------------------------------------
# S1: continuous_batch_schedule is the degenerate (all-at-zero, fifo) case
# ---------------------------------------------------------------------------


def test_degenerate_trace_matches_batch_schedule_bitwise():
    rng = np.random.default_rng(7)
    for _ in range(8):
        mix = RequestMix.sampled(rng, int(rng.integers(1, 24)),
                                 (1, 96), (1, 13))
        for slots in (1, 3, 8):
            s = continuous_batch_schedule(mix, slots)
            r = _continuous_batch_schedule_ref(mix, slots)
            assert s.n_decode_steps == r.n_decode_steps
            np.testing.assert_array_equal(s.admit_step, r.admit_step)
            np.testing.assert_array_equal(s.finish_step, r.finish_step)
            np.testing.assert_array_equal(s.decode_tokens, r.decode_tokens)


# ---------------------------------------------------------------------------
# event-skip scheduler == per-step reference (bitwise)
# ---------------------------------------------------------------------------


def test_fast_schedule_matches_reference_bitwise():
    for seed in range(6):
        for kind in ("poisson", "spike", "diurnal"):
            t = synth_trace(kind, 24, seed=seed, tenants=TWO_TENANTS,
                            shares=(0.5, 0.5))
            for slots in (1, 2, 5):
                for pol in POOL_POLICIES:
                    _sched_equal(trace_schedule(t, slots, pol),
                                 _trace_schedule_ref(t, slots, pol))


def test_schedule_rejects_bad_args():
    t = poisson_trace(4, seed=0)
    with pytest.raises(ValueError):
        trace_schedule(t, 0, "fifo")
    with pytest.raises(ValueError):
        trace_schedule(t, 4, "lifo")


# ---------------------------------------------------------------------------
# policy semantics
# ---------------------------------------------------------------------------


def _contended_trace():
    # 4 batch requests arrive first and occupy both slots; a chat request
    # arrives while they are still decoding
    return RequestTrace(
        arrival_steps=(0, 0, 0, 0, 2),
        prompt_lens=(16, 16, 16, 16, 16),
        out_lens=(12, 12, 12, 12, 4),
        tenant_ids=(1, 1, 1, 1, 0),
        tenants=TWO_TENANTS)


def test_priority_admits_interactive_before_waiting_batch():
    t = _contended_trace()
    fifo = trace_schedule(t, 2, "fifo")
    prio = trace_schedule(t, 2, "priority")
    # fifo: chat waits behind both queued batch requests
    assert prio.admit_step[4] <= fifo.admit_step[4]
    assert prio.n_preemptions == fifo.n_preemptions == 0
    # priority jumps the queue but never evicts: batch 2/3 admit later
    assert prio.admit_step[2] >= fifo.admit_step[2]


def test_preempt_evicts_batch_and_preserves_tokens():
    t = _contended_trace()
    s = trace_schedule(t, 2, "preempt")
    assert s.n_preemptions >= 1
    # chat admitted at its arrival step (a batch victim was evicted)
    assert s.admit_step[4] == 2
    # every request still emits exactly out_len tokens
    np.testing.assert_array_equal(
        np.asarray(s.decode_tokens),
        np.maximum(np.asarray(t.out_lens) - 1, 1))
    # the victim finishes later than it would have unpreempted
    fifo = trace_schedule(t, 2, "fifo")
    assert s.finish_step.max() >= fifo.finish_step.max()
    assert max(s.finish_step) < s.n_steps


# ---------------------------------------------------------------------------
# S3: event-skip performance guard
# ---------------------------------------------------------------------------


def test_event_skip_schedules_10k_diurnal_under_1s():
    t = diurnal_trace(10_000, rate=0.5, period=512, amplitude=0.9,
                      tenants=TWO_TENANTS, shares=(0.5, 0.5), seed=0)
    t0 = time.perf_counter()
    s = trace_schedule(t, 8, "preempt")
    dt = time.perf_counter() - t0
    assert (np.asarray(s.admit_step) >= 0).all()
    assert dt < 1.0, f"10k-request diurnal schedule took {dt:.2f}s"


# ---------------------------------------------------------------------------
# windowed goodput metrics
# ---------------------------------------------------------------------------


def test_trace_metrics_shapes_and_slo_binding():
    t = spike_trace(32, tenants=TWO_TENANTS, shares=(0.5, 0.5), seed=2)
    s = trace_schedule(t, 4, "fifo")
    tp = np.array([0.05, 0.05])
    td = np.array([0.01, 10.0])          # candidate 1: hopeless tpot
    m = trace_serving_metrics(s, t, tp, 512, td, window_steps=16)
    for k in ("goodput", "interactive_goodput", "worst_window_goodput",
              "throughput", "slo_attainment"):
        assert m[k].shape == (2,), k
    assert m["ttft"].shape == m["tpot"].shape == (2, t.n_requests)
    assert m["goodput"][0] >= m["interactive_goodput"][0] >= 0
    # slow candidate misses every chat SLO: zero interactive goodput
    assert m["interactive_goodput"][1] == 0.0
    assert m["worst_window_goodput"][1] == 0.0
    # worst-window rate can't beat the zero-SLO throughput ceiling
    assert (m["worst_window_goodput"] <= m["throughput"] + 1e-9).all()


def test_trace_metrics_huge_slo_goodput_equals_throughput():
    lax = (TenantClass("a", ttft_s=1e9, tpot_s=1e9),)
    t = poisson_trace(16, tenants=lax, seed=5)
    s = trace_schedule(t, 4, "fifo")
    m = trace_serving_metrics(s, t, np.array([0.1]), 256,
                              np.array([0.02]), window_steps=32)
    np.testing.assert_allclose(m["goodput"], m["throughput"])
    assert m["slo_attainment"][0] == 1.0


# ---------------------------------------------------------------------------
# evaluator + searchable policy axis
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_pool():
    from benchmarks.common import sample_valid_designs
    return sample_valid_designs(3, seed=5)


def test_evaluate_trace_serving_batch_all_policies(small_pool):
    wl = GPT_BENCHMARKS[7]
    t = spike_trace(20, tenants=TWO_TENANTS, shares=(0.5, 0.5), seed=3)
    cands = [PolicyDesign(small_pool[i % len(small_pool)], pol)
             for i, pol in enumerate(POLICIES)]
    res = evaluate_trace_serving_batch(cands, wl, t, slots=4,
                                       window_steps=16, max_strategies=8)
    assert [r.policy for r in res] == list(POLICIES)
    for r in res:
        if r.feasible:
            assert r.throughput_tok_s > 0 and r.power_w > 0
            assert r.n_steps >= r.n_decode_steps > 0
            assert set(r.per_tenant) == {"chat", "batch"}
    # plain designs default to the call's policy
    plain = evaluate_trace_serving_batch(small_pool[:1], wl, t, slots=4,
                                         policy="priority",
                                         window_steps=16, max_strategies=8)
    assert plain[0].policy == "priority"


def _hex(x):
    """Every number of a result as `float.hex`, recursively."""
    if dataclasses.is_dataclass(x):
        return {f.name: _hex(getattr(x, f.name))
                for f in dataclasses.fields(x)}
    if isinstance(x, dict):
        return {k: _hex(v) for k, v in x.items()}
    if isinstance(x, (float, np.floating)):
        return float(x).hex()
    return x


def _scalar_fed_disaggregated(d, wl, t, slots, window_steps, prefill_ratio,
                              granularity="reticle", n_wafers=None):
    """The coupled model fed by scalar `evaluate_design` stage results at
    24 strategies: the path disaggregated designs took before their stages
    were batched."""
    from repro.core.evaluator import evaluate_design
    from repro.core.heterogeneity import _trace_coupled, stage_split
    from repro.core.traces import serving_workloads
    wl_p, wl_d, p_ref = serving_workloads(wl, t, slots)
    split = stage_split(granularity, prefill_ratio, n_wafers)
    rp = evaluate_design(d, wl_p, "analytical", n_wafers=split.nw_p,
                         max_strategies=24)
    rd = evaluate_design(d, wl_d, "analytical", n_wafers=split.nw_d,
                         max_strategies=24)
    return _trace_coupled(rp, rd, d, wl, granularity, split, t, slots,
                          window_steps, p_ref)


def test_disaggregated_batch_matches_scalar_stages_bitwise():
    """Disaggregated candidates scored in one batched call equal, bit for
    bit, the coupled model fed by the scalar per-design stage evaluations;
    in a batch mixed with pool candidates the order of results is kept, and
    an infeasible stage keeps its reason."""
    from benchmarks.common import sample_valid_designs
    from repro.core.evaluator import clear_eval_cache
    wl = GPT_BENCHMARKS[7]
    t = spike_trace(20, tenants=TWO_TENANTS, shares=(0.5, 0.5), seed=3)
    designs = sample_valid_designs(8, seed=11)
    # far over a wafer's power budget at prefill: an infeasible stage
    hot = dataclasses.replace(designs[5], mac_num=4096, core_array=(16, 16),
                              reticle_array=(20, 20))
    dis = designs + [hot]
    pools = [PolicyDesign(d, p) for d, p in zip(designs[:3], POOL_POLICIES)]
    cands = ([pools[0]] + [PolicyDesign(d, "disaggregated") for d in dis[:5]]
             + pools[1:] + [PolicyDesign(d, "disaggregated")
                            for d in dis[5:]])
    kw = dict(slots=4, window_steps=16, prefill_ratio=0.5, max_strategies=8)

    clear_eval_cache()
    got = evaluate_trace_serving_batch(cands, wl, t, **kw)
    clear_eval_cache()
    want_dis = [_scalar_fed_disaggregated(d, wl, t, 4, 16, 0.5) for d in dis]
    clear_eval_cache()
    want_pool = [evaluate_trace_serving_batch([c], wl, t, **kw)[0]
                 for c in pools]

    assert [r.policy for r in got] == [c.policy for c in cands]
    got_dis = [r for r in got if r.policy == "disaggregated"]
    got_pool = [r for r in got if r.policy != "disaggregated"]
    assert [_hex(r) for r in got_dis] == [_hex(r) for r in want_dis]
    assert [_hex(r) for r in got_pool] == [_hex(r) for r in want_pool]
    assert sum(r.feasible for r in got_dis) >= 8
    assert not got_dis[-1].feasible
    assert got_dis[-1].reason in ("prefill_infeasible", "decode_infeasible")
    assert got_dis[-1].reason == want_dis[-1].reason


@pytest.mark.parametrize("granularity", ["core", "wafer"])
def test_hetero_trace_serving_granularities_match_scalar_stages(granularity):
    """The scalar `evaluate_hetero_trace_serving`, a batch of one, equals
    the scalar-fed coupled model bit for bit at the granularities the
    trace-serving batch does not use (wafer: each stage on its own share
    of whole wafers)."""
    from benchmarks.common import sample_valid_designs
    from repro.core.evaluator import clear_eval_cache
    from repro.core.heterogeneity import evaluate_hetero_trace_serving
    wl = GPT_BENCHMARKS[0]
    t = spike_trace(12, tenants=TWO_TENANTS, shares=(0.5, 0.5), seed=1)
    for d in sample_valid_designs(2, seed=4):
        clear_eval_cache()
        got = evaluate_hetero_trace_serving(d, d, wl, granularity, 0.25, t,
                                            slots=4, window_steps=16,
                                            n_wafers=4)
        clear_eval_cache()
        want = _scalar_fed_disaggregated(d, wl, t, 4, 16, 0.25,
                                         granularity, n_wafers=4)
        assert got.feasible and _hex(got) == _hex(want)


def test_sample_policy_candidates_axis():
    rng = np.random.default_rng(0)
    pts, cands = sample_policy_candidates(rng, 16)
    assert pts.shape == (16, 14)
    assert (0.0 <= pts).all() and (pts <= 1.0).all()
    assert all(isinstance(c, PolicyDesign) for c in cands)
    assert {c.policy for c in cands} <= set(POLICIES)
    assert "policy=" in cands[0].describe()
    # restricted menu decodes only into the allowed policies
    _, only = sample_policy_candidates(np.random.default_rng(1), 16,
                                       policies=("priority",))
    assert {c.policy for c in only} == {"priority"}


# ---------------------------------------------------------------------------
# campaign integration (TraceSpec)
# ---------------------------------------------------------------------------


def _trace_spec(policy="search", **kw):
    from repro.explore import CampaignSpec, FidelitySchedule, TraceSpec
    return CampaignSpec(
        name="t", workload="GPT-175B", scenario="trace_serving",
        strategy="random", fidelity=FidelitySchedule(f0="analytical",
                                                     d0=2, k=0),
        n_evals_f0=4, q=2, seed=3, max_strategies=8,
        trace=TraceSpec(kind="spike", n_requests=12, seed=1, slots=4,
                        window_steps=16, policy=policy,
                        tenants=({"name": "chat", "ttft_s": 9.0,
                                  "tpot_s": 0.5, "priority": 2,
                                  "interactive": True, "share": 0.5},
                                 {"name": "batch", "ttft_s": 1e4,
                                  "tpot_s": 1e3, "priority": 0,
                                  "interactive": False, "share": 0.5}),
                        **kw))


def test_trace_spec_round_trip_and_validation():
    from repro.explore import CampaignSpec
    spec = _trace_spec()
    spec.validate()
    assert CampaignSpec.from_json(spec.to_json()) == spec
    mets = spec.known_metrics()
    assert {"worst_window_goodput", "tenant:chat:goodput",
            "tenant:batch:slo_attainment"} <= set(mets)
    with pytest.raises(ValueError):
        _trace_spec(policy="lifo").validate()
    with pytest.raises(ValueError):
        # restricting the policy menu only makes sense under search
        _trace_spec(policy="fifo", policies=("fifo", "priority")).validate()
    import dataclasses
    no_trace = dataclasses.replace(spec, trace=None)
    with pytest.raises(ValueError):
        no_trace.validate()


def test_trace_campaign_searches_policy_axis():
    from repro.explore import Campaign
    res = Campaign(_trace_spec()).run()
    assert res.trace.n_evals == 4
    assert all(isinstance(d, PolicyDesign) for d in res.trace.designs)
    for f in res.front:
        assert f["design"]["policy"] in POLICIES
        assert "policy=" in f["describe"]


# ---------------------------------------------------------------------------
# S2 + S4: the real engine — submit validation and trace replay
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    jax = pytest.importorskip("jax")
    from repro.configs import reduced_config
    from repro.models import model as M
    cfg = reduced_config("smollm-135m")
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    return cfg, params


def _engine(tiny_model, **kw):
    from repro.models.runtime import CPU_TEST as RT
    from repro.serve.engine import ServeEngine
    cfg, params = tiny_model
    return ServeEngine(cfg, RT, params, max_len=64, **kw)


def test_submit_rejects_oversized_and_bad_requests(tiny_model):
    from repro.serve.engine import Request
    eng = _engine(tiny_model, slots=2)
    long_prompt = np.zeros(60, dtype=np.int32)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(Request(0, long_prompt, max_new_tokens=10))
    with pytest.raises(ValueError, match="submit_at"):
        eng.submit(Request(1, np.zeros(4, np.int32), 2, submit_at=-1))
    with pytest.raises(ValueError):
        _engine(tiny_model, slots=2, policy="lifo")
    eng.submit(Request(2, np.zeros(4, np.int32), 2))  # still usable


def _replay_trace():
    # narrow prompt/out ranges keep jit retraces bounded
    return spike_trace(
        12, rate=0.4, spike_factor=6.0, spike_len=8, gap_len=24,
        tenants=TWO_TENANTS, shares=(0.5, 0.5),
        prompt_ranges=((4, 8), (4, 8)), out_ranges=((2, 5), (4, 8)),
        seed=11)


def test_engine_respects_arrival_order_under_contention(tiny_model):
    from repro.serve.engine import replay_trace
    t = _replay_trace()
    eng = _engine(tiny_model, slots=2, policy="fifo")
    reqs = replay_trace(eng, t)
    admits = np.array([r.admit_step for r in reqs])
    assert (admits >= 0).all()
    assert (admits >= np.asarray(t.arrival_steps)).all()
    # fifo: admission order == arrival order (rid-tiebroken)
    order = np.argsort(admits, kind="stable")
    np.testing.assert_array_equal(order, np.arange(len(reqs)))


@pytest.mark.parametrize("policy", POOL_POLICIES)
def test_engine_replay_matches_trace_schedule_bitwise(tiny_model, policy):
    from repro.serve.engine import replay_trace
    t = _replay_trace()
    eng = _engine(tiny_model, slots=3, policy=policy)
    reqs = replay_trace(eng, t)
    s = trace_schedule(t, 3, policy)
    np.testing.assert_array_equal([r.admit_step for r in reqs],
                                  s.admit_step)
    np.testing.assert_array_equal([r.finish_step for r in reqs],
                                  s.finish_step)
    assert sum(r.n_preemptions for r in reqs) == s.n_preemptions
    for r in reqs:
        assert len(r.output) == r.max_new_tokens


def test_engine_preempted_request_decodes_same_tokens(tiny_model):
    from repro.serve.engine import replay_trace
    t = _replay_trace()
    s = trace_schedule(t, 3, "preempt")
    assert s.n_preemptions >= 1, "trace must exercise preemption"
    eng = _engine(tiny_model, slots=3, policy="preempt")
    rng = np.random.default_rng(4)
    reqs = replay_trace(eng, t, rng=rng)
    victims = [r for r in reqs if r.n_preemptions > 0]
    assert victims
    # greedy decode is deterministic: an evicted-and-resumed request must
    # produce the same tokens it would have produced uncontended
    from repro.serve.engine import Request
    for v in victims:
        solo = _engine(tiny_model, slots=1).run(
            [Request(0, v.prompt, v.max_new_tokens)])[0]
        assert solo == v.output
