"""Request-level serving on one arrival batch (repro.core.serving: the
request mix scored as the degenerate trace of repro.core.traces) + bugfix
regressions: per-request decode temperature, prefill KV length under
sharding, wafer-granularity area accounting, DRAM-energy consistency."""
import dataclasses

import numpy as np
import pytest

from repro.core.design_space import DesignBatch, WSCDesign
from repro.core.chunk_eval import evaluate_step_batch
from repro.core.heterogeneity import (
    evaluate_hetero_serving_batch,
    wafer_split,
)
from repro.core.serving import (
    ServingSLO,
    continuous_batch_schedule,
    disaggregated_metrics,
)
from repro.core.traces import (
    RequestTrace,
    TenantClass,
    evaluate_pool_serving_batch,
    evaluate_trace_serving,
    trace_schedule,
    trace_serving_metrics,
)
from repro.core.validator import validate
from repro.core.workload import (
    GPT_BENCHMARKS,
    RequestMix,
    inference_workload,
)

STACKED = WSCDesign(use_stacked_dram=True, dram_bw_tbps_per_100mm2=2.0)


# ---------------------------------------------------------------------------
# discrete continuous-batching schedule
# ---------------------------------------------------------------------------


def test_schedule_uniform_two_waves():
    mix = RequestMix.uniform(8, prompt_len=128, out_len=5)
    s = continuous_batch_schedule(mix, slots=4)
    # two waves of 4; each request decodes out_len-1 = 4 steps
    assert s.n_decode_steps == 8
    assert list(s.admit_step) == [0, 0, 0, 0, 4, 4, 4, 4]
    assert list(s.finish_step) == [3, 3, 3, 3, 7, 7, 7, 7]


def test_schedule_engine_semantics_min_one_decode_step():
    # max_new_tokens=1 still costs one decode step (ServeEngine's done
    # check runs after the post-admission decode)
    mix = RequestMix.uniform(1, prompt_len=16, out_len=1)
    s = continuous_batch_schedule(mix, slots=4)
    assert s.n_decode_steps == 1 and s.decode_tokens[0] == 1


def test_schedule_bounds_random_mixes():
    rng = np.random.default_rng(0)
    for _ in range(10):
        mix = RequestMix.sampled(rng, int(rng.integers(1, 20)),
                                 (1, 64), (1, 17))
        slots = int(rng.integers(1, 6))
        s = continuous_batch_schedule(mix, slots)
        # list-scheduling bounds: makespan within [max load, load/slots + max]
        total = int(s.decode_tokens.sum())
        assert s.n_decode_steps >= max(int(s.decode_tokens.max()),
                                       -(-total // slots))
        assert s.n_decode_steps <= total
        assert (s.finish_step >= s.admit_step).all()


def test_request_mix_validation():
    with pytest.raises(ValueError):
        RequestMix((4, 5), (1,))
    with pytest.raises(ValueError):
        RequestMix((), ())
    with pytest.raises(ValueError):
        RequestMix((4,), (0,))


# ---------------------------------------------------------------------------
# wall-clock metrics (synthetic step times): the mix's degenerate trace
# ---------------------------------------------------------------------------


def _mix_trace(mix, slo):
    return RequestTrace.from_mix(mix, TenantClass.from_slo(slo))


def _mix_metrics(mix, slots, slo, t_prefill_ref, prompt_ref, t_decode):
    """A mix's wall-clock metrics: its degenerate trace under fifo."""
    t = _mix_trace(mix, slo)
    return trace_serving_metrics(trace_schedule(t, slots, "fifo"), t,
                                 t_prefill_ref, prompt_ref, t_decode)


def _mix():
    return RequestMix.uniform(6, prompt_len=100, out_len=5)


def test_slo_non_binding_goodput_equals_throughput():
    mix = _mix()
    m = _mix_metrics(mix, 3, ServingSLO(1e9, 1e9),
                        np.array([0.1]), 100, np.array([0.01]))
    assert m["slo_attainment"][0] == 1.0
    assert m["goodput"][0] == pytest.approx(m["throughput"][0])


def test_slo_binding_zero_goodput():
    mix = _mix()
    m = _mix_metrics(mix, 3, ServingSLO(1e-6, 1e-6),
                        np.array([0.1]), 100, np.array([0.01]))
    assert m["slo_attainment"][0] == 0.0
    assert m["goodput"][0] == 0.0 and m["throughput"][0] > 0


def test_ttft_waves_and_prefill_stall():
    mix = _mix()
    t_p, t_d = 0.5, 0.01
    m = _mix_metrics(mix, 3, ServingSLO(1e9, 1e9),
                        np.array([t_p]), 100, np.array([t_d]))
    ttft, tpot = m["ttft"][0], m["tpot"][0]
    # wave 2 waits for wave 1's decode + all prior prefills
    assert ttft[3] > ttft[2] > ttft[0]
    assert ttft[0] == pytest.approx(t_p)
    # a wave's first request observes decode stalled by its wave peers'
    # prefills (admitted at the same step, serially, before the decode)
    assert (tpot >= t_d - 1e-12).all()
    assert tpot[0] > t_d
    # last wave decodes without further admissions: pure step time
    assert tpot[-1] == pytest.approx(t_d)


def test_prefill_time_scales_with_prompt_length():
    mix = RequestMix((100, 200), (4, 4))
    m = _mix_metrics(mix, 2, ServingSLO(1e9, 1e9),
                        np.array([1.0]), 100, np.array([0.0]))
    # both admitted at step 0: TTFT = cumulative prefill, second is 1+2
    assert m["ttft"][0][0] == pytest.approx(1.0)
    assert m["ttft"][0][1] == pytest.approx(3.0)


def test_candidate_axis_broadcast():
    mix = _mix()
    m = _mix_metrics(mix, 3, ServingSLO(1e9, 1e9),
                        np.array([0.1, 0.2]), 100, np.array([0.01, 0.02]))
    assert m["ttft"].shape == (2, mix.n_requests)
    assert m["goodput"].shape == (2,)
    # slower candidate is slower everywhere
    assert (m["ttft"][1] > m["ttft"][0]).all()
    assert m["throughput"][1] < m["throughput"][0]


# ---------------------------------------------------------------------------
# end-to-end serving evaluation (through the fidelity registry)
# ---------------------------------------------------------------------------


def test_evaluate_serving_batch_gpt175b():
    wl = GPT_BENCHMARKS[7]
    d = validate(STACKED).design
    mix = RequestMix.uniform(8, prompt_len=2048, out_len=32)
    slo = ServingSLO(ttft_s=60.0, tpot_s=1.0)
    trace = _mix_trace(mix, slo)
    r = evaluate_pool_serving_batch([d], wl, trace, slots=4,
                                    max_strategies=8)[0]
    assert r.feasible
    assert r.goodput_tok_s <= r.throughput_tok_s + 1e-9
    assert 0.0 < r.ttft_s <= r.ttft_max_s
    assert 0.0 < r.tpot_s <= r.tpot_max_s
    assert np.isfinite(r.power_w) and r.power_w > 0
    assert r.n_decode_steps == continuous_batch_schedule(mix, 4).n_decode_steps
    # the trace scenario's scalar entry agrees
    r2 = evaluate_trace_serving(d, wl, trace, slots=4, max_strategies=8)
    assert r2.goodput_tok_s == pytest.approx(r.goodput_tok_s)


def test_evaluate_serving_unknown_fidelity_raises():
    wl = GPT_BENCHMARKS[0]
    d = validate(WSCDesign()).design
    mix = RequestMix.uniform(2, 128, 4)
    with pytest.raises(ValueError, match="registered"):
        evaluate_pool_serving_batch([d], wl,
                                    _mix_trace(mix, ServingSLO(1, 1)),
                                    fidelity="bogus")


def test_serving_objectives_batch_aware():
    from repro.explore.objectives import ServingObjective
    wl = GPT_BENCHMARKS[0]
    mix = RequestMix.uniform(4, 512, 8)
    f = ServingObjective(wl, mix, ServingSLO(60.0, 1.0), slots=2)
    assert f.batched and f.fidelity == "analytical"
    ds = [validate(WSCDesign()).design, validate(STACKED).design]
    ys = f(ds)
    assert len(ys) == 2
    assert all(len(y) == 2 and y[1] > 0 for y in ys)
    y0 = f(ds[0])
    assert y0[0] == pytest.approx(ys[0][0])


# ---------------------------------------------------------------------------
# disaggregated (hetero) coupled request model
# ---------------------------------------------------------------------------


def test_disaggregated_no_prefill_stall_on_decode():
    mix = RequestMix.uniform(4, 100, 5)
    m = disaggregated_metrics(mix, ServingSLO(1e9, 1e9), slots=2,
                              t_prefill=np.full(4, 0.5),
                              kv_s=np.zeros(4), t_decode=0.01)
    # second wave exists, but decode never stalls for prefill: the last
    # request's TPOT is bounded by step time plus its slot wait amortized
    assert m["n_decode_steps"] >= 8
    assert m["throughput_tok_s"] > 0


def test_disaggregated_kv_transfer_delays_admission():
    mix = RequestMix.uniform(2, 100, 3)
    slow = disaggregated_metrics(mix, ServingSLO(1e9, 1e9), slots=2,
                                 t_prefill=np.full(2, 0.1),
                                 kv_s=np.full(2, 5.0), t_decode=0.01)
    fast = disaggregated_metrics(mix, ServingSLO(1e9, 1e9), slots=2,
                                 t_prefill=np.full(2, 0.1),
                                 kv_s=np.zeros(2), t_decode=0.01)
    assert slow["total_time_s"] > fast["total_time_s"] + 4.0
    # TTFT comes from the prefill stage and is unaffected by KV shipping
    assert slow["ttft_s"] == pytest.approx(fast["ttft_s"])


def test_evaluate_hetero_serving_runs_all_granularities():
    wl = inference_workload(GPT_BENCHMARKS[1], "decode", batch=32, seq=2048)
    d = validate(STACKED).design
    mix = RequestMix.uniform(6, 1024, 16)
    slo = ServingSLO(30.0, 1.0)
    for gran in ("core", "reticle", "wafer"):
        (h,) = evaluate_hetero_serving_batch([d], [d], wl, gran, 0.5, mix,
                                             slo, slots=4, n_wafers=4)
        assert h.feasible and h.throughput_tok_s > 0
        assert h.goodput_tok_s <= h.throughput_tok_s + 1e-9
        assert h.ttft_s > 0 and h.tpot_s > 0


# ---------------------------------------------------------------------------
# regression: wafer-granularity area accounting (ISSUE 4 satellite)
# ---------------------------------------------------------------------------


def test_wafer_split_respects_area_budget():
    for n in (2, 3, 8, 16):
        for ratio in (0.0, 0.01, 0.3, 0.5, 0.7, 0.99, 1.0):
            nw_p, nw_d = wafer_split(n, ratio)
            assert nw_p + nw_d == n          # never n + 1 extra silicon
            assert nw_p >= 1 and nw_d >= 1
    with pytest.raises(ValueError):
        wafer_split(1, 0.5)


# ---------------------------------------------------------------------------
# regression: prefill KV length under dp/microbatch sharding
# ---------------------------------------------------------------------------


def test_prefill_kv_len_independent_of_token_sharding():
    wl = inference_workload(GPT_BENCHMARKS[0], "prefill", batch=32, seq=2048)
    full = wl.layer_ops(tp=1)
    split = wl.layer_ops(tp=1, mb_tokens=wl.tokens_per_step() // 8)
    # scores: (M, hd) x (hd, kv_len); attnv: (M, kv_len) x (kv_len, hd)
    for ops in (full, split):
        assert ops[1].name == "scores" and ops[1].N == wl.seq
        assert ops[2].name == "attnv" and ops[2].K == wl.seq
    # per-token attention FLOPs must not shrink with the split
    assert split[1].flops() == pytest.approx(full[1].flops() / 8)


def test_train_kv_len_is_full_seq():
    wl = GPT_BENCHMARKS[0]                   # train phase
    ops = wl.layer_ops(tp=1, mb_tokens=wl.tokens_per_step() // 16)
    assert ops[1].N == wl.seq and ops[2].K == wl.seq


@pytest.mark.parametrize("phase", ["train", "prefill", "decode"])
def test_layer_ops_scalar_batched_parity(phase):
    base = GPT_BENCHMARKS[0]
    wl = base if phase == "train" else inference_workload(
        base, phase, batch=32, seq=2048)
    tps = np.array([1, 4, 16, 64])
    mbs = np.array([wl.tokens_per_step(), wl.tokens_per_step() // 4,
                    wl.tokens_per_step() // 16, 128])
    batched = wl.layer_ops_batch(tps, mbs)
    for c, (tp, mb) in enumerate(zip(tps, mbs)):
        ops = wl.layer_ops(tp=int(tp), mb_tokens=int(mb))
        for i, op in enumerate(ops):
            assert batched["M"][i, c] == op.M, (phase, op.name)
            assert batched["K"][i, c] == op.K, (phase, op.name)
            assert batched["N"][i, c] == op.N, (phase, op.name)


# ---------------------------------------------------------------------------
# regression: DRAM-energy capacity term (legacy keyword)
# ---------------------------------------------------------------------------


def _step_batch(wl, nw, **kw):
    d = validate(WSCDesign()).design
    geom = DesignBatch.from_designs([d])
    one = np.array([1])
    return evaluate_step_batch(
        geom, wl, one, one, one, one,
        np.array([1e6]), np.array([1e12]), np.array([1e9]),
        np.array([nw]), **kw)


def test_dram_energy_legacy_matches_default_when_consistent():
    # train, one wafer, no KV: the capacity terms coincide, so both modes
    # must be bit-identical
    wl = GPT_BENCHMARKS[7]
    a = _step_batch(wl, 1)
    b = _step_batch(wl, 1, legacy_dram_energy=True)
    assert a["energy_j"][0] == b["energy_j"][0]


def test_dram_energy_nw_factor_fixed():
    # multi-wafer: the legacy capacity term sized the SRAM pool per wafer
    # (no nw) while the latency term used nw wafers — the default now uses
    # the same per-system pool for both, so it charges at most the legacy
    # energy, and strictly less when the pools straddle the weights
    wl = GPT_BENCHMARKS[7]
    a = _step_batch(wl, 8)
    b = _step_batch(wl, 8, legacy_dram_energy=True)
    assert a["energy_j"][0] < b["energy_j"][0]
    # and the latency-side DRAM term is identical in both modes
    assert a["dram_s"][0] == b["dram_s"][0]


def test_decode_kv_streaming_in_dram_traffic():
    # decode streams the KV cache per token: DRAM time must exceed the
    # pure weight-spill time of the same design under the same strategy
    wl_d = inference_workload(GPT_BENCHMARKS[7], "decode", batch=32,
                              seq=2048)
    wl_t = GPT_BENCHMARKS[7]
    a = _step_batch(wl_d, 1)
    b = _step_batch(wl_t, 1)
    assert a["dram_s"][0] > b["dram_s"][0] / 3.0   # bwd_mult aside, KV adds
    # prefill now writes its KV cache: nonzero DRAM traffic even when
    # weights alone would spill the same amount
    wl_p = inference_workload(GPT_BENCHMARKS[7], "prefill", batch=32,
                              seq=2048)
    c = _step_batch(wl_p, 1)
    assert c["dram_s"][0] > 0


# ---------------------------------------------------------------------------
# cross-validation against the real ServeEngine (tiny config) + the
# per-request temperature regression (ISSUE 4 satellite)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    jax = pytest.importorskip("jax")
    from repro.configs import reduced_config
    from repro.models import model as M
    cfg = reduced_config("smollm-135m")
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    return cfg, params


def _drain_counting_steps(eng):
    steps = 0
    while eng.queue or any(a is not None for a in eng.active):
        if eng.step():
            steps += 1
    return steps


def test_engine_step_count_matches_analytical_schedule(tiny_model):
    from repro.models.runtime import CPU_TEST as RT
    from repro.serve.engine import Request, ServeEngine
    cfg, params = tiny_model
    prompts = [np.arange(4 + i, dtype=np.int32) % cfg.vocab
               for i in range(5)]
    outs = [6, 3, 9, 5, 7]
    eng = ServeEngine(cfg, RT, params, slots=2, max_len=64)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        eng.submit(Request(i, p, o))
    engine_steps = _drain_counting_steps(eng)

    mix = RequestMix(tuple(len(p) for p in prompts), tuple(outs))
    analytical = continuous_batch_schedule(mix, slots=2).n_decode_steps
    # acceptance bound: within 10% of the real engine (currently exact)
    assert abs(engine_steps - analytical) <= max(1, 0.1 * engine_steps)


def test_engine_decode_honors_per_request_temperature(tiny_model):
    from repro.models.runtime import CPU_TEST as RT
    from repro.serve.engine import Request, ServeEngine
    cfg, params = tiny_model
    p0 = np.arange(4, dtype=np.int32) % cfg.vocab
    p1 = (np.arange(6, dtype=np.int32) * 3) % cfg.vocab
    greedy0 = ServeEngine(cfg, RT, params, slots=2, max_len=64).run(
        [Request(0, p0, 10)])[0]
    greedy1 = ServeEngine(cfg, RT, params, slots=2, max_len=64).run(
        [Request(0, p1, 10)])[0]
    both = ServeEngine(cfg, RT, params, slots=2, max_len=64).run(
        [Request(0, p0, 10, temperature=0.0),
         Request(1, p1, 10, temperature=8.0)])
    # the greedy request is untouched by its hot neighbor
    assert both[0] == greedy0
    # the hot request actually samples on DECODE steps too (it used to
    # sample only its first token, then decode greedily forever)
    assert both[1][1:] != greedy1[1:]


def test_sample_logits_per_row_temperatures():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.serve.serve_step import sample_logits
    logits = jnp.asarray([[0.0, 5.0, 1.0], [0.0, 5.0, 1.0]])
    temps = jnp.asarray([0.0, 3.0])
    outs = {tuple(int(x) for x in
                  np.asarray(sample_logits(logits, jax.random.PRNGKey(s),
                                           temps)))
            for s in range(25)}
    # row 0 (T=0) is always the argmax; row 1 (T>0) varies across seeds
    assert all(o[0] == 1 for o in outs)
    assert len({o[1] for o in outs}) > 1
    assert all(0 <= o[1] <= 2 for o in outs)
