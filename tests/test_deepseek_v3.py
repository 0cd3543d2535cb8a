"""DeepSeek-V3 as an explorer workload: the table of layer kinds
(`LLMWorkload.layer_kinds`, `kind_ops`), checked against the plain
`jax.numpy` reference of its blocks (`repro.models.deepseek_v3_ref`) at
small widths and against the published counts at full width; expert
parallelism in the strategy grid; the program's counters; and the loud
refusals of the readers that model one uniform layer."""
import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.workload import BYTES, DEEPSEEK_V3, GPT_BENCHMARKS

ROOT = Path(__file__).resolve().parents[1]

#: DeepSeek-V3's kinds at small widths (D 64, 4 heads, ranks 32/16,
#: nope 16, rope 8, v 16, 8 experts top-2, 1 shared)
SMALL = dataclasses.replace(
    DEEPSEEK_V3, name="ds-small", n_layers=2, d_model=64, n_heads=4,
    n_kv=4, d_ff=96, vocab=128, seq=16, batch=2, n_dense_layers=1,
    d_ff_expert=24, moe_experts=8, moe_topk=2, moe_shared=1, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    gpu_budget=16)


def _subjaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def dot_flops(jaxpr) -> int:
    """2 x MACs of every dot_general in a jaxpr, sub-jaxprs included."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (_, rc), (_, rb) = eqn.params["dimension_numbers"]
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            free = math.prod(d for i, d in enumerate(rhs)
                             if i not in rc and i not in rb)
            total += 2 * math.prod(lhs) * free
        for sub in _subjaxprs(eqn.params):
            total += dot_flops(sub)
    return total


def _kind(wl, name):
    return {k.name: k for k in wl.layer_kinds()}[name]


def _ops_flops(wl, name):
    return int(sum(o.flops() for o in wl.layer_ops(1, kind=_kind(wl, name))))


@pytest.fixture(scope="module")
def params():
    from repro.models import deepseek_v3_ref as R
    return R.init_params(jax.random.PRNGKey(0), SMALL)


# ---------------------------------------------------------------------------
# the kinds against the plain reference, small widths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["dense", "moe", "mtp"])
def test_train_kind_flops_match_reference_jaxpr(params, name):
    from repro.models import deepseek_v3_ref as R
    B, S, D = SMALL.batch, SMALL.seq, SMALL.d_model
    x = jnp.zeros((B, S, D))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    with jax.default_matmul_precision("highest"):
        if name == "mtp":
            jx = jax.make_jaxpr(lambda p, h, e: R.mtp_block(
                p, SMALL, h, e, pos))(params["mtp"], x, x)
        else:
            jx = jax.make_jaxpr(lambda p, h: R.block(
                p, SMALL, h, pos)[0])(params[name], x)
    assert dot_flops(jx.jaxpr) == _ops_flops(SMALL, name)


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_decode_kind_flops_match_absorbed_reference(params, name):
    """Decode is the absorbed form: one token a sequence against a cache
    of `seq` positions."""
    from repro.models import deepseek_v3_ref as R
    wl = dataclasses.replace(SMALL, phase="decode")
    B, T = wl.batch, wl.seq
    assert [k.name for k in wl.layer_kinds()] == ["dense", "moe"]
    cache = (jnp.zeros((B, T, wl.kv_lora_rank)),
             jnp.zeros((B, T, wl.qk_rope_head_dim)))
    x = jnp.zeros((B, 1, wl.d_model))
    pos = jnp.full((B,), 3)
    with jax.default_matmul_precision("highest"):
        jx = jax.make_jaxpr(lambda p, h, c: R.block_decode(
            p, wl, h, c, pos)[0])(params[name], x, cache)
    assert dot_flops(jx.jaxpr) == _ops_flops(wl, name)


def test_cache_per_token_matches_kv_bytes(params):
    from repro.models import deepseek_v3_ref as R
    tokens = jnp.zeros((SMALL.batch, SMALL.seq), jnp.int32)
    _, caches = R.forward(params, SMALL, tokens)
    for c_kv, k_rope in caches:
        per_token = (c_kv.shape[-1] + k_rope.shape[-1]) * BYTES
        assert per_token == SMALL.kv_bytes_per_layer() / (SMALL.batch
                                                           * SMALL.seq)


@pytest.mark.parametrize("keep_rope", [True, False],
                         ids=["latent_cache", "control_without_rope"])
def test_prefill_then_decode_matches_full_forward(params, keep_rope):
    """Prefill 6 tokens, then decode the rest one at a time through the
    576-wide (here 24-wide) latent cache: the full forward's logits at
    rtol 1e-5 of their scale (float32 at highest precision over a handful
    of matmuls; a logit near 0 has no relative error to speak of). The
    control leaves the rope key out of the cache and must fail."""
    from repro.models import deepseek_v3_ref as R
    B, S, P = SMALL.batch, SMALL.seq, 6
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                SMALL.vocab)
    with jax.default_matmul_precision("highest"):
        full, _ = R.forward(params, SMALL, tokens)
        _, caches = R.prefill(params, SMALL, tokens[:, :P], S)
        if not keep_rope:
            caches = tuple((c, jnp.zeros_like(r)) for c, r in caches)
        got = []
        for t in range(P, S):
            lg, caches = R.decode(params, SMALL, tokens[:, t], caches,
                                  jnp.full((B,), t), keep_rope)
            got.append(lg)
    got = np.stack(got, axis=1)
    want = np.asarray(full[:, P:])
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    assert (err <= 1e-5) == keep_rope, err


# ---------------------------------------------------------------------------
# published widths
# ---------------------------------------------------------------------------


def test_published_parameter_and_active_counts():
    no_mtp = dataclasses.replace(DEEPSEEK_V3, mtp_layers=0)
    assert abs(no_mtp.params_bytes() / BYTES / 671.0e9 - 1) <= 0.005
    assert abs(DEEPSEEK_V3.params_bytes() / BYTES / 682.6e9 - 1) <= 0.001
    assert abs(no_mtp.active_params() / 37e9 - 1) <= 0.03
    assert abs(DEEPSEEK_V3.active_params() / 37e9 - 1) <= 0.03
    assert DEEPSEEK_V3.kv_bytes_per_layer() == (
        DEEPSEEK_V3.batch * DEEPSEEK_V3.seq * 576 * BYTES)


@pytest.mark.parametrize("drop", ["q_lora_rank", "kv_lora_rank"])
def test_kind_table_needs_mla_ranks(drop):
    with pytest.raises(ValueError, match="MLA"):
        dataclasses.replace(DEEPSEEK_V3, **{drop: 0})


def test_kind_table_and_unequal_stages():
    kinds = DEEPSEEK_V3.layer_kinds()
    assert [(k.name, k.count) for k in kinds] == [("dense", 3), ("moe", 58),
                                                   ("mtp", 1)]
    assert [k.name for k in dataclasses.replace(
        DEEPSEEK_V3, phase="prefill").layer_kinds()] == ["dense", "moe"]
    for pp in (1, 2, 16, 32):
        st = DEEPSEEK_V3.stage_kind_counts(pp)
        assert st.shape == (pp, 3)
        assert list(st.sum(axis=0)) == [3, 58, 1]      # no layer dropped
        assert st[:, :2].sum(axis=1).max() - st[:, :2].sum(axis=1).min() \
            <= 1
        assert st[-1, 2] == 1
    # the uniform rows keep their one kind and their GEMMs
    g = GPT_BENCHMARKS[0]
    assert g.uniform and len(g.layer_kinds()) == 1
    assert [o.name for o in g.layer_ops(2, 1024)] == [
        "qkv", "scores", "attnv", "attn_out", "mlp_in", "mlp_out"]


def test_ep_divides_only_routed_expert_bytes():
    from repro.core.compiler import _grid_need, strategy_memory_need
    wl = DEEPSEEK_V3
    p, e = wl.params_bytes(), wl.expert_params_bytes()
    # routed experts only: not the shared expert, the router or attention
    fe, D = wl.d_ff_expert, wl.d_model
    assert e == (58 + 1) * 256 * 3 * D * fe * BYTES
    for ep in (2, 8, 64):
        assert _grid_need(wl, 4, 2, ep) == 2 * ((p - e) + e / ep) * 6.0 / 4
        assert float(strategy_memory_need(wl, 1, 4, 2, 1, ep=ep)) < float(
            strategy_memory_need(wl, 1, 4, 2, 1, ep=1))
    assert _grid_need(wl, 4, 2, 1) == 2 * p * 6.0 / 4


# ---------------------------------------------------------------------------
# the strategy grid
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["GPT-1.7B", "GPT-175B"])
def test_dense_grid_equals_frozen_reference(name):
    import importlib
    RW = importlib.import_module("bench.reference.workload")
    from bench.reference.compiler import _strategy_grid as ref_grid

    from repro.core.compiler import _strategy_grid
    wl = {w.name: w for w in GPT_BENCHMARKS}[name]
    want = ref_grid({w.name: w for w in RW.GPT_BENCHMARKS}[name])
    got = _strategy_grid(wl)
    assert set(got) == set(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        assert np.array_equal(got[k], want[k]), k


def test_ep_rows_reach_the_strategy_cap():
    """Expert-parallel rows follow their ep = 1 row, within ep <= dp
    and ep <= moe_experts; at the campaign's cap of 24 the first feasible
    rows of every design that has any ep > 1 row hold one (a design on
    which nothing fits gets the (1, 1, 1, 1, 1) fall-back)."""
    from repro.core.compiler import _strategy_grid, feasible_strategy_arrays
    from repro.core.design_space import DesignBatch, decode_batch
    from repro.core.evaluator import _wafers_for_budget_batch
    g = _strategy_grid(DEEPSEEK_V3)
    assert (g["ep"] <= np.minimum(256, g["dp"])).all()
    assert set(np.unique(g["ep"])) == {2 ** i for i in range(9)}
    ds = decode_batch(np.random.default_rng(0).random((32, 13)))
    geom = DesignBatch.from_designs(ds)
    nw = _wafers_for_budget_batch(geom, DEEPSEEK_V3)
    with_ep = any_ep = 0
    for i in range(len(ds)):
        budget = float(geom.buffer_kb[i] * 1024.0 * geom.total_cores[i]
                       * nw[i] + geom.dram_gb_per_reticle[i] * 1e9
                       * geom.n_reticles[i] * nw[i])
        cores = int(geom.total_cores[i] * nw[i])
        rows = feasible_strategy_arrays(DEEPSEEK_V3, cores, budget, 24)
        every = feasible_strategy_arrays(DEEPSEEK_V3, cores, budget, 10 ** 9)
        assert rows.shape[1] == 5
        with_ep += bool((rows[:, 4] > 1).any())
        any_ep += bool((every[:, 4] > 1).any())
    assert with_ep == any_ep >= len(ds) // 2


@pytest.mark.parametrize("tp", [1, 8, 32])
def test_ep_group_is_drawn_from_dp_replicas(tp):
    """A chunk is one (pp, dp) replica holding all of its TP ranks, so TP
    does not widen the expert-parallel group: at dp 1 every row, in the
    grid and in the reference grid, keeps all routed experts (ep 1), and
    its memory column is the whole replica's."""
    from bench.reference import deepseek_v3 as ref

    from repro.core.compiler import _grid_need, _strategy_grid
    g = _strategy_grid(DEEPSEEK_V3)
    at = (g["dp"] == 1) & (g["tp"] == tp)
    assert at.any() and (g["ep"][at] == 1).all()
    assert (g["need"][at] >= DEEPSEEK_V3.params_bytes() * 6.0
            / g["pp"][at]).all()
    at2 = (g["dp"] == 2) & (g["tp"] == tp)
    assert set(g["ep"][at2]) == {1, 2}
    assert _grid_need(DEEPSEEK_V3, 1, 1, 1) == (
        DEEPSEEK_V3.params_bytes() * 6.0)
    config = json.loads((ROOT / "bench/configs/deepseek-v3.json")
                        .read_text())
    rows = ref.strategy_grid(ref.workload(config))
    assert all(r[4] <= min(256, r[2]) for r in rows)
    assert {r[4] for r in rows if r[2] == 1 and r[0] == tp} == {1}


def test_frozen_reference_module_matches_program():
    """`bench/reference/deepseek_v3.py` scores random designs as the
    program does, bit for bit (the cell's `analytical_mismatch`)."""
    from bench import check
    from bench.reference import deepseek_v3 as ref
    from bench.reference import fold

    from repro.core.design_space import decode_batch
    from repro.core.evaluator import clear_eval_cache, evaluate_design_batch
    from repro.explore.objectives import EvaluatorObjective
    config = json.loads((ROOT / "bench/configs/deepseek-v3.json").read_text())
    spec = json.loads((ROOT / "bench/traffic/train-analytical.json")
                      .read_text())["spec"]
    ds = decode_batch(np.random.default_rng(3).random((12, 13)))
    clear_eval_cache()
    rs = evaluate_design_batch(ds, DEEPSEEK_V3, max_strategies=24)
    got = fold(EvaluatorObjective.metrics_from_results(rs),
               spec["objectives"])
    want = ref.train_objectives([check.design_dict(d) for d in ds], config,
                                spec)
    assert check._mismatch(got, want) == 0
    with pytest.raises(ValueError, match="trace_serving"):
        ref.trace_objectives([], config, spec)


# ---------------------------------------------------------------------------
# counters and the per-layer metric
# ---------------------------------------------------------------------------


def _campaign(workload: str, traffic: str):
    from repro.core.evaluator import clear_eval_cache
    from repro.explore import Campaign, CampaignSpec
    tr = json.loads((ROOT / "bench/traffic" / f"{traffic}.json").read_text())
    spec = CampaignSpec.from_dict(dict(tr["spec"], workload=workload,
                                       seed=tr["campaign_seeds"][0]))
    clear_eval_cache()
    camp = Campaign(spec)
    camp.run()
    return camp


def _read_metric(camps):
    import importlib.util
    path = ROOT / "bench/metrics/evaluate_us.analytical.layer_row.py"
    spec = importlib.util.spec_from_file_location("layer_row_metric", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    class Run:
        campaigns = camps
    return mod.read(Run())


def test_counters_and_metric_in_a_deepseek_campaign():
    camp = _campaign("DeepSeek-V3", "train-analytical")
    counters = camp.result().telemetry["counters"]
    # design rows x 24 strategy columns x 3 kinds: a multiple of 72
    assert counters["evaluate.layer_rows"] > 0
    assert counters["evaluate.layer_rows"] % 72 == 0
    assert counters["evaluate.best_ep"] > 0
    v = _read_metric([camp])
    assert v is not None and v > 0


def test_metric_is_none_in_the_trace_cell():
    camp = _campaign("GPT-175B", "serve-trace-spike")
    assert _read_metric([camp]) is None


# ---------------------------------------------------------------------------
# readers of one uniform layer refuse a table of kinds
# ---------------------------------------------------------------------------


def _one_design():
    from repro.core.design_space import WSCDesign
    from repro.core.validator import validate
    return validate(WSCDesign()).design


def _refusal_cases():
    from repro.core import heterogeneity, traces
    from repro.core.compiler import compile_chunk
    from repro.core.evaluator import evaluate_design_batch
    from repro.core.serving import ServingSLO
    from repro.core.workload import RequestMix
    from repro.dist.oracle import model_config_for_workload
    from repro.explore.objectives import TraceServingObjective
    d = _one_design()
    tr = traces.synth_trace("poisson", 4, seed=0)
    mix = RequestMix.uniform(4, 256, 8)
    fake_gnn = {"layers": []}
    return {
        "gnn_features": lambda: evaluate_design_batch(
            [d], DEEPSEEK_V3, fidelity="gnn", gnn_params=fake_gnn),
        "sim_lanes": lambda: evaluate_design_batch([d], DEEPSEEK_V3,
                                                   fidelity="sim"),
        "trace_serving": lambda: traces.evaluate_trace_serving_batch(
            [d], DEEPSEEK_V3, tr),
        # the trace scenario sizes its stage workloads from one uniform
        # layer (the shared `serving_workloads` itself also serves the
        # request-mix scenario, which takes kind tables)
        "trace_workloads": lambda: TraceServingObjective(
            DEEPSEEK_V3, tr, slots=4).metrics([d]),
        "hetero_serving": lambda: heterogeneity
        .evaluate_hetero_serving_batch([d], [d], DEEPSEEK_V3, "reticle",
                                       0.5, mix, ServingSLO(5.0, 0.05)),
        "hetero_trace": lambda: heterogeneity
        .evaluate_hetero_trace_serving_batch([d], [d], DEEPSEEK_V3,
                                             "reticle", 0.5, tr),
        "hetero": lambda: heterogeneity.evaluate_hetero(
            d, d, DEEPSEEK_V3, "reticle", 0.5),
        "compile_chunk": lambda: compile_chunk(d, DEEPSEEK_V3, 1, 64, 64),
        "layer_ops_batch": lambda: DEEPSEEK_V3.layer_ops_batch(
            np.ones(2, np.int64), np.full(2, 64)),
        "runtime_oracle": lambda: model_config_for_workload(DEEPSEEK_V3),
    }


READERS = ("gnn_features", "sim_lanes", "trace_serving", "trace_workloads",
           "hetero_serving", "hetero_trace", "hetero", "compile_chunk",
           "layer_ops_batch", "runtime_oracle")


@pytest.mark.parametrize("reader", READERS)
def test_uniform_layer_readers_refuse_kind_tables(reader):
    from repro.core.evaluator import clear_eval_cache
    clear_eval_cache()
    with pytest.raises(ValueError, match="layer kinds"):
        _refusal_cases()[reader]()
