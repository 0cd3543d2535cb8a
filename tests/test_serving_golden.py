"""The request-mix scenario as the degenerate trace, and the disaggregated
stage scorer shared by the three split models, against the objective
metrics captured before either fold (`captured_at`, analytical fidelity):
every value equal bit for bit. Also which workloads each serving entry
takes: the mix scenario a table of layer kinds, the trace scenario only
one uniform layer."""
import json
import pathlib

import pytest

from repro.core.design_space import WSCDesign
from repro.core.evaluator import clear_eval_cache
from repro.core.serving import ServingSLO
from repro.core.workload import GPT_BENCHMARKS, RequestMix
from repro.explore.objectives import HeteroServingObjective, ServingObjective

GOLDEN = json.loads((pathlib.Path(__file__).parent / "data"
                     / "serving_fold_pr17_golden.json").read_text())
MIXES = {m["name"]: m for m in GOLDEN["mixes"]}
CASES = {(c["mix"], c["scenario"]): c["metrics"] for c in GOLDEN["cases"]}


def _designs():
    return [WSCDesign(**{k: tuple(v) if isinstance(v, list) else v
                         for k, v in d.items()})
            for d in GOLDEN["designs"]]


def _objective(mix_name: str, scenario: str):
    m = MIXES[mix_name]
    mix = RequestMix(tuple(m["prompt_lens"]), tuple(m["out_lens"]))
    slo = ServingSLO(ttft_s=m["ttft_s"], tpot_s=m["tpot_s"])
    wl = GPT_BENCHMARKS[0]
    assert wl.name == GOLDEN["workload"]
    if scenario == "serving":
        return ServingObjective(wl, mix, slo, slots=m["slots"])
    return HeteroServingObjective(wl, mix, slo, slots=m["slots"],
                                  granularity=scenario.split(":")[1])


@pytest.mark.parametrize("mix_name,scenario", sorted(CASES),
                         ids=[f"{m}-{s}" for m, s in sorted(CASES)])
def test_serving_metrics_equal_golden(mix_name, scenario):
    clear_eval_cache()
    got = _objective(mix_name, scenario).metrics(_designs())
    want = CASES[(mix_name, scenario)]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert sorted(g) == sorted(w)
        for k in w:
            assert g[k] == w[k], (k, g[k], w[k])


def test_golden_covers_mixes_and_granularities():
    scenarios = {s for _, s in CASES}
    assert len(MIXES) >= 6
    assert scenarios == {"serving", "hetero:core", "hetero:reticle",
                         "hetero:wafer"}
    assert set(CASES) == {(m, s) for m in MIXES for s in scenarios}


def test_mix_scenario_takes_kind_tables_trace_scenario_refuses():
    from repro.core.traces import RequestTrace, evaluate_trace_serving_batch
    from repro.core.validator import validate
    from repro.core.workload import DEEPSEEK_V3
    d = validate(WSCDesign()).design
    mix = RequestMix.uniform(4, 512, 8)
    slo = ServingSLO(60.0, 1.0)
    (m,) = ServingObjective(DEEPSEEK_V3, mix, slo, slots=2).metrics([d])
    assert m["feasible"] and m["throughput"] > 0
    with pytest.raises(ValueError, match="layer kinds"):
        evaluate_trace_serving_batch([d], DEEPSEEK_V3,
                                     RequestTrace.from_mix(mix), slots=2)
