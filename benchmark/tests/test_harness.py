"""Fast checks of the harness's own arithmetic: CPU, no server, no jax.

Not collected by tier-1 (``pytest tests/``); run by hand:

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import reduce_trace  # noqa: E402
import run  # noqa: E402

METRICS_START = """# HELP x
kllms_continuous_step_seconds_sum 1.0
kllms_continuous_step_seconds_count 100
kllms_continuous_step_seconds_bucket{le="0.1"} 100
kllms_grammar_events_total{event="grammar.masked_steps"} 10
kllms_continuous_steps 100
"""
METRICS_END = """kllms_continuous_step_seconds_sum 4.0
kllms_continuous_step_seconds_count 250
kllms_grammar_events_total{event="grammar.masked_steps"} 50
kllms_continuous_steps 250
kllms_hbm_page_pool_peak_in_use 30
"""
SRC = {
    "metrics_start": run.parse_metrics(METRICS_START),
    "metrics_end": run.parse_metrics(METRICS_END),
    "metrics_drained": {"kllms_continuous_steps": 260.0},
    "capture": {"start": {"kllms_continuous_steps": 120.0}, "end": {"kllms_continuous_steps": 220.0},
                "seconds": 4.0},
    "health_start": {"device": {"compile": {"programs": 20}}, "hbm": {"param_bytes": 1000}},
    "health_end": {"device": {"compile": {"programs": 23}}, "hbm": {"param_bytes": 1000}},
    "requests": [{"phases": {"consolidate": 0.2}}, {"phases": {"consolidate": 0.4}}, {"phases": {}}],
    "trace": {"busy_s": 1.5, "window_s": 2.0},
    "config": {"vocab_size": 512, "hidden_size": 64},
    "peaks": {"hbm_GB_per_s": 819},
    "client": {"sent": 12, "answered": 10, "latency_sum_s": 25.0},
}


def traffic_of(cell):
    return run.load_cell(cell)[3]


def cells():
    return [w["name"] for w in run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("cell", cells())
def test_generator_is_a_pure_function_of_seed_and_traffic_file(cell):
    traffic = traffic_of(cell)
    big = 3_000_000_011  # more than 32 signed bits hold
    a = [run.make_request(traffic, "m", big, "window", k) for k in range(70)]
    assert a == [run.make_request(traffic, "m", big, "window", k) for k in range(70)]
    assert a != [run.make_request(traffic, "m", big + 1, "window", k) for k in range(70)]
    assert a[0] != run.make_request(traffic, "m", big, "warmup", 0)
    dist = traffic["doc_tokens"]
    lengths = [len(r["messages"][-1]["content"]) for r in a]
    assert all(dist["min"] <= n <= dist["max"] for n in lengths)
    # every seed offers the same work in another order
    pool = run.length_pool(dist)
    assert len(pool) == dist.get("pool", 64) and sorted(lengths[:len(pool)]) == sorted(pool)
    assert sorted(run.shuffled_lengths(traffic, 1, "window")) == sorted(pool)
    if traffic["shared_prefix_tokens"]:
        assert {len(r["messages"][0]["content"]) for r in a} == {traffic["shared_prefix_tokens"]}
    assert all(0 <= r["seed"] < 2 ** 31 and r["n"] == traffic["n"] for r in a)


def test_percentiles_and_rates_on_a_hand_made_sample():
    answered = [{"latency": s, "ttft": s / 10, "tokens": 100} for s in (1.0, 2.0, 3.0, 4.0, 5.0)]
    assert run.end_to_end("latency_p50_ms", answered, 10.0, 1, 7.0) == 3000.0
    assert run.end_to_end("latency_p95_ms", answered, 10.0, 1, 7.0) == pytest.approx(4800.0)
    assert run.end_to_end("ttft_p95_ms", answered, 10.0, 1, 7.0) == pytest.approx(480.0)
    assert run.end_to_end("tokens_per_s", answered, 10.0, 1, 7.0) == 50.0
    assert run.end_to_end("tokens_per_s", answered, 10.0, 4, 7.0) == 12.5
    assert run.end_to_end("setup_s", answered, 10.0, 1, 7.0) == 7.0
    with pytest.raises(run.BenchFailure):
        run.end_to_end("ttft_p95_ms", [{"latency": 1.0, "ttft": None, "tokens": 1}], 10.0, 1, 7.0)


@pytest.mark.parametrize("spec, want", [
    ({"delta": "kllms_continuous_step_seconds_count"}, 150.0),
    ({"delta": "grammar.masked_steps"}, 40.0),
    ({"delta": "absent"}, None),
    ({"drained_delta": "kllms_continuous_steps"}, 160.0),
    ({"gauge": "kllms_hbm_page_pool_peak_in_use"}, 30.0),
    ({"capture_delta": "kllms_continuous_steps"}, 50.0),  # 100 steps x 2.0 s traced / 4.0 s between snapshots
    ({"health": "hbm.param_bytes"}, 1000),
    ({"health": "device.compile.programs", "of": "delta"}, 3),
    ({"health": "device.nothing"}, None),
    ({"phase": "consolidate"}, pytest.approx(0.6)),
    ({"phase": "consolidate", "of": "count"}, 2),
    ({"phase": "decode"}, None),
    ({"client": "answered"}, 10),
    ({"trace": "busy_s"}, 1.5),
    ({"config": "vocab_size"}, 512),
    ({"peak": "hbm_GB_per_s"}, 819),
    ({"const": 2}, 2),
])
def test_each_term_kind(spec, want):
    assert run.term(spec, SRC) == want


def test_read_expressions():
    mean_ms = {"scale": 1000, "num": [{"delta": "kllms_continuous_step_seconds_sum"}],
               "den": [{"delta": "kllms_continuous_step_seconds_count"}]}
    assert run.evaluate(mean_ms, SRC) == pytest.approx(20.0)
    idle = {"scale": 100, "num": [{"const": 1}], "den": [{"const": 1}],
            "minus": {"num": [{"trace": "busy_s"}], "den": [{"trace": "window_s"}]}}
    assert run.evaluate(idle, SRC) == pytest.approx(25.0)
    # an absent source, on either side, leaves the metric out: never zero
    assert run.evaluate({"num": [{"delta": "absent"}]}, SRC) is None
    assert run.evaluate(dict(idle, minus={"num": [{"trace": "nothing"}]}), SRC) is None
    assert run.evaluate(mean_ms, dict(SRC, metrics_end=SRC["metrics_start"])) is None  # 0 / 0
    # every committed metric file evaluates over the canned sources or is left out
    for name in os.listdir(os.path.join(run.HERE, "layer_metrics")):
        value = run.evaluate(run.load_json(run.HERE, "layer_metrics", name)["read"], SRC)
        assert value is None or isinstance(value, float)


def test_trace_union_gaps_and_reduction():
    assert reduce_trace.union([(0, 10), (5, 20), (30, 40), (32, 35)]) == [[0, 20], [30, 40]]
    assert reduce_trace.gaps([[0, 20], [30, 40], [100, 110]]) == [(60, 40, 100), (10, 20, 30)]
    ms = 1_000_000
    planes = {
        "/device:TPU:0": {
            "XLA Ops": [("%while.1 = (s32[]{:T(128)}, bf16[2]{0}) while(%tuple.1), body=%b", 0, 30 * ms),
                        ("%fusion.1 = bf16[32,64]{1,0:T(8,128)} fusion(%p.1), kind=kLoop", 0, 20 * ms),
                        ("%sort.2 = f32[32,512]{1,0} sort(%p.2)", 22 * ms, 30 * ms),
                        ("%fusion.1 = bf16[32,64]{1,0:T(8,128)} fusion(%p.1), kind=kLoop", 50 * ms, 60 * ms)],
            "XLA Modules": [("jit_step(123)", 0, 30 * ms), ("jit_step(123)", 50 * ms, 60 * ms)],
        },
        "/host:CPU": {"python3": [("$thread.py:1 run", 0, 100 * ms), ("$threading.py:9 wait", 31 * ms, 49 * ms),
                                  ("$loop.py:7 readback", 30 * ms, 49 * ms)]},
    }
    out = reduce_trace.reduce(planes)
    assert out["busy_s"] == pytest.approx(0.040) and out["window_s"] == pytest.approx(0.060)
    assert out["breakdown"]["device_ops"] == [
        ["program jit_step", pytest.approx(0.040)],
        ["%fusion.1 fusion bf16[32,64]", pytest.approx(0.030)],
        ["%sort.2 sort f32[32,512]", pytest.approx(0.008)],
        ["%while.1 while (s32[], bf16[2])", pytest.approx(0.002)],
    ]
    assert out["breakdown"]["idle_gaps"] == [["$loop.py:7 readback", pytest.approx(0.020)]]
    assert reduce_trace.reduce({"/host:CPU": {}})["busy_s"] is None


def test_schema_check_accepts_and_refuses():
    traffic = dict(traffic_of("qwen2-7b.extract"), n=2)
    good = json.dumps({"kind": "quote", "paid": True, "currency": "EUR"})
    choice = {"message": {"content": good}, "sample_logprob": -1.5}
    completion = {"choices": [choice] * 3, "usage": {"completion_tokens": 9},
                  "likelihoods": {"kind": 1.0, "paid": 1.0, "currency": 1.0}}
    run.check_completion(completion, traffic)
    bad = json.dumps({"kind": "memo", "paid": True, "currency": "EUR"})
    for broken in (
        dict(completion, choices=[choice] * 2),
        dict(completion, degraded=["consensus"]),
        dict(completion, likelihoods=None),
        dict(completion, choices=[choice, choice, dict(choice, sample_logprob=float("nan"))]),
        dict(completion, choices=[choice, choice, {"message": {"content": bad}, "sample_logprob": -1.0}]),
    ):
        with pytest.raises(run.BenchFailure):
            run.check_completion(broken, traffic)


def test_manifest_resolves_and_names_are_permitted():
    assert run.check_manifest() == []
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    for cell in cells():
        entry, _, config, traffic, e2e, layers = run.load_cell(cell)
        assert "setup_s" in e2e and len(e2e) >= 2 and layers
        assert config["serve"]["debug_endpoints"] and traffic["loop"] == "closed"
        assert entry["chips"] == config["chips"]
    layers = {m["layer"] for m in manifest["per_layer"]}
    perf = open(os.path.join(run.ROOT, "PERF.md")).read()
    assert all(f"**{layer}**" in perf for layer in layers)
