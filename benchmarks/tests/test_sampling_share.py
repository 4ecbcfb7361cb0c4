"""`layer_metrics/sampling.py`: found by name, in agreement with
BENCHMARK.json, listed in every cell, silent without a capture, and its
arithmetic on hand-made planes."""

import importlib.util
import os

import pytest

from harness import spec

NAME = "dev_share_sample_pct"
MS = 1e6     # nanoseconds
STEP = "jit(decode_step_sampled)/"


def op(name, start_ms, dur_ms, tf_op=None):
    return {"name": name, "start_ns": start_ms * MS, "dur_ns": dur_ms * MS,
            "stats": {"tf_op": tf_op} if tf_op else {}}


def load():
    return spec.discover_layer_metrics()[NAME]


def reader():
    path = os.path.join(spec.BENCH_DIR, "layer_metrics", "sampling.py")
    s = importlib.util.spec_from_file_location("layer_metric_sampling", path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def test_found_by_name_and_agrees_with_benchmark_json():
    decl, _read = load()
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in doc["per_layer"] if m["name"] == NAME]
    for key in ("unit", "layer", "moves", "source"):
        assert decl[key] == entry[key], key
    assert entry["unit"] == "%" and entry["better"] == "lower"
    assert entry["layer"] == "step programs"
    assert entry["moves"] == "out_tok_s"
    assert entry["source"] == "device_trace"
    # every cell of the benchmark as this metric found it runs the sampler
    assert entry["workloads"][:7] == [
        "mistral7b.chat-closed", "mistral7b.decode-long",
        "qwen32b.chat-closed-4chip", "olmoe7b.chat-closed",
        "glm52.longdoc-closed", "nemotron3s.agent-closed",
        "zaya1.reason-closed"]
    for cell in entry["workloads"]:
        assert NAME in spec.Cell(cell).names("per_layer")


def test_reads_nothing_without_a_capture(tmp_path):
    _decl, read = load()
    assert read({}) == {}
    assert read({"trace": None}) == {}
    assert read({"trace": {"kernels": []}}) == {}
    assert read({"trace": {"xplane": str(tmp_path / "gone.pb")}}) == {}


def shares(planes):
    return reader().shares(planes)


def test_share_is_self_time_under_the_scope_over_busy_time():
    ops = [
        op("%fusion.1 = bf16[32] fusion(%p)", 0, 6, STEP + "layers/ffn/dot:"),
        # the search's while holds its body: 3 ms long, 1 ms its own
        op("%while.9 = (u32[32,1]) while(%t)", 6, 3,
           STEP + "sample/jit(sample_tokens_ragged)/sample/while:"),
        op("%fusion.7 = f32[32,1] fusion(%k)", 6.5, 2,
           STEP + "sample/jit(sample_tokens_ragged)/sample/while/body/"
           "reduce_sum:"),
        op("%fusion.8 = f32[32] fusion(%k)", 9, 1, STEP + "sample/argmax:"),
        # a scope that only contains the word is not the scope
        op("%fusion.9 = f32[32] fusion(%k)", 12, 2, STEP + "resample/add:"),
        op("%zero = f32[] add()", 20, 0),
    ]
    planes = [
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [op("%x = f32[] add()", 0, 99,
                                              STEP + "sample/add:")]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": []},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "", "events": []}]},
    ]
    # busy: [0, 10) and [12, 14); the gap between is idle
    assert shares(planes) == {NAME: pytest.approx(100 * 4 / 12)}


def test_a_program_without_the_scope_yields_nothing():
    ops = [op("%fusion.1 = bf16[32] fusion(%p)", 0, 6, STEP + "ffn/dot:")]
    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops", "events": ops}]}]
    assert shares(planes) == {}
    assert shares([{"name": "/host:CPU", "lines": []}]) == {}
    assert shares([{"name": "/device:TPU:0", "lines": []}]) == {}
