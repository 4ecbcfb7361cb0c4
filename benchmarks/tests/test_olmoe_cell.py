"""The OLMoE configuration, its cell and its per-layer metrics as
shipped: found by name, in agreement with BENCHMARK.json, and the
expert roofline's arithmetic against cases computed by hand."""

import importlib.util
import os

import pytest

from harness import moe_roofline, spec

CELL = "olmoe7b.chat-closed"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog's numbers for OLMoE-1B-7B-0125-Instruct (model-configs guide)
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def load_reader():
    path = os.path.join(spec.BENCH_DIR, "layer_metrics", "moe.py")
    s = importlib.util.spec_from_file_location("layer_metric_moe", path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def test_shipped_configuration_is_the_published_one():
    cell = spec.Cell(CELL)
    for key, value in PUBLISHED.items():
        assert cell.model_config[key] == value, key
    assert cell.model_config["eos_token_id"] == PUBLISHED["vocab_size"]
    assert cell.cell["reduced"] == ["eos_token_id"]
    assert cell.cell["assumed"] == [] and len(cell.cell["source"]) <= 200
    # the option only this program's CLI knows: an older program dies
    # in argparse instead of serving the directory as a dense Llama
    assert cell.cell["server_args"]["require-model-type"] == "olmoe"
    assert cell.traffic_name == "chat-closed" and cell.chips == 1


def test_cell_reports_what_the_issue_lists():
    cell = spec.Cell(CELL)
    assert set(cell.names("end_to_end")) == {
        "ttft_mean_ms", "itl_p95_ms", "out_tok_s", "setup_s"}
    layers = set(cell.names("per_layer"))
    mistral = set(spec.Cell("mistral7b.chat-closed").names("per_layer"))
    assert "decode_step_roofline" not in layers
    assert layers - mistral == {
        "moe_experts_roofline", "dev_share_moe_route_pct",
        "moe_rows_padded_pct", "moe_expert_load_max_over_mean"}
    assert mistral <= layers


def test_reader_agrees_with_benchmark_json():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in load_reader().METRICS}
    entries = {m["name"]: m for m in doc["per_layer"]
               if m["name"] in declared}
    assert set(entries) == set(declared)
    for name, m in entries.items():
        # the sparse cells after it joined three of the five by list
        assert m["workloads"][0] == CELL
        assert (m["workloads"] == [CELL]) == (m["moves"] == "ttft_mean_ms")
        for key in ("unit", "layer", "moves", "source"):
            assert declared[name][key] == m[key], (name, key)


def test_reference_copy_is_the_programs():
    here = os.path.join(spec.BENCH_DIR, "configs", "olmoe-1b-7b-int8",
                        "reference.py")
    there = os.path.join(spec.ROOT, "cake_tpu", "models", "reference",
                         "olmoe.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        assert a.read() == b.read()


# -- the roofline's counts, by hand --------------------------------------------

ONE_EXPERT = 3 * 2048 * 1024            # parameters: gate, up, down


def test_one_expert_touched_is_bandwidth_bound():
    # 8 rows on one expert: 6 291 456 int8 weight bytes, 8 rows in and
    # out at 2048 x 2 bytes; 8 * 2 * 6 291 456 operations
    nbytes, ops = moe_roofline.experts_need(PUBLISHED, rows=8, touched=1)
    assert nbytes == ONE_EXPERT + 8 * 2 * 2048 * 2 == 6356992
    assert ops == 8 * 2 * ONE_EXPERT == 100663296
    t = moe_roofline.experts_least_s(PUBLISHED, 8, 1, PEAK)
    assert t == pytest.approx(6356992 / 819e9)       # 7.76 us, by bytes


def test_all_experts_touched_by_a_full_mixed_step_is_compute_bound():
    # 2048 tokens x 8: 16384 rows over 64 experts
    nbytes, ops = moe_roofline.experts_need(PUBLISHED, 16384, 64)
    assert nbytes == 64 * ONE_EXPERT + 16384 * 2 * 2048 * 2 == 536870912
    assert ops == 16384 * 2 * ONE_EXPERT == 206158430208
    t = moe_roofline.experts_least_s(PUBLISHED, 16384, 64, PEAK)
    assert t == pytest.approx(206158430208 / 197e12)  # 1.05 ms, by ops
    assert t > nbytes / 819e9


def test_padded_tile_rows_are_not_needed():
    # 130 rows fill two 128-row tiles; the 126 rows of padding the
    # kernel computes are no part of what the algorithm needs
    _, ops = moe_roofline.experts_need(PUBLISHED, 130, 2)
    assert ops == 130 * 2 * ONE_EXPERT
    assert ops < 256 * 2 * ONE_EXPERT


# -- the reader, on a made-up run ----------------------------------------------


class FakeCell:
    cell = {"shape": {"weight_bytes": 1}}


def fake_run(**over):
    steps = [{"kind": "decode", "compiled": False, "moe_rows": 16 * 8 * 16,
              "moe_experts_touched": 56 * 16}] * 3
    run = {"model_config": PUBLISHED, "cell": FakeCell(),
           "device": {"kind": "TPU v5 lite"},
           "health": {"decode_slots": 16}, "steps": steps,
           "metrics_0": {"cake_moe_rows_total": 100.0,
                         "cake_moe_rows_padded_total": 400.0,
                         "cake_moe_expert_load_max": 10.0,
                         "cake_moe_expert_load_mean": 5.0},
           "metrics_1": {"cake_moe_rows_total": 1100.0,
                         "cake_moe_rows_padded_total": 4400.0,
                         "cake_moe_expert_load_max": 40.0,
                         "cake_moe_expert_load_mean": 25.0},
           "trace": None}
    run.update(over)
    return run


def test_counters_over_the_window():
    got = load_reader().read(fake_run())
    assert got["moe_rows_padded_pct"] == pytest.approx(75.0)
    assert got["moe_expert_load_max_over_mean"] == pytest.approx(1.5)
    assert got["moe_experts_roofline"] is None
    assert got["dev_share_moe_route_pct"] is None


def test_a_program_without_the_counters_yields_nothing():
    run = fake_run(metrics_0={}, metrics_1={}, steps=[])
    assert {k: v for k, v in load_reader().read(run).items()
            if v is not None} == {}


def test_roofline_share_from_kernel_events():
    reader = load_reader()
    # one decode step's 48 events (3 projections x 16 layers), each
    # taking twice its least time: 50 %
    per_layer = moe_roofline.experts_least_s(PUBLISHED, 128, 56, PEAK)
    event = {"device": 0, "dur_s": 2 * per_layer,
             "name": "%cake_moe_gmm.3 = bf16[128,1024]{1,0} custom-call("
                     "...), custom_call_target=\"tpu_custom_call\""}
    other = {"device": 0, "dur_s": 1.0,
             "name": "%cake_decode_attn = bf16[16,1,16,128]{3,2,1,0} "
                     "custom-call(), custom_call_target=\"tpu_custom_call\""}
    run = fake_run(trace={"kernels": [event] * 48 + [other]})
    assert reader.experts_roofline(run) == pytest.approx(100.0 / 6)
    # (the need is per LAYER, the events are per projection: 48 events
    # of 2x a layer's least time are 6x one step's need)
    assert reader.result_rows(event["name"]) == 128
