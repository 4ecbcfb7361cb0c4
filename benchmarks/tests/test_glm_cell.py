"""The GLM-5.2 configuration, its cell and its per-layer metrics as
shipped: found by name, in agreement with BENCHMARK.json and with the
catalog's published numbers, the traffic's proportions, and the
attention roofline's counts against cases computed by hand."""

import importlib.util
import os

import pytest

from harness import mla_roofline, spec, traffic as tfc

CELL = "glm52.longdoc-closed"
CONFIG = "glm-5.2-int8-share16"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog's numbers for GLM-5.2 that the cut leaves as published
PUBLISHED = {
    "hidden_size": 6144, "intermediate_size": 12288, "head_dim": 192,
    "num_attention_heads": 64, "num_key_value_heads": 64,
    "q_lora_rank": 2048, "kv_lora_rank": 512, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
    "index_topk_freq": 4, "index_skip_topk_offset": 3,
    "moe_intermediate_size": 2048, "n_shared_experts": 1,
    "num_experts_per_tok": 8, "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "topk_method": "noaux_tc",
    "rms_norm_eps": 1e-05, "max_position_embeddings": 1048576,
    "rope_interleave": True, "indexer_rope_interleave": True,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "model_type": "glm_moe_dsa", "moe_layer_freq": 1, "ep_size": 1,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "attention_bias": False}
REDUCED = {"num_hidden_layers": 9, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 19360,
           "num_nextn_predict_layers": 0, "eos_token_id": 19360}


def load_reader():
    path = os.path.join(spec.BENCH_DIR, "layer_metrics", "dsa.py")
    s = importlib.util.spec_from_file_location("layer_metric_dsa", path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def test_shipped_configuration_keeps_the_published_widths():
    cell = spec.Cell(CELL)
    cfg = cell.model_config
    for key, value in {**PUBLISHED, **REDUCED}.items():
        assert cfg[key] == value, key
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 8
    assert cfg["indexer_types"] == ["full"] + (["shared"] * 3 + ["full"]) * 2
    # the published counts stated beside the held ones, and the deployment
    assert cfg["n_routed_experts_total"] == 256
    assert cfg["published"]["num_hidden_layers"] == 78
    assert cfg["published"]["vocab_size"] == 154880 == 8 * cfg["vocab_size"]
    assert set(cell.cell["reduced"]) == set(REDUCED) | {
        "mlp_layer_types", "indexer_types"}
    assert set(cell.cell["reduced_why"]) == set(cell.cell["reduced"])
    assert len(cell.cell["assumed"]) == 4 and "16 chips" in \
        cell.cell["deployment"]
    assert len(cell.cell["source"]) <= 200
    args = cell.cell["server_args"]
    assert args["require-model-type"] == "glm_moe_dsa"
    assert (args["max-slots"], args["max-seq-len"], args["kv-pages"],
            args["prefill-chunk"]) == (8, 12800, 800, 512)
    assert cell.cell["expect_impl"] == {"mixed": "paged-dsa-pallas",
                                        "decode": "paged-dsa-pallas"}
    assert cell.traffic_name == "longdoc-closed" and cell.chips == 1


def test_benchmark_json_entry_matches_the_cell_file():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    cell = spec.Cell(CELL)
    assert entry["reduced"] == cell.cell["reduced"]
    assert entry["source"] == cell.cell["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}/config.json"
    (work,) = [w for w in doc["workloads"] if w["name"] == CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "longdoc-closed", 1)


def test_traffic_is_the_stated_cycle():
    cell = spec.Cell(CELL)
    t = cell.traffic
    assert (t["loop"], t["clients"], t["ramp_s"]) == ("closed", 8, 16)
    classes = tfc.class_by_name(t)
    assert (classes["d6k"]["lo"], classes["d6k"]["hi"]) == (6017, 6144)
    assert (classes["d12k"]["lo"], classes["d12k"]["hi"]) == (12161, 12288)
    counts = {}
    for item in t["multiset"]:
        counts[item["class"]] = counts.get(item["class"], 0) + item["n"]
    total = sum(counts.values())
    assert counts == {"d6k": 8, "d12k": 4} and total == 12
    for name, c in classes.items():
        assert c["weight"] == pytest.approx(counts[name] / total)
    assert sorted((i["class"], i["out"], i["n"]) for i in t["multiset"]) == [
        ("d12k", 128, 2), ("d12k", 256, 2), ("d6k", 128, 4), ("d6k", 256, 4)]
    assert t["probe"] == {"class": "d6k", "out": 128}
    # the mix builds: every context fits the server's window
    mix = tfc.Mix(t, 5, cell.model_config["vocab_size"])
    assert max(c["hi"] for c in classes.values()) + 256 <= \
        cell.cell["server_args"]["max-seq-len"]
    assert len(mix.warmup_items()) == 2


def test_cell_reports_what_the_issue_lists():
    cell = spec.Cell(CELL)
    assert set(cell.names("end_to_end")) == {"tpot_p50_ms", "out_tok_s",
                                             "setup_s"}
    layers = set(cell.names("per_layer"))
    new = {m["name"] for m in load_reader().METRICS}
    assert new <= layers and len(new) == 6
    # the plain readings of a cell judged by tokens, joined by list
    assert {"mixed_step_ms.tok", "mixed_step_device_ms.tok",
            "ttft_p50_ms.tok"} <= layers
    for name in ("rows_busy_pct", "pages_in_use_pct", "mixed_step_share_pct",
                 "decode_steps_chained_pct", "dev_share_moe_route_pct",
                 "moe_rows_padded_pct", "moe_expert_load_max_over_mean",
                 "host_emit_p50_ms", "decode_step_device_ms", "peak_hbm_gib"):
        assert name in layers, name
    for name in ("mixed_step_ms", "mixed_step_device_ms",
                 "mixed_attn_roofline", "moe_experts_roofline",
                 "queue_wait_p50_ms", "http_ttft_overhead_p50_ms",
                 "decode_step_roofline"):
        assert name not in layers, name


def test_reader_agrees_with_benchmark_json():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in load_reader().METRICS}
    entries = {m["name"]: m for m in doc["per_layer"]
               if m["name"] in declared}
    assert set(entries) == set(declared)
    for name, m in entries.items():
        # later cells join a metric by list; this one stays on it
        assert CELL in m["workloads"] and m["moves"] == "out_tok_s"
        for key in ("unit", "layer", "moves", "source"):
            assert declared[name][key] == m[key], (name, key)


def test_reference_copy_is_the_programs():
    here = os.path.join(spec.BENCH_DIR, "configs", CONFIG, "reference.py")
    there = os.path.join(spec.ROOT, "cake_tpu", "models", "reference",
                         "glm_moe_dsa.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        assert a.read() == b.read()


# -- the roofline's counts, by hand --------------------------------------------


def cfg():
    return spec.Cell(CELL).model_config


def test_one_decode_token_is_bound_by_its_rows():
    # 2048 selected keys, each its own row: 64 heads x (576 + 512) x 2
    # operations a key; 576 numbers x 2 bytes a row
    nbytes, ops = mla_roofline.attn_need(cfg(), selected=2048, distinct=2048)
    assert ops == 2048 * 64 * 1088 * 2 == 285212672
    assert nbytes == 2048 * 576 * 2 == 2359296
    t = mla_roofline.attn_least_s(cfg(), 2048, 2048, PEAK)
    assert t == pytest.approx(2359296 / 819e9)         # 2.88 us, by bytes
    assert t > ops / 197e12


def test_a_window_is_bound_by_its_operations():
    # 512 queries x 2048 keys over 8,000 distinct rows of one document
    nbytes, ops = mla_roofline.attn_need(cfg(), 512 * 2048, 8000)
    assert ops == 512 * 285212672 and nbytes == 8000 * 1152
    t = mla_roofline.attn_least_s(cfg(), 512 * 2048, 8000, PEAK)
    assert t == pytest.approx(ops / 197e12)             # 0.74 ms, by ops
    assert t > nbytes / 819e9


def test_the_stored_rows_padding_is_not_needed():
    # the program keeps 640 numbers a row; the latent is 576
    d = mla_roofline.mla_dims(cfg())
    assert (d["row"], d["value"], d["H"], d["L"], d["L_full"]) == (
        576, 512, 64, 9, 3)


# -- the reader, on a made-up run ----------------------------------------------


class FakeCell:
    cell = {"shape": {"kv_bytes": 2}}


def fake_run(**over):
    # a decode record: 8 rows x 2048 keys x 9 layers, one dispatch; a
    # mixed record of two dispatches
    decode = {"kind": "decode", "compiled": False, "wall_s": 0.010,
              "dsa_index_layers": 3.0,
              "dsa_keys_selected": 9 * 8 * 2048.0,
              "dsa_rows_distinct": 9 * 8 * 2048.0}
    mixed = {"kind": "mixed", "compiled": False, "wall_s": 0.180,
             "dsa_index_layers": 6.0,
             "dsa_keys_selected": 2 * 9 * 520 * 2048.0,
             "dsa_rows_distinct": 2 * 9 * 9000.0}
    run = {"model_config": cfg(), "cell": FakeCell(),
           "device": {"kind": "TPU v5 lite"}, "health": {"decode_slots": 8},
           "steps": [decode] * 3 + [mixed], "records": [], "t0": 0.0,
           "t1": 48.0,
           "metrics_0": {"cake_dsa_keys_visible_total": 1000.0,
                         "cake_dsa_keys_selected_total": 1000.0,
                         "cake_dsa_index_layers_total": 30.0,
                         "cake_dsa_index_reused_total": 60.0,
                         "cake_moe_rows_total": 10.0,
                         "cake_moe_rows_routed_total": 100.0},
           "metrics_1": {"cake_dsa_keys_visible_total": 9000.0,
                         "cake_dsa_keys_selected_total": 3000.0,
                         "cake_dsa_index_layers_total": 330.0,
                         "cake_dsa_index_reused_total": 660.0,
                         "cake_moe_rows_total": 60.0,
                         "cake_moe_rows_routed_total": 900.0},
           "trace": None}
    run.update(over)
    return run


def test_counters_over_the_window():
    got = load_reader().read(fake_run())
    assert got["dsa_selected_share_pct"] == pytest.approx(25.0)
    assert got["dsa_index_reuse_pct"] == pytest.approx(200.0 / 3)
    assert got["moe_held_rows_share_pct"] == pytest.approx(6.25)
    assert got["mla_attn_roofline"] is None
    assert "dev_share_indexer_pct" not in got
    assert not [k for k in got if k.startswith(("mixed_step", "ttft_"))]


def test_a_program_without_the_counters_yields_nothing():
    run = fake_run(metrics_0={}, metrics_1={}, steps=[])
    assert {k: v for k, v in load_reader().read(run).items()
            if v is not None} == {}


def test_roofline_share_from_kernel_events():
    reader = load_reader()
    c = cfg()
    one_decode = 9 * mla_roofline.attn_least_s(c, 8 * 2048, 8 * 2048, PEAK)
    one_mixed = 9 * mla_roofline.attn_least_s(c, 520 * 2048, 9000, PEAK)
    assert reader.need_per_dispatch(fake_run(), "decode") == \
        pytest.approx(one_decode)
    assert reader.need_per_dispatch(fake_run(), "mixed") == \
        pytest.approx(one_mixed)

    def event(name, shape, dur):
        return {"device": 0, "dur_s": dur,
                "name": f"%{name} = bf16[{shape}]{{2,1,0}} custom-call(...), "
                        "custom_call_target=\"tpu_custom_call\""}
    # the capture: one mixed dispatch (9 window events, 9 rows events)
    # and two decode steps (18 rows events), each kernel event taking
    # as long as to make the total four times the need: 25 %
    need = one_mixed + 2 * one_decode
    events = ([event("cake_mla_window_attn.3", "32768,512", 0)] * 9
              + [event("cake_mla_attn.7", "8,64,512", 4 * need / 27)] * 27
              + [event("cake_moe_gmm.1", "128,2048", 1.0)])
    run = fake_run(trace={"kernels": events})
    assert reader.attn_roofline(run) == pytest.approx(25.0)
