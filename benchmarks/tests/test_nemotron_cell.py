"""The Nemotron-3-Super configuration, its cell and its per-layer metrics
as shipped: found by name, in agreement with BENCHMARK.json and with the
catalog's published numbers, the traffic's proportions, and the counts
of `ssm_roofline.py` and `latent_moe_roofline.py` against cases computed
by hand."""

import importlib.util
import os

import pytest

from harness import latent_moe_roofline, spec, ssm_roofline, traffic as tfc

CELL = "nemotron3s.agent-closed"
CONFIG = "nemotron3-super-int8-share4"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog's numbers that the cut leaves as published
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True}
REDUCED = {"num_hidden_layers": 22,
           "hybrid_override_pattern": "EMEMEMEMEM*EMEMEMEMEM*",
           "n_routed_experts": 128, "vocab_size": 32768,
           "num_nextn_predict_layers": 0, "eos_token_id": 32768}


def load_reader():
    path = os.path.join(spec.BENCH_DIR, "layer_metrics", "ssm.py")
    s = importlib.util.spec_from_file_location("layer_metric_ssm", path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def test_shipped_configuration_keeps_the_published_widths():
    cell = spec.Cell(CELL)
    cfg = cell.model_config
    for key, value in {**PUBLISHED, **REDUCED}.items():
        assert cfg[key] == value, key
    # the published counts stated beside the held ones, and the deployment
    assert cfg["n_routed_experts_total"] == 512
    assert cfg["first_routed_expert"] == 0
    pub = cfg["published"]
    assert pub["num_hidden_layers"] == 88 == 4 * cfg["num_hidden_layers"]
    assert pub["vocab_size"] == 131072 == 4 * cfg["vocab_size"]
    assert pub["hybrid_override_pattern"][26:48] == \
        cfg["hybrid_override_pattern"]
    assert (pub["hybrid_override_pattern"].count("M"),
            pub["hybrid_override_pattern"].count("E"),
            pub["hybrid_override_pattern"].count("*")) == (40, 40, 8)
    assert "16 chips" in pub["deployment"]
    assert set(cell.cell["reduced"]) == set(REDUCED)
    assert set(cell.cell["reduced_why"]) == set(cell.cell["reduced"])
    assert len(cell.cell["assumed"]) == 5 == len(cfg["assumed"])
    assert "16 chips" in cell.cell["deployment"]
    assert "4 chips" in cell.cell["deployment"]
    assert len(cell.cell["source"]) <= 200
    args = cell.cell["server_args"]
    assert args["require-model-type"] == "nemotron_h"
    assert (args["max-slots"], args["max-seq-len"], args["kv-pages"],
            args["kv-page-size"], args["prefill-chunk"]) == (
        32, 4608, 1152, 128, 512)
    assert args["kv-pages"] * args["kv-page-size"] == 32 * 4608
    assert cell.cell["expect_impl"] == {"mixed": "paged-ssm-pallas",
                                        "decode": "paged-ssm-pallas"}
    assert "SEQUENCE MIXING" in cell.cell["why"]
    assert cell.traffic_name == "agent-closed" and cell.chips == 1


def test_benchmark_json_entry_matches_the_cell_file():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    cell = spec.Cell(CELL)
    assert entry["reduced"] == cell.cell["reduced"]
    assert entry["source"] == cell.cell["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}/config.json"
    (work,) = [w for w in doc["workloads"] if w["name"] == CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "agent-closed", 1)
    # PR 33 was refused once for a `why` of 215 characters
    for text in (entry["why"], entry["source"], work["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1


def test_traffic_is_the_stated_cycle():
    cell = spec.Cell(CELL)
    t = cell.traffic
    assert (t["loop"], t["clients"], t["ramp_s"]) == ("closed", 32, 16)
    classes = tfc.class_by_name(t)
    assert (classes["t1k"]["lo"], classes["t1k"]["hi"]) == (897, 1024)
    assert (classes["t4k"]["lo"], classes["t4k"]["hi"]) == (3969, 4096)
    counts, outs = {}, {}
    for item in t["multiset"]:
        counts[item["class"]] = counts.get(item["class"], 0) + item["n"]
        outs[item["class"]] = outs.get(item["class"], 0) + \
            item["n"] * item["out"]
    total = sum(counts.values())
    assert counts == {"t1k": 16, "t4k": 8} and total == 24
    for name, c in classes.items():
        assert c["weight"] == pytest.approx(counts[name] / total)
    assert sum(outs.values()) / total == 384
    assert sorted((i["class"], i["out"], i["n"]) for i in t["multiset"]) == [
        ("t1k", 256, 8), ("t1k", 512, 8), ("t4k", 256, 4), ("t4k", 512, 4)]
    assert t["probe"] == {"class": "t1k", "out": 256}
    assert (t["warmup_wave"], t["warmup_wave_out"]) == (32, 8)
    # the mix builds: every context fits the server's window
    mix = tfc.Mix(t, 2147484999, cell.model_config["vocab_size"])
    assert max(c["hi"] for c in classes.values()) + 512 <= \
        cell.cell["server_args"]["max-seq-len"]
    assert len(mix.warmup_items()) == 2


def test_cell_reports_what_the_issue_lists():
    cell = spec.Cell(CELL)
    assert set(cell.names("end_to_end")) == {"tpot_p50_ms", "out_tok_s",
                                             "setup_s"}
    layers = set(cell.names("per_layer"))
    new = {m["name"] for m in load_reader().METRICS}
    assert new <= layers and len(new) == 8
    # the plain readings of a cell judged by tokens, joined by list
    assert {"mixed_step_ms.tok", "mixed_step_device_ms.tok",
            "ttft_p50_ms.tok"} <= layers
    for name in ("rows_busy_pct", "pages_in_use_pct", "mixed_step_share_pct",
                 "decode_steps_chained_pct", "mixed_steps_chained_pct",
                 "dev_share_moe_route_pct", "moe_rows_padded_pct",
                 "moe_expert_load_max_over_mean", "moe_held_rows_share_pct",
                 "host_build_p50_ms", "host_emit_p50_ms", "loop_uncovered_pct",
                 "decode_step_device_ms", "dev_share_attn_pct",
                 "dev_share_ffn_pct", "dev_share_kv_pct",
                 "idle_attributed_pct", "peak_hbm_gib",
                 "compiles_in_window", "decode_step_ms"):
        assert name in layers, name
    for name in ("mixed_step_ms", "mixed_step_device_ms",
                 "mixed_attn_roofline", "moe_experts_roofline",
                 "queue_wait_p50_ms", "http_ttft_overhead_p50_ms",
                 "decode_step_roofline", "decode_attn_roofline",
                 "tpot_p50_ms.obs", "dsa_selected_share_pct",
                 "mla_attn_roofline", "ttft_p50_ms.longshort-s1k"):
        assert name not in layers, name


def test_reader_agrees_with_benchmark_json():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in load_reader().METRICS}
    entries = {m["name"]: m for m in doc["per_layer"]
               if m["name"] in declared}
    assert set(entries) == set(declared)
    names = [m["name"] for m in doc["per_layer"]]
    at = names.index(load_reader().METRICS[0]["name"])
    assert names[at:at + len(declared)] == list(declared)     # in order
    for name, m in entries.items():
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        for key in ("unit", "layer", "moves", "source"):
            assert declared[name][key] == m[key], (name, key)
        if name.endswith("_roofline"):
            assert m["unit"] == "%" and m["layer"] == "kernels"


def test_reference_copy_is_the_programs():
    here = os.path.join(spec.BENCH_DIR, "configs", CONFIG, "reference.py")
    there = os.path.join(spec.ROOT, "cake_tpu", "models", "reference",
                         "nemotron_h.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        assert a.read() == b.read()


# -- the rooflines' counts, by hand --------------------------------------------


def cfg():
    return spec.Cell(CELL).model_config


def test_a_rows_state_is_four_mebibytes_a_block():
    d = ssm_roofline.ssm_dims(cfg())
    assert (d["H"], d["P"], d["G"], d["N"], d["Q"], d["L_M"]) == (
        128, 64, 8, 128, 128, 10)
    assert (d["d_inner"], d["conv_dim"]) == (8192, 10240)
    assert ssm_roofline.state_bytes(cfg()) == 4 * 2**20


def test_one_decode_step_streams_every_rows_state_twice():
    # 32 rows x 10 blocks, each 4 MiB read and 4 MiB written: 2.68 GB,
    # 3.28 ms at 819 GB/s
    need = ssm_roofline.step_need_bytes(cfg(), 32 * 10)
    assert need == 320 * 2 * 4 * 2**20 == 2684354560
    assert ssm_roofline.step_least_s(cfg(), 320, PEAK) == \
        pytest.approx(2684354560 / 819e9)


def test_a_window_of_the_scan_is_bound_by_its_bytes():
    # per token and block: C.B 2*8*128*128, masked scores x inputs
    # 2*128*128*64, into and out of the chunk's state 4*128*64*128
    nbytes, ops = ssm_roofline.scan_need(cfg(), 1.0)
    assert ops == 262144 + 2097152 + 4194304 == 6553600
    assert nbytes == (10240 + 8192) * 2
    # a 512-token window over 10 blocks: 33.6 GFLOP are 0.17 ms, the
    # tokens' 189 MB in and out 0.23 ms: the greater
    t = ssm_roofline.scan_least_s(cfg(), 512 * 10, PEAK)
    assert t == pytest.approx(5120 * nbytes / 819e9)
    assert t > 5120 * 6553600 / 197e12


def test_an_experts_two_matrices():
    assert latent_moe_roofline.expert_params(cfg()) == 2 * 1024 * 2688 \
        == 5505024
    # a decode step's block: 32 tokens x 22 pairs, a quarter of them held
    # (176 rows) over, say, 96 of the 128 held experts: bound by bytes
    nbytes, ops = latent_moe_roofline.experts_need(cfg(), 176, 96)
    assert nbytes == 96 * 5505024 + 176 * 2 * 1024 * 2 == 529203200
    assert ops == 176 * 2 * 5505024
    t = latent_moe_roofline.experts_least_s(cfg(), 176, 96, PEAK)
    assert t == pytest.approx(529203200 / 819e9)
    # a mixed dispatch's block: 544 tokens, 2,992 held rows over all 128:
    # still the weights' bytes (0.87 ms) over the operations (0.17 ms)
    nbytes, ops = latent_moe_roofline.experts_need(cfg(), 2992, 128)
    t = latent_moe_roofline.experts_least_s(cfg(), 2992, 128, PEAK)
    assert t == pytest.approx(nbytes / 819e9) and t > ops / 197e12


# -- the reader, on a made-up run ----------------------------------------------


class FakeCell:
    cell = {"shape": {"kv_bytes": 2, "weight_bytes": 1, "mixed_width": 512}}


def fake_run(**over):
    # a decode record: 32 rows through 10 Mamba blocks, 10 E blocks; a
    # mixed record of two dispatches (2 x 544 positions)
    decode = {"kind": "decode", "compiled": False, "wall_s": 0.020,
              "ssm_state_rows": 320.0, "ssm_tokens_stepped": 320.0,
              "ssm_tokens_scanned": 0.0, "moe_rows": 1760.0,
              "moe_experts_touched": 960.0}
    mixed = {"kind": "mixed", "compiled": False, "wall_s": 0.110,
             "tokens_computed": 1088, "ssm_state_rows": 2 * 320.0,
             "ssm_tokens_stepped": 2 * 310.0,
             "ssm_tokens_scanned": 2 * 5120.0, "moe_rows": 2 * 29920.0,
             "moe_experts_touched": 2 * 1280.0}
    run = {"model_config": cfg(), "cell": FakeCell(),
           "device": {"kind": "TPU v5 lite"}, "health": {"decode_slots": 32},
           "steps": [decode] * 3 + [mixed], "records": [], "t0": 0.0,
           "t1": 48.0,
           "metrics_0": {"cake_ssm_tokens_scanned_total": 1000.0,
                         "cake_ssm_tokens_stepped_total": 1000.0,
                         "cake_ssm_state_rows_total": 100.0},
           "metrics_1": {"cake_ssm_tokens_scanned_total": 4000.0,
                         "cake_ssm_tokens_stepped_total": 2000.0,
                         "cake_ssm_state_rows_total": 1380.0},
           "trace": None}
    run.update(over)
    return run


def test_counters_over_the_window():
    got = load_reader().read(fake_run())
    assert got["ssm_scanned_share_pct"] == pytest.approx(75.0)
    # 1280 rows x blocks over 10 blocks and 4 records
    assert got["ssm_state_rows_per_step"] == pytest.approx(32.0)
    assert got["latent_moe_experts_roofline"] is None
    assert "dev_share_ssm_pct" not in got
    assert not [k for k in got if k.startswith(("mixed_step", "ttft_"))]


def test_a_program_without_the_counters_yields_nothing():
    run = fake_run(metrics_0={}, metrics_1={}, steps=[],
                   model_config={"num_hidden_layers": 2})
    assert {k: v for k, v in load_reader().read(run).items()
            if v is not None} == {}


def test_per_execution_counts_a_mixed_records_dispatches():
    reader = load_reader()
    run = fake_run()
    assert reader.dispatches(run, run["steps"][-1]) == 2.0
    assert reader.per_execution(run, "mixed", "ssm_tokens_scanned") == 5120.0
    assert reader.per_execution(run, "decode", "ssm_tokens_stepped") == 320.0
    assert reader.per_execution(run, "mixed", "absent") is None


def test_experts_roofline_from_kernel_events():
    reader = load_reader()
    c = cfg()
    one_decode = 10 * latent_moe_roofline.experts_least_s(c, 176, 96, PEAK)
    one_mixed = 10 * latent_moe_roofline.experts_least_s(c, 2992, 128, PEAK)

    def event(rows, cols, dur):
        return {"device": 0, "dur_s": dur,
                "name": f"%cake_moe_gmm.3 = bf16[{rows},{cols}]{{1,0}} "
                        "custom-call(...), "
                        "custom_call_target=\"tpu_custom_call\""}
    # the capture: one mixed dispatch and two decode steps, 20 events
    # each, every event taking as long as makes the total twice the need
    need = one_mixed + 2 * one_decode
    events = ([event(12032, 2688, 2 * need / 60)] * 10
              + [event(12032, 1024, 2 * need / 60)] * 10
              + [event(704, 2688, 2 * need / 60)] * 20
              + [event(704, 1024, 2 * need / 60)] * 20
              + [{"device": 0, "dur_s": 1.0, "name": "%cake_decode_attn.1 = "
                  "bf16[32,1,32,128]{3,2,1,0} custom-call(...)"}])
    run = fake_run(trace={"kernels": events})
    assert reader.experts_roofline(run) == pytest.approx(50.0)
