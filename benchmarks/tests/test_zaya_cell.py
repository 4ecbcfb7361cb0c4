"""The ZAYA1-8B configuration, its cell and its per-layer metrics as
shipped: found by name (in a temporary copy too), in agreement with
BENCHMARK.json and with the catalog's published numbers, the traffic's
proportions, the counts of `zaya_roofline.py` against cases computed by
hand, what the attention roofline makes of this config as it stands,
and the reader on a made-up run."""

import importlib.util
import os
import shutil

import pytest

from harness import roofline, spec, traffic as tfc, zaya_roofline

CELL = "zaya1.reason-closed"
CONFIG = "zaya1-8b-int8"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog's `config` for ZAYA1-8B (model-configs guide), every key
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}
NEW = ["dev_share_cca_mix_pct", "dev_share_router_pct",
       "top1_moe_experts_roofline", "moe_experts_touched_per_layer"]
# the plain readings of a cell judged by tokens, joined by list (until
# PR 55 this reader's `ttft_p50_ms.reason` and `mixed_step_ms.reason`);
# no device reading: a capture can fall inside one decode stretch
JOINED = {"ttft_p50_ms.tok", "mixed_step_ms.tok"}


def load_reader(bench_dir=spec.BENCH_DIR):
    path = os.path.join(bench_dir, "layer_metrics", "zaya.py")
    s = importlib.util.spec_from_file_location("layer_metric_zaya", path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def cfg():
    return spec.Cell(CELL).model_config


def test_shipped_configuration_is_the_published_one_uncut():
    cell = spec.Cell(CELL)
    c = cell.model_config
    for key, value in PUBLISHED.items():
        assert c[key] == value, key
    # the file equals its source but for `reduced`
    assert set(c) - set(PUBLISHED) == {"eos_token_id"}
    assert c["eos_token_id"] == c["vocab_size"]
    assert cell.cell["reduced"] == ["eos_token_id"]
    assert set(cell.cell["reduced_why"]) == {"eos_token_id"}
    assert "NO CUT" in cell.cell["reduced_why"]["eos_token_id"]
    assert len(cell.cell["source"]) <= 200
    assumed = " ".join(cell.cell["assumed"])
    for said in ("grouping of conv 1 by head", "q-k mean", "shifted half",
                 "L2 norm", "router's depth", "gamma per channel",
                 "choice only", "normed stream", "residual scaling",
                 "no mixture-of-depths", "ties to the lower index",
                 "seeded draws"):
        assert said in assumed, said
    args = cell.cell["server_args"]
    assert args["require-model-type"] == "zaya" and args["quant"] == "int8"
    assert (args["max-slots"], args["max-seq-len"], args["kv-pages"],
            args["kv-page-size"], args["prefill-chunk"]) == (
        32, 2048, 512, 128, 128)
    assert args["kv-pages"] * args["kv-page-size"] == 32 * 2048
    assert cell.cell["expect_impl"] == {"mixed": "paged-cca-pallas",
                                        "decode": "paged-cca-pallas"}
    assert cell.cell["shape"] == {"weight_bytes": 1, "kv_bytes": 2,
                                  "mixed_width": 128, "stages": 1, "tp": 1}
    toy = cell.cell["rehearse"]["config"]
    assert (toy["num_hidden_layers"], toy["hidden_size"], toy["head_dim"],
            toy["num_experts"], toy["vocab_size"]) == (4, 64, 16, 4, 512)
    assert cell.traffic_name == "reason-closed" and cell.chips == 1


def test_benchmark_json_entries_match_the_cells_files():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    work = next(w for w in doc["workloads"] if w["name"] == CELL)
    cell = spec.Cell(CELL)
    assert entry["reduced"] == cell.cell["reduced"]
    assert entry["source"] == cell.cell["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}/config.json"
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "reason-closed", 1)
    for text in (entry["why"], entry["source"], work["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    # one cell in four may take four chips, and one always may
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 4)
    assert sum(w["config"] == CONFIG for w in doc["workloads"]) == 1


def test_cell_reports_what_the_issue_lists():
    cell = spec.Cell(CELL)
    assert set(cell.names("end_to_end")) == {"tpot_p50_ms", "out_tok_s",
                                             "setup_s"}
    layers = set(cell.names("per_layer"))
    assert set(NEW) | JOINED <= layers
    for name in ("rows_busy_pct", "pages_in_use_pct", "mixed_step_share_pct",
                 "host_emit_p50_ms", "host_build_p50_ms",
                 "loop_uncovered_pct",
                 "decode_step_device_ms", "dev_share_attn_pct",
                 "dev_share_ffn_pct", "dev_share_kv_pct",
                 "dev_share_unscoped_pct", "idle_attributed_pct",
                 "prefill_rows_per_mixed_step", "decode_steps_chained_pct",
                 "mixed_steps_chained_pct", "chain_breaks_per_s",
                 "chain_breaks_queue_per_s",
                 "chain_breaks_row_finished_per_s", "chain_breaks_cap_per_s",
                 "boundary_gap_p50_ms", "boundary_gap_p99_ms",
                 "boundary_gap_share_pct", "boundary_admit_p50_ms",
                 "chained_steps_late_pct", "host_detok_p50_ms",
                 "peak_hbm_gib",
                 "compiles_in_window", "decode_step_ms"):
        assert name in layers, name
    # `layer_metrics/moe.py` and `attn_roofline.py` read a width under
    # `intermediate_size`, which the published config does not have:
    # their readers raise in a traced run of this cell (PERF.md, 7)
    for name in ("dev_share_moe_route_pct", "moe_rows_padded_pct",
                 "moe_expert_load_max_over_mean", "decode_attn_roofline",
                 "mixed_step_ms", "mixed_step_device_ms",
                 "mixed_attn_roofline", "moe_experts_roofline",
                 "queue_wait_p50_ms", "http_ttft_overhead_p50_ms",
                 "decode_step_roofline", "tpot_p50_ms.obs",
                 "moe_held_rows_share_pct", "dsa_selected_share_pct",
                 "dev_share_ssm_pct", "mixed_step_device_ms.tok",
                 "step_gap_p50_ms", "host_schedule_p50_ms",
                 "host_sample_p50_ms", "loop_covered_pct"):
        assert name not in layers, name


def test_reader_agrees_with_benchmark_json():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in load_reader().METRICS}
    assert list(declared) == NEW
    entries = {m["name"]: m for m in doc["per_layer"]
               if m["name"] in declared}
    assert set(entries) == set(declared)
    # appended in the reader's order, wherever later PRs put theirs
    names = [m["name"] for m in doc["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    for name, m in entries.items():
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        for key in ("unit", "layer", "moves", "source"):
            assert declared[name][key] == m[key], (name, key)
        if name.endswith("_roofline"):
            assert m["unit"] == "%" and m["layer"] == "kernels"
            assert m["better"] == "higher"


def test_reference_copy_is_the_programs():
    here = os.path.join(spec.BENCH_DIR, "configs", CONFIG, "reference.py")
    there = os.path.join(spec.ROOT, "cake_tpu", "models", "reference",
                         "zaya.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        assert a.read() == b.read()


def test_traffic_is_the_stated_cycle():
    cell = spec.Cell(CELL)
    t = cell.traffic
    assert (t["loop"], t["clients"], t["ramp_s"]) == ("closed", 32, 16)
    classes = tfc.class_by_name(t)
    assert (classes["p256"]["lo"], classes["p256"]["hi"]) == (129, 256)
    assert (classes["p1k"]["lo"], classes["p1k"]["hi"]) == (897, 1024)
    counts, outs = {}, {}
    for item in t["multiset"]:
        counts[item["class"]] = counts.get(item["class"], 0) + item["n"]
        outs[item["class"]] = outs.get(item["class"], 0) + \
            item["n"] * item["out"]
    total = sum(counts.values())
    assert counts == {"p256": 16, "p1k": 8} and total == 24
    for name, c in classes.items():
        assert c["weight"] == pytest.approx(counts[name] / total)
    assert sorted((i["class"], i["out"], i["n"]) for i in t["multiset"]) == [
        ("p1k", 512, 3), ("p1k", 768, 3), ("p1k", 1024, 2),
        ("p256", 512, 6), ("p256", 768, 5), ("p256", 1024, 5)]
    # as ISSUE 37 gives it: mean output ~740, mean prompt ~450
    assert "outputs_shortened" not in t
    assert "several hundred to a thousand tokens" in t["who"]
    assert sum(outs.values()) / total == pytest.approx(746.67, abs=0.01)
    items = tfc.expand_multiset(t)
    assert len(items) == 24
    assert 440 <= sum(i["prompt"] for i in items) / 24 <= 460
    assert t["probe"] == {"class": "p256", "out": 512}
    assert (t["warmup_wave"], t["warmup_wave_out"]) == (32, 8)
    assert [w["class"] for w in t["warmup"]] == ["p256", "p1k"]
    # the mix builds under a seed past 2**31, and every context fits
    mix = tfc.Mix(t, 2147484999, cell.model_config["vocab_size"])
    assert max(c["hi"] for c in classes.values()) + 1024 <= \
        cell.cell["server_args"]["max-seq-len"]
    assert len(mix.warmup_items()) == 2


# -- discovery in a temporary copy ---------------------------------------------


def test_the_cell_is_found_by_name_in_a_copy(tmp_path):
    bench = tmp_path / "benchmarks"
    for d in ("configs", "traffic", "layer_metrics", "harness"):
        shutil.copytree(os.path.join(spec.BENCH_DIR, d), bench / d)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    cell = spec.Cell(CELL, str(bench), str(tmp_path / "BENCHMARK.json"))
    assert cell.config_dir == str(bench / "configs" / CONFIG)
    assert cell.traffic["clients"] == 32
    found = spec.discover_layer_metrics(str(bench))
    assert set(NEW) <= set(found)
    got = spec.read_layer_metrics(cell, fake_run(cell=cell), found)
    assert got["moe_experts_touched_per_layer"] == {"value": 7.0,
                                                    "unit": "experts"}
    assert got["ttft_p50_ms.tok"]["value"] == pytest.approx(400.0)
    assert got["mixed_step_ms.tok"]["value"] == pytest.approx(60.0)
    assert "top1_moe_experts_roofline" not in got      # no capture
    # an old cell does not report the new metrics
    old = spec.Cell("olmoe7b.chat-closed", str(bench),
                    str(tmp_path / "BENCHMARK.json"))
    assert not set(NEW) & set(old.names("per_layer"))


# -- the rooflines' counts, by hand --------------------------------------------


def test_an_experts_three_matrices_of_2048_by_2048():
    assert zaya_roofline.as_moe_config(
        {"moe_intermediate_size": 2048})["intermediate_size"] == 2048
    assert zaya_roofline.expert_params(cfg()) == 3 * 2048 * 2048 == 12582912


def test_a_decode_steps_layer_is_bound_by_the_experts_it_touches():
    # 32 rows, one expert each, over 8 distinct experts: 100.7 MB of
    # int8 weights and 0.26 MB of rows in and out, 0.123 ms at 819 GB/s;
    # the operations (0.81 GFLOP) would take 4 us
    t = zaya_roofline.experts_least_s(cfg(), 32, 8, PEAK)
    nbytes = 8 * 12582912 + 32 * 2 * 2048 * 2
    assert nbytes == 100925440
    assert t == pytest.approx(nbytes / 819e9)
    assert t > 32 * 2 * 12582912 / 197e12
    # all 16 touched: twice the weights
    assert zaya_roofline.experts_least_s(cfg(), 32, 16, PEAK) == \
        pytest.approx((16 * 12582912 + 262144) / 819e9)


def test_a_mixed_dispatchs_layer_is_bound_by_the_weights_still():
    # 288 packed tokens over all 16 experts: 201 MB against 7.2 GFLOP
    # (0.246 ms against 0.037 ms)
    t = zaya_roofline.experts_least_s(cfg(), 288, 16, PEAK)
    assert t == pytest.approx((16 * 12582912 + 288 * 2 * 2048 * 2) / 819e9)
    assert t > 288 * 2 * 12582912 / 197e12


def test_the_attention_roofline_cannot_read_this_config_as_it_stands():
    """`layer_metrics/attn_roofline.py` goes through `roofline.dims`,
    which reads `intermediate_size`: on the published config it raises,
    so the cell stays off `decode_attn_roofline`'s list. Given the width
    under that name the count is right: K and V of 2 heads of 128 at 2
    bytes are 1 KiB a token a layer (40 KiB over the 40 layers)."""
    with pytest.raises(KeyError, match="intermediate_size"):
        roofline.dims(cfg())
    c = zaya_roofline.as_moe_config(cfg())
    assert roofline.kv_bytes_per_token(c) == 40 * 1024
    d = roofline.dims(c)
    assert (d["heads"], d["kv"], d["hd"]) == (8, 2, 128)
    # one decode row over a context of 1,024: K and V once, q in and out
    nbytes, ops = roofline.attention_need(c, [(1, 1024)])
    assert nbytes == 1024 * 1024 + 2 * 8 * 128 * 2
    assert ops == 4 * 8 * 128 * 1024
    seconds, bound = roofline.least_s(nbytes * 32, ops * 32, PEAK)
    assert bound == "bandwidth" and seconds == pytest.approx(
        32 * nbytes / 819e9)


# -- the reader, on a made-up run ----------------------------------------------


class FakeCell:
    cell = {"shape": {"kv_bytes": 2, "weight_bytes": 1, "mixed_width": 128}}


def fake_run(**over):
    # a decode record: 32 rows through 40 layers, 7 experts a layer; a
    # mixed record: one dispatch of 160 real tokens over 12 a layer
    decode = {"kind": "decode", "compiled": False, "wall_s": 0.030,
              "moe_rows": 40 * 32.0, "moe_experts_touched": 40 * 7.0}
    mixed = {"kind": "mixed", "compiled": False, "wall_s": 0.060,
             "moe_rows": 40 * 160.0, "moe_experts_touched": 40 * 12.0}
    records = [{"failed": False, "class": "p256", "t_send": 1.0 + i,
                "token_t": [1.0 + i + 0.1 * (i + 2)]} for i in range(5)]
    run = {"model_config": cfg(), "cell": FakeCell(),
           "device": {"kind": "TPU v5 lite"}, "health": {"decode_slots": 32},
           "steps": [decode] * 3 + [mixed], "records": records, "t0": 0.0,
           "t1": 48.0, "turnarounds": [], "healthy_s": 1.0, "warmup_s": 2.0,
           "metrics_0": {}, "metrics_1": {}, "metrics_2": {}, "trace": None}
    run.update(over)
    return run


def test_counters_and_the_clients_clock():
    got = load_reader().read(fake_run())
    assert got["moe_experts_touched_per_layer"] == pytest.approx(7.0)
    assert got["top1_moe_experts_roofline"] is None
    assert not [k for k in got if k.startswith(("mixed_step", "ttft_"))]
    assert "dev_share_cca_mix_pct" not in got
    assert "dev_share_router_pct" not in got


def test_another_program_yields_nothing():
    """The parent cannot run the cell, and a dense or another sparse
    model's run must not grow these metrics."""
    for other in ({"model_type": "olmoe", "num_hidden_layers": 16},
                  {"num_hidden_layers": 2}):
        run = fake_run(model_config=other, steps=[], records=[])
        assert {k: v for k, v in load_reader().read(run).items()
                if v is not None} == {}
    run = fake_run(steps=[{"kind": "decode", "compiled": False,
                           "wall_s": 0.03}], records=[])
    assert {k: v for k, v in load_reader().read(run).items()
            if v is not None} == {}
    # OLMoE's kernel events under a config with no `moe_intermediate_size`
    run = fake_run(model_config={"num_hidden_layers": 16,
                                 "num_experts_per_tok": 8},
                   trace={"kernels": [{"device": 0, "dur_s": 1.0,
                                       "name": "%cake_moe_gmm.1 = "
                                               "bf16[128,1024]{1,0}"}]})
    assert load_reader().experts_roofline(run) is None


def test_experts_roofline_from_kernel_events():
    reader = load_reader()
    c = cfg()
    one_decode = 40 * zaya_roofline.experts_least_s(c, 32, 7, PEAK)
    one_mixed = 40 * zaya_roofline.experts_least_s(c, 160, 12, PEAK)

    def event(rows, dur):
        return {"device": 0, "dur_s": dur,
                "name": f"%cake_moe_gmm.3 = bf16[{rows},2048]{{1,0}} "
                        "custom-call(...), "
                        "custom_call_target=\"tpu_custom_call\""}
    # the capture: one mixed dispatch and two decode steps, 120 events
    # each (3 projections x 40 layers), every event taking as long as
    # makes the total twice the need; the attention kernel's is not ours
    need = one_mixed + 2 * one_decode
    events = ([event(288, 2 * need / 360)] * 120
              + [event(32, 2 * need / 360)] * 240
              + [{"device": 0, "dur_s": 1.0, "name": "%cake_decode_attn.1 = "
                  "bf16[32,1,8,128]{3,2,1,0} custom-call(...)"},
                 dict(event(32, 9.0), device=1)])
    run = fake_run(trace={"kernels": events})
    share = reader.experts_roofline(run)
    assert share == pytest.approx(50.0)
    assert 0.0 < share <= 100.0
