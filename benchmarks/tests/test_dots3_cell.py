"""The dots3-note configuration, its cell and its per-layer metrics as
shipped: found by name (in a temporary copy too), in agreement with
BENCHMARK.json and with the catalog's published numbers, the traffic's
proportions, the counts of `swa_roofline.py` against cases computed by
hand, and the reader on a made-up run."""

import importlib.util
import os
import shutil

import pytest

from harness import mla_roofline, spec, swa_roofline, traffic as tfc

CELL = "dots3.longshort-closed"
CONFIG = "dots3-note-int8-share8"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog's `config` for dots3-note-prev (model-configs guide), every key
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512,
    "layer_types": ["full_attention"] * 2 + PERIOD * 11,
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 46, "num_key_value_heads": 128,
    "q_lora_rank": 1024, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152064}
REDUCED = ["num_hidden_layers", "layer_types", "n_routed_experts",
           "vocab_size", "eos_token_id"]
NEW = ["swa_attended_share_pct", "dev_share_swa_attn_pct", "dev_share_swa_proj_pct",
       "swa_attn_roofline", "dsa_full_attn_roofline",
       "ttft_p50_ms.longshort-s1k", "ttft_p50_ms.longshort-d8k",
       "tpot_p50_ms.longshort"]
# the plain readings of a cell judged by tokens, joined by list (until
# PR 55 this reader's `mixed_step_ms.longshort` and
# `mixed_step_device_ms.longshort`); the cell's TTFT is read BY CLASS
JOINED = {"mixed_step_ms.tok", "mixed_step_device_ms.tok"}


def load_reader(bench_dir=spec.BENCH_DIR):
    path = os.path.join(bench_dir, "layer_metrics", "swa.py")
    s = importlib.util.spec_from_file_location("layer_metric_swa", path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def cfg():
    return spec.Cell(CELL).model_config


def test_shipped_configuration_is_the_published_one_but_for_reduced():
    cell = spec.Cell(CELL)
    c = cell.model_config
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert c[key] == value, key
    assert cell.cell["reduced"] == REDUCED
    assert set(cell.cell["reduced_why"]) == set(REDUCED)
    # no width among them
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"], c["eos_token_id"]) == (9, 32, 19008, 19008)
    # published layer 0 and layers 2-9: two whole periods
    kept = [PUBLISHED["layer_types"][i] for i in [0] + list(range(2, 10))]
    assert c["layer_types"] == kept == (["full_attention"] + PERIOD * 2)
    assert c["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert (c["n_routed_experts_total"], c["first_routed_expert"]) == (256, 0)
    assert c["published"]["num_hidden_layers"] == 46
    assert c["published"]["n_routed_experts"] == 256
    assert c["published"]["vocab_size"] == 152064
    # `indexer_types` stays out: dsa.py's mla_attn_roofline would count
    # cake_mla_* events over 9 layers where 3 run them
    assert "indexer_types" not in c and "index_topk_freq" not in c
    assert len(cell.cell["source"]) <= 200
    assumed = " ".join(cell.cell["assumed"])
    for said in ("arXiv:2505.06708", "LongCat-Flash", "counts the query",
                 "DeepSeek-V3.2", "interleaved pairs"):
        assert said in assumed, said
    for said in ("8 chips", "0-31", "8 ways", "published 0 and 2-9"):
        assert said in cell.cell["deployment"], said
    assert "towers" in cell.cell["not_served"]
    args = cell.cell["server_args"]
    assert args["require-model-type"] == "dots3_note"
    assert (args["quant"], args["max-slots"], args["max-seq-len"],
            args["kv-pages"], args["kv-page-size"], args["prefill-chunk"],
            args["paged-attn"]) == ("int8", 32, 16896, 2400, 128, 512,
                                    "pallas")
    # the least multiple of the window that holds 16,384 + 256 (ISSUE
    # 41's long class; kept as stated under the d8k fallback)
    assert args["max-seq-len"] % args["prefill-chunk"] == 0
    assert args["max-seq-len"] - args["prefill-chunk"] < 16640
    # 16 rows of the issue's long class and 16 short ones fit the
    # full pools
    assert 16 * (16640 // 128) + 16 * (1280 // 128) <= args["kv-pages"]
    # the window pool has no option: slots x R by the cache's shape
    assert "max-slots x R" in cell.cell["window_pool_rule"]
    assert not any("window" in key for key in args)
    assert cell.cell["expect_impl"] == {"mixed": "paged-dsa-pallas",
                                        "decode": "paged-dsa-pallas"}
    assert cell.cell["shape"] == {"weight_bytes": 1, "kv_bytes": 2,
                                  "mixed_width": 512, "stages": 1, "tp": 1}
    toy = cell.cell["rehearse"]
    assert (toy["config"]["sliding_window_size"],
            toy["server_args"]["kv-page-size"]) == (6, 4)
    assert toy["config"]["layer_types"].count("sliding_attention") == 4
    assert cell.traffic_name == "longshort-closed" and cell.chips == 1
    assert "fallback_taken" in cell.cell


def test_benchmark_json_entries_match_the_cells_files():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    work = next(w for w in doc["workloads"] if w["name"] == CELL)
    cell = spec.Cell(CELL)
    assert entry["reduced"] == cell.cell["reduced"]
    assert entry["source"] == cell.cell["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}/config.json"
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "longshort-closed", 1)
    for text in (entry["why"], entry["source"], work["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert "over its share" in work["why"]
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 4)
    assert sum(w["config"] == CONFIG for w in doc["workloads"]) == 1


def test_cell_reports_what_the_issue_lists():
    cell = spec.Cell(CELL)
    # tpot_p50_ms is reported (`tpot_p50_ms.longshort`) and not judged:
    # its median falls on one of two levels and a set of six spread
    # 2.27 % against half its bound (cell.json `tpot_not_judged`)
    assert set(cell.names("end_to_end")) == {"out_tok_s", "setup_s"}
    layers = set(cell.names("per_layer"))
    assert set(NEW) | JOINED <= layers
    for name in ("rows_busy_pct", "pages_in_use_pct", "mixed_step_share_pct",
                 "host_emit_p50_ms", "host_build_p50_ms",
                 "loop_uncovered_pct", "dev_share_attn_pct",
                 "dev_share_ffn_pct", "dev_share_kv_pct",
                 "dev_share_unscoped_pct", "idle_attributed_pct",
                 "dev_share_sample_pct", "decode_steps_chained_pct",
                 "mixed_steps_chained_pct", "chain_breaks_per_s",
                 "boundary_gap_p50_ms", "boundary_admit_p50_ms",
                 "chained_steps_late_pct", "host_detok_p50_ms",
                 "moe_rows_padded_pct", "moe_expert_load_max_over_mean",
                 "dev_share_moe_route_pct", "moe_held_rows_share_pct",
                 "dsa_selected_share_pct", "dsa_index_reuse_pct",
                 "dev_share_indexer_pct", "dev_share_mla_proj_pct",
                 "peak_hbm_gib", "compiles_in_window", "decode_step_ms"):
        assert name in layers, name
    # no metric whose `moves` the cell does not report, none of another
    # cell's own, not the GLM cell's attention roofline (it divides by
    # num_hidden_layers); no device time of a decode step: the capture
    # holds mixed dispatches and no decode step (null on both sides of
    # every PR from 47 to 54), `decode_step_ms` stands on the host clock
    for name in ("mla_attn_roofline", "ttft_p50_ms.tok",
                 "decode_step_device_ms",
                 "mixed_step_ms", "mixed_step_device_ms",
                 "mixed_attn_roofline", "queue_wait_p50_ms",
                 "decode_step_roofline", "decode_attn_roofline",
                 "dev_share_ssm_pct", "step_gap_p50_ms",
                 "prefill_rows_per_mixed_step"):
        assert name not in layers, name


def test_reader_agrees_with_benchmark_json():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in load_reader().METRICS}
    assert list(declared) == NEW
    entries = {m["name"]: m for m in doc["per_layer"]
               if m["name"] in declared}
    assert set(entries) == set(declared)
    names = [m["name"] for m in doc["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    for name, m in entries.items():
        # K-EXAONE's sliding layers emit the same three counter keys
        assert m["workloads"] == [CELL] + (
            ["kexaone.longreply-closed"]
            if name == "swa_attended_share_pct" else [])
        assert m["moves"] == "out_tok_s"
        for key in ("unit", "layer", "moves", "source"):
            assert declared[name][key] == m[key], (name, key)
        if name.endswith("_roofline"):
            assert m["unit"] == "%" and m["layer"] == "kernels"
            assert m["better"] == "higher"


def test_reference_copy_is_the_programs():
    here = os.path.join(spec.BENCH_DIR, "configs", CONFIG, "reference.py")
    there = os.path.join(spec.ROOT, "cake_tpu", "models", "reference",
                         "dots3_note.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        assert a.read() == b.read()


def test_traffic_is_the_stated_cycle():
    cell = spec.Cell(CELL)
    t = cell.traffic
    assert (t["loop"], t["clients"], t["ramp_s"]) == ("closed", 32, 16)
    classes = tfc.class_by_name(t)
    assert (classes["s1k"]["lo"], classes["s1k"]["hi"]) == (897, 1024)
    assert (classes["d8k"]["lo"], classes["d8k"]["hi"]) == (8065, 8192)
    counts = {}
    for item in t["multiset"]:
        counts[item["class"]] = counts.get(item["class"], 0) + item["n"]
    total = sum(counts.values())
    assert counts == {"s1k": 12, "d8k": 4} and total == 16
    for name, c in classes.items():
        assert c["weight"] == pytest.approx(counts[name] / total)
    assert sorted((i["class"], i["out"], i["n"]) for i in t["multiset"]) == [
        ("d8k", 128, 2), ("d8k", 256, 2), ("s1k", 128, 6),
        ("s1k", 256, 6)]
    assert len(tfc.expand_multiset(t)) == 16
    assert t["probe"] == {"class": "s1k", "out": 128}
    assert (t["warmup_wave"], t["warmup_wave_out"]) == (32, 8)
    assert [w["class"] for w in t["warmup"]] == ["s1k", "d8k"]
    assert "one in four" in t["who"]
    # the mix builds under a seed past 2**31, and every context fits
    mix = tfc.Mix(t, 2147484999, cell.model_config["vocab_size"])
    assert max(c["hi"] for c in classes.values()) + 256 <= \
        cell.cell["server_args"]["max-seq-len"]
    assert len(mix.warmup_items()) == 2


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
    assert got["swa_attended_share_pct"] == {"value": 5.0, "unit": "%"}
    assert got["ttft_p50_ms.longshort-s1k"]["value"] == pytest.approx(400.0)
    assert got["mixed_step_ms.tok"]["value"] == pytest.approx(80.0)
    assert "swa_attn_roofline" not in got              # no capture
    assert "mixed_step_device_ms.tok" not in got
    # an old cell does not report the new metrics
    old = spec.Cell("glm52.longdoc-closed", str(bench),
                    str(tmp_path / "BENCHMARK.json"))
    assert not set(NEW) & set(old.names("per_layer"))


# -- the rooflines' counts, by hand --------------------------------------------


def test_the_two_geometries_sizes():
    d = swa_roofline.swa_dims(cfg())
    assert d == {"L_sliding": 6, "L_full": 3, "H": 64, "row": 1088,
                 "value": 1024, "window": 513}
    full = mla_roofline.mla_dims(swa_roofline.as_full_config(cfg()))
    assert full == {"L": 3, "L_full": 3, "H": 128, "row": 576, "value": 512}


def test_a_decode_rows_sliding_layer_is_bound_by_its_rows():
    # 32 rows past the window: 32 x 513 pairs, each its own row of
    # 1,088 bf16 numbers: 35.7 MB, 43.6 us at 819 GB/s; the operations
    # (64 x 2,112 x 2 a pair: 4.4 GFLOP) take 22.5 us
    pairs = 32 * 513
    nbytes, ops = swa_roofline.swa_need(cfg(), pairs, pairs)
    assert nbytes == pairs * 1088 * 2 == 35721216
    assert ops == pairs * 64 * (1088 + 1024) * 2 == 4437835776
    t = swa_roofline.swa_least_s(cfg(), pairs, pairs, PEAK)
    assert t == pytest.approx(nbytes / 819e9) and t > ops / 197e12


def test_a_windows_sliding_layer_is_bound_by_the_operations():
    # 512 queries x 513 keys: 71 GFLOP, 0.36 ms at 197 TFLOP/s; its
    # rows (at least 513 pairs' worth) are microseconds
    pairs = 512 * 513
    t = swa_roofline.swa_least_s(cfg(), pairs, pairs / 512, PEAK)
    assert t == pytest.approx(pairs * 64 * 2112 * 2 / 197e12)
    assert t > (pairs / 512) * 1088 * 2 / 819e9


def test_a_full_layers_window_at_128_heads():
    # 512 queries x 2,048 selected keys, 128 heads x (576 + 512) x 2:
    # 292 GFLOP, 1.48 ms; 8k distinct rows of 576 are 9.4 MB, 11.5 us
    t = swa_roofline.full_least_s(cfg(), 512 * 2048, 8192, PEAK)
    assert t == pytest.approx(512 * 2048 * 128 * 1088 * 2 / 197e12)
    assert t > 8192 * 576 * 2 / 819e9


# -- the reader, on a made-up run ----------------------------------------------


class FakeCell:
    cell = {"shape": {"kv_bytes": 2, "weight_bytes": 1, "mixed_width": 512}}


def fake_run(**over):
    # a decode record: 32 rows past the window, one dispatch; a mixed
    # record: one dispatch, a 512-token window at 8k and 16 decode rows
    decode = {"kind": "decode", "compiled": False, "wall_s": 0.040,
              "swa_layers": 6.0, "swa_keys_attended": 6 * 32 * 513.0,
              "swa_keys_visible": 6 * 32 * 4000.0,
              "dsa_index_layers": 3.0, "dsa_keys_selected": 3 * 32 * 2048.0,
              "dsa_rows_distinct": 3 * 32 * 2048.0}
    mixed = {"kind": "mixed", "compiled": False, "wall_s": 0.080,
             "swa_layers": 6.0, "swa_keys_attended": 6 * 528 * 513.0,
             "swa_keys_visible": 6 * 528 * 8000.0,
             "dsa_index_layers": 3.0, "dsa_keys_selected": 3 * 528 * 2048.0,
             "dsa_rows_distinct": 3 * (8192.0 + 16 * 2048)}
    records = [{"failed": False, "finished": True, "class": "s1k",
                "t_send": 1.0 + i, "token_t": [1.0 + i + 0.1 * (i + 2)]}
               for i in range(5)]
    records.append({"failed": False, "finished": True, "class": "d8k",
                    "t_send": 2.0, "token_t": [5.0, 5.05, 5.1, 5.15]})
    metrics_0 = {"cake_swa_keys_visible_total": 1000.0,
                 "cake_swa_keys_attended_total": 900.0}
    metrics_1 = {"cake_swa_keys_visible_total": 101000.0,
                 "cake_swa_keys_attended_total": 5900.0}
    run = {"model_config": cfg(), "cell": FakeCell(),
           "device": {"kind": "TPU v5 lite"}, "health": {"decode_slots": 32},
           "steps": [decode] * 3 + [mixed], "records": records, "t0": 0.0,
           "t1": 48.0, "turnarounds": [], "healthy_s": 1.0, "warmup_s": 2.0,
           "metrics_0": metrics_0, "metrics_1": metrics_1, "metrics_2": {},
           "trace": None}
    run.update(over)
    return run


def test_counters_and_the_clients_clock():
    got = load_reader().read(fake_run())
    assert got["swa_attended_share_pct"] == pytest.approx(5.0)
    # s1k TTFTs 0.2 .. 0.6 s: the plain median; one d8k at 3 s
    assert got["ttft_p50_ms.longshort-s1k"] == pytest.approx(400.0)
    assert got["ttft_p50_ms.longshort-d8k"] == pytest.approx(3000.0)
    # the one request with more than one token: 3 gaps of 50 ms
    assert got["tpot_p50_ms.longshort"] == pytest.approx(50.0)
    assert got["swa_attn_roofline"] is None
    assert got["dsa_full_attn_roofline"] is None
    for name in ("dev_share_swa_attn_pct", "dev_share_swa_proj_pct"):
        assert name not in got
    assert not [k for k in got if k.startswith("mixed_step")]


def test_another_program_yields_nothing():
    """The parent cannot run the cell, and GLM's or a dense model's run
    must not grow these metrics: no counters, and GLM's
    cake_mla_* events are not read as this model's full layers'."""
    glm = spec.Cell("glm52.longdoc-closed").model_config
    events = [{"device": 0, "dur_s": 1.0,
               "name": "%cake_mla_attn.1 = bf16[8,64,512]{2,1,0} "
                       "custom-call(...)"}]
    for other in (glm, {"num_hidden_layers": 2}):
        run = fake_run(model_config=other, steps=[], records=[],
                       metrics_0={}, metrics_1={},
                       trace={"kernels": events})
        assert {k: v for k, v in load_reader().read(run).items()
                if v is not None} == {}
    # this model's config, a program without the counters
    run = fake_run(steps=[{"kind": "decode", "compiled": False,
                           "wall_s": 0.03}], records=[], metrics_0={},
                   metrics_1={}, trace={"kernels": [
                       {"device": 0, "dur_s": 1.0,
                        "name": "%cake_swa_attn.2 = bf16[32,64,1024]{2,1,0} "
                                "custom-call(...)"}]})
    assert {k: v for k, v in load_reader().read(run).items()
            if v is not None} == {}


def test_rooflines_from_kernel_events():
    reader = load_reader()
    c = cfg()
    swa_decode = 6 * swa_roofline.swa_least_s(c, 32 * 513, 32 * 513, PEAK)
    swa_mixed = 6 * swa_roofline.swa_least_s(c, 528 * 513,
                                             528 * 513 / 512, PEAK)
    full_decode = 3 * swa_roofline.full_least_s(c, 32 * 2048, 32 * 2048,
                                                PEAK)
    full_mixed = 3 * swa_roofline.full_least_s(
        c, 528 * 2048, 8192 + 16 * 2048, PEAK)

    def event(name, dur, device=0):
        return {"device": device, "dur_s": dur,
                "name": f"%{name}.4 = bf16[32,64,1024]{{2,1,0}} "
                        "custom-call(...), "
                        "custom_call_target=\"tpu_custom_call\""}
    # the capture: one mixed dispatch and two decode steps. Sliding: 6
    # one-pass events a dispatch (18) and 6 window events; full: 9 and 3.
    # Every event as long as makes the total four times the need.
    swa_need = swa_mixed + 2 * swa_decode
    full_need = full_mixed + 2 * full_decode
    events = ([event("cake_swa_attn", 4 * swa_need / 24)] * 18
              + [event("cake_swa_window_attn", 4 * swa_need / 24)] * 6
              + [event("cake_mla_attn", 4 * full_need / 12)] * 9
              + [event("cake_mla_window_attn", 4 * full_need / 12)] * 3
              + [event("cake_moe_gmm", 1.0),
                 event("cake_swa_attn", 9.0, device=1)])
    run = fake_run(trace={"kernels": events})
    assert reader.kernels_roofline(run, reader.SWA_KERNELS, "swa") == \
        pytest.approx(25.0)
    assert reader.kernels_roofline(run, reader.FULL_KERNELS, "full") == \
        pytest.approx(25.0)
    got = reader.read(run)
    assert 0.0 < got["swa_attn_roofline"] <= 100.0
    assert 0.0 < got["dsa_full_attn_roofline"] <= 100.0
