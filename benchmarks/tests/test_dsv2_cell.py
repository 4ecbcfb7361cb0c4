"""The DeepSeek-V2 configuration, its cell and its per-layer metrics as
shipped: found by name (in a temporary copy too), in agreement with
BENCHMARK.json and with the catalog's published numbers, the traffic's
multiset, the counts of `mla_dense_roofline.py` at the published sizes,
and the reader on a made-up run."""

import importlib.util
import os
import shutil

import pytest

from harness import mla_dense_roofline as roof
from harness import spec, traffic as tfc

CELL = "dsv2.code-closed"
CONFIG = "deepseek-v2-int8-share8"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog's `config` for DeepSeek-V2 (model-configs guide), every key
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 128,
    "vocab_size": 102400}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "eos_token_id"]
NEW = ["mla_decode_attn_roofline", "mla_dense_window_roofline",
       "dev_share_mla_attn_pct", "mla_keys_per_decode_row",
       "moe_group_held_share_pct"]
# the plain readings of a cell judged by tokens, joined by list (until
# PR 55 this reader's three `.code` names)
JOINED = {"mixed_step_ms.tok", "mixed_step_device_ms.tok",
          "ttft_p50_ms.tok"}
# Ling-3.0's two latent layers run the same kernels and counters
ALSO = "ling3.longreply-closed"


def load_reader(bench_dir=spec.BENCH_DIR):
    path = os.path.join(bench_dir, "layer_metrics", "mla_dense.py")
    s = importlib.util.spec_from_file_location("layer_metric_mla_dense", path)
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
            c["vocab_size"], c["eos_token_id"]) == (15, 20, 12800, 12800)
    # the floors: the dense layer and >= 4 expert layers, >= 8 experts,
    # >= an eighth of the vocabulary; the held experts are ONE group
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert (c["n_routed_experts_total"], c["first_routed_expert"]) == (160, 0)
    assert c["n_routed_experts"] == c["n_routed_experts_total"] // c["n_group"]
    assert c["published"] == {
        "num_hidden_layers": 60, "n_routed_experts": 160,
        "vocab_size": 102400,
        "layers_here": c["published"]["layers_here"]}
    # no indexer key: dsa.py's mla_attn_roofline yields nothing here
    assert "indexer_types" not in c and "index_topk" not in c
    assert cell.cell["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/"
        "config.json")
    assumed = " ".join(cell.cell["assumed"])
    for said in ("lower index", "3,072", "bf16 latent rows", "seeded draw"):
        assert said in assumed, said
    for said in ("32 chips", "4 pipeline stages", "experts 0-19",
                 "1/8 of the vocabulary", "9.9 GB", "3.15 GB"):
        assert said in cell.cell["deployment"], said
    args = cell.cell["server_args"]
    assert args["require-model-type"] == "deepseek_v2"
    assert (args["quant"], args["max-slots"], args["max-seq-len"],
            args["kv-pages"], args["kv-page-size"], args["prefill-chunk"],
            args["paged-attn"]) == ("int8", 32, 5120, 1280, 128, 512,
                                    "pallas")
    assert args["max-seq-len"] % args["prefill-chunk"] == 0
    # every row's whole table fits the pool: no request waits for a page
    assert (args["max-slots"] * args["max-seq-len"] // args["kv-page-size"]
            == args["kv-pages"])
    assert cell.cell["expect_impl"] == {"mixed": "paged-mla-pallas",
                                        "decode": "paged-mla-pallas"}
    assert cell.cell["shape"] == {"weight_bytes": 1, "kv_bytes": 2,
                                  "mixed_width": 512, "stages": 1, "tp": 1}
    assert set(cell.cell["fallbacks"]) == {"a", "b"}
    assert "fallback_taken" in cell.cell
    toy = cell.cell["rehearse"]["config"]
    assert (toy["n_group"], toy["topk_group"], toy["n_shared_experts"]) == (
        8, 3, 2)
    assert "rope_scaling" not in toy            # YaRN as published
    assert toy["n_routed_experts"] * 8 == toy["n_routed_experts_total"]
    assert cell.traffic_name == "code-closed" and cell.chips == 1


def test_the_toy_and_the_shipped_config_both_parse():
    from cake_tpu.models.llama.config import load_config_dict
    cell = spec.Cell(CELL)
    c = load_config_dict(cell.model_config)
    assert c.family.impl == "paged-mla-"
    toy = load_config_dict(dict(cell.model_config,
                                **cell.cell["rehearse"]["config"]))
    assert (toy.n_group, toy.topk_group, toy.n_shared_experts,
            toy.num_local_experts) == (8, 3, 2, 2)
    assert toy.rope_scaling == c.rope_scaling


def test_benchmark_json_entries_match_the_cells_files():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    work = next(w for w in doc["workloads"] if w["name"] == CELL)
    cell = spec.Cell(CELL)
    assert entry["reduced"] == cell.cell["reduced"]
    assert entry["source"] == cell.cell["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}/config.json"
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "code-closed", 1)
    for text in (entry["why"], entry["source"], work["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert "over its share" in work["why"]
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 4)
    assert sum(w["config"] == CONFIG for w in doc["workloads"]) == 1
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_cell_reports_what_the_issue_lists():
    cell = spec.Cell(CELL)
    assert set(cell.names("end_to_end")) == {"tpot_p50_ms", "out_tok_s",
                                             "setup_s"}
    layers = set(cell.names("per_layer"))
    assert set(NEW) | JOINED <= layers
    for name in ("decode_attn_pages_live_pct",
                 "moe_held_rows_share_pct", "dev_share_mla_proj_pct",
                 "dev_share_moe_route_pct", "moe_rows_padded_pct",
                 "moe_expert_load_max_over_mean", "mixed_steps_chained_pct",
                 "boundary_admit_p50_ms", "rows_busy_pct",
                 "pages_in_use_pct", "mixed_step_share_pct",
                 "host_build_p50_ms", "host_emit_p50_ms",
                 "loop_uncovered_pct",
                 "dev_share_attn_pct", "dev_share_ffn_pct",
                 "idle_attributed_pct", "decode_steps_chained_pct",
                 "chain_breaks_per_s", "boundary_gap_p50_ms",
                 "chained_steps_late_pct", "dev_share_sample_pct",
                 "peak_hbm_gib", "compiles_in_window", "decode_step_ms"):
        assert name in layers, name
    # none whose `moves` the cell does not report, none of another
    # cell's own; not moe_experts_roofline (moe_dims would read the
    # DENSE layer's 12,288 as an expert's width), not GLM's attention
    # roofline, not the dense step's
    # not decode_step_device_ms either: the capture lies where prompts
    # still queue behind the ramp and every step is a mixed dispatch,
    # so its reader finds no decode program there (PERF.md section 6)
    for name in ("decode_step_device_ms", "moe_experts_roofline",
                 "mla_attn_roofline",
                 "decode_step_roofline", "dsa_selected_share_pct",
                 "dsa_index_reuse_pct", "dev_share_indexer_pct",
                 "swa_attn_roofline", "step_gap_p50_ms",
                 "mixed_step_ms", "mixed_step_device_ms",
                 "queue_wait_p50_ms", "prefill_rows_per_mixed_step"):
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
        assert m["workloads"] == [CELL, ALSO]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for key in ("unit", "layer", "moves", "source"):
            assert declared[name][key] == m[key], (name, key)
        if name.endswith("_roofline"):
            assert m["unit"] == "%" and m["layer"] == "kernels"
            assert m["better"] == "higher"
    assert entries["mla_decode_attn_roofline"]["moves"] == "tpot_p50_ms"
    assert entries["mla_dense_window_roofline"]["moves"] == "out_tok_s"


def test_reference_copy_is_the_programs():
    here = os.path.join(spec.BENCH_DIR, "configs", CONFIG, "reference.py")
    there = os.path.join(spec.ROOT, "cake_tpu", "models", "reference",
                         "deepseek_v2.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        assert a.read() == b.read()


def test_traffic_is_the_stated_cycle():
    cell = spec.Cell(CELL)
    t = cell.traffic
    assert (t["loop"], t["clients"], t["ramp_s"]) == ("closed", 32, 16)
    classes = tfc.class_by_name(t)
    assert list(classes) == ["f4k"]
    assert (classes["f4k"]["lo"], classes["f4k"]["hi"],
            classes["f4k"]["weight"]) == (3585, 4096, 1.0)
    assert sorted((i["class"], i["out"], i["n"]) for i in t["multiset"]) == [
        ("f4k", 256, 8), ("f4k", 512, 8), ("f4k", 768, 8)]
    items = tfc.expand_multiset(t)
    assert len(items) == 24
    assert sum(i["out"] for i in items) / 24 == 512
    # every prompt is 8 windows of 512, every context fits a row
    width = cell.cell["server_args"]["prefill-chunk"]
    assert {-(-i["prompt"] // width) for i in items} == {8}
    assert max(i["prompt"] + i["out"] for i in items) == 4864 <= \
        cell.cell["server_args"]["max-seq-len"]
    assert t["probe"] == {"class": "f4k", "out": 256}
    assert t["warmup"] == [{"class": "f4k", "out": 8}]
    assert (t["warmup_wave"], t["warmup_wave_out"]) == (32, 8)
    assert "code assistant" in t["who"] and "prefix pages" in t["why"]
    # the mix builds under a seed past 2**31
    mix = tfc.Mix(t, 2147484999, cell.model_config["vocab_size"])
    assert len(mix.warmup_items()) == 1


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
    assert got["moe_group_held_share_pct"] == {"value": 37.5, "unit": "%"}
    assert got["mla_keys_per_decode_row"]["value"] == pytest.approx(4200.0)
    assert got["ttft_p50_ms.tok"]["value"] == pytest.approx(2250.0)
    assert got["mixed_step_ms.tok"]["value"] == pytest.approx(100.0)
    assert "mla_decode_attn_roofline" not in got          # no capture
    assert "mixed_step_device_ms.tok" not in got
    # an old cell does not report the new metrics
    old = spec.Cell("glm52.longdoc-closed", str(bench),
                    str(tmp_path / "BENCHMARK.json"))
    assert not set(NEW) & set(old.names("per_layer"))


# -- the roofline's counts, by hand -----------------------------------------


def test_a_key_costs_278528_operations_and_1152_bytes():
    c = cfg()
    assert roof.dims(c) == {"L": 15, "H": 128, "row": 576, "value": 512}
    assert roof.ops_per_pair(c) == 128 * (576 + 512) * 2 == 278528
    assert roof.bytes_per_key(c) == 576 * 2 == 1152
    # on the ridge of a v5e: 1.414 ns by the peak, 1.407 ns by HBM
    by_ops = 278528 / 197e12
    by_bytes = 1152 / 819e9
    assert by_ops == pytest.approx(1.414e-9, rel=1e-3)
    assert by_bytes == pytest.approx(1.407e-9, rel=1e-3)
    assert roof.least_s(c, 1.0, 1.0, PEAK) == pytest.approx(by_ops)


def test_a_decode_steps_layer_and_a_windows_layer():
    c = cfg()
    # 32 rows of 4,200 keys: 134,400 keys, 37.4 GFLOP, 190 us a layer
    keys = 32 * 4200
    assert roof.least_s(c, keys, keys, PEAK) == pytest.approx(
        keys * 278528 / 197e12)
    # a 512-token window whose last query sees 4,096 keys: pairs s <= t,
    # bound by the operations (its 4,096 rows are 5.8 us)
    pairs = 512 * 4096 - 512 * 511 / 2
    t = roof.least_s(c, pairs, 4096, PEAK)
    assert t == pytest.approx(pairs * 278528 / 197e12) and t > 2.7e-3
    assert t > 4096 * 1152 / 819e9


# -- the reader, on a made-up run -------------------------------------------


class FakeCell:
    cell = {"shape": {"kv_bytes": 2, "weight_bytes": 1, "mixed_width": 512}}


ARGS = {"max-slots": 32, "max-seq-len": 5120, "kv-page-size": 128}


def fake_run(**over):
    # a decode record: 32 rows of 4,200 keys, one step; a mixed record:
    # one dispatch of 31 single-token rows and a window
    decode = {"kind": "decode", "compiled": False, "wall_s": 0.030,
              "rows": 32, "attn_pages_table": 32 * 40,
              "mla_keys_attended": 15 * 32 * 4200.0, "step": 2, "ts": 11.0}
    mixed = {"kind": "mixed", "compiled": False, "wall_s": 0.100,
             "rows": 32, "tokens_computed": 544,
             "mla_keys_attended": 15 * 31 * 4200.0, "step": 1, "ts": 10.0,
             "rids": [7]}
    records = [{"failed": False, "finished": True, "class": "f4k",
                "rid": 100 + i, "prompt": 4000, "t_send": 1.0 + i,
                "token_t": [1.0 + i + 0.5 * (i + 2)]} for i in range(5)]
    # the request whose fourth window the mixed step holds
    records.append({"failed": False, "finished": True, "class": "f4k",
                    "rid": 7, "prompt": 4000, "t_send": 9.0,
                    "token_t": [12.0]})
    metrics_0 = {"cake_moe_rows_routed_total": 600.0,
                 "cake_moe_tokens_group_held_total": 50.0}
    metrics_1 = {"cake_moe_rows_routed_total": 600.0 + 6 * 8000.0,
                 "cake_moe_tokens_group_held_total": 50.0 + 3000.0}
    run = {"model_config": cfg(), "cell": FakeCell(), "server_args": ARGS,
           "device": {"kind": "TPU v5 lite"}, "health": {"decode_slots": 32},
           "steps": [decode] * 3 + [mixed],
           "all_steps": [dict(mixed, step=0, ts=9.5 + 0.1 * k)
                         for k in range(3)] + [mixed, decode],
           "records": records, "t0": 0.0, "t1": 48.0, "wall_0": 0.0,
           "wall_1": 48.0, "turnarounds": [], "healthy_s": 1.0,
           "warmup_s": 2.0, "metrics_0": metrics_0, "metrics_1": metrics_1,
           "metrics_2": {}, "trace": None}
    run.update(over)
    return run


def test_counters_and_the_clients_clock():
    got = load_reader().read(fake_run())
    # 3,000 token-layers held of 8,000 routed: 3 of 8 groups a token
    assert got["moe_group_held_share_pct"] == pytest.approx(37.5)
    assert got["mla_keys_per_decode_row"] == pytest.approx(4200.0)
    # no capture: nothing of the device
    for name in ("mla_decode_attn_roofline", "mla_dense_window_roofline",
                 "dev_share_mla_attn_pct"):
        assert name not in got
    assert not [k for k in got if k.startswith(("mixed_step", "ttft_"))]


def test_another_program_yields_nothing():
    """The `workloads` lists are the gate: no other cell but Ling's
    (two latent layers of the same kind) lists a metric of this file,
    so its reader never runs there (GLM's and dots3's
    cake_mla_window_attn events are not read as this model's windows);
    and this model's config on a program without the counters or the
    kernels grows nothing."""
    names = {m["name"] for m in load_reader().METRICS}
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    for w in doc["workloads"]:
        if w["name"] not in (CELL, ALSO):
            assert not names & {m["name"]
                                for m in spec.Cell(w["name"]).per_layer}
    run = fake_run(steps=[{"kind": "decode", "compiled": False, "rows": 32,
                           "wall_s": 0.03}], records=[], metrics_0={},
                   metrics_1={}, all_steps=[])
    assert {k: v for k, v in load_reader().read(run).items()
            if v is not None} == {}


def kernel_op(name, start, dur):
    return {"name": f"%{name}.3 = bf16[32,128,512]{{2,1,0}} "
                    "custom-call(...), custom_call_target="
                    "\"tpu_custom_call\"",
            "start_ns": start, "dur_ns": dur, "stats": {}}


def capture(ops, fetches):
    """Planes of a capture: device 0's ops, and the engine thread's
    `cake/fetch` spans as (start, end, step)."""
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [
                kernel_op("cake_mla_decode_attn", 0, 10 ** 9)]}]},
        {"name": "/host:CPU", "lines": [{"name": "engine", "events": [
            {"name": "cake/fetch", "start_ns": a, "dur_ns": b - a,
             "stats": {"step": step}} for a, b, step in fetches] + [
            {"name": "cake/emit", "start_ns": 0, "dur_ns": 5,
             "stats": {"step": 99}}]}]}]


def test_an_events_keys_are_its_own_records():
    """An event of the page-walking kernel belongs to the step whose
    fetch is the first to end after it; one past the last fetch, to the
    record after; the need is those records' keys and no floor."""
    reader = load_reader()
    c = cfg()
    base, mixed = fake_run()["steps"][0], fake_run()["steps"][-1]
    # records 5 (decode, 4,000 keys a row), 6 (a mixed record of ONE
    # dispatch, 31 rows), 7 (a mixed record of TWO dispatches: its rows
    # ride one, each event takes the mean), 8 (in flight at the end)
    steps = [dict(base, step=5, mla_keys_attended=15 * 32 * 4000.0),
             dict(mixed, step=6, mla_keys_attended=15 * 31 * 4200.0),
             dict(mixed, step=7, tokens_computed=1088,
                  mla_keys_attended=15 * 30 * 4400.0),
             dict(base, step=8, mla_keys_attended=15 * 32 * 100.0),
             dict(base, step=9, mla_keys_attended=15 * 32 * 9999.0)]
    ops = ([kernel_op("cake_mla_decode_attn", 10 + k, 1) for k in (0, 20)]
           + [kernel_op("cake_mla_decode_attn", 110, 1)]
           + [kernel_op("cake_mla_decode_attn", 210 + k, 1)
              for k in (0, 30, 60)]
           + [kernel_op("cake_mla_decode_attn", 410, 1),
              kernel_op("cake_mla_window_attn", 120, 40),
              kernel_op("cake_moe_gmm", 60, 500)])
    planes = capture(ops, [(90, 100, 5), (150, 200, 6), (290, 300, 7)])
    run = fake_run(steps=steps, all_steps=steps)
    events = reader.kernel_ops(planes, "cake_mla_decode_attn")
    assert len(events) == 7
    keys = 2 * 32 * 4000 + 31 * 4200 + 3 * 30 * 4400 / 2 + 32 * 100
    assert reader.keys_attended(run, planes, events) == pytest.approx(keys)
    assert reader.decode_roofline(run, planes) == pytest.approx(
        100.0 * roof.least_s(c, keys, keys, PEAK) / (7 / 1e9))
    # no fetch span in the capture, or a record gone: nothing
    assert reader.decode_roofline(run, capture(ops, [])) is None
    assert reader.decode_roofline(
        fake_run(steps=steps[:2], all_steps=steps[:2]), planes) is None


def test_the_windows_roofline():
    reader = load_reader()
    c = cfg()
    # request 7's first four windows lie in the window: queries
    # 512 (k - 1) .. 512 k - 1 over keys s <= t; the mean of the four
    window = sum(
        roof.least_s(c, 512 * 512 * k - 512 * 511 / 2, 512 * k, PEAK)
        for k in (1, 2, 3, 4)) / 4
    # 15 events of the window kernel, each twice the mean need
    dur = 2e9 * window
    ops = [kernel_op("cake_mla_window_attn", 2 * dur * k, dur)
           for k in range(15)] + [kernel_op("cake_moe_gmm", 5, 10)]
    got = reader.window_roofline(fake_run(), capture(ops, []))
    assert got == pytest.approx(50.0)
    assert reader.window_roofline(fake_run(), capture([], [])) is None
