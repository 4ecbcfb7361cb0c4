"""The Ling-3.0-flash configuration, its cell and its per-layer metrics as
shipped: found by name (in a temporary copy too), in agreement with
BENCHMARK.json and with the catalog's published numbers, the traffic's
multiset, the counts of `kda_roofline.py` at the published sizes, and
the reader on a made-up run."""

import importlib.util
import os
import shutil

import pytest

from harness import kda_roofline as roof
from harness import spec, traffic as tfc

CELL = "ling3.longreply-closed"
CONFIG = "ling-3.0-flash-int8-share4"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog's `config` for Ling-3.0-flash (model-configs guide), every
# key but the two limit lists (42 entries each: below)
PUBLISHED = {
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768,
    "mtp_loss_scaling_factor": 0, "mtp_use_kda": False, "n_group": 8,
    "no_kda_lora": True, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 512, "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False,
    "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
    "v_head_dim": 128, "value_norm": False, "vocab_size": 157184,
    "model_type": "bailing_hybrid"}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "num_nextn_predict_layers", "eos_token_id",
           "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"]
NEW = ["dev_share_kda_pct", "dev_share_kda_proj_pct", "kda_step_roofline",
       "kda_chunk_roofline", "kda_chunked_share_pct",
       "kda_state_rows_per_step"]
# the plain readings of a cell judged by tokens, joined by list (until
# PR 55 this reader's three `.longreply` names)
JOINED = {"mixed_step_ms.tok", "mixed_step_device_ms.tok",
          "ttft_p50_ms.tok"}


def load_reader(bench_dir=spec.BENCH_DIR):
    path = os.path.join(bench_dir, "layer_metrics", "kda.py")
    s = importlib.util.spec_from_file_location("layer_metric_kda", path)
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
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"],
            c["num_nextn_predict_layers"], c["eos_token_id"]) == (
        12, 128, 39296, 0, 39296)
    # the limit lists cut to the served layers, none of which clamps
    assert c["expert_swiglu_limit_list"] == [0] * 12
    assert c["share_expert_swiglu_limit_list"] == [0] * 12
    # the floors: two whole periods, the dense layers and >= 4 expert
    # layers, >= 8 experts, >= an eighth of the vocabulary; the held
    # experts are TWO whole groups
    assert c["num_hidden_layers"] == 2 * c["layer_group_size"]
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert (c["num_experts_total"], c["first_routed_expert"]) == (512, 0)
    assert c["num_experts"] == 2 * c["num_experts_total"] // c["n_group"]
    assert c["published"]["num_hidden_layers"] == 42
    assert cell.cell["source"] == (
        "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/"
        "config.json")
    assumed = " ".join(cell.cell["assumed"])
    for said in ("layer_group_size", "flash-linear-attention", "bounded",
                 "L2 norm", "no rotation", "head_wise", "two best",
                 "lower index", "float32 KDA state", "half-life"):
        assert said in assumed, said
    for said in ("16 chips", "4 pipeline stages", "experts 0-127",
                 "1/4 of the vocabulary", "8.7 GB", "640 MiB", "10.24 GB"):
        assert said in cell.cell["deployment"], said
    args = cell.cell["server_args"]
    assert args["require-model-type"] == "bailing_hybrid"
    assert (args["quant"], args["max-slots"], args["max-seq-len"],
            args["kv-pages"], args["kv-page-size"], args["prefill-chunk"],
            args["paged-attn"]) == ("int8", 32, 9728, 2432, 128, 512,
                                    "pallas")
    assert args["max-seq-len"] % args["prefill-chunk"] == 0
    # every row's whole table fits the pool: no request waits for a page
    assert (args["max-slots"] * args["max-seq-len"] // args["kv-page-size"]
            == args["kv-pages"])
    assert cell.cell["expect_impl"] == {"mixed": "paged-kda-pallas",
                                        "decode": "paged-kda-pallas"}
    assert cell.cell["shape"] == {"weight_bytes": 1, "kv_bytes": 2,
                                  "mixed_width": 512, "stages": 1, "tp": 1}
    assert set(cell.cell["fallbacks"]) == {"a", "b"}
    assert "fallback_taken" in cell.cell
    toy = cell.cell["rehearse"]["config"]
    assert (toy["n_group"], toy["topk_group"], toy["layer_group_size"]) == (
        8, 4, 3)
    assert toy["num_experts"] * 4 == toy["num_experts_total"]
    assert cell.traffic_name == "longreply-closed" and cell.chips == 1


def test_the_toy_and_the_shipped_config_both_parse():
    from cake_tpu.models.llama.config import load_config_dict
    cell = spec.Cell(CELL)
    c = load_config_dict(cell.model_config)
    assert c.family.impl == "paged-kda-"
    assert (len(c.kda_layers), len(c.latent_layers)) == (10, 2)
    toy = load_config_dict(dict(cell.model_config,
                                **cell.cell["rehearse"]["config"]))
    assert (toy.n_group, toy.topk_group, toy.group_top,
            toy.num_local_experts) == (8, 4, 2, 4)
    assert toy.indexer_types == ("kda", "kda", "dense") * 2


def test_benchmark_json_entries_match_the_cells_files():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    work = next(w for w in doc["workloads"] if w["name"] == CELL)
    cell = spec.Cell(CELL)
    assert entry["reduced"] == cell.cell["reduced"]
    assert entry["source"] == cell.cell["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}/config.json"
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "longreply-closed", 1)
    for text in (entry["why"], entry["source"], work["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert "over its share" in work["why"]
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 4)
    assert sum(w["config"] == CONFIG for w in doc["workloads"]) == 1
    assert len(doc["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_cell_reports_what_the_issue_lists():
    cell = spec.Cell(CELL)
    assert set(cell.names("end_to_end")) == {"tpot_p50_ms", "out_tok_s",
                                             "setup_s"}
    layers = set(cell.names("per_layer"))
    assert set(NEW) | JOINED <= layers
    for name in ("decode_step_device_ms", "decode_attn_pages_live_pct",
                 "moe_held_rows_share_pct", "moe_group_held_share_pct",
                 "dev_share_mla_proj_pct", "dev_share_mla_attn_pct",
                 "mla_dense_window_roofline", "mla_window_pages_per_fold",
                 "mla_decode_attn_roofline", "mla_keys_per_decode_row",
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
                 "host_detok_p50_ms", "detok_ids_per_token",
                 "peak_hbm_gib", "compiles_in_window", "decode_step_ms"):
        assert name in layers, name
    # none whose `moves` the cell does not report, none of another
    # cell's own. mla_decode_attn_roofline and mla_keys_per_decode_row
    # joined at PR 55: mla_dense.py divides a record's keys by the
    # LATENT layers (2 of this model's 12); not moe_experts_roofline
    # (moe_dims would read the DENSE layers' 6,144 as an expert's width)
    for name in ("moe_experts_roofline", "mla_attn_roofline",
                 "decode_step_roofline", "ssm_step_roofline",
                 "dsa_selected_share_pct", "dev_share_indexer_pct",
                 "step_gap_p50_ms", "loop_covered_pct", "mixed_step_ms",
                 "mixed_step_device_ms", "queue_wait_p50_ms",
                 "prefill_rows_per_mixed_step"):
        assert name not in layers, name


def test_reader_agrees_with_benchmark_json():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in load_reader().METRICS}
    assert list(declared) == NEW
    names = [m["name"] for m in doc["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW                      # in order
    for m in doc["per_layer"][at:at + len(NEW)]:
        assert m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for key in ("unit", "layer", "moves", "source"):
            assert declared[m["name"]][key] == m[key], (m["name"], key)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["layer"] == "kernels"
            assert m["better"] == "higher"
        assert m["moves"] == "out_tok_s"


def test_reference_copy_is_the_programs():
    here = os.path.join(spec.BENCH_DIR, "configs", CONFIG, "reference.py")
    there = os.path.join(spec.ROOT, "cake_tpu", "models", "reference",
                         "bailing_hybrid.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        assert a.read() == b.read()


def test_traffic_is_the_stated_cycle():
    cell = spec.Cell(CELL)
    t = cell.traffic
    assert (t["loop"], t["clients"], t["ramp_s"]) == ("closed", 32, 16)
    classes = tfc.class_by_name(t)
    assert list(classes) == ["t2k", "d8k"]
    assert (classes["t2k"]["lo"], classes["t2k"]["hi"]) == (1921, 2048)
    assert (classes["d8k"]["lo"], classes["d8k"]["hi"]) == (8065, 8192)
    assert sorted((i["class"], i["out"], i["n"]) for i in t["multiset"]) == [
        ("d8k", 512, 3), ("d8k", 768, 3), ("d8k", 1024, 2),
        ("t2k", 512, 6), ("t2k", 768, 5), ("t2k", 1024, 5)]
    items = tfc.expand_multiset(t)
    assert len(items) == 24
    assert sum(i["out"] for i in items) / 24 == pytest.approx(746.67, abs=0.01)
    assert 4000 < sum(i["prompt"] for i in items) / 24 < 4100
    # a prompt is 4 or 16 windows of 512; every context fits a row
    width = cell.cell["server_args"]["prefill-chunk"]
    assert {-(-i["prompt"] // width) for i in items} == {4, 16}
    assert max(i["prompt"] + i["out"] for i in items) == 9216 <= \
        cell.cell["server_args"]["max-seq-len"]
    # the share of steps that carry a window, whatever a step costs:
    # rows x windows a request over tokens a request
    windows = sum(-(-i["prompt"] // width) for i in items) / 24
    share = 32 * windows / (sum(i["out"] for i in items) / 24)
    assert 0.25 <= share / (1 + share) <= 0.45 and 0.33 < share < 0.36
    # the probe is an item of the multiset (harness/traffic.py)
    assert t["probe"] == {"class": "t2k", "out": 512}
    assert t["warmup"] == [{"class": "t2k", "out": 8},
                           {"class": "d8k", "out": 8}]
    assert (t["warmup_wave"], t["warmup_wave_out"]) == (32, 8)
    assert "assistant" in t["who"] and "Prefix pages" in t["why"]
    # the mix builds under a seed past 2**31
    mix = tfc.Mix(t, 2147484999, cell.model_config["vocab_size"])
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
    assert got["kda_chunked_share_pct"] == {"value": 80.0, "unit": "%"}
    assert got["kda_state_rows_per_step"]["value"] == pytest.approx(31.0)
    assert got["ttft_p50_ms.tok"]["value"] == pytest.approx(2000.0)
    assert got["mixed_step_ms.tok"]["value"] == pytest.approx(50.0)
    assert "kda_step_roofline" not in got                 # no capture
    assert "mixed_step_device_ms.tok" not in got
    # an old cell does not report the new metrics
    old = spec.Cell("nemotron3s.agent-closed", str(bench),
                    str(tmp_path / "BENCHMARK.json"))
    assert not set(NEW) & set(old.names("per_layer"))


# -- the roofline's counts, by hand -----------------------------------------


def test_a_row_and_layers_state_is_two_mebibytes():
    c = cfg()
    assert roof.kda_dims(c) == {"H": 32, "dk": 128, "dv": 128, "L_kda": 10}
    assert roof.state_bytes(c) == 32 * 128 * 128 * 4 == 2 * 2 ** 20
    # a decode step of 32 rows: 320 (row, layer) pairs read and written,
    # 1.34 GB, 1.64 ms at the HBM rate
    assert roof.step_need_bytes(c, 320) == 320 * 2 * 2 * 2 ** 20
    assert roof.step_least_s(c, 320, PEAK) == pytest.approx(1.639e-3,
                                                            rel=1e-3)


def test_a_chunked_token_at_a_stated_chunk_of_64():
    c = cfg()
    nbytes, ops = roof.chunk_need(c, 1.0)
    # per head: scores K+K-^T, Q+K-^T and T(beta K+): 3 x 2 x 64 x 128;
    # T(beta V) and P U: 2 x 2 x 64 x 128; the solve 2 x 64^2 / 3; the
    # three products with the state 3 x 2 x 128 x 128
    per_head = (3 * 16384 + 2 * 16384 + 2 * 4096 / 3 + 3 * 32768)
    assert ops == pytest.approx(32 * per_head) and 5.85e6 < ops < 5.86e6
    # q, k, v in and o out at 2 bytes, the decay (128 a head) and beta
    # in float32
    assert nbytes == 32 * (4 * 128 * 2 + 129 * 4) == 49280
    # the count does not follow the chunk the program picks
    assert roof.CHUNK == 64
    assert roof.chunk_need(c, 1.0, chunk=16)[1] < ops
    # bound by the bytes on a v5e: 60 ns against 30 ns
    assert nbytes / 819e9 > ops / 197e12
    assert roof.chunk_least_s(c, 512 * 10, PEAK) == pytest.approx(
        5120 * 49280 / 819e9)


# -- the reader, on a made-up run -------------------------------------------


class FakeCell:
    cell = {"shape": {"kv_bytes": 2, "weight_bytes": 1, "mixed_width": 512}}


def fake_run(**over):
    # a decode record: 32 rows stepped in 10 layers; a mixed record: a
    # window of 512 chunked, 30 rows stepped
    decode = {"kind": "decode", "compiled": False, "wall_s": 0.015,
              "rows": 32, "kda_tokens_stepped": 320.0,
              "kda_tokens_chunked": 0.0, "kda_state_rows": 320.0,
              "step": 2, "ts": 11.0}
    mixed = {"kind": "mixed", "compiled": False, "wall_s": 0.050,
             "rows": 31, "tokens_computed": 544,
             "kda_tokens_stepped": 300.0, "kda_tokens_chunked": 5120.0,
             "kda_state_rows": 310.0, "step": 1, "ts": 10.0}
    records = [{"failed": False, "finished": True, "class": "t2k",
                "rid": 100 + i, "prompt": 2000, "t_send": 1.0 + i,
                "token_t": [1.0 + i + 0.5 * (i + 2)]} for i in range(5)]
    metrics_0 = {"cake_kda_tokens_chunked_total": 100.0,
                 "cake_kda_tokens_stepped_total": 50.0,
                 "cake_kda_state_rows_total": 10.0}
    metrics_1 = {"cake_kda_tokens_chunked_total": 100.0 + 4000.0,
                 "cake_kda_tokens_stepped_total": 50.0 + 1000.0,
                 "cake_kda_state_rows_total": 10.0 + 4 * 310.0}
    run = {"model_config": cfg(), "cell": FakeCell(), "server_args": {},
           "device": {"kind": "TPU v5 lite"}, "health": {"decode_slots": 32},
           "steps": [decode] * 3 + [mixed],
           "all_steps": [mixed, decode],
           "records": records, "t0": 0.0, "t1": 48.0, "wall_0": 0.0,
           "wall_1": 48.0, "turnarounds": [], "healthy_s": 1.0,
           "warmup_s": 2.0, "metrics_0": metrics_0, "metrics_1": metrics_1,
           "metrics_2": {}, "trace": None}
    run.update(over)
    return run


def test_counters_and_the_clients_clock():
    got = load_reader().read(fake_run())
    assert got["kda_chunked_share_pct"] == pytest.approx(80.0)
    # 1,240 (row, layer) pairs over 10 layers and 4 steps
    assert got["kda_state_rows_per_step"] == pytest.approx(31.0)
    for name in ("kda_step_roofline", "kda_chunk_roofline",
                 "dev_share_kda_pct"):
        assert name not in got
    assert not [k for k in got if k.startswith(("mixed_step", "ttft_"))]


def test_the_latent_layers_metrics_divide_by_the_latent_layers():
    """`mla_decode_attn_roofline` and `mla_keys_per_decode_row` (joined
    at PR 55) read a record's `mla_keys_attended`, which this family
    sums over its LATENT layers: 2 of 12 ((i + 1) mod `layer_group_size`
    = 0), not `num_hidden_layers`; a config without the key (DeepSeek-
    V2) keeps every layer."""
    from harness import mla_dense_roofline
    c = cfg()
    assert (c["num_hidden_layers"], c["layer_group_size"]) == (12, 6)
    assert mla_dense_roofline.dims(c) == {"L": 2, "H": 32, "row": 576,
                                          "value": 512}
    dsv2 = spec.Cell("dsv2.code-closed").model_config
    assert "layer_group_size" not in dsv2
    assert mla_dense_roofline.dims(dsv2)["L"] == dsv2["num_hidden_layers"]
    # 32 heads: a key's 69,632 operations take 0.35 ns at the bf16 peak
    # and its 1,152 bytes 1.41 ns at the HBM rate: bound by bytes
    assert mla_dense_roofline.ops_per_pair(c) == 32 * (576 + 512) * 2
    assert mla_dense_roofline.least_s(c, 1e6, 1e6, PEAK) == pytest.approx(
        1e6 * 1152 / 819e9)
    # a decode record of 32 rows at 6,000 keys in each of the 2 layers
    path = os.path.join(spec.BENCH_DIR, "layer_metrics", "mla_dense.py")
    s = importlib.util.spec_from_file_location("layer_metric_mla_dense", path)
    mla_dense = importlib.util.module_from_spec(s)
    s.loader.exec_module(mla_dense)
    decode = {"kind": "decode", "compiled": False, "wall_s": 0.02,
              "rows": 32, "attn_pages_table": 32 * 76,
              "mla_keys_attended": 2 * 32 * 6000.0}
    run = fake_run(steps=[decode] * 3, server_args={
        "max-slots": 32, "max-seq-len": 9728, "kv-page-size": 128})
    assert mla_dense.counters(run)["mla_keys_per_decode_row"] == \
        pytest.approx(6000.0)


def test_another_program_yields_nothing():
    """The `workloads` lists are the gate: no other cell lists a metric
    of this file; and this model's config on a program without the
    counters (the parent commit's, had it served the config) grows
    nothing and does not raise."""
    names = {m["name"] for m in load_reader().METRICS}
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    for w in doc["workloads"]:
        if w["name"] != CELL:
            assert not names & {m["name"]
                                for m in spec.Cell(w["name"]).per_layer}
    run = fake_run(steps=[{"kind": "decode", "compiled": False, "rows": 32,
                           "wall_s": 0.03}], records=[], metrics_0={},
                   metrics_1={}, all_steps=[])
    assert {k: v for k, v in load_reader().read(run).items()
            if v is not None} == {}


def op(scope, start, dur):
    return {"name": "%fusion.7 = f32[32,32,128,128]{3,2,1,0} fusion(...)",
            "start_ns": start, "dur_ns": dur,
            "stats": {"tf_op": f"jit(step)/layers/attn/{scope}/mul:"}}


def capture(ops, fetches):
    """Planes of a capture: device 0's ops, and the engine thread's
    `cake/fetch` spans as (start, end, step)."""
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "engine", "events": [
            {"name": "cake/fetch", "start_ns": a, "dur_ns": b - a,
             "stats": {"step": step}} for a, b, step in fetches]}]}]


def test_a_steps_need_is_its_own_records():
    """An op under the step's (or the chunk's) scopes belongs to the
    step whose fetch is the first to end after it; the capture's first
    record and the ops past its last fetch are left out, time and need
    alike; a record's need is its own counters'."""
    reader = load_reader()
    c = cfg()
    base, mixed = fake_run()["steps"][0], fake_run()["steps"][-1]
    steps = [dict(base, step=4), dict(base, step=5),
             dict(mixed, step=6), dict(base, step=7, kda_tokens_stepped=10.0)]
    ops = ([op("kda_step", 10, 50)]                        # record 4: cut
           + [op("kda_step", 110, 1000), op("kda_state", 1200, 3000)]   # 5
           + [op("kda_step", 5000, 2000), op("kda_chunk", 8000, 40000),
              op("kda_gate", 9000, 7)]                     # 6
           + [op("kda_step", 90000, 999)])                 # past the last
    planes = capture(ops, [(90, 100, 4), (4500, 4600, 5), (60000, 60100, 6)])
    run = fake_run(steps=steps, all_steps=steps)
    scoped = [(e, e["dur_ns"], e["stats"]["tf_op"].rstrip(":").split("/"))
              for e in ops]
    got = reader.rooflines(run, planes, scoped)
    need = roof.step_least_s(c, 320.0, PEAK) + roof.step_least_s(
        c, 300.0, PEAK)
    assert got["kda_step_roofline"] == pytest.approx(
        100.0 * need / (6000 / 1e9))
    assert got["kda_chunk_roofline"] == pytest.approx(
        100.0 * roof.chunk_least_s(c, 5120.0, PEAK) / (40000 / 1e9))
    # fewer than two fetch spans, or a record gone: nothing
    assert reader.rooflines(run, capture(ops, [(90, 100, 4)]), scoped) == {}
    assert reader.rooflines(fake_run(steps=steps[:1], all_steps=steps[:1]),
                            planes, scoped) == {}
