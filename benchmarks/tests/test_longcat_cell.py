"""The LongCat-Flash configuration, its cell and its per-layer metrics
as shipped: found by name, in agreement with BENCHMARK.json and with the
catalog's published numbers, the reference's copy, the mapping of
`scmoe_roofline.py`, the reader on a made-up run of this config, and
every JOINED metric's reader on this cell's config.json. (Nothing here
pins the LAST entry of a list or a count of cells: the next PR appends.)"""

import importlib.util
import json
import os

import pytest

from harness import mla_dense_roofline as dense_roof
from harness import scmoe_roofline as roof
from harness import spec

CELL = "longcat.longreply-closed"
CONFIG = "longcat-flash-int8-share32"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog's `config` for LongCat-Flash-Chat (model-configs guide)
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}
REDUCED = ["num_layers", "n_routed_experts", "vocab_size", "eos_token_id"]
NEW = ["scmoe_decode_attn_roofline", "scmoe_window_attn_roofline",
       "moe_zero_pairs_pct", "dev_share_shortcut_moe_pct"]
# their readers ask the config for `num_hidden_layers`,
# `num_experts_per_tok` or `intermediate_size`, which this one spells
# `num_layers`, `moe_topk`, `expert_ffn_hidden_size` (ISSUE 65)
NOT_JOINED = {"mla_decode_attn_roofline", "mla_dense_window_roofline",
              "dev_share_mla_attn_pct", "mla_keys_per_decode_row",
              "moe_group_held_share_pct", "moe_rows_padded_pct",
              "moe_expert_load_max_over_mean", "dev_share_moe_route_pct"}


def load_reader(fname="scmoe.py"):
    path = os.path.join(spec.BENCH_DIR, "layer_metrics", fname)
    s = importlib.util.spec_from_file_location(
        "layer_metric_" + fname[:-3], path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def cfg():
    return spec.Cell(CELL).model_config


def bench():
    return spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))


def test_shipped_configuration_is_the_published_one_but_for_reduced():
    cell = spec.Cell(CELL)
    c = cell.model_config
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert c[key] == value, key
    assert cell.cell["reduced"] == REDUCED
    assert set(cell.cell["reduced_why"]) == set(REDUCED)
    assert (c["num_layers"], c["n_routed_experts"], c["vocab_size"],
            c["eos_token_id"]) == (7, 16, 16384, 16384)
    assert (c["n_routed_experts_total"], c["first_routed_expert"]) == (512, 0)
    assert c["published"]["num_layers"] == PUBLISHED["num_layers"]
    # no key of another family's spelling is aliased in
    assert not {"num_hidden_layers", "num_experts_per_tok",
                "intermediate_size", "moe_intermediate_size"} & set(c)
    assert len(cell.cell["source"]) <= 200
    assert [a[:3] for a in cell.cell["assumed"]] == [
        f"({x})" for x in "abcdefghi"]
    for key in ("deployment", "not_served", "departures_in_the_served_path",
                "fallbacks", "fallback_taken", "rehearse", "windows_why",
                "share_vs_deployment"):
        assert cell.cell[key], key
    sa = cell.cell["server_args"]
    assert 8192 + 1024 <= sa["max-seq-len"] and sa["max-seq-len"] % 512 == 0
    assert sa["require-model-type"] == c["model_type"] == "longcat_flash"
    assert cell.cell["expect_impl"] == {"mixed": "paged-mla-pallas",
                                        "decode": "paged-mla-pallas"}
    toy = cell.cell["rehearse"]["config"]
    assert (toy["n_routed_experts"], toy["n_routed_experts_total"],
            toy["zero_expert_num"]) == (2, 16, 8)


def test_benchmark_json_entries_match_the_cells_files():
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    cell = spec.Cell(CELL)
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cell.cell["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}/config.json"
    work = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "longreply-closed", 1)
    assert len(work["why"]) <= 200 and len(entry["why"]) <= 200
    for said in ("0.34", "14 latent sublayers at 64 heads", "zero",
                 "attention over its share; an expert sees 0.5 tokens a "
                 "decode step (deployment: 16)"):
        assert said in work["why"], said
    assert len(b["per_layer"]) <= 128 and len(b["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536


def test_cell_reports_what_the_issue_lists():
    b = bench()
    mine = {m["name"] for m in b["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) <= mine and not (NOT_JOINED & mine)
    for name in ("mixed_step_ms.tok", "mixed_step_device_ms.tok",
                 "ttft_p50_ms.tok", "decode_step_device_ms", "rows_busy_pct",
                 "pages_in_use_pct", "mixed_step_share_pct",
                 "decode_attn_pages_live_pct", "mla_window_pages_per_fold",
                 "dev_share_attn_pct", "dev_share_ffn_pct",
                 "dev_share_mla_proj_pct", "moe_held_rows_share_pct",
                 "host_emit_p50_ms", "stream_writer_share_pct",
                 "boundary_gap_p50_ms", "emit_us_per_token"):
        assert name in mine, name
    # every list DeepSeek-V2's cell is on, but the eight above
    dsv2 = {m["name"] for m in b["per_layer"]
            if "dsv2.code-closed" in m.get("workloads", ())}
    assert dsv2 - mine == NOT_JOINED
    for m in b["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
        # no metric is named after a cell or a traffic file
        assert "longcat" not in m["name"] and "longreply" not in m["name"]
    judged = {m["name"] for m in b["end_to_end"]
              if CELL in m.get("workloads", (CELL,))}
    assert judged == {"tpot_p50_ms", "out_tok_s", "setup_s"}


def test_reader_agrees_with_benchmark_json():
    declared = {d["name"]: d for d in load_reader().METRICS}
    assert list(declared) == NEW
    for m in bench()["per_layer"]:
        if m["name"] in declared:
            for key in ("unit", "layer", "moves", "source"):
                assert declared[m["name"]][key] == m[key]


def test_reference_copy_is_the_programs():
    here = os.path.join(spec.BENCH_DIR, "configs", CONFIG, "reference.py")
    there = os.path.join(spec.ROOT, "cake_tpu", "models", "reference",
                         "longcat_flash.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        assert a.read() == b.read()


def test_traffic_is_the_file_that_stands():
    mix = spec.Cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["ramp_s"]) == ("closed", 32, 16)
    assert {c["name"]: (c["lo"], c["hi"]) for c in mix["prompt_classes"]} \
        == {"t2k": (1921, 2048), "d8k": (8065, 8192)}
    for other in ("ling3.longreply-closed", "kexaone.longreply-closed"):
        assert spec.Cell(other).traffic == mix


def test_the_mapping_counts_two_latent_layers_a_layer():
    c = cfg()
    mapped = roof.as_mla_dense_config(c)
    assert dense_roof.dims(mapped) == {"L": 14, "H": 64, "row": 576,
                                       "value": 512}
    assert dense_roof.ops_per_pair(mapped) == 64 * (576 + 512) * 2 == 139264
    assert dense_roof.bytes_per_key(mapped) == 1152
    # at 64 heads a key costs 0.71 ns by the bf16 peak and 1.41 ns by HBM
    assert dense_roof.least_s(mapped, 1e6, 1e6, PEAK) == pytest.approx(
        1e6 * 1152 / 819e9)
    assert "num_hidden_layers" not in c
    # a config this file does not know
    for other in ("dsv2.code-closed", "mistral7b.decode-long",
                  "ling3.longreply-closed"):
        assert roof.as_mla_dense_config(spec.Cell(other).model_config) is None
    assert roof.as_mla_dense_config({"num_layers": 7}) is None


# -- the reader, on a made-up run of this config ----------------------------


def fake_run(cell=None, model_config=None, **over):
    cell = cell or spec.Cell(CELL)
    # a decode record: 32 rows of 4,400 keys over 14 latent layers; a
    # mixed record: one dispatch of 31 single-token rows and a window
    decode = {"kind": "decode", "compiled": False, "wall_s": 0.030,
              "rows": 32, "attn_pages_table": 32 * 76,
              "mla_keys_attended": 14 * 32 * 4400.0, "step": 2, "ts": 11.0}
    mixed = {"kind": "mixed", "compiled": False, "wall_s": 0.100,
             "rows": 32, "tokens_computed": 544,
             "mla_keys_attended": 14 * 31 * 4400.0, "step": 1, "ts": 10.0,
             "rids": [7]}
    records = [{"failed": False, "finished": True, "class": "t2k",
                "rid": 7, "prompt": 2000, "t_send": 9.0, "token_t": [12.0]}]
    series = {"cake_moe_rows_routed_total": (600.0, 600.0 + 12 * 7 * 9000.0),
              "cake_moe_pairs_zero_total": (200.0, 200.0 + 4 * 7 * 9000.0),
              "cake_moe_rows_total": (10.0, 10.0 + 7 * 9000.0 / 4)}
    run = {"cell": cell,
           "model_config": (model_config if model_config is not None
                            else cell.model_config),
           "device": {"kind": "TPU v5 lite"}, "health": {"decode_slots": 32},
           "server_args": dict(cell.cell["server_args"]),
           "steps": [decode] * 3 + [mixed], "all_steps": [mixed, decode],
           "records": records, "t0": 0.0, "t1": 48.0, "wall_0": 0.0,
           "wall_1": 48.0, "turnarounds": [], "healthy_s": 1.0,
           "warmup_s": 2.0,
           "metrics_0": {k: v[0] for k, v in series.items()},
           "metrics_1": {k: v[1] for k, v in series.items()},
           "metrics_2": {}, "trace": None}
    run.update(over)
    return run


def kernel_op(name, start, dur, scope=""):
    return {"name": f"%{name}.3 = bf16[32,64,512]{{2,1,0}} "
                    "custom-call(...), custom_call_target="
                    "\"tpu_custom_call\"",
            "start_ns": start, "dur_ns": dur, "stats": {"tf_op": scope}}


def capture(ops, fetches):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "engine", "events": [
            {"name": "cake/fetch", "start_ns": a, "dur_ns": b - a,
             "stats": {"step": step}} for a, b, step in fetches]}]}]


def test_the_counter_reads_a_third_under_an_even_router():
    got = load_reader().read(fake_run())
    assert got == {"moe_zero_pairs_pct": pytest.approx(100.0 / 3)}


def test_the_capture_reads_both_kernels_and_the_scope():
    """Two decode-kernel events of record 2 (32 rows of 4,400 keys, one
    layer each) and one window event of record 1's dispatch, whose
    window is request 7's first (512 queries over 512 keys); a grouped
    matmul under `shortcut_moe` and an op under `ffn`."""
    reader = load_reader()
    run = fake_run()
    layers = "jit(step)/layers/"
    ops = [kernel_op("cake_mla_decode_attn", 100_000 + k, 5000,
                     layers + "attn/mla_attn") for k in (0, 6000)]
    ops.append(kernel_op("cake_mla_window_attn", 100, 50_000,
                         layers + "attn/mla_attn"))
    ops.append(kernel_op("cake_moe_gmm", 60_000, 3000,
                         layers + "ffn/shortcut_moe/experts"))
    ops.append({"name": "%fusion.9 = bf16[544,6144]{1,0} fusion(...)",
                "start_ns": 70_000, "dur_ns": 4000,
                "stats": {"tf_op": layers + "ffn"}})
    run["_planes"] = capture(ops, [(52_000, 55_000, 1),
                                   (120_000, 120_100, 2)])
    got = reader.read(run)
    mapped = roof.as_mla_dense_config(run["model_config"])
    keys = 2 * 32 * 4400
    assert got["scmoe_decode_attn_roofline"] == pytest.approx(
        100.0 * dense_roof.least_s(mapped, keys, keys, PEAK) / 10_000e-9)
    assert got["scmoe_window_attn_roofline"] == pytest.approx(
        100.0 * dense_roof.least_s(mapped, 512 * 512 - 512 * 511 / 2, 512,
                                   PEAK) / 50_000e-9)
    assert got["dev_share_shortcut_moe_pct"] == pytest.approx(
        100.0 * 3000 / (2 * 5000 + 50_000 + 3000 + 4000))
    assert got["moe_zero_pairs_pct"] == pytest.approx(100.0 / 3)
    # DeepSeek-V2's reader reads the same events as the same work
    dense = reader.dense_reader()
    assert dense.decode_roofline(dict(run, model_config=mapped),
                                 run["_planes"]) == pytest.approx(
        got["scmoe_decode_attn_roofline"])


def test_another_program_or_no_capture_yields_nothing():
    reader = load_reader()
    run = fake_run(metrics_0={}, metrics_1={})
    assert reader.read(run) == {}                       # no counter
    run["trace"] = {"xplane": "/nonexistent.xplane.pb"}
    assert reader.read(run) == {}                       # no capture
    for other in ("dsv2.code-closed", "mistral7b.decode-long",
                  "ling3.longreply-closed", "kexaone.longreply-closed"):
        cell = spec.Cell(other)
        assert reader.read(fake_run(cell=cell)) == {}
    # this config's program without the kernels or the scope
    run = fake_run(metrics_0={}, metrics_1={})
    run["_planes"] = capture([], [])
    assert {k: v for k, v in reader.read(run).items()
            if v is not None} == {}


def test_every_joined_metrics_reader_returns_on_this_config():
    """A reader that raises on a config key silences its whole file
    (README): run each file that declares a name this cell lists on a
    made-up run of this cell's config.json, untraced and with a capture
    that is gone, and hold it to asking for no key the config lacks."""
    missed = []

    class Strict(dict):
        def __missing__(self, key):
            missed.append(key)
            raise KeyError(key)

    found = spec.discover_layer_metrics()
    cell = spec.Cell(CELL)
    assert len({found[m["name"]][1] for m in cell.per_layer}) >= 10
    for trace in (None, {"xplane": "/nonexistent.xplane.pb", "kernels": []}):
        run = fake_run(cell, Strict(cell.model_config))
        run["trace"] = trace
        got = spec.read_layer_metrics(cell, run, found)
        assert missed == []
        # dsa.py's counters get through on this config
        assert got["moe_held_rows_share_pct"]["value"] == pytest.approx(
            100.0 / 48)
        assert got["moe_zero_pairs_pct"]["value"] == pytest.approx(100.0 / 3)
