"""The Keye-VL-2.0 configuration, its cell and its per-layer metrics as
shipped: found by name, in agreement with BENCHMARK.json and with the
catalog's published numbers, the reference's copy, the counts of
`dsa_gqa_roofline.py` at the published sizes, the reader on a made-up
run, and every JOINED metric's reader on this cell's config.json."""

import importlib.util
import json
import os

import pytest

from harness import dsa_gqa_roofline as roof
from harness import spec

CELL = "keyevl2.longctx-closed"
CONFIG = "keye-vl-2.0-lm-int8-8of48"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog's `config` for Keye-VL-2.0-30B-A3B (model-configs guide)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "max_window_layers", "eos_token_id",
           "vision_config"]
NEW = ["dsa_gqa_attn_roofline", "dev_share_dsa_gather_pct",
       "dev_share_gqa_proj_pct", "dsa_keys_per_decode_row",
       "dsa_keys_scanned_per_decode_row"]
NOT_JOINED = {"dsa_index_reuse_pct", "moe_experts_roofline",
              "mla_attn_roofline", "dev_share_mla_proj_pct",
              "mla_window_pages_per_fold", "moe_held_rows_share_pct",
              # the capture lies 2-5 s into the window, where the ramp's
              # prompts still queue: it holds no decode-only step (as
              # dots3's and dsv2's; the driver's first check refused the
              # name here for that)
              "decode_step_device_ms"}


def load_reader(fname="dsa_gqa.py"):
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
    assert not [k for k in REDUCED
                if k.endswith(("_dim", "_rank", "_size"))]
    assert (c["num_hidden_layers"], c["max_window_layers"],
            c["eos_token_id"]) == (8, 8, 151936)
    assert "vision_config" not in c and "audio_config" not in c
    assert len(cell.cell["source"]) <= 200


def test_benchmark_json_entries_match_the_cells_files():
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    cell = spec.Cell(CELL)
    assert entry == b["configs"][-1] and entry["reduced"] == REDUCED
    assert entry["source"] == cell.cell["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}/config.json"
    work = b["workloads"][-1]
    assert (work["name"], work["config"], work["traffic"],
            work["chips"]) == (CELL, CONFIG, "longctx-closed", 1)
    assert len(work["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(b["workloads"]) == 13 and len(b["per_layer"]) <= 120
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    assert len(json.dumps(b, indent=1)) < 64 * 1024


def test_cell_reports_what_the_issue_lists():
    b = bench()
    mine = {m["name"] for m in b["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) <= mine and not (NOT_JOINED & mine)
    for name in ("dsa_selected_share_pct", "dev_share_indexer_pct",
                 "mixed_step_ms.tok", "mixed_step_device_ms.tok",
                 "ttft_p50_ms.tok", "rows_busy_pct", "pages_in_use_pct",
                 "dev_share_moe_route_pct", "moe_rows_padded_pct",
                 "moe_expert_load_max_over_mean", "dev_share_attn_pct",
                 "stream_writer_share_pct"):
        assert name in mine, name
    for m in b["per_layer"][-len(NEW):]:
        assert m["name"] in NEW and m["workloads"] == [CELL]
        assert m["moves"] == "out_tok_s"
    judged = {m["name"] for m in b["end_to_end"]
              if CELL in m.get("workloads", (CELL,))}
    assert judged == {"tpot_p50_ms", "out_tok_s", "setup_s"}
    # no metric is named after a cell or a traffic file
    for m in b["per_layer"]:
        assert "keye" not in m["name"] and "longctx" not in m["name"]


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
                         "keye_vl2.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        assert a.read() == b.read()


def test_traffic_weights_equal_the_multiset():
    mix = spec.Cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["ramp_s"]) == ("closed", 8, 16)
    total = sum(m["n"] for m in mix["multiset"])
    assert total == 12
    for c in mix["prompt_classes"]:
        share = sum(m["n"] for m in mix["multiset"]
                    if m["class"] == c["name"]) / total
        assert share == pytest.approx(c["weight"])
    assert {c["name"]: (c["lo"], c["hi"]) for c in mix["prompt_classes"]} \
        == {"p8k": (7681, 8192), "p16k": (15873, 16384)}
    outs = sorted((m["class"], m["out"], m["n"]) for m in mix["multiset"])
    # (p16k: ISSUE 60's stated fallback from p32k, cell.json says why)
    assert outs == [("p16k", 128, 1), ("p16k", 192, 2), ("p16k", 256, 1),
                    ("p8k", 128, 3), ("p8k", 192, 3), ("p8k", 256, 2)]
    assert "TAKEN" in spec.Cell(CELL).cell["fallback_taken"]
    assert mix["probe"] == {"class": "p8k", "out": 128}
    # the longest context fits a row, and every row its longest at once
    sa = spec.Cell(CELL).cell["server_args"]
    assert 32768 + 256 <= sa["max-seq-len"] and sa["max-seq-len"] % 512 == 0
    assert sa["kv-pages"] * sa["kv-page-size"] >= (
        sa["max-slots"] * sa["max-seq-len"])


def test_dims_and_a_decode_rows_need():
    d = roof.dsa_gqa_dims(cfg())
    assert d == {"L": 8, "H": 32, "KV": 4, "hd": 128, "topk": 2048}
    # one row, one layer, 2,048 selected keys: 2,048 x 32 x 128 x 4 =
    # 33.6 MFLOP; K and V of 2,048 rows = 4 MiB: bandwidth-bound, 5.1 us
    nbytes, ops = roof.attn_need(d, 2048, 2048, 1)
    assert ops == 2048 * 32 * 128 * 4
    assert nbytes == 2 * 2048 * 512 * 2 + 2 * 32 * 128 * 2
    assert roof.attn_least_s(d, 2048, 2048, 1, PEAK) == pytest.approx(
        nbytes / 819e9)
    # a 512-token window past topk: compute-bound on its selected pairs
    pairs = 512 * 2048
    assert roof.attn_least_s(d, pairs, 16000, 512, PEAK) == pytest.approx(
        4 * 32 * 128 * pairs / 197e12)
    # a config this file does not know
    assert roof.dsa_gqa_dims({"model_type": "llama"}) is None
    assert roof.dsa_gqa_dims({"sa_config": {"topk": 8}}) is None


def fake_run(cell=None, model_config=None):
    cell = cell or spec.Cell(CELL)
    series = {
        "cake_gqa_rows_single_total": (10.0, 1010.0),
        "cake_dsa_keys_single_total": (0.0, 8 * 1000 * 2048.0),
        "cake_dsa_keys_scanned_single_total": (0.0, 8 * 1000 * 16000.0),
        "cake_dsa_keys_visible_total": (0.0, 1e9),
        "cake_dsa_keys_selected_total": (0.0, 1.6e8),
        "cake_moe_rows_total": (0.0, 1e6),
        "cake_moe_rows_routed_total": (0.0, 1e6)}
    return {
        "cell": cell,
        "model_config": (model_config if model_config is not None
                         else cell.model_config),
        "device": {"kind": "TPU v5 lite"},
        "metrics_0": {k: v[0] for k, v in series.items()},
        "metrics_1": {k: v[1] for k, v in series.items()},
        "steps": [
            {"kind": "mixed", "compiled": False, "wall_s": 0.050,
             "step": 1},
            {"kind": "decode", "compiled": False, "wall_s": 0.012,
             "step": 2}],
        "records": [
            {"class": "p8k", "t_send": 1.0, "token_t": [1.4, 1.5],
             "failed": False, "finished": True},
            {"class": "p16k", "t_send": 2.0, "token_t": [3.0, 3.1],
             "failed": False, "finished": True}],
        "t0": 0.0, "t1": 10.0, "trace": None}


def test_counters_read_the_regime():
    got = load_reader().read(fake_run())
    assert got["dsa_keys_per_decode_row"] == pytest.approx(2048.0)
    assert got["dsa_keys_scanned_per_decode_row"] == pytest.approx(16000.0)
    assert "dsa_gqa_attn_roofline" not in got           # no capture
    assert "dev_share_dsa_gather_pct" not in got


def test_another_program_yields_nothing():
    reader = load_reader()
    run = fake_run()
    run["metrics_0"] = run["metrics_1"] = {}
    assert reader.read(run) == {}
    for other in ("mistral7b.decode-long", "glm52.longdoc-closed",
                  "kexaone.longreply-closed"):
        assert reader.read(fake_run(cell=spec.Cell(other))) == {}
    run = fake_run()
    run["trace"] = {"xplane": "/nonexistent.xplane.pb"}
    assert "dsa_gqa_attn_roofline" not in reader.read(run)


def test_a_records_need_is_its_own_counters():
    reader = load_reader()
    d = roof.dsa_gqa_dims(cfg())
    rec = {"gqa_rows_single": 7, "tokens_real": 7 + 512,
           "dsa_keys_single": 8 * 7 * 2048,
           "dsa_keys_selected": 8 * (7 * 2048 + 512 * 2048),
           "dsa_rows_distinct": 8 * (7 * 2048 + 12000)}
    assert reader.record_need(rec, "cake_decode_attn", d, PEAK, 2) == \
        pytest.approx(roof.attn_least_s(d, 8 * 7 * 2048, 8 * 7 * 2048,
                                        8 * 7, PEAK))
    assert reader.record_need(rec, "cake_mixed_attn", d, PEAK, 2) == \
        pytest.approx(roof.attn_least_s(d, 8 * 512 * 2048, 8 * 12000,
                                        8 * 512, PEAK))
    # a decode step has no window; a record without the counters: None
    assert reader.record_need(dict(rec, tokens_real=7,
                                   dsa_keys_selected=8 * 7 * 2048),
                              "cake_mixed_attn", d, PEAK, 2) == 0.0
    assert reader.record_need({"kind": "decode"}, "cake_decode_attn", d,
                              PEAK, 2) is None


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
    for trace in (None, {"xplane": "/nonexistent.xplane.pb", "kernels": [
            {"device": 0, "dur_s": 1e-4,
             "name": "%cake_decode_attn.1 = bf16[8,1,32,128]{3,2,1,0} "
                     "custom-call(...)"}]}):
        run = fake_run(cell, Strict(cell.model_config))
        run["trace"] = trace
        out = spec.read_layer_metrics(cell, run, found)
        assert missed == []
        assert out["dsa_selected_share_pct"]["value"] == pytest.approx(16.0)
        assert out["dsa_keys_per_decode_row"]["value"] == pytest.approx(2048)
