"""The K-EXAONE configuration, its cell and its per-layer metrics as
shipped: found by name (in a temporary copy too), in agreement with
BENCHMARK.json and with the catalog's published numbers, the reference's
copy, the counts of `gqa_window_roofline.py` at the published sizes, and
the reader on a made-up run."""

import importlib.util
import os
import shutil

import pytest

from harness import gqa_window_roofline as roof
from harness import spec, traffic as tfc

CELL = "kexaone.longreply-closed"
CONFIG = "k-exaone-236b-int8-share8"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog's `config` for K-EXAONE-236B-A23B (model-configs guide),
# every key but the three per-layer lists (48 entries each: below)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432,
    "max_position_embeddings": 262144, "model_type": "exaone_moe",
    "moe_intermediate_size": 2048, "mtp_layer_types": ["full_attention"],
    "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "sliding_windows", "num_experts", "vocab_size",
           "num_nextn_predict_layers", "eos_token_id"]
NEW = ["gqa_window_attn_roofline", "gqa_full_attn_roofline",
       "dev_share_gqa_window_pct", "dev_share_gqa_full_pct",
       "gqa_window_pages_per_decode_row"]
# the plain readings of a cell judged by tokens, joined by list (until
# PR 55 this reader's three `.kexaone` names), and dots3's counter of
# the sliding layers' keys, which this model's step programs emit too
JOINED = {"mixed_step_ms.tok", "mixed_step_device_ms.tok",
          "ttft_p50_ms.tok", "swa_attended_share_pct"}


def load_reader(bench_dir=spec.BENCH_DIR):
    path = os.path.join(bench_dir, "layer_metrics", "gqa_window.py")
    s = importlib.util.spec_from_file_location("layer_metric_gqa_window",
                                               path)
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
        12, 16, 19200, 0, 19200)
    # the per-layer lists cut to the served layers: three whole periods
    assert c["layer_types"] == (["sliding_attention"] * 3
                                + ["full_attention"]) * 3
    assert c["sliding_windows"] == [128, 128, 128, 0] * 3
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 11
    # the floors: a whole period and >= 4 expert layers, >= 8 experts,
    # >= an eighth of the vocabulary
    assert c["num_hidden_layers"] % 4 == 0
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["num_experts"] >= 8
    assert c["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert (c["num_experts_total"], c["first_routed_expert"]) == (128, 0)
    assert c["published"]["num_hidden_layers"] == 48
    assert c["published"]["num_experts"] == 128
    assert cell.cell["source"] == (
        "https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/"
        "config.json")
    assumed = cell.cell["assumed"]
    assert [a[:3] for a in assumed] == [f"({x})" for x in "abcdefg"]
    for said in ("pre-norm", "EXAONE 4.0", "sliding layers only",
                 "counts the query", "choice only", "lower index",
                 "half-split", "seeded draw"):
        assert said in " ".join(assumed), said
    assert "multi-token-prediction" in cell.cell["not_served"]
    for said in ("32 chips", "4 pipeline stages", "experts 0-15",
                 "1/8 of the vocabulary"):
        assert said in cell.cell["deployment"], said
    args = cell.cell["server_args"]
    assert args["require-model-type"] == "exaone_moe"
    assert (args["quant"], args["max-slots"], args["max-seq-len"],
            args["kv-pages"], args["kv-page-size"], args["prefill-chunk"],
            args["paged-attn"]) == ("int8", 32, 9728, 1792, 128, 512,
                                    "pallas")
    assert args["max-seq-len"] % args["prefill-chunk"] == 0
    # the fixed cycle's worst case (16 rows at 73 pages, 16 at 25) fits
    assert 16 * 73 + 16 * 25 <= args["kv-pages"]
    assert cell.cell["expect_impl"] == {"mixed": "paged-swa-pallas",
                                        "decode": "paged-swa-pallas"}
    assert cell.cell["shape"] == {"weight_bytes": 1, "kv_bytes": 2,
                                  "mixed_width": 512, "stages": 1, "tp": 1}


def test_the_toy_and_the_shipped_config_both_parse():
    from cake_tpu.models.llama.config import load_config_dict
    cell = spec.Cell(CELL)
    c = load_config_dict(cell.model_config)
    assert c.family.impl == "paged-swa-"
    assert (len(c.sliding_layers), len(c.full_layers)) == (9, 3)
    assert c.window_ring_pages(128, 512) == 6
    toy = load_config_dict(dict(cell.model_config,
                                **cell.cell["rehearse"]["config"]))
    assert toy.indexer_types == ("sliding", "sliding", "sliding",
                                 "full") * 2
    assert (toy.num_local_experts, toy.n_routed_experts_total) == (4, 16)


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
    assert "2 tokens" in work["why"] and "16" in work["why"]
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
                 "moe_held_rows_share_pct",
                 "dev_share_moe_route_pct", "moe_rows_padded_pct",
                 "moe_expert_load_max_over_mean", "mixed_steps_chained_pct",
                 "boundary_admit_p50_ms", "rows_busy_pct",
                 "pages_in_use_pct", "mixed_step_share_pct",
                 "host_build_p50_ms", "host_emit_p50_ms",
                 "loop_uncovered_pct", "dev_share_attn_pct",
                 "dev_share_ffn_pct", "idle_attributed_pct",
                 "decode_steps_chained_pct", "chain_breaks_per_s",
                 "dev_share_sample_pct", "detok_ids_per_token",
                 "peak_hbm_gib", "compiles_in_window", "decode_step_ms"):
        assert name in layers, name
    # none whose `moves` the cell does not report, none of another
    # cell's own (swa_attended_share_pct joined at PR 55: swa.py's
    # `windowed()` asks for dots3_note's `swa_*` keys before it reads
    # that geometry, so this config costs the reader nothing)
    for name in ("moe_experts_roofline", "decode_attn_roofline",
                 "mixed_attn_roofline", "decode_step_roofline",
                 "swa_attn_roofline", "dsa_full_attn_roofline",
                 "dev_share_swa_attn_pct", "mla_window_pages_per_fold",
                 "dev_share_mla_proj_pct", "moe_group_held_share_pct",
                 "kda_step_roofline", "step_gap_p50_ms",
                 "mixed_step_ms", "mixed_step_device_ms"):
        assert name not in layers, name


def test_reader_agrees_with_benchmark_json():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in load_reader().METRICS}
    assert list(declared) == NEW
    listed = {m["name"]: m for m in doc["per_layer"]}
    for name in NEW:
        m = listed[name]
        assert m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for key in ("unit", "layer", "moves", "source"):
            assert declared[name][key] == m[key], (name, key)
        if name.endswith("_roofline"):
            assert m["unit"] == "%" and m["layer"] == "kernels"
            assert m["better"] == "higher"
        assert m["moves"] == "out_tok_s"


def test_reference_copy_is_the_programs():
    here = os.path.join(spec.BENCH_DIR, "configs", CONFIG, "reference.py")
    there = os.path.join(spec.ROOT, "cake_tpu", "models", "reference",
                         "exaone_moe.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        assert a.read() == b.read()


def test_traffic_is_the_file_that_is_there():
    cell = spec.Cell(CELL)
    t = cell.traffic
    assert (t["loop"], t["clients"], t["ramp_s"]) == ("closed", 32, 16)
    items = tfc.expand_multiset(t)
    assert len(items) == 24
    width = cell.cell["server_args"]["prefill-chunk"]
    assert {-(-i["prompt"] // width) for i in items} == {4, 16}
    assert max(i["prompt"] + i["out"] for i in items) == 9216 <= \
        cell.cell["server_args"]["max-seq-len"]
    # the ring wraps: a d8k request's 9,216 positions are 72 logical
    # pages over 6 ring pages
    assert 9216 // 128 // 6 == 12
    mix = tfc.Mix(t, 2147484999, cell.model_config["vocab_size"])
    assert len(mix.warmup_items()) == 2


# -- the roofline's counts, by hand -----------------------------------------


def test_dims_and_a_decode_rows_band():
    c = cfg()
    assert roof.gqa_dims(c) == {"L_sliding": 9, "L_full": 3, "H": 64,
                                "KV": 8, "hd": 128, "window": 128}
    # one row, one sliding layer, a band of 128 keys: 128 pairs x 64
    # heads x 128 x 4 = 4.19 MFLOP; K and V of 128 keys = 512 KiB + the
    # query in and out 32 KiB: bandwidth-bound, 0.68 us
    nbytes, ops = roof.attn_need(c, 128, 128, 1)
    assert ops == 128 * 64 * 128 * 4
    assert nbytes == 2 * 128 * 1024 * 2 + 2 * 64 * 128 * 2
    assert roof.attn_least_s(c, 128, 128, 1, PEAK) == pytest.approx(
        nbytes / 819e9)
    # the same row in a full layer at 8k of context: 64 times the keys
    assert roof.attn_need(c, 8192, 8192, 1)[0] == (
        64 * 2 * 128 * 1024 * 2 + 2 * 64 * 128 * 2)


def test_a_windows_need_is_compute():
    c = cfg()
    # a 512-token window that ends at 4,096 in a full layer
    pairs = 512 * 4096 - 512 * 511 / 2
    assert roof.window_context(pairs, 512) == 4096
    nbytes, ops = roof.attn_need(c, pairs, 4096, 512)
    assert ops / 197e12 > nbytes / 819e9
    assert roof.attn_least_s(c, pairs, 4096, 512, PEAK) == pytest.approx(
        ops / 197e12)
    # under the band: 512 x 128 pairs at most, 2.1 GFLOP, 10.9 us of
    # the matrix unit; the window's queries in and results out are 16.8
    # MB beside 2.1 MB of keys and values: 23 us, bandwidth-bound
    assert 512 * 128 * 64 * 128 * 4 / 197e12 == pytest.approx(10.9e-6,
                                                              rel=1e-2)
    assert roof.attn_least_s(c, 512 * 128, 512, 512, PEAK) == \
        pytest.approx((2 * 512 * 1024 * 2 + 2 * 512 * 8192 * 2) / 819e9)


# -- the reader ---------------------------------------------------------------


def fake_run(cell=None, model_config=None, steps=None):
    cell = cell or spec.Cell(CELL)
    series = {
        "cake_gqa_rows_single_total": (100.0, 1100.0),
        "cake_gqa_window_pages_walked_total": (1800.0, 19800.0)}
    return {
        "cell": cell,
        "model_config": (model_config if model_config is not None
                         else cell.model_config),
        "device": {"kind": "TPU v5 lite"},
        "metrics_0": {k: v[0] for k, v in series.items()},
        "metrics_1": {k: v[1] for k, v in series.items()},
        "steps": steps if steps is not None else [
            {"kind": "mixed", "compiled": False, "wall_s": 0.050},
            {"kind": "mixed", "compiled": False, "wall_s": 0.070},
            {"kind": "decode", "compiled": False, "wall_s": 0.012}],
        "records": [
            {"class": "t2k", "t_send": 1.0, "token_t": [1.4, 1.5],
             "failed": False, "finished": True},
            {"class": "d8k", "t_send": 2.0, "token_t": [3.0, 3.1],
             "failed": False, "finished": True}],
        "t0": 0.0, "t1": 10.0, "trace": None}


def test_counters_and_the_clients_clock():
    got = load_reader().read(fake_run())
    # 18,000 pages over 1,000 rows and 9 sliding layers
    assert got["gqa_window_pages_per_decode_row"] == pytest.approx(2.0)
    assert "gqa_window_attn_roofline" not in got          # no capture
    assert not [k for k in got if k.startswith(("mixed_step", "ttft_"))]


def test_the_sliding_layers_counter_reads_through_dots3s_reader():
    """`swa_attended_share_pct` (joined at PR 55) is `swa.py`'s: this
    config has `sliding_attention` layers and none of dots3_note's
    `swa_*` keys, and a traced run's kernel events must not take the
    reader's whole output with a KeyError."""
    path = os.path.join(spec.BENCH_DIR, "layer_metrics", "swa.py")
    s = importlib.util.spec_from_file_location("layer_metric_swa", path)
    swa = importlib.util.module_from_spec(s)
    s.loader.exec_module(swa)
    assert "sliding_attention" in cfg()["layer_types"]
    assert not swa.windowed(cfg())
    assert swa.windowed(spec.Cell("dots3.longshort-closed").model_config)
    run = fake_run()
    run["metrics_0"] = {"cake_swa_keys_visible_total": 1000.0,
                        "cake_swa_keys_attended_total": 500.0}
    run["metrics_1"] = {"cake_swa_keys_visible_total": 101000.0,
                        "cake_swa_keys_attended_total": 4340.0}
    run["trace"] = {"xplane": "/nonexistent.xplane.pb", "kernels": [
        {"device": 0, "dur_s": 1e-4,
         "name": "%cake_decode_attn.1 = bf16[32,64,128]{2,1,0} "
                 "custom-call(...)"}]}
    got = swa.read(run)
    assert got["swa_attended_share_pct"] == pytest.approx(3.84)
    assert got["swa_attn_roofline"] is None
    assert got["dsa_full_attn_roofline"] is None
    assert not [k for k in got if k.startswith("dev_share_")]


def test_another_program_yields_nothing():
    reader = load_reader()
    # the parent's program: no such series
    run = fake_run()
    run["metrics_0"] = run["metrics_1"] = {}
    got = reader.read(run)
    assert "gqa_window_pages_per_decode_row" not in got
    # another family's configuration: nothing at all, and no exception
    other = spec.Cell("mistral7b.decode-long")
    assert reader.read(fake_run(cell=other)) == {}
    # a traced run whose capture is gone
    run = fake_run()
    run["trace"] = {"xplane": "/nonexistent.xplane.pb"}
    assert "gqa_full_attn_roofline" not in reader.read(run)


def test_a_records_need_is_its_own_counters():
    reader = load_reader()
    c = cfg()
    rec = {"gqa_rows_single": 31, "tokens_real": 31 + 512,
           "gqa_window_keys_single": 9 * 31 * 128,
           "gqa_full_keys_single": 3 * 31 * 4000,
           "swa_keys_attended": 9 * (31 * 128 + 512 * 128),
           "gqa_full_keys_attended": 3 * (31 * 4000 + 512 * 2048
                                          - 512 * 511 // 2)}
    need = {(k, n): reader.record_need(rec, k, n, c, PEAK, 2)
            for k in ("gqa_window", "gqa_full")
            for n in ("cake_decode_attn", "cake_mixed_attn")}
    # the single rows: nine layers of 31 bands, bandwidth-bound
    assert need["gqa_window", "cake_decode_attn"] == pytest.approx(
        9 * 31 * roof.attn_least_s(c, 128, 128, 1, PEAK))
    assert need["gqa_full", "cake_decode_attn"] == pytest.approx(
        3 * 31 * roof.attn_least_s(c, 4000, 4000, 1, PEAK))
    # the window: under the band its queries' bytes bound it, in a full
    # layer the pairs do
    assert need["gqa_window", "cake_mixed_attn"] == pytest.approx(
        9 * roof.attn_least_s(c, 512 * 128, 512, 512, PEAK))
    assert need["gqa_full", "cake_mixed_attn"] == pytest.approx(
        3 * (512 * 2048 - 512 * 511 // 2) * 64 * 128 * 4 / 197e12)
    # a decode record has no window; another family's record no counters
    alone = dict(rec, tokens_real=31, swa_keys_attended=9 * 31 * 128)
    assert reader.record_need(alone, "gqa_window", "cake_mixed_attn", c,
                              PEAK, 2) == 0.0
    assert reader.record_need({}, "gqa_full", "cake_decode_attn", c, PEAK,
                              2) is None


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
    assert got["gqa_window_pages_per_decode_row"] == {"value": 2.0,
                                                      "unit": "pages"}
    assert got["mixed_step_ms.tok"]["value"] == pytest.approx(60.0)
    assert got["ttft_p50_ms.tok"]["value"] == pytest.approx(700.0)
    # an old cell does not report the new metrics
    old = spec.Cell("ling3.longreply-closed", str(bench),
                    str(tmp_path / "BENCHMARK.json"))
    assert not set(NEW) & set(old.names("per_layer"))
