"""The Brumby configuration, its cell and its per-layer metrics as
shipped: found by name, in agreement with BENCHMARK.json and with the
catalog's published numbers, the reference's copy, the counts of
`retention_roofline.py` at the published sizes, the reader on a made-up
run, and every JOINED metric's reader on this cell's config.json.
(Nothing here pins the LAST entry of a list or a count of cells: the
next PR appends.)"""

import importlib.util
import json
import os

import pytest

from harness import retention_roofline as roof
from harness import spec

CELL = "brumby14b.longreply16-closed"
CONFIG = "brumby-14b-int8-10of40"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog's `config` for Brumby-14B-Base (model-configs guide)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "eos_token_id"]
NEW = ["dev_share_retention_pct", "retention_step_roofline",
       "decode_step_retention_roofline"]
# it has no K/V and its pages hold nothing; no attention kernel runs
NOT_JOINED = {"decode_attn_pages_live_pct", "pages_in_use_pct",
              # the capture lies 2-5 s into the window, where all sixteen
              # rows decode (their prompts went through during the ramp):
              # it holds no mixed step, as ZAYA's
              "mixed_step_device_ms.tok", "retention_window_roofline",
              "dev_share_kv_pct", "decode_attn_roofline",
              "mixed_attn_roofline", "kda_step_roofline",
              "mamba_step_roofline", "decode_step_state_roofline"}


def load_reader(fname="retention.py"):
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
    assert set(c) == set(PUBLISHED) | {"eos_token_id"}
    assert cell.cell["reduced"] == REDUCED
    assert set(cell.cell["reduced_why"]) == set(REDUCED)
    assert (c["num_hidden_layers"], c["eos_token_id"]) == (10, 151936)
    # no key is invented for the mechanism
    assert not [k for k in c if "deg" in k or "gate" in k or "chunk" in k]
    assert len(cell.cell["source"]) <= 200
    assert [a[:3] for a in cell.cell["assumed"][:6]] == [
        "(a)", "(b)", "(c)", "(d)", "(e)", "(f)"]
    for key in ("deployment", "not_served", "departures_in_the_served_path",
                "fallbacks", "fallback_taken", "chip_compare", "rehearse"):
        assert cell.cell[key], key


def test_benchmark_json_entries_match_the_cells_files():
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    cell = spec.Cell(CELL)
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cell.cell["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}/config.json"
    work = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "longreply16-closed", 1)
    assert len(work["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(b["per_layer"]) <= 128 and len(b["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    assert len(json.dumps(b, indent=1)) < 64 * 1024


def test_cell_reports_what_the_issue_lists():
    b = bench()
    mine = {m["name"] for m in b["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) <= mine and not (NOT_JOINED & mine)
    for name in ("mixed_step_ms.tok", "ttft_p50_ms.tok", "rows_busy_pct",
                 "mixed_step_share_pct", "dev_share_attn_pct",
                 "dev_share_ffn_pct", "dev_share_sample_pct",
                 "dev_share_unscoped_pct",
                 "decode_step_device_ms", "idle_attributed_pct",
                 "stream_writer_share_pct", "host_emit_p50_ms"):
        assert name in mine, name
    for m in b["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        # no metric is named after a cell or a traffic file
        assert "brumby" not in m["name"] and "longreply" not in m["name"]
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
                         "brumby.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        assert a.read() == b.read()


def test_traffic_is_longreplys_lengths_at_half_its_callers():
    """`longreply-closed`'s classes, multiset, outputs, probe and
    warm-up at 16 callers: exactly ISSUE 63's parameters (its fallback
    (b), d8k -> d4k, is NOT taken: cell.json says on which readings)."""
    mix = spec.Cell(CELL).traffic
    ling = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                       "longreply-closed.json"))
    assert (mix["loop"], mix["clients"], mix["ramp_s"]) == ("closed", 16, 16)
    assert ling["clients"] == 32 and mix["warmup_wave"] == 16
    assert mix["probe"] == ling["probe"] == {"class": "t2k", "out": 512}
    assert {c["name"]: (c["lo"], c["hi"]) for c in mix["prompt_classes"]} \
        == {"t2k": (1921, 2048), "d8k": (8065, 8192)}
    for key in ("prompt_classes", "multiset", "warmup", "warmup_wave_out"):
        assert mix[key] == ling[key]
    assert "(b) NOT taken" in spec.Cell(CELL).cell["fallback_taken"]
    total = sum(m["n"] for m in mix["multiset"])
    assert total == 24
    for c in mix["prompt_classes"]:
        share = sum(m["n"] for m in mix["multiset"]
                    if m["class"] == c["name"]) / total
        assert share == pytest.approx(c["weight"])
    # the longest context fits a row, and the window divides the row
    sa = spec.Cell(CELL).cell["server_args"]
    assert 8192 + 1024 <= sa["max-seq-len"] and sa["max-seq-len"] % 512 == 0
    assert sa["kv-pages"] * sa["kv-page-size"] >= (
        sa["max-slots"] * sa["max-seq-len"])
    assert sa["max-slots"] == 16 and sa["paged-attn"] == "pallas"


def test_dims_and_the_needs_at_the_published_sizes():
    d = roof.dims(cfg())
    assert d == {"H": 40, "KV": 8, "hd": 128, "D": 8256, "L": 10,
                 "hidden": 5120, "F": 17408, "V": 151936}
    # a row and layer's state at the LEAST D: 8 x 8,256 x 129 x 4 B
    assert roof.state_bytes(d) == 8 * 8256 * 129 * 4 == 34080768
    # 16 rows x 10 layers once each way: 10.9 GB, 13.3 ms at 819 GB/s
    assert roof.step_least_s(cfg(), 160, PEAK) == pytest.approx(
        160 * 2 * 34080768 / 819e9)
    # a token and layer of a 512-token window: operations bound it
    nbytes, ops = roof.window_need(cfg(), 1, 512)
    assert ops == 2 * 48 * 8256 * 129 + 40 * 4 * 128 * 513 / 2
    assert nbytes == 96 * 128 * 2 + 2 * 34080768 / 512
    assert roof.window_least_s(cfg(), 1, 512, PEAK) == pytest.approx(
        ops / 197e12)
    # the matrices of ten layers and the head, int8; the gate float32
    assert roof.weight_bytes_of(cfg()) == (
        10 * (2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408)
        + 5120 * 151936 + 4 * 10 * 5120 * 8)
    assert roof.decode_step_least_s(cfg(), 160, PEAK) == pytest.approx(
        (roof.weight_bytes_of(cfg()) + 160 * 2 * 34080768) / 819e9)
    # a config this file does not know
    assert roof.dims({"model_type": "llama"}) is None
    assert roof.dims(dict(cfg(), model_type="qwen2")) is None
    assert roof.dims({"model_type": "brumby"}) is None
    assert roof.step_least_s({"model_type": "llama"}, 1, PEAK) is None


def fake_run(cell=None, model_config=None):
    cell = cell or spec.Cell(CELL)
    series = {"cake_retention_tokens_stepped_total": (0.0, 160000.0),
              "cake_retention_state_rows_total": (0.0, 160000.0)}
    return {
        "cell": cell,
        "model_config": (model_config if model_config is not None
                         else cell.model_config),
        "device": {"kind": "TPU v5 lite"},
        "server_args": dict(cell.cell["server_args"]),
        "metrics_0": {k: v[0] for k, v in series.items()},
        "metrics_1": {k: v[1] for k, v in series.items()},
        "steps": [
            {"kind": "mixed", "compiled": False, "wall_s": 0.050,
             "step": 1, "rows": 16},
            {"kind": "decode", "compiled": False, "wall_s": 0.020,
             "step": 2, "rows": 16}],
        "records": [
            {"class": "t2k", "t_send": 1.0, "token_t": [1.4, 1.5],
             "failed": False, "finished": True},
            {"class": "d8k", "t_send": 2.0, "token_t": [3.0, 3.1],
             "failed": False, "finished": True}],
        "t0": 0.0, "t1": 10.0, "trace": None}


def test_another_program_or_no_capture_yields_nothing():
    reader = load_reader()
    assert reader.read(fake_run()) == {}                 # no capture
    run = fake_run()
    run["trace"] = {"xplane": "/nonexistent.xplane.pb"}
    assert reader.read(run) == {}
    for other in ("mistral7b.decode-long", "ling3.longreply-closed",
                  "granite4h.sessions-closed"):
        run = fake_run(cell=spec.Cell(other))
        run["trace"] = {"xplane": "/nonexistent.xplane.pb"}
        assert reader.read(run) == {}


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
        spec.read_layer_metrics(cell, run, found)
        assert missed == []
