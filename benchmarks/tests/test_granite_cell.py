"""The granite-4.0-h-micro configuration, its cell and its per-layer
metrics as shipped: found by name, in agreement with BENCHMARK.json and
with the catalog's published config (NO CUT), the traffic's stated
cycle, `mamba_roofline.as_ssm_config` against `ssm_roofline` by hand,
and the reader on a made-up run and on other families' configs."""

import importlib.util
import os

import pytest

from harness import mamba_roofline as roof
from harness import spec, ssm_roofline, traffic as tfc

CELL = "granite4h.sessions-closed"
CONFIG = "granite-4.0-h-micro-int8"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog's `config` for granite-4.0-h-micro (model-configs guide),
# every key but layer_types (40 entries: below)
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
NEW = ["dev_share_mamba_pct", "dev_share_mamba_proj_pct",
       "mamba_step_roofline", "mamba_scan_roofline",
       "mamba_state_rows_per_step", "decode_step_state_roofline"]
# the lists as they stood before this cell (PR 55), which it may only
# extend: the cells judged by tpot_p50_ms, and nemotron's neighbours on
# a shared reading
TPOT_AT_PR_55 = ["mistral7b.decode-long", "qwen32b.chat-closed-4chip",
                 "glm52.longdoc-closed", "nemotron3s.agent-closed",
                 "zaya1.reason-closed", "dsv2.code-closed",
                 "ling3.longreply-closed", "kexaone.longreply-closed"]
CELLS_AT_PR_55 = ["mistral7b.chat-closed", "mistral7b.decode-long",
                  "qwen32b.chat-closed-4chip", "olmoe7b.chat-closed",
                  "glm52.longdoc-closed", "nemotron3s.agent-closed",
                  "zaya1.reason-closed", "dots3.longshort-closed",
                  "dsv2.code-closed", "ling3.longreply-closed",
                  "kexaone.longreply-closed"]


def load_reader():
    path = os.path.join(spec.BENCH_DIR, "layer_metrics", "mamba.py")
    s = importlib.util.spec_from_file_location("layer_metric_mamba", path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def cfg():
    return spec.Cell(CELL).model_config


def doc():
    return spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))


def test_shipped_configuration_is_the_published_one_uncut():
    cell = spec.Cell(CELL)
    c = cell.model_config
    for key, value in PUBLISHED.items():
        assert c[key] == value, key
    types = c["layer_types"]
    assert len(types) == 40 and [i for i, t in enumerate(types)
                                 if t == "attention"] == [5, 15, 25, 35]
    assert types.count("mamba") == 36
    # config.json is its source but for eos_token_id: no alias key
    assert set(c) == set(PUBLISHED) | {"layer_types", "eos_token_id"}
    assert c["eos_token_id"] == c["vocab_size"]
    assert cell.cell["reduced"] == ["eos_token_id"]
    assert set(cell.cell["reduced_why"]) == {"eos_token_id"}
    assert "NO CUT" in cell.cell["reduced_why"]["eos_token_id"]
    assert cell.cell["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
        "config.json")
    said = " ".join(cell.cell["assumed"])
    for text in ("log U(1, 16)", "[0.001, 0.1]", "1e-4", "no upper clamp",
                 "gate before the norm", "float32 SSM state",
                 "seeded draws"):
        assert text in said, text
    departures = " ".join(cell.cell["departures_in_the_served_path"])
    for text in ("chunked scan", "bf16 operands", "[K, channels]",
                 "sub-windows of 128", "w_gate"):
        assert text in departures, text
    for text in ("one chip holds the model whole", "64 sessions"):
        assert text in cell.cell["deployment"], text
    args = cell.cell["server_args"]
    assert args["require-model-type"] == "granitemoehybrid"
    assert (args["quant"], args["max-slots"], args["max-seq-len"],
            args["kv-pages"], args["kv-page-size"], args["prefill-chunk"],
            args["paged-attn"]) == ("int8", 64, 2560, 1280, 128, 512,
                                    "pallas")
    assert args["kv-pages"] * args["kv-page-size"] == 64 * 2560
    assert cell.cell["expect_impl"] == {"mixed": "paged-ssm-pallas",
                                        "decode": "paged-ssm-pallas"}
    assert cell.cell["shape"] == {"weight_bytes": 1, "kv_bytes": 2,
                                  "mixed_width": 512, "stages": 1, "tp": 1}
    toy = cell.cell["rehearse"]["config"]
    assert toy["layer_types"] == ["mamba", "mamba", "attention", "mamba",
                                  "mamba"]
    assert (toy["hidden_size"], toy["mamba_d_state"],
            toy["mamba_chunk_size"], toy["vocab_size"]) == (64, 16, 8, 512)
    assert cell.cell["rehearse"]["server_args"] == {"dtype": "f32",
                                                    "paged-attn": "fold"}


def test_the_toy_and_the_shipped_config_both_parse():
    from cake_tpu.models.llama.config import load_config_dict
    cell = spec.Cell(CELL)
    c = load_config_dict(cell.model_config)
    assert c.family.impl == "paged-ssm-" and c.family.name == \
        "granitemoehybrid"
    assert (len(c.mamba_layers), c.attn_layers) == (36, (5, 15, 25, 35))
    toy = load_config_dict(dict(cell.model_config,
                                **cell.cell["rehearse"]["config"]))
    assert (toy.head_dim, toy.d_inner, toy.attn_layers) == (16, 128, (2,))


def test_benchmark_json_entries_match_the_cells_files():
    d = doc()
    entry = next(c for c in d["configs"] if c["name"] == CONFIG)
    work = next(w for w in d["workloads"] if w["name"] == CELL)
    cell = spec.Cell(CELL)
    assert entry["reduced"] == cell.cell["reduced"] == ["eos_token_id"]
    assert entry["source"] == cell.cell["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}/config.json"
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "sessions-closed", 1)
    for text in (entry["why"], entry["source"], work["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert sum(w["config"] == CONFIG for w in d["workloads"]) == 1
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_the_cell_extends_the_lists_and_changes_none():
    d = doc()
    names = [w["name"] for w in d["workloads"]]
    assert names[:11] == CELLS_AT_PR_55 and names[11] == CELL
    assert (len(d["per_layer"]), len(d["end_to_end"]), len(names)) == (
        114, 5, 12)
    assert sum(w["chips"] == 4 for w in d["workloads"]) == 1
    tpot = next(m for m in d["end_to_end"] if m["name"] == "tpot_p50_ms")
    assert tpot["workloads"] == TPOT_AT_PR_55 + [CELL]
    assert [m["name"] for m in d["per_layer"]][-6:] == NEW
    for m in d["per_layer"]:
        lists = m.get("workloads", [])
        if CELL in lists and m["name"] not in NEW:
            # joined at the END of a list Nemotron's cell is on
            assert lists[-1] == CELL and "nemotron3s.agent-closed" in lists
            assert lists[:-1] == [w for w in lists[:-1]
                                  if w in CELLS_AT_PR_55]


def test_cell_reports_what_the_issue_lists():
    cell = spec.Cell(CELL)
    assert set(cell.names("end_to_end")) == {"tpot_p50_ms", "out_tok_s",
                                             "setup_s"}
    layers = set(cell.names("per_layer"))
    assert set(NEW) <= layers
    for name in ("rows_busy_pct", "pages_in_use_pct", "mixed_step_share_pct",
                 "host_emit_p50_ms", "host_build_p50_ms",
                 "decode_step_device_ms", "dev_share_attn_pct",
                 "dev_share_ffn_pct", "dev_share_kv_pct",
                 "dev_share_unscoped_pct", "dev_share_sample_pct",
                 "idle_attributed_pct", "idle_unnamed_pct", "idle_gc_pct",
                 "chain_breaks_per_s", "boundary_gap_p50_ms",
                 "boundary_admit_p50_ms", "decode_attn_pages_live_pct",
                 "detok_ids_per_token", "host_detok_p50_ms",
                 "emit_us_per_token", "loop_uncovered_pct",
                 "gc_pause_share_pct", "mixed_step_ms.tok",
                 "mixed_step_device_ms.tok", "ttft_p50_ms.tok",
                 "stream_writer_share_pct", "stream_chunks_per_wake",
                 "decode_steps_chained_pct", "mixed_steps_chained_pct",
                 "peak_hbm_gib", "compiles_in_window", "decode_step_ms"):
        assert name in layers, name
    # not the expert readings, and not ssm.py's: its reader asks for
    # Nemotron's keys
    for name in layers:
        assert not name.startswith(("moe_", "dev_share_moe_", "ssm_",
                                    "dev_share_ssm_", "latent_moe_"))
    for name in ("mixed_step_ms", "mixed_step_device_ms",
                 "decode_step_roofline", "decode_attn_roofline",
                 "mixed_attn_roofline"):
        assert name not in layers, name


def test_reader_agrees_with_benchmark_json():
    declared = {m["name"]: m for m in load_reader().METRICS}
    assert list(declared) == NEW
    entries = {m["name"]: m for m in doc()["per_layer"]
               if m["name"] in declared}
    assert set(entries) == set(declared)
    for name, m in entries.items():
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        for key in ("unit", "layer", "moves", "source"):
            assert declared[name][key] == m[key], (name, key)
        if name.endswith("_roofline"):
            assert m["unit"] == "%"


def test_traffic_is_the_stated_cycle():
    cell = spec.Cell(CELL)
    t = cell.traffic
    assert (t["loop"], t["clients"], t["ramp_s"]) == ("closed", 64, 16)
    classes = tfc.class_by_name(t)
    assert (classes["p512"]["lo"], classes["p512"]["hi"]) == (385, 512)
    assert (classes["p2k"]["lo"], classes["p2k"]["hi"]) == (1921, 2048)
    assert sorted((i["class"], i["out"], i["n"]) for i in t["multiset"]) == [
        ("p2k", 256, 3), ("p2k", 384, 3), ("p2k", 512, 2),
        ("p512", 256, 6), ("p512", 384, 5), ("p512", 512, 5)]
    items = tfc.expand_multiset(t)
    assert len(items) == 24
    assert sum(i["out"] for i in items) / 24 == pytest.approx(373.33, abs=.01)
    assert 955 < sum(i["prompt"] for i in items) / 24 < 965
    assert max(i["prompt"] + i["out"] for i in items) == 2560 == \
        cell.cell["server_args"]["max-seq-len"]
    assert t["probe"] == {"class": "p512", "out": 256}
    assert t["warmup"] == [{"class": "p512", "out": 8},
                           {"class": "p2k", "out": 8}]
    assert (t["warmup_wave"], t["warmup_wave_out"]) == (64, 8)
    assert "think" not in t and "burst" not in t
    assert "sessions" in t["who"] and "26 %" in t["why"]
    mix = tfc.Mix(t, 2147484999, cell.model_config["vocab_size"])
    assert len(mix.warmup_items()) == 2


def test_reference_copy_is_the_programs():
    here = os.path.join(spec.BENCH_DIR, "configs", CONFIG, "reference.py")
    there = os.path.join(spec.ROOT, "cake_tpu", "models", "reference",
                         "granite_hybrid.py")
    with open(here, "rb") as a, open(there, "rb") as b:
        text = a.read()
        assert text == b.read()
    for module in (b"cake_tpu.ops", b"cake_tpu.models.llama",
                   b"cake_tpu.models.moe"):
        assert b"import " + module not in text
        assert b"from " + module not in text


# -- the mapping, and the counts by hand ----------------------------------------


def test_the_mapping_gives_ssm_roofline_the_same_dims_by_hand():
    mapped = roof.as_ssm_config(cfg())
    assert mapped == {"mamba_num_heads": 64, "mamba_head_dim": 64,
                      "n_groups": 1, "ssm_state_size": 128,
                      "chunk_size": 256,
                      "hybrid_override_pattern": "M" * 36}
    d = ssm_roofline.ssm_dims(mapped)
    assert (d["H"], d["P"], d["G"], d["N"], d["Q"], d["L_M"]) == (
        64, 64, 1, 128, 256, 36)
    assert (d["d_inner"], d["conv_dim"]) == (4096, 4352)
    # a row's state: 2 MiB a layer, 72 MiB over the 36
    assert ssm_roofline.state_bytes(mapped) == 2 * 2**20
    assert roof.mamba_layers(cfg()) == 36


def test_one_decode_step_of_64_rows_moves_9_66_gb_of_state():
    need = ssm_roofline.step_need_bytes(roof.as_ssm_config(cfg()), 64 * 36)
    assert need == 64 * 36 * 2 * 2 * 2**20 == 9663676416
    assert roof.step_least_s(cfg(), 64 * 36, PEAK) == pytest.approx(
        9663676416 / 819e9)
    # the chunked scan, per token and layer: C.B 2*1*256*128, masked
    # scores x inputs 2*64*256*64, in and out of the chunk's state
    # 4*64*64*128; bytes (4352 + 4096) * 2
    nbytes, ops = ssm_roofline.scan_need(roof.as_ssm_config(cfg()), 1.0)
    assert ops == 65536 + 2097152 + 2097152 == 4259840
    assert nbytes == 8448 * 2
    t = roof.scan_least_s(cfg(), 512 * 36, PEAK)
    assert t == pytest.approx(max(512 * 36 * 4259840 / 197e12,
                                  512 * 36 * 16896 / 819e9))


def test_a_decode_steps_bytes_by_hand():
    # a Mamba layer 2,048 x 8,512 + 4,096 x 2,048 + the SwiGLU's
    # 3 x 2,048 x 8,192 = 76.15 M; an attention layer 10.49 M + 50.33 M;
    # the tied head 205.5 M
    mamba = 2048 * 8512 + 4096 * 2048 + 3 * 2048 * 8192
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert (mamba, attn) == (76152832, 60817408)
    params = 36 * mamba + 4 * attn + 2048 * 100352
    assert roof.weight_params(cfg()) == params == 3190292480
    # 64 live rows at 1,500 keys each: 8 KiB a key over the 4 layers
    need = roof.decode_step_need_bytes(cfg(), 64 * 36, 64 * 1500)
    assert need == params + 9663676416 + 64 * 1500 * 8192
    assert roof.decode_step_least_s(cfg(), 64 * 36, 64 * 1500, PEAK) == \
        pytest.approx(need / 819e9)
    assert 0.0165 < need / 819e9 < 0.0170


@pytest.mark.parametrize("other", ["nemotron3s.agent-closed",
                                   "mistral7b.decode-long",
                                   "kexaone.longreply-closed"])
def test_another_familys_config_yields_nothing(other):
    c = spec.Cell(other).model_config
    assert roof.as_ssm_config(c) is None and roof.mamba_layers(c) is None
    assert roof.step_least_s(c, 10, PEAK) is None
    assert roof.scan_least_s(c, 10, PEAK) is None
    assert roof.decode_step_least_s(c, 10, 10, PEAK) is None
    run = fake_run(model_config=c)
    assert load_reader().read(run) == {}


# -- the reader, on a made-up run ----------------------------------------------


class FakeCell:
    cell = {"shape": {"kv_bytes": 2, "weight_bytes": 1, "mixed_width": 512}}


def fake_run(**over):
    decode = {"kind": "decode", "compiled": False, "step": 11, "rows": 64,
              "ssm_state_rows": 64 * 36.0, "ssm_tokens_stepped": 64 * 36.0,
              "ssm_tokens_scanned": 0.0, "attn_pages": 64 * 12}
    mixed = {"kind": "mixed", "compiled": False, "step": 12, "rows": 64,
             "ssm_state_rows": 64 * 36.0, "ssm_tokens_stepped": 63 * 36.0,
             "ssm_tokens_scanned": 512 * 36.0}
    run = {"model_config": cfg(), "cell": FakeCell(),
           "device": {"kind": "TPU v5 lite"},
           "server_args": {"kv-page-size": 128},
           "steps": [decode] * 3 + [mixed], "records": [],
           "metrics_0": {"cake_ssm_state_rows_total": 1000.0},
           "metrics_1": {"cake_ssm_state_rows_total": 1000.0 + 4 * 64 * 36},
           "trace": None}
    run.update(over)
    return run


def test_counters_over_the_window():
    got = load_reader().read(fake_run())
    assert got == {"mamba_state_rows_per_step": pytest.approx(64.0)}


def test_a_program_without_the_counters_yields_nothing():
    assert load_reader().read(fake_run(metrics_0={}, metrics_1={},
                                       steps=[])) == {}


def op(name, scope, start, dur, program_id=7):
    return {"name": name, "start_ns": float(start), "dur_ns": float(dur),
            "stats": {"tf_op": f"jit(x)/layers/{scope}/mul:",
                      "program_id": program_id}}


def test_each_executions_need_is_its_own_records(monkeypatch):
    """A capture of one decode and one mixed execution: the one-step
    update's need comes from each execution's OWN record (64 and 63
    rows), the scan's from the mixed record alone, and the decode step's
    share from its live rows and a floor on its keys."""
    reader = load_reader()
    c = cfg()
    ms = 1e6
    step_need = roof.step_least_s(c, 64 * 36, PEAK) * 1e9      # ns
    step_need_63 = roof.step_least_s(c, 63 * 36, PEAK) * 1e9
    scan_need = roof.scan_least_s(c, 512 * 36, PEAK) * 1e9
    keys = (64 * 12 - 64) * 128 + 64
    decode_need = roof.decode_step_least_s(c, 64 * 36, keys, PEAK) * 1e9
    ops = [
        # the decode execution, 0 .. 40 ms: the state update takes
        # twice its need, the rest is the SwiGLU
        op("fusion.1", "attn/ssm_step", 0, step_need),
        op("fusion.2", "attn/ssm_state", step_need, step_need),
        op("fusion.3", "ffn", 2 * step_need, 2 * decode_need - 2 * step_need),
        # the mixed execution, 100 ms on
        op("fusion.4", "attn/ssm_state", 100 * ms, 4 * step_need_63),
        op("fusion.5", "attn/ssm_scan", 100 * ms + 4 * step_need_63,
           5 * scan_need),
        op("fusion.6", "qkv/ssm_in", 160 * ms, 1 * ms),
        # outside every execution: counted by the shares alone
        op("fusion.7", "attn/ssm_gate", 300 * ms, 1 * ms),
    ]
    modules = [
        {"name": "jit_decode_step_sampled(7)", "start_ns": 0.0,
         "dur_ns": 2 * decode_need, "stats": {}},
        {"name": "jit_mixed_step_sampled(8)", "start_ns": 100 * ms,
         "dur_ns": 62 * ms, "stats": {}},
        {"name": "jit_other(9)", "start_ns": 300 * ms, "dur_ns": 1 * ms,
         "stats": {}},
    ]
    fetch = [{"name": "cake/fetch", "start_ns": 10 * ms,
              "dur_ns": 2 * decode_need - 10 * ms + 3 * ms,
              "stats": {"step": 11}},
             {"name": "cake/fetch", "start_ns": 120 * ms, "dur_ns": 45 * ms,
              "stats": {"step": 12}}]
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "engine",
                                         "events": fetch}]},
    ]
    run = fake_run()
    run["all_steps"] = run["steps"]
    monkeypatch.setattr(reader.readers, "planes", lambda r: planes)
    got = reader.read(run)
    assert got["mamba_step_roofline"] == pytest.approx(
        100.0 * (step_need + step_need_63)
        / (2 * step_need + 4 * step_need_63))
    assert got["mamba_scan_roofline"] == pytest.approx(20.0)
    assert got["decode_step_state_roofline"] == pytest.approx(50.0)
    busy = sum(e["dur_ns"] for e in ops)
    assert got["dev_share_mamba_pct"] == pytest.approx(
        100.0 * (2 * step_need + 4 * step_need_63 + 5 * scan_need + 1 * ms)
        / busy)
    assert got["dev_share_mamba_proj_pct"] == pytest.approx(
        100.0 * 1 * ms / busy)
    assert all(v <= 100.0 for k, v in got.items() if k.endswith("roofline"))
    # no fetch span in the capture: the shares alone
    planes[1]["lines"][0]["events"] = []
    got = reader.read(run)
    assert "dev_share_mamba_pct" in got
    assert not [k for k, v in got.items()
                if k.endswith("roofline") and v is not None]
