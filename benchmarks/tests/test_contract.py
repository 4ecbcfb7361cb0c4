"""BENCHMARK.json against the limits of the benchmark's contract that a
file can be checked for, so that an entry added later is refused here
and not by the driver."""

import json
import os
import re

import pytest

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what `reduced` may never name: a hidden, intermediate, latent, state
# or projection size, a key that ends in `_dim` or `_rank`, a head size,
# an expansion factor, the experts a token. A COUNT OF LAYERS is a
# depth, which every cut configuration lists (`num_hidden_layers` has
# `hidden` in it and is no width)
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                    r"head_dim|head_size|expand|expansion|experts_per_tok)")
DEPTHS = re.compile(r"^(num|n)_[a-z0-9_]*layers?$")
PER_LAYER_CAP, END_TO_END_CAP, CELLS_CAP = 128, 16, 24


def is_width(key: str) -> bool:
    return bool(WIDTHS.search(key)) and not DEPTHS.match(key)


def doc():
    path = os.path.join(spec.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    return spec.load_json(path)


def line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    d = doc()
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(d["paths"]) <= 16 and all(PATH.match(p) for p in d["paths"])
    assert len(d["command"]) <= 32 and all(line(w) for w in d["command"])
    assert isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51
    # a full check at the full 24 cells must fit into 43200 s
    assert (2 + 14 * 24) * (d["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    d = doc()
    assert 1 <= len(d["configs"]) <= 24
    used = {w["config"] for w in d["workloads"]}
    files = set()
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in d["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        assert isinstance(json.load(open(os.path.join(spec.ROOT, c["file"]))),
                          dict)
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not is_width(key), key
        cell = spec.load_json(os.path.join(
            os.path.dirname(os.path.join(spec.ROOT, c["file"])), "cell.json"))
        assert cell["source"] == c["source"]
        assert cell["reduced"] == c["reduced"]
    assert len({c["name"] for c in d["configs"]}) == len(d["configs"])


@pytest.mark.parametrize("key, width", [
    ("num_hidden_layers", False), ("num_nextn_predict_layers", False),
    ("n_layers", False), ("vocab_size", False), ("eos_token_id", False),
    ("n_routed_experts", False), ("num_experts", False),
    ("layer_types", False), ("hybrid_override_pattern", False),
    ("first_k_dense_replace", False), ("sliding_windows", False),
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("moe_latent_size", True),
    ("ssm_state_size", True), ("mamba_d_state", True),
    ("kv_lora_rank", True), ("q_lora_rank", True), ("head_dim", True),
    ("qk_rope_head_dim", True), ("index_head_dim", True),
    ("mamba_proj_bias", True), ("expand", True), ("mamba_expand", True),
    ("num_experts_per_tok", True), ("hidden_layers_size", True)])
def test_a_depth_passes_and_a_width_is_refused(key, width):
    assert is_width(key) is width


def test_workloads():
    d = doc()
    assert 1 <= len(d["workloads"]) <= CELLS_CAP
    pairs = set()
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in d["workloads"])
    assert four <= max(1, len(d["workloads"]) // 4)
    assert len({w["name"] for w in d["workloads"]}) == len(d["workloads"])


def test_metrics():
    d = doc()
    cells = {w["name"] for w in d["workloads"]}
    names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
    assert len(names) == len(set(names))
    assert 1 <= len(d["end_to_end"]) <= END_TO_END_CAP
    assert 1 <= len(d["per_layer"]) <= PER_LAYER_CAP
    for m in d["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in d["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in d["end_to_end"] + d["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    assert "setup_s" in names
    for name in cells:
        cell = spec.Cell(name)     # refuses a metric whose `moves` is missing
        assert "setup_s" in cell.names("end_to_end")
        assert len(cell.names("end_to_end")) >= 2
        assert len(cell.names("per_layer")) >= 1
