"""`mixed_attn_q_tiles_folded_pct` (layer_metrics/mixed_attn_tiles.py):
read from the window's step records in the shape `/api/v1/steps` gives
them, found by name, and reported in the two chat cells, whose mixed
steps hand their rows to `cake_mixed_attn` as they are."""

import os

import pytest

from harness import spec

NAME = "mixed_attn_q_tiles_folded_pct"
CELLS = ["mistral7b.chat-closed", "olmoe7b.chat-closed"]


def step(kind, tiles=None, window=None):
    rec = {"kind": kind, "compiled": False, "wall_s": 0.04, "ts": 1.0}
    if tiles is not None:
        rec.update(attn_q_tiles=tiles, attn_q_tiles_window=window)
    return rec


def test_share_of_the_windows_tiles_the_kernel_folded():
    decl, read = spec.discover_layer_metrics()[NAME]
    assert decl["layer"] == "kernels" and decl["unit"] == "%"
    assert decl["moves"] == "ttft_mean_ms"
    assert decl["source"] == "program_counter"
    # 14 decode rows beside windows of 128 and 37 tokens at a tile of
    # one query, then a step of 16 decode rows: sums, not a mean of
    # the steps' shares
    steps = [step("mixed", 14 + 2 * 128, 16 * 128), step("decode"),
             step("mixed", 16, 16 * 128)]
    assert read({"steps": steps})[NAME] == pytest.approx(
        100 * (270 + 16) / (2 * 2048))
    # a decode step carries no such fields and is not counted
    assert read({"steps": [step("decode"), step("prefill")]}) == {}
    # a program whose mixed records lack the fields (the parent commit,
    # an engine whose rows bypass the kernel) reports nothing, and
    # nothing is raised
    assert read({"steps": [step("mixed")] * 4 + [step("decode")]}) == {}
    assert read({"steps": []}) == {} and read({}) == {}


def test_the_metric_is_found_by_name_in_its_cells():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in doc["per_layer"] if m["name"] == NAME]
    assert entry["better"] == "lower" and entry["workloads"] == CELLS
    for name in CELLS:
        cell = spec.Cell(name)
        assert NAME in cell.names("per_layer")
        assert "ttft_mean_ms" in cell.names("end_to_end")
    for name in ("mistral7b.decode-long", "glm52.longdoc-closed",
                 "nemotron3s.agent-closed", "qwen32b.chat-closed-4chip"):
        assert NAME not in spec.Cell(name).names("per_layer")
