"""`decode_attn_pages_live_pct` (layer_metrics/decode_attn_pages.py):
read from the window's step records in the shape `/api/v1/steps` gives
them, found by name, and reported in the five cells whose decode rows
go through `cake_decode_attn`."""

import os

import pytest

from harness import spec

NAME = "decode_attn_pages_live_pct"
# the cells whose decode rows run `cake_decode_attn`, as of PR 55 (a
# later cell appends itself)
CELLS = ["mistral7b.chat-closed", "mistral7b.decode-long",
         "olmoe7b.chat-closed", "nemotron3s.agent-closed",
         "zaya1.reason-closed", "dsv2.code-closed",
         "ling3.longreply-closed", "kexaone.longreply-closed"]


def step(kind, pages=None, table=None):
    rec = {"kind": kind, "compiled": False, "wall_s": 0.02, "ts": 1.0}
    if pages is not None:
        rec.update(attn_pages=pages, attn_pages_table=table)
    return rec


def test_share_of_the_table_that_holds_work():
    decl, read = spec.discover_layer_metrics()[NAME]
    assert decl["layer"] == "kernels" and decl["unit"] == "%"
    assert decl["moves"] == "out_tok_s"
    assert decl["source"] == "program_counter"
    # 16 slots of 16 pages: a step of 16 rows at 3 pages each, a mixed
    # step between, then a step of 9 rows at 2: sums, not a mean of the
    # steps' shares
    steps = [step("decode", 48, 256), step("mixed"),
             step("decode", 18, 256)]
    assert read({"steps": steps})[NAME] == pytest.approx(
        100 * (48 + 18) / 512)
    # a mixed step's fields are another kernel's and are not counted
    mixed = dict(step("mixed"), attn_q_tiles=16, attn_q_tiles_window=2048)
    assert read({"steps": [mixed, step("prefill")]}) == {}
    # a program whose decode records lack the fields (the parent commit,
    # latent attention, a dense cache) reports nothing, and nothing is
    # raised
    assert read({"steps": [step("decode")] * 4 + [step("mixed")]}) == {}
    assert read({"steps": []}) == {} and read({}) == {}


def test_the_metric_is_found_by_name_in_its_cells():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in doc["per_layer"] if m["name"] == NAME]
    assert entry["better"] == "higher"
    assert entry["workloads"][:len(CELLS)] == CELLS
    for name in CELLS:
        cell = spec.Cell(name)
        assert NAME in cell.names("per_layer")
        assert "out_tok_s" in cell.names("end_to_end")
    for name in ("qwen32b.chat-closed-4chip", "glm52.longdoc-closed",
                 "dots3.longshort-closed"):
        assert NAME not in spec.Cell(name).names("per_layer")
