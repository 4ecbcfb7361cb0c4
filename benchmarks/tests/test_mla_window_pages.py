"""`mla_window_pages_per_fold` (layer_metrics/mla_window_pages.py):
read from the window's step records in the shape `/api/v1/steps` gives
them, found by name, and reported in the three cells whose windows go
through `cake_mla_window_attn`."""

import os

import pytest

from harness import spec

NAME = "mla_window_pages_per_fold"
# the latent families' cells, as of PR 55 (a later cell appends itself)
CELLS = ["glm52.longdoc-closed", "dots3.longshort-closed",
         "dsv2.code-closed", "ling3.longreply-closed"]


def step(kind, pages=None, folds=None):
    rec = {"kind": kind, "compiled": False, "wall_s": 0.1, "ts": 1.0}
    if pages is not None:
        rec.update(window_pages=pages, window_folds=folds)
    return rec


def test_pages_a_softmax_update():
    decl, read = spec.discover_layer_metrics()[NAME]
    assert decl["layer"] == "kernels" and decl["unit"] == "pages"
    assert decl["moves"] == "out_tok_s"
    assert decl["source"] == "program_counter"
    # 15 layers: a window that ends on page 18 at 4 pages a fold (5
    # folds), a decode step between, one that ends on page 4: sums, not
    # a mean of the steps' ratios
    steps = [step("mixed", 15 * 18, 15 * 5), step("decode"),
             step("mixed", 15 * 4, 15 * 1)]
    assert read({"steps": steps})[NAME] == pytest.approx(22 / 6)
    # a kernel that updates at every page reads 1.0
    assert read({"steps": [step("mixed", 270, 270)]})[NAME] == 1.0
    # a decode record's page counts are another kernel's
    decode = dict(step("decode"), attn_pages=46, attn_pages_table=256)
    assert read({"steps": [decode, step("prefill")]}) == {}
    # a program whose mixed records lack the fields (the parent commit,
    # a family with no such kernel) reports nothing, and nothing is
    # raised
    assert read({"steps": [step("mixed")] * 4 + [step("decode")]}) == {}
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
    for name in ("mistral7b.chat-closed", "olmoe7b.chat-closed",
                 "nemotron3s.agent-closed", "zaya1.reason-closed"):
        assert NAME not in spec.Cell(name).names("per_layer")
