"""`mixed_steps_chained_pct` (layer_metrics/mixed_in_flight.py): read
from the window's step records in the shape `/api/v1/steps` gives them,
found by name, and reported in the one-chip cells (the four-chip cell's
dense-slot engine runs no mixed step)."""

import os

import pytest

from harness import spec

NAME = "mixed_steps_chained_pct"
# every cell on a paged engine, as of PR 55: the four-chip cell's dense
# engine has no mixed step (a later cell appends itself)
CELLS = ["mistral7b.chat-closed", "mistral7b.decode-long",
         "olmoe7b.chat-closed", "glm52.longdoc-closed",
         "nemotron3s.agent-closed", "zaya1.reason-closed",
         "dots3.longshort-closed", "dsv2.code-closed",
         "ling3.longreply-closed", "kexaone.longreply-closed"]


def step(kind, chained=None):
    rec = {"kind": kind, "compiled": False, "wall_s": 0.04, "ts": 1.0}
    if chained is not None:
        rec["chained"] = chained
    return rec


def test_share_of_the_windows_mixed_steps_that_were_chained():
    decl, read = spec.discover_layer_metrics()[NAME]
    assert decl["layer"] == "step dispatch" and decl["unit"] == "%"
    assert decl["moves"] == "out_tok_s"
    assert decl["source"] == "program_counter"
    steps = ([step("mixed", False)] + [step("mixed", True)] * 5
             + [step("decode", True)] * 9 + [step("mixed", False)]
             + [step("mixed", True)])
    assert read({"steps": steps})[NAME] == pytest.approx(100 * 6 / 8)
    # every mixed step a stretch's first: 0, not nothing
    assert read({"steps": [step("mixed", False)] * 3})[NAME] == 0.0
    # a decode step is not a mixed step, chained or not
    assert read({"steps": [step("decode", True), step("prefill")]}) == {}
    # a program whose mixed records have no such field (the parent
    # commit) reports nothing, and nothing is raised
    assert read({"steps": [step("mixed")] * 4 + [step("decode", True)]}) \
        == {}
    assert read({"steps": []}) == {} and read({}) == {}


def test_the_metric_is_found_by_name_in_its_cells():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in doc["per_layer"] if m["name"] == NAME]
    assert entry["better"] == "higher"
    assert entry["workloads"][:len(CELLS)] == CELLS
    assert "qwen32b.chat-closed-4chip" not in entry["workloads"]
    for name in CELLS:
        cell = spec.Cell(name)
        assert NAME in cell.names("per_layer")
        assert "out_tok_s" in cell.names("end_to_end")
