"""`decode_steps_chained_pct` (layer_metrics/in_flight.py): read from the
window's step records in the shape `/api/v1/steps` gives them, found by
name, and reported in every cell."""

import os

import pytest

from harness import spec

NAME = "decode_steps_chained_pct"


def step(kind, chained=None, compiled=False):
    rec = {"kind": kind, "compiled": compiled, "wall_s": 0.016, "ts": 1.0}
    if chained is not None:
        rec["chained"] = chained
    return rec


def test_share_of_the_windows_decode_steps_that_were_chained():
    decl, read = spec.discover_layer_metrics()[NAME]
    assert decl["layer"] == "step dispatch" and decl["unit"] == "%"
    assert decl["moves"] == "out_tok_s"
    assert decl["source"] == "program_counter"
    steps = ([step("decode", False)] + [step("decode", True)] * 23
             + [step("mixed")] + [step("decode", False)]
             + [step("decode", True)] * 7)
    assert read({"steps": steps})[NAME] == pytest.approx(100 * 30 / 32)
    # every step synchronous (a multi-host engine): 0, not nothing
    assert read({"steps": [step("decode", False)] * 3})[NAME] == 0.0
    # a mixed step is not a decode step, chained or not
    assert read({"steps": [step("mixed"), step("prefill")]}) == {}
    # a program whose records have no such field (the parent commit)
    # reports nothing, and nothing is raised
    assert read({"steps": [step("decode")] * 4}) == {}
    assert read({"steps": []}) == {} and read({}) == {}


def test_the_metric_is_found_by_name_and_belongs_to_every_cell():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in doc["per_layer"] if m["name"] == NAME]
    assert entry["better"] == "higher"
    assert entry["workloads"] == [w["name"] for w in doc["workloads"]]
    for w in doc["workloads"]:
        cell = spec.Cell(w["name"])
        assert NAME in cell.names("per_layer"), w["name"]
        # the end-to-end metric it moves is one the cell reports
        assert "out_tok_s" in cell.names("end_to_end")
