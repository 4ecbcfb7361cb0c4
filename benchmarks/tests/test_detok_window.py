"""`detok_ids_per_token` (layer_metrics/detok_window.py): read from the
window's step records in the shape `/api/v1/steps` gives them, found by
name, and reported in every cell (all nine stream)."""

import os

import pytest

from harness import spec

NAME = "detok_ids_per_token"


def step(kind, tokens, detok_ids=None):
    rec = {"kind": kind, "compiled": False, "wall_s": 0.02, "ts": 1.0,
           "tokens": tokens}
    if detok_ids is not None:
        rec.update(detok_ids=detok_ids,
                   parts={"emit.detok": 1e-5 * detok_ids})
    return rec


def test_ids_decoded_a_token():
    decl, read = spec.discover_layer_metrics()[NAME]
    assert decl["layer"] == "step dispatch" and decl["unit"] == "ids"
    assert decl["moves"] == "out_tok_s"
    assert decl["source"] == "program_counter"
    # 32 rows of whole words: the last word, then it and the new one
    steps = [step("decode", 32, 96)] * 10
    assert read({"steps": steps})[NAME] == 3.0
    # sums over the window, not a mean of the steps' ratios; a mixed
    # step's first tokens count; a record that emitted nothing (the
    # field absent) adds its tokens only
    steps = [step("mixed", 2, 64), step("decode", 30, 90),
             step("decode", 32)]
    assert read({"steps": steps})[NAME] == pytest.approx(154 / 64)
    # a detokeniser that starts from token 0 would read the output's
    # length: nothing here caps or rescales the count
    assert read({"steps": [step("decode", 32, 32 * 380)]})[NAME] == 380.0


def test_a_program_without_the_field_reports_nothing():
    _, read = spec.discover_layer_metrics()[NAME]
    # the parent commit: records with parts and tokens, no `detok_ids`
    old = dict(step("decode", 32), parts={"emit.detok": 0.0058})
    assert read({"steps": [old] * 5 + [step("mixed", 3)]}) == {}
    # nothing emitted, no records, no step list at all: nothing raised
    assert read({"steps": [step("mixed", 0, 0)]}) == {}
    assert read({"steps": []}) == {} and read({}) == {}


def test_the_metric_is_found_by_name_in_every_cell():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(m for m in doc["per_layer"] if m["name"] == NAME)
    assert entry["better"] == "lower" and entry["unit"] == "ids"
    assert entry["workloads"] == [w["name"] for w in doc["workloads"]]
    for name in entry["workloads"]:
        cell = spec.Cell(name)
        assert NAME in cell.names("per_layer")
        assert "out_tok_s" in cell.names("end_to_end")
