"""`layer_metrics/stream_writer.py` (PR 54's two metrics, in the list
since PR 55) on hand-made and on recorded step records."""

import json
import os

import pytest

from harness import spec

NAMES = ("stream_writer_share_pct", "stream_chunks_per_wake")
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def read():
    found = spec.discover_layer_metrics()
    assert found[NAMES[0]][1] is found[NAMES[1]][1]
    return found[NAMES[0]][1]


def step(chunks=None, direct=None, wakes=None, kind="decode"):
    rec = {"kind": kind, "compiled": False, "wall_s": 0.02, "ts": 1.0,
           "tokens": 32}
    for key, value in (("stream_chunks", chunks), ("stream_direct", direct),
                       ("stream_wakes", wakes)):
        if value is not None:       # the program leaves a zero field out
            rec[key] = value
    return rec


def test_the_two_are_in_every_cell_as_issue_54_specified():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    found = spec.discover_layer_metrics()
    units = {"stream_writer_share_pct": "%",
             "stream_chunks_per_wake": "chunks"}
    for name in NAMES:
        (m,) = [m for m in doc["per_layer"] if m["name"] == name]
        assert m == {"name": name, "unit": units[name], "better": "higher",
                     "source": "program_counter", "layer": "step dispatch",
                     "moves": "out_tok_s",
                     "workloads": [w["name"] for w in doc["workloads"]]}
        decl = found[name][0]
        for key in ("unit", "layer", "moves", "source"):
            assert decl[key] == m[key]


def test_window_sums_over_the_records(read):
    # 3 steps of 32 chunks for the writer, one wake each; one step whose
    # 4 deltas went to a handed-back stream; a mixed step's first token
    steps = [step(32, None, 1)] * 3 + [step(28, 4, 1),
                                       step(1, None, 1, kind="mixed")]
    got = read({"steps": steps})
    assert got["stream_writer_share_pct"] == pytest.approx(
        100.0 * 125 / 129)
    assert got["stream_chunks_per_wake"] == pytest.approx(125 / 5)
    # all streamed by the writer: 100, never more
    assert read({"steps": [step(32, None, 1)] * 4}) == {
        "stream_writer_share_pct": pytest.approx(100.0),
        "stream_chunks_per_wake": pytest.approx(32.0)}


def test_nothing_where_no_record_has_the_fields(read):
    assert read({"steps": [step(), step(kind="mixed")]}) == {}   # a parent
    assert read({"steps": []}) == {} and read({}) == {}
    # in-process callbacks alone: a share of 0 is a reading (nothing
    # went through the writer), chunks a wake has nothing to divide by
    assert read({"steps": [step(None, 8, None)]}) == {
        "stream_writer_share_pct": 0.0}


def test_on_recorded_records(read):
    """Step records of a CPU rehearsal of `mistral7b.decode-long` (16
    clients over HTTP; `tests/recorded_steps.json`, PR 55): every delta
    went through the writer, a step's rows in one wake."""
    with open(os.path.join(HERE, "recorded_steps.json")) as f:
        steps = json.load(f)
    assert all(s["stream_wakes"] == 1 and "stream_direct" not in s
               for s in steps)
    got = read({"steps": steps})
    assert got["stream_writer_share_pct"] == 100.0
    assert got["stream_chunks_per_wake"] == pytest.approx(
        sum(s["stream_chunks"] for s in steps) / len(steps))
    assert 14 <= got["stream_chunks_per_wake"] <= 16
