"""The stretch-boundary readers (layer_metrics/stretch_boundaries.py,
layer_metrics/host_slack.py) on hand-made step records in the shape
`/api/v1/steps` gives them: what a record that is not chained says of
the chain that ended before it, and what a chained record says of its
own fetch. Found by name; nothing here pins a list's end."""

import os

import pytest

from harness import spec

BOUNDARY = ["chain_breaks_per_s", "chain_breaks_queue_per_s",
            "chain_breaks_row_finished_per_s", "chain_breaks_cap_per_s",
            "boundary_gap_p50_ms", "boundary_gap_p99_ms",
            "boundary_gap_share_pct", "boundary_admit_p50_ms"]
SLACK = ["chained_steps_late_pct", "host_detok_p50_ms"]
ONE_CHIP = ["mistral7b.chat-closed", "mistral7b.decode-long",
            "olmoe7b.chat-closed", "glm52.longdoc-closed",
            "nemotron3s.agent-closed"]
FOUR_CHIP = "qwen32b.chat-closed-4chip"


def head(cause=None, gap_s=None, admitted=0, parts=None, compiled=False,
         kind="mixed"):
    """A stretch's first record: not chained."""
    rec = {"kind": kind, "compiled": compiled, "chained": False,
           "wall_s": 0.03, "ts": 1.0, "rows_admitted": admitted,
           "phases": {"dispatch": 0.001}}
    if cause is not None:
        rec["chain_break"] = cause
    if gap_s is not None:
        rec["gap_s"] = gap_s
    if parts:
        rec["parts"] = parts
    return rec


def flown(wait_s=None, late=None, detok=None, kind="decode"):
    """A chained record."""
    rec = {"kind": kind, "compiled": False, "chained": True,
           "wall_s": 0.015, "ts": 1.0, "gap_s": 0.0,
           "phases": {"fetch": 0.012}}
    if wait_s is not None:
        rec.update(fetch_wait_s=wait_s, late=late)
    if detok is not None:
        rec["parts"] = {"emit.detok": detok}
    return rec


def parent_style():
    """Records of a program from before the fields existed."""
    steps = [head(gap_s=0.008), flown(), flown(), head(gap_s=0.009)]
    for s in steps:
        s.pop("rows_admitted", None)
    return steps


@pytest.fixture(scope="module")
def found():
    return spec.discover_layer_metrics()


def test_a_parent_style_run_reports_nothing(found):
    for name in BOUNDARY + SLACK:
        _decl, read = found[name]
        assert read({"steps": parent_style(), "seconds": 48.0}) == {}
        assert read({"steps": [], "seconds": 48.0}) == {}
        assert read({}) == {}


def test_rates_divide_by_the_window_and_leave_idle_and_compiled_out(found):
    _decl, read = found["chain_breaks_per_s"]
    steps = (
        [head("queue", 0.010, admitted=1)] * 5
        + [head("row_finished", 0.008)] * 12
        + [head("stretch_cap", 0.009, kind="decode")] * 3
        + [head("cancel", 0.007)]
        # not boundaries of a busy engine: the loop had nothing to run,
        # a step that compiled, the engine's first step
        + [head("idle"), head("queue", 0.5, compiled=True), head()]
        + [flown(0.012, False)] * 100)
    got = read({"steps": steps, "seconds": 24.0})
    assert got["chain_breaks_per_s"] == pytest.approx(21 / 24.0)
    assert got["chain_breaks_queue_per_s"] == pytest.approx(5 / 24.0)
    assert got["chain_breaks_row_finished_per_s"] == pytest.approx(12 / 24.0)
    assert got["chain_breaks_cap_per_s"] == pytest.approx(3 / 24.0)
    by_cause = sum(got[n] for n in BOUNDARY[1:4])
    assert by_cause <= got["chain_breaks_per_s"]
    # the share is the rate times the mean gap
    gaps = [0.010] * 5 + [0.008] * 12 + [0.009] * 3 + [0.007]
    assert got["boundary_gap_share_pct"] == pytest.approx(
        100.0 * sum(gaps) / 24.0)
    assert got["boundary_gap_share_pct"] == pytest.approx(
        100.0 * got["chain_breaks_per_s"] * sum(gaps) / len(gaps))
    assert got["boundary_gap_p50_ms"] == pytest.approx(8.0)
    # a window with the fields and no boundary at all reads 0, not nothing
    quiet = read({"steps": [head(), flown(0.012, False)], "seconds": 48.0})
    assert quiet["chain_breaks_per_s"] == 0.0
    assert "boundary_gap_p50_ms" not in quiet


def test_the_p99_of_three_samples_is_the_largest(found):
    _decl, read = found["boundary_gap_p99_ms"]
    steps = [head("queue", 0.007), head("row_finished", 0.0329),
             head("queue", 0.009)]
    got = read({"steps": steps, "seconds": 48.0})
    assert got["boundary_gap_p99_ms"] == pytest.approx(32.9)
    assert got["boundary_gap_p50_ms"] == pytest.approx(9.0)
    # a boundary behind an idle loop has no gap_s: not a sample
    steps.append(head("row_finished"))
    assert read({"steps": steps, "seconds": 48.0})[
        "boundary_gap_p99_ms"] == pytest.approx(32.9)


def test_admit_reads_both_parts_of_the_boundaries_that_admitted(found):
    _decl, read = found["boundary_admit_p50_ms"]
    parts = {"schedule.plan": 0.0001, "schedule.admit_pages": 0.0012,
             "schedule.admit_ring": 0.0031, "dispatch.launch": 0.0004}
    steps = [head("queue", 0.010, admitted=1, parts=parts),
             head("queue", 0.012, admitted=2,
                  parts=dict(parts, **{"schedule.admit_ring": 0.0061})),
             head("queue", 0.011, admitted=1, parts=parts),
             # admitted nobody: a plan and a launch, no admission
             head("row_finished", 0.008, parts={"schedule.plan": 0.0001})]
    got = read({"steps": steps, "seconds": 48.0})
    assert got["boundary_admit_p50_ms"] == pytest.approx(4.3)
    none = read({"steps": steps[3:], "seconds": 48.0})
    assert "boundary_admit_p50_ms" not in none


def test_late_share_and_detok_median(found):
    _decl, read = found["chained_steps_late_pct"]
    steps = ([flown(0.012, False, detok=0.004)] * 18
             + [flown(0.0001, True, detok=0.0041)] * 2
             + [head("queue", 0.01, parts={"emit.detok": 0.0039})]
             # a chained record from before the field: not in the share
             + [flown()])
    got = read({"steps": steps, "seconds": 48.0})
    assert got["chained_steps_late_pct"] == pytest.approx(10.0)
    assert got["host_detok_p50_ms"] == pytest.approx(4.0)
    # no chained step, no share; no stream, no detokenisation
    assert read({"steps": [head("queue", 0.01)], "seconds": 48.0}) == {}


def test_declarations_agree_with_benchmark_json(found):
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in doc["per_layer"]}
    for name in BOUNDARY + SLACK:
        decl, _read = found[name]
        entry = entries[name]
        for key in ("unit", "layer", "moves", "source"):
            assert decl[key] == entry[key], (name, key)
        assert entry["better"] == "lower" and entry["moves"] == "out_tok_s"
        assert set(ONE_CHIP) <= set(entry["workloads"])
        assert (FOUR_CHIP in entry["workloads"]) == (
            name != "boundary_admit_p50_ms")
    assert entries["boundary_admit_p50_ms"]["layer"] == \
        "scheduler and page allocator"
    for cell in ONE_CHIP + [FOUR_CHIP]:
        names = spec.Cell(cell).names("per_layer")
        assert set(BOUNDARY[:7] + SLACK) <= set(names)
        assert "out_tok_s" in spec.Cell(cell).names("end_to_end")
