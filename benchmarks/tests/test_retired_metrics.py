"""What five metrics retired at PR 55 guarded, held here instead:
`emit_unnamed_us_per_token` said that the six named parts of `emit` add
up to the span; `loop_covered_pct` was the complement of
`loop_uncovered_pct`; three medians read 0.0 because the median step is
chained. Over step records RECORDED from the program (a CPU rehearsal
of `mistral7b.decode-long`, `tests/recorded_steps.json`), and the named
failure of a trace reduction that passes its limit."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from harness import spec
from harness.e2e import median

HERE = os.path.dirname(os.path.abspath(__file__))


def reader(fname):
    path = os.path.join(spec.BENCH_DIR, "layer_metrics", fname)
    s = importlib.util.spec_from_file_location("retired_" + fname[:-3], path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_steps.json")) as f:
        steps = json.load(f)
    assert {s["kind"] for s in steps} == {"decode", "mixed"}
    return steps


def test_the_parts_of_emit_add_up_to_the_span(recorded):
    """The seams of `serve/engine._emit` are read by one clock, each
    part from the read before it: nothing of the span is unnamed. A
    record's fields are rounded to the microsecond, so six parts can be
    three microseconds off their span."""
    emit_parts = reader("emit_parts.py")
    assert len(emit_parts.NAMED) == 6
    for s in recorded:
        assert set(emit_parts.NAMED) - {"emit.retire"} <= set(s["parts"])
        span = s["phases"]["emit"]
        named = sum(s["parts"].get(key, 0.0) for key in emit_parts.NAMED)
        assert span > 0 and abs(span - named) <= 3e-6, s["step"]
    # what the metric read: the window's sum of the difference, a token
    tokens = sum(s["tokens"] for s in recorded)
    unnamed_us = 1e6 * sum(
        s["phases"]["emit"] - sum(s["parts"].get(k, 0.0)
                                  for k in emit_parts.NAMED)
        for s in recorded) / tokens
    assert abs(unnamed_us) < 0.05                    # the ledger: +-0.001
    got = emit_parts.read({"steps": recorded})
    assert "emit_unnamed_us_per_token" not in got
    assert sum(got[n] for n in emit_parts.BY_PART) <= \
        got["emit_us_per_token"] + 0.05


def test_what_the_spans_leave_of_the_loop_is_what_the_clock_says(recorded):
    """`loop_covered_pct` divided the spans by wall-clock `ts`;
    `loop_uncovered_pct` takes a record's own `loop_s`, and on the same
    records the two add up to 100 (98.369 + 1.630 in Nemotron's cell,
    99.508 + 0.487 in dots3's: ledger, PR 54)."""
    got = reader("loop_clock.py").read({"steps": recorded, "seconds": 48.0})
    spans = sum(sum(s["phases"].values()) for s in recorded)
    loop = sum(s["loop_s"] for s in recorded)
    assert got["loop_uncovered_pct"] == pytest.approx(
        100.0 * (loop - spans) / loop)
    assert 0.0 <= got["loop_uncovered_pct"] < 10.0
    assert "loop_covered_pct" not in reader("step_phases.py").read(
        {"steps": recorded})


def test_the_median_step_is_chained_so_three_medians_read_nothing(recorded):
    """Why `step_gap_p50_ms`, `host_schedule_p50_ms` and
    `host_sample_p50_ms` went: a chained record has `gap_s` 0.0 and no
    `schedule` or `sample` span, and most records are chained."""
    chained = [s for s in recorded if s.get("chained")]
    assert chained and all(s["gap_s"] == 0.0 for s in chained)
    assert not any("sample" in s["phases"] or "schedule" in s["phases"]
                   for s in chained)
    # the step that starts a stretch carries them, and
    # stretch_boundaries.py reads it
    heads = [s for s in recorded if not s.get("chained")]
    assert heads and all("schedule" in s["phases"] for s in heads)
    window = chained * 10 + heads
    assert median([s["gap_s"] for s in window]) == 0.0
    got = reader("step_phases.py").read({"steps": window})
    assert set(got) == {"host_emit_p50_ms", "host_build_p50_ms"}
    assert got["host_emit_p50_ms"] > 0


def test_a_reduction_past_its_limit_is_a_failed_run_with_a_name(
        tmp_path, monkeypatch):
    sys.path.insert(0, spec.BENCH_DIR)
    s = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(spec.BENCH_DIR, "run.py"))
    run_py = importlib.util.module_from_spec(s)
    s.loader.exec_module(run_py)

    def too_slow(cmd, **kw):
        assert cmd[1].endswith(os.path.join("harness", "trace_reduce.py"))
        assert kw["timeout"] == run_py.REDUCE_LIMIT_S == 900
        raise subprocess.TimeoutExpired(cmd, kw["timeout"])

    monkeypatch.setattr(run_py.subprocess, "run", too_slow)
    with pytest.raises(run_py.RunFailure) as failure:
        run_py.reduce_trace(str(tmp_path / "profile"), str(tmp_path))
    # `main` reports a RunFailure as `BENCHMARK RUN FAILED: ...`, exit 1
    assert "trace reduction" in str(failure.value)
    assert "900 s" in str(failure.value)
    assert "trace_reduce.py" in str(failure.value)
