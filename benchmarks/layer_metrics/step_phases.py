"""Step dispatch and scheduler: where the engine thread's time goes
between steps, from the step records of the window (`GET
/api/v1/steps`). Each record carries `phases` (host seconds by step
phase since the previous record: `obs/steps.StepTelemetry.span` around
admin / schedule / build / dispatch / sample / fetch / emit in
`serve/engine.py`) and `gap_s` (end of the previous step's fetch to
the start of this step's dispatch: the device with nothing queued).
Host clock inside the program, so an untraced run's `layers:` line
carries them too. A program without the spans has neither field and
nothing is reported."""

from harness.e2e import median

DISPATCH = "step dispatch"

METRICS = [
    {"name": "step_gap_p50_ms", "unit": "ms", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
    {"name": "host_emit_p50_ms", "unit": "ms", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
    {"name": "host_schedule_p50_ms", "unit": "ms",
     "layer": "scheduler and page allocator", "moves": "out_tok_s",
     "source": "program_span"},
    {"name": "host_build_p50_ms", "unit": "ms", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
    {"name": "host_sample_p50_ms", "unit": "ms", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
    {"name": "loop_covered_pct", "unit": "%", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
]


def read(run):
    steps = [s for s in run.get("steps", []) if s.get("phases")]
    if not steps:
        return {}
    out = {}
    steady = [s for s in steps if not s["compiled"]]

    def p50(value):
        xs = [value(s["phases"]) for s in steady]
        return 1000.0 * median(xs) if xs else None

    gaps = [s["gap_s"] for s in steady if s.get("gap_s") is not None]
    if gaps:
        out["step_gap_p50_ms"] = 1000.0 * median(gaps)
    out["host_emit_p50_ms"] = p50(lambda p: p.get("emit", 0.0))
    out["host_schedule_p50_ms"] = p50(
        lambda p: p.get("admin", 0.0) + p.get("schedule", 0.0))
    out["host_build_p50_ms"] = p50(lambda p: p.get("build", 0.0))
    out["host_sample_p50_ms"] = p50(lambda p: p.get("sample", 0.0))
    # what the spans do not cover is a blind spot of the loop. A
    # record's phases are the spans between the record before it and
    # itself, so all records but the first cover the time from the
    # first record to the last
    by_time = sorted(steps, key=lambda s: s["ts"])
    elapsed = by_time[-1]["ts"] - by_time[0]["ts"]
    if elapsed > 0:
        out["loop_covered_pct"] = 100.0 * sum(
            sum(s["phases"].values()) for s in by_time[1:]) / elapsed
    return out
