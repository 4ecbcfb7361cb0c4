"""Step dispatch: the median step's `emit` and `build`, from the step
records of the window (`GET /api/v1/steps`). Each record carries
`phases` (host seconds by step phase since the previous record:
`obs/steps.StepTelemetry.span` around admin / schedule / build /
dispatch / sample / fetch / emit in `serve/engine.py`). Host clock
inside the program, so an untraced run's `layers:` line carries them
too. A program without the spans has no such field and nothing is
reported.

No median of `gap_s`, `schedule` or `sample` here: the median step is
chained, with no gap and neither span of its own, so each reads 0.0
(the cost sits in the step that starts a stretch:
`stretch_boundaries.py`); what the spans leave of the loop is
`loop_clock.py`'s `loop_uncovered_pct`, exact a record."""

from harness.e2e import median

DISPATCH = "step dispatch"

METRICS = [
    {"name": "host_emit_p50_ms", "unit": "ms", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
    {"name": "host_build_p50_ms", "unit": "ms", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
]


def read(run):
    steady = [s["phases"] for s in run.get("steps", [])
              if s.get("phases") and not s["compiled"]]
    if not steady:
        return {}
    return {name: 1000.0 * median([p.get(phase, 0.0) for p in steady])
            for name, phase in (("host_emit_p50_ms", "emit"),
                                ("host_build_p50_ms", "build"))}
