"""Step dispatch: what a token's `emit` is made of, from the step
records of the window (`GET /api/v1/steps`). `phases["emit"]` is the
`emit` span since the record before; `parts` names it a row a token by
the clock reads at the seams of `serve/engine._emit`
(`obs/steps.EMIT_SEAMS`): `emit.rows` what the span does for a row
outside `_emit` (the mirrors, the `tolist()` / `zip` of the
alternatives), `emit.trace` the request tracer and the TTFT series,
`emit.report` the journal's note, the stats and the scheduler's report,
`emit.detok` the detokenisation (`host_detok_p50_ms` reads it),
`emit.stream` the request's callback (a queue put, and whatever the
interpreter hands the woken handler thread before it returns),
`emit.retire` a finished row's release.

Window SUMS over the records, over the tokens they emitted, in
microseconds a token: a median of records reads 0.0 wherever most
records lack the part. The emit of a step follows its record, so the
two sums are one step apart at each end of the window. A program whose
records name no such part (the parent: `emit.detok` alone) reports
nothing.

The six parts are read at the seams of one span, so they add up to it
and no metric reads the difference: a test over recorded step records
holds it (`tests/test_retired_metrics.py`, over this file's `NAMED`)."""

DISPATCH = "step dispatch"
BY_PART = {"emit_trace_us_per_token": "emit.trace",
           "emit_report_us_per_token": "emit.report",
           "emit_stream_us_per_token": "emit.stream",
           "emit_retire_us_per_token": "emit.retire",
           "emit_rows_us_per_token": "emit.rows"}
NAMED = (*BY_PART.values(), "emit.detok")

METRICS = [
    {"name": name, "unit": "us", "layer": DISPATCH, "moves": "out_tok_s",
     "source": "program_span"}
    for name in ("emit_us_per_token", *BY_PART)
]


def read(run):
    steps = run.get("steps", [])
    tokens = sum(s.get("tokens", 0) for s in steps)
    if not tokens or not any("emit.rows" in s.get("parts", {})
                             for s in steps):
        return {}

    def us(seconds):
        return 1e6 * seconds / tokens

    span = sum(s.get("phases", {}).get("emit", 0.0) for s in steps)
    part = {key: sum(s.get("parts", {}).get(key, 0.0) for s in steps)
            for key in NAMED}
    out = {name: us(part[key]) for name, key in BY_PART.items()}
    out["emit_us_per_token"] = us(span)
    return out
