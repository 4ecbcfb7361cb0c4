"""Step dispatch and scheduler: the boundaries between stretches of
in-flight steps, from the step records of the window (`GET
/api/v1/steps`). A record that is not chained carries `chain_break`
where a chain ended before it: the first gate that refused to dispatch
ahead (`obs/steps.BREAKS`: stop, queue, cancel, command, sync,
stretch_cap, row_finished, budget, window_end), or `idle` where the
loop had nothing to run. The same record carries `gap_s` (the device
with nothing queued: end of the last fetch to this step's first
dispatch), `rows_admitted`, and `parts`: host seconds under
`schedule.plan`, `schedule.admit_pages`, `schedule.admit_ring`,
`dispatch.launch`, `emit.detok` (`obs/steps.PARTS`).

The medians of `step_phases.py` run over all steps, most of them
chained with a gap of 0.0; these run over the boundaries alone, where
the host's cost sits. `idle` boundaries are left out everywhere: a
request that met an idle engine waited for nobody. Host clock inside
the program, so an untraced run's `layers:` line carries them too. A
program whose records have neither `chain_break` nor `rows_admitted`
reports nothing."""

import math

from harness.e2e import median

DISPATCH = "step dispatch"
SCHEDULER = "scheduler and page allocator"
BY_CAUSE = {"chain_breaks_queue_per_s": "queue",
            "chain_breaks_row_finished_per_s": "row_finished",
            "chain_breaks_cap_per_s": "stretch_cap"}

METRICS = [
    {"name": name, "unit": "1/s", "layer": DISPATCH, "moves": "out_tok_s",
     "source": "program_counter"}
    for name in ("chain_breaks_per_s", *BY_CAUSE)
] + [
    {"name": "boundary_gap_p50_ms", "unit": "ms", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
    {"name": "boundary_gap_p99_ms", "unit": "ms", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
    {"name": "boundary_gap_share_pct", "unit": "%", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
    {"name": "boundary_admit_p50_ms", "unit": "ms", "layer": SCHEDULER,
     "moves": "out_tok_s", "source": "program_span"},
]


def nearest_rank(values, q: float) -> float:
    """The smallest sample with at least q % of the samples at or under
    it: of a handful of boundaries the p99 is the longest, never a
    value between two that no boundary took."""
    xs = sorted(values)
    return xs[max(0, math.ceil(len(xs) * q / 100.0) - 1)]


def read(run):
    steps = [s for s in run.get("steps", []) if not s["compiled"]]
    if not any("chain_break" in s or "rows_admitted" in s for s in steps):
        return {}
    seconds = run["seconds"]
    ends = [s for s in steps if s.get("chain_break", "idle") != "idle"]
    out = {"chain_breaks_per_s": len(ends) / seconds}
    for name, cause in BY_CAUSE.items():
        out[name] = sum(s["chain_break"] == cause for s in ends) / seconds
    gaps = [s["gap_s"] for s in ends if s.get("gap_s") is not None]
    if gaps:
        out["boundary_gap_p50_ms"] = 1000.0 * median(gaps)
        out["boundary_gap_p99_ms"] = 1000.0 * nearest_rank(gaps, 99.0)
    # the host clock's reading of the device's idle share at boundaries
    out["boundary_gap_share_pct"] = 100.0 * sum(gaps) / seconds
    admits = [s.get("parts", {}) for s in ends if s.get("rows_admitted")]
    if admits:
        out["boundary_admit_p50_ms"] = 1000.0 * median(
            [p.get("schedule.admit_pages", 0.0)
             + p.get("schedule.admit_ring", 0.0) for p in admits])
    return out
