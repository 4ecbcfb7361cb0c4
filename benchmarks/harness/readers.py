"""Helpers the per-layer readers share: what `run` holds, and the
estimates of each step's rows from the client's records.

`run` is the dict `run.py` builds after the window: `records` (client),
`turnarounds`, `steps` (the window's step records, newest first),
`all_steps`, `traces` ({rid: /api/v1/requests record}), `health`,
`metrics_0/1/2` (/metrics at the window's start, its end, and after the
loop stopped), `t0`/`t1` (monotonic) and `wall_0`/`wall_1`
(time.time()), `healthy_s`, `warmup_s`, `setup_s`, `model_config`,
`server_args`, `cell`, `device`, and `trace` (the reduced trace, or
None in an untraced run).

A traced run's capture is read ONCE here for all the readers
(`planes`), and `trace_spans.reduce_spans` is made once over it
(`span_reduction`); both are kept on `run` under keys of their own.
"""

from __future__ import annotations

import os

from . import spec, trace_spans
from .e2e import median

PLANES_KEY, SPANS_KEY = "_planes", "_span_reduction"


def planes(run: dict):
    """The capture's planes as `trace_spans.read_xspace` gives them, or
    None where the run has no capture. Read once a run: the readers
    share the list and leave it as it is."""
    if PLANES_KEY not in run:
        xplane = (run.get("trace") or {}).get("xplane")
        run[PLANES_KEY] = (trace_spans.read_xspace(xplane)
                           if xplane and os.path.isfile(xplane) else None)
    return run[PLANES_KEY]


def span_reduction(run: dict):
    """`trace_spans.reduce_spans` over the run's capture, made once a
    run, or None where there is no capture. The full tables go to
    `benchmarks/.run/<cell>/trace_spans.json` and one `spans: {...}`
    line to stderr, as `trace_spans.py` run by hand writes them."""
    if SPANS_KEY not in run:
        found = planes(run)
        result = trace_spans.reduce_spans(found) if found else None
        if result is not None and run.get("cell") is not None:
            trace_spans.write(result, os.path.join(
                spec.BENCH_DIR, ".run", run["cell"].name,
                "trace_spans.json"))
        run[SPANS_KEY] = result
    return run[SPANS_KEY]


def steps_of(run: dict, kind: str) -> list:
    return [s for s in run["steps"]
            if s["kind"] == kind and not s["compiled"]]


def median_wall_ms(run: dict, kind: str):
    xs = [s["wall_s"] for s in steps_of(run, kind)]
    return 1000.0 * median(xs) if xs else None


def mono(run: dict, wall_ts: float) -> float:
    """A step record's time.time() on the client's monotonic clock."""
    return run["t0"] + (wall_ts - run["wall_0"])


def by_rid(run: dict) -> dict:
    return {r["rid"]: r for r in run["records"] if r["rid"] is not None}


def context_at(rec: dict, t: float) -> int:
    """Prompt plus the tokens the client had received by time t."""
    return rec["prompt"] + sum(1 for x in rec["token_t"] if x <= t)


def live_tokens_at(run: dict, t: float) -> int:
    """Context held by the requests that were decoding at time t."""
    total = 0
    for r in run["records"]:
        if r["token_t"] and r["token_t"][0] <= t < r.get("t_end", t + 1):
            total += context_at(r, t)
    return total


def mixed_step_rows(run: dict, width: int) -> list:
    """For every mixed step of the window, its rows as [(query tokens,
    context after them)]: a request before its first token is a prefill
    row on its k-th window of `width`, one after it is a decode row."""
    recs = by_rid(run)
    windows_done = {}
    out = []
    for s in sorted(run["all_steps"], key=lambda s: s["step"]):
        if s["kind"] != "mixed" or "rids" not in s:
            continue
        t = mono(run, s["ts"])
        rows = []
        for rid in s["rids"]:
            r = recs.get(rid)
            if r is None:
                continue
            if r["token_t"] and r["token_t"][0] < t - 1e-3:
                rows.append((1, context_at(r, t)))
            else:
                k = windows_done.get(rid, 0)
                windows_done[rid] = k + 1
                q = max(1, min(width, r["prompt"] - k * width))
                rows.append((q, min(r["prompt"], (k + 1) * width)))
        if run["wall_0"] <= s["ts"] < run["wall_1"] and rows:
            out.append(rows)
    return out
