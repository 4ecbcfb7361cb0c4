"""Step dispatch: how much room the host has left under a chained
step, from the step records of the window (`GET /api/v1/steps`). A
chained record carries `fetch_wait_s`, the seconds its own fetch
waited, and `late`: true when that was no wait at all (under
`obs/steps.LATE_FETCH_S`), so the device had finished the step before
the host sent the one after it and sat idle under the chain, where no
boundary shows it. `parts["emit.detok"]` is the detokenisation inside
the `emit` span since the record before: since PR 47 a few ids a token
through the request's `StreamDetokenizer`, whatever the output's length
(`detok_window.py` counts them; before, a row's whole output decoded
again for every token), hidden behind the device while the step is
longer than the host's work on the one before it. A program whose
records lack the fields reports nothing."""

from harness.e2e import median

DISPATCH = "step dispatch"

METRICS = [
    {"name": "chained_steps_late_pct", "unit": "%", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
    {"name": "host_detok_p50_ms", "unit": "ms", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
]


def read(run):
    steps = [s for s in run.get("steps", []) if not s["compiled"]]
    out = {}
    flown = [s for s in steps if s.get("chained") and "fetch_wait_s" in s]
    if flown:
        out["chained_steps_late_pct"] = (
            100.0 * sum(bool(s.get("late")) for s in flown) / len(flown))
    detok = [s["parts"]["emit.detok"] for s in steps
             if "emit.detok" in s.get("parts", {})]
    if detok:
        out["host_detok_p50_ms"] = 1000.0 * median(detok)
    return out
