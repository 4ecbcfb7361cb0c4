"""Step dispatch: how often a decode step was kept in flight. Every
`decode` step record of the window carries `chained`: true when the
engine dispatched it from the tokens still on the device while the step
before it was running (`serve/engine.py` `_decode_burst`), so the host's
fetch, emit and bookkeeping for one step ran under the device's work on
the next. The rest are the synchronous steps: the first of a stretch,
and whatever the engine keeps off the device carry (multi-host, a row at
the window's end, speculation's leftovers). A program whose records have
no such field reports nothing."""

DISPATCH = "step dispatch"

METRICS = [{"name": "decode_steps_chained_pct", "unit": "%",
            "layer": DISPATCH, "moves": "out_tok_s",
            "source": "program_counter"}]


def read(run):
    flags = [s["chained"] for s in run.get("steps", [])
             if s["kind"] == "decode" and "chained" in s]
    if not flags:
        return {}
    return {"decode_steps_chained_pct":
            100.0 * sum(bool(f) for f in flags) / len(flags)}
