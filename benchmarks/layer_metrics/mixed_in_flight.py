"""Step dispatch: how often a mixed step was kept in flight. Every
`mixed` step record of the window carries `chained`: true when the
engine dispatched it while the step before it was still running, its
decode rows fed from that step's tokens on the device and its prompt
windows from the host (`serve/engine.py` `_mixed_burst`), so the fetch,
the record and the emit of one step ran under the device's work on the
next. The rest are the first steps of their stretches: after a request
finished, a submit, a cancel or a command, and every `STRETCH_STEPS`
steps. A program whose mixed records have no such field reports
nothing."""

DISPATCH = "step dispatch"

METRICS = [{"name": "mixed_steps_chained_pct", "unit": "%",
            "layer": DISPATCH, "moves": "out_tok_s",
            "source": "program_counter"}]


def read(run):
    flags = [s["chained"] for s in run.get("steps", [])
             if s["kind"] == "mixed" and "chained" in s]
    if not flags:
        return {}
    return {"mixed_steps_chained_pct":
            100.0 * sum(bool(f) for f in flags) / len(flags)}
