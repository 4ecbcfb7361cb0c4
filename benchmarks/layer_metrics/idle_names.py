"""Device: how much of the capture's idle device time has a name.

`harness/trace_spans.py` splits device 0's idle time by the `cake/`
event of the host plane it lay under (`idle_s`, every name the capture
holds) and reports the share under its fixed list of host-work spans
(`idle_attributed_pct`). These two read the ends of that table, with
its functions and from the capture itself:

- `idle_unnamed_pct`: 100 x idle time under NO `cake/` event / idle
  time: what lies between one span's exit and the next one's enter.
- `idle_gc_pct`: 100 x idle time under a `cake/gc` event (a
  generation-2 collection, on whichever thread ran it:
  `obs/steps._GcWatch`) / idle time.

Every idle gap of LONG_MS or more goes to
`benchmarks/.run/<cell>/idle_long_gaps.json` and one `idle_gaps: [...]`
line on stderr with the `cake/` events it overlapped and their `step`
stats: a span carries the number of the step record being put
together, so the gap lies inside that record's `loop_s`
(`host_pauses.json`; `cake/record` alone carries the number of the
record it writes, one less).

Nothing in an untraced run. A program whose loop has no `gate`,
`record` or `release` span (the parent) reports nothing: its table has another
vocabulary, and `trace_spans.json` holds it."""

import json
import os
import sys

from harness import readers, spec, trace_reduce as tr, trace_spans as ts

DEVICE = "device"
LONG_MS = 30.0
NEW_SPANS = ("gate", "record", "release")

METRICS = [
    {"name": name, "unit": "%", "layer": DEVICE, "moves": "out_tok_s",
     "source": "device_trace"}
    for name in ("idle_unnamed_pct", "idle_gc_pct")
]


def idle_and_spans(planes: list):
    """(device 0's idle gaps [[start, end]] in ns, {span name: [(start,
    end, step)]} of every host line) from `trace_spans.read_xspace`'s
    dicts."""
    devices = sorted((p for p in planes if tr.is_device_plane(p["name"])),
                     key=lambda p: p["name"])
    ops = tr._line(devices[0], (ts.OPS_LINE,)) if devices else None
    busy = ts.merge((e["start_ns"], e["start_ns"] + e["dur_ns"])
                    for e in (ops["events"] if ops else [])
                    if e["dur_ns"] > 0)
    spans = {}
    for p in planes:
        if not ts.is_host_plane(p["name"]):
            continue
        for ln in p["lines"]:
            for e in ln["events"]:
                if e["name"].startswith(ts.SPAN_PREFIX) and e["dur_ns"] > 0:
                    spans.setdefault(
                        e["name"][len(ts.SPAN_PREFIX):], []).append(
                        (e["start_ns"], e["start_ns"] + e["dur_ns"],
                         e["stats"].get("step")))
    return [list(g) for g in tr.gaps_of(busy)], spans


def shares(idle: list, spans: dict) -> dict:
    idle_ns = sum(e - s for s, e in idle)
    if idle_ns <= 0 or not any(name in spans for name in NEW_SPANS):
        return {}
    named = ts.merge((s, e) for iv in spans.values() for s, e, _ in iv)
    gc = ts.merge((s, e) for s, e, _ in spans.get("gc", []))
    return {"idle_unnamed_pct":
            100.0 * (1.0 - ts.overlap_ns(idle, named) / idle_ns),
            "idle_gc_pct": 100.0 * ts.overlap_ns(idle, gc) / idle_ns}


def long_gaps(idle: list, spans: dict) -> list:
    """The idle gaps of LONG_MS or more: when (ms after the capture's
    first gap), how long, the `cake/` events that overlapped each as
    {name: [steps]}, and the last event to end before it and the first
    to start after it as [name, step]."""
    flat = [(a, b, name, step) for name, iv in spans.items()
            for a, b, step in iv]
    out = []
    for s, e in idle:
        if (e - s) / 1e6 < LONG_MS:
            continue
        under = {}
        for a, b, name, step in flat:
            if a < e and b > s:
                under.setdefault(name, set()).add(step)
        before = max((x for x in flat if x[1] <= s), key=lambda x: x[1],
                     default=None)
        after = min((x for x in flat if x[0] >= e), key=lambda x: x[0],
                    default=None)
        out.append({
            "at_ms": (s - idle[0][0]) / 1e6, "ms": (e - s) / 1e6,
            "under": {name: sorted(steps, key=lambda x: (x is None, x))
                      for name, steps in sorted(under.items())},
            "before": before and [before[2], before[3]],
            "after": after and [after[2], after[3]]})
    return out


def read(run):
    planes = readers.planes(run)
    if not planes:
        return {}
    idle, spans = idle_and_spans(planes)
    gaps = long_gaps(idle, spans)
    if run.get("cell") is not None:
        path = os.path.join(spec.BENCH_DIR, ".run", run["cell"].name,
                            "idle_long_gaps.json")
        with open(path, "w") as f:
            json.dump(gaps, f, indent=1)
    print("idle_gaps: " + json.dumps(gaps), file=sys.stderr, flush=True)
    return shares(idle, spans)
