"""Step dispatch: whether the engine thread's clock adds up, from the
step records of the window (`GET /api/v1/steps`). A record carries
`loop_s`, the thread's seconds since the record before it (or since a
`wait` ended), beside `phases`, the seconds of its spans: the
difference lay outside every span. `offcpu` is, by phase, a span's
wall seconds less its thread's CPU seconds: under `fetch` and
`dispatch` the device or the runtime, under the host-work phases the
thread WAITING while it had work (the interpreter lock held by a
handler thread, a collection on another thread, the kernel's
scheduler). `gc_s`, `gc_n`, `gc_max_s` are the process's cyclic
collections since the record before.

- `loop_uncovered_pct`: 100 x sum(loop_s - sum(phases)) / sum(loop_s).
- `host_offcpu_pct`: 100 x sum(offcpu) / sum(phases) over HOST_WORK.
- `gc_pause_share_pct`: 100 x sum(gc_s) / window seconds;
  `gc_pause_max_ms`: the longest collection.
- `host_pause_max_ms`: the longest host interval of the window, a
  record's `loop_s` less its `fetch` (the device's side). The five
  longest, and every one of LONG_S or more, go decomposed (phases,
  parts, offcpu, gc_s, what no span held) to
  `benchmarks/.run/<cell>/host_pauses.json` and one `pauses: {...}`
  line on stderr.
- `host_room_p50_ms`: median `fetch_wait_s` of the chained records,
  what the device still had to do when the host came to fetch: the
  host's slack under a step, readable untraced where `late` saturates.

Window SUMS, not medians of records. A program whose records lack a
field reports nothing of what reads it (the parent: `host_room_p50_ms`
alone, `fetch_wait_s` is PR 35's)."""

import json
import os
import sys

from harness import spec
from harness.e2e import median

DISPATCH = "step dispatch"
# the spans under which the engine thread has work of its own
HOST_WORK = ("admin", "schedule", "build", "sample", "emit", "gate",
             "record", "release")
TOP = 5
LONG_S = 0.030     # host intervals listed besides the TOP longest
LONG_MAX = 64

METRICS = [
    {"name": name, "unit": unit, "layer": DISPATCH, "moves": "out_tok_s",
     "source": source}
    for name, unit, source in (
        ("loop_uncovered_pct", "%", "program_span"),
        ("host_offcpu_pct", "%", "program_span"),
        ("gc_pause_share_pct", "%", "program_counter"),
        ("gc_pause_max_ms", "ms", "program_counter"),
        ("host_pause_max_ms", "ms", "program_span"),
        ("host_room_p50_ms", "ms", "program_span"))
]


def host_s(step: dict) -> float:
    """A record's interval on the host's side: its loop less the
    spans in which the thread waits for the device or for work."""
    phases = step.get("phases", {})
    return (step["loop_s"] - phases.get("fetch", 0.0)
            - phases.get("wait", 0.0))


def unnamed_s(step: dict) -> float:
    """What of a record's loop lay outside every span."""
    return step["loop_s"] - sum(step.get("phases", {}).values())


def decomposed(step: dict) -> dict:
    """One host interval by what the records say of it. `host_s` is
    the sum of `phases` without fetch and wait, plus `unnamed_s`; each
    phase holds its `offcpu` and its `parts`; `gc_s` lies wherever the
    collections ran (on this thread inside a phase's CPU time, on
    another inside its offcpu)."""
    out = {"host_s": host_s(step), "loop_s": step["loop_s"],
           "unnamed_s": unnamed_s(step)}
    for key in ("step", "kind", "chained", "chain_break", "rows",
                "tokens", "phases", "parts", "offcpu", "gc_s", "gc_n",
                "gc_max_s"):
        if key in step:
            out[key] = step[key]
    return out


def pauses(steps: list) -> dict:
    """The TOP longest host intervals of `steps`, longest first; every
    one of LONG_S or more by step number; and by kind of record
    ("decode", or "decode.head" for one that is not chained) what an
    ordinary one holds outside every span: records, their mean loop_s,
    the mean and the median of what no span held, and the mean of that
    off the CPU (`offcpu["none"]`), in microseconds."""
    timed = sorted((s for s in steps if "loop_s" in s), key=host_s,
                   reverse=True)
    longs = [s for s in timed if host_s(s) >= LONG_S][:LONG_MAX]
    kinds = {}
    for s in timed:
        kinds.setdefault(
            s["kind"] + ("" if s.get("chained") else ".head"), []).append(s)

    return {"longest": [decomposed(s) for s in timed[:TOP]],
            "long": [decomposed(s)
                     for s in sorted(longs, key=lambda s: s["step"])],
            "unnamed_us": {
                kind: {"records": len(ss),
                       "loop_mean": 1e6 * sum(s["loop_s"] for s in ss)
                       / len(ss),
                       "mean": 1e6 * sum(map(unnamed_s, ss)) / len(ss),
                       "p50": 1e6 * median([unnamed_s(s) for s in ss]),
                       "offcpu_mean": 1e6 * sum(
                           s.get("offcpu", {}).get("none", 0.0)
                           for s in ss) / len(ss)}
                for kind, ss in sorted(kinds.items())}}


def read(run):
    steps = [s for s in run.get("steps", []) if not s["compiled"]]
    out = {}
    flown = [s["fetch_wait_s"] for s in steps
             if s.get("chained") and "fetch_wait_s" in s]
    if flown:
        out["host_room_p50_ms"] = 1000.0 * median(flown)
    timed = [s for s in steps if "loop_s" in s]
    loop = sum(s["loop_s"] for s in timed)
    if loop > 0:
        out["loop_uncovered_pct"] = 100.0 * sum(map(unnamed_s, timed)) / loop
        found = pauses(timed)
        out["host_pause_max_ms"] = 1000.0 * found["longest"][0]["host_s"]
        if run.get("cell") is not None:
            path = os.path.join(spec.BENCH_DIR, ".run", run["cell"].name,
                                "host_pauses.json")
            with open(path, "w") as f:
                json.dump(found, f, indent=1)
        print("pauses: " + json.dumps(
            {"longest": found["longest"], "long": len(found["long"]),
             "unnamed_us": found["unnamed_us"]}),
            file=sys.stderr, flush=True)
    off = [s for s in steps if "offcpu" in s]
    work = sum(s["phases"].get(k, 0.0) for s in off for k in HOST_WORK)
    if work > 0:
        out["host_offcpu_pct"] = 100.0 * sum(
            s["offcpu"].get(k, 0.0) for s in off for k in HOST_WORK) / work
    swept = [s for s in steps if "gc_s" in s]
    if swept and run.get("seconds"):
        out["gc_pause_share_pct"] = (
            100.0 * sum(s["gc_s"] for s in swept) / run["seconds"])
        out["gc_pause_max_ms"] = 1000.0 * max(s["gc_max_s"] for s in swept)
    return out
