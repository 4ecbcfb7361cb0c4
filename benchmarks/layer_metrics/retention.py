"""Power retention layers, a matrix state a row and K/V head beside a
page pool of no layers (brumby).

- `dev_share_retention_pct`: device self time under the scopes
  `ret_step` and `ret_window` over busy device time, in the captured
  steps (`harness/trace_spans.py` files both under `attn`; the
  projections' `ret_in` and `ret_out` under `qkv` and `o_proj`).
- `retention_step_roofline`: the device time of the kernel
  `cake_retention_step` against 2 x the float32 state (S and z, at the
  least D any layout holds: `harness/retention_roofline.py`) of every
  (row, layer) that took the one-step form. The need of each execution
  of a step program is its OWN record's `retention_tokens_stepped`: an
  execution's record is read off the capture (the engine's `cake/fetch`
  span carries the record's number and ends after the step's module, as
  `mamba.py` reads it; one window a step, so a record is one
  execution), and only the ops inside an execution whose record was
  found are counted on either side.
  The window form (`ret_window`) has no share of its own here: the
  capture lies 2-5 s into the window, where this cell's sixteen rows
  mostly decode (their prompts went through during the ramp, and the
  next ones come as the first replies end, about where the capture
  does): a run's capture holds no mixed step or a few at its tail, by
  its seed, so a share that needs one cannot be relied on to be there
  and is not listed; its count is kept
  (`retention_roofline.window_least_s`) for the benchmark that can
  place a capture. When the tail does hold windows,
  `dev_share_retention_pct` sums both forms over them: it is the share
  of whatever steps the capture caught, not of decode steps alone.
- `decode_step_retention_roofline`: a decode execution's device time
  (first to last op) against (the weights' bytes + 2 x the LIVE rows'
  state) over the HBM rate: the share of the whole step. Live rows from
  the record's `retention_state_rows`.

Every need is a floor, so no share passes 100 on a correct run. A
config of another family, a program without the counters, the scopes or
the kernel, or a capture without fetch spans yields nothing for the
metric concerned.
"""

import bisect

from harness import readers, retention_roofline as roof
from harness import trace_reduce as tr, trace_spans as ts
from harness.peaks import peaks

RETENTION_SCOPES = ("ret_step", "ret_window")
STEP_KERNEL = "cake_retention_step"
FETCH_SPAN = ts.SPAN_PREFIX + "fetch"
PROGRAMS, KERNELS = "step programs", "kernels"

METRICS = [
    {"name": "dev_share_retention_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "retention_step_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "decode_step_retention_roofline", "unit": "%",
     "layer": PROGRAMS, "moves": "out_tok_s", "source": "device_trace"},
]


def scopes_of(event) -> list:
    return str(event["stats"].get("tf_op") or "").rstrip(":").split("/")


def fetched_steps(planes) -> list:
    """[(end_ns, step)] of the capture's `cake/fetch` spans, by end."""
    return sorted(
        (e["start_ns"] + e["dur_ns"], int(e["stats"]["step"]))
        for p in planes if ts.is_host_plane(p["name"])
        for line in p["lines"] for e in line["events"]
        if e["name"] == FETCH_SPAN and e["stats"].get("step") is not None)


def executions(run, planes, device) -> list:
    """Device 0's executions of a step program whose record the capture
    names: [{"lo", "hi", "kind", "record"}] by start."""
    fetches = fetched_steps(planes)
    modules = tr._line(device, (ts.MODULES_LINE,))
    if not fetches or not modules:
        return []
    ends = [end for end, _step in fetches]
    records = {s["step"]: s for s in run.get("all_steps") or run["steps"]}
    out = []
    for m in sorted(modules["events"], key=lambda e: e["start_ns"]):
        kind = ts.step_kind(ts.program_of(m["name"]))
        hi = m["start_ns"] + m["dur_ns"]
        i = bisect.bisect_left(ends, hi)
        record = records.get(fetches[i][1]) if i < len(ends) else None
        if kind and record is not None and record["kind"] == kind:
            out.append({"lo": m["start_ns"], "hi": hi, "kind": kind,
                        "record": record, "kernel_ns": 0.0,
                        "first": None, "last": None})
    return out


def from_trace(run, planes) -> dict:
    devices = sorted((p for p in planes if tr.is_device_plane(p["name"])),
                     key=lambda p: p["name"])
    ops = tr._line(devices[0], (ts.OPS_LINE,)) if devices else None
    ops = [e for e in (ops["events"] if ops else []) if e["dur_ns"] > 0]
    if not ops:
        return {}
    timed = tr.self_times(ops)
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    under = sum(ns for e, ns in timed
                if any(p in RETENTION_SCOPES for p in scopes_of(e)))
    out = {}
    if busy > 0 and under > 0:
        out["dev_share_retention_pct"] = 100.0 * under / busy
    cfg = run["model_config"]
    ran = executions(run, planes, devices[0])
    if not ran:
        return out
    starts = [x["lo"] for x in ran]
    for e, ns in timed:
        i = bisect.bisect_right(starts, e["start_ns"]) - 1
        if i < 0 or e["start_ns"] >= ran[i]["hi"]:
            continue
        x, end = ran[i], e["start_ns"] + e["dur_ns"]
        x["first"] = e["start_ns"] if x["first"] is None else x["first"]
        x["last"] = end if x["last"] is None else max(x["last"], end)
        if ts.kernel_of(e) == STEP_KERNEL:
            x["kernel_ns"] += ns
    peak = peaks(run["device"]["kind"])
    shape = run["cell"].cell["shape"]

    def ratio(need_s, ns):
        return 100.0 * need_s / (ns / 1e9) if need_s > 0 and ns > 0 else None

    out["retention_step_roofline"] = ratio(
        sum(roof.step_least_s(
            cfg, x["record"].get("retention_tokens_stepped", 0), peak)
            for x in ran),
        sum(x["kernel_ns"] for x in ran))
    need = dur = 0.0
    for x in ran:
        rec = x["record"]
        if (x["kind"] != "decode" or x["first"] is None
                or not rec.get("retention_state_rows")):
            continue
        need += roof.decode_step_least_s(
            cfg, rec["retention_state_rows"], peak,
            weight_bytes=shape["weight_bytes"])
        dur += x["last"] - x["first"]
    out["decode_step_retention_roofline"] = ratio(need, dur)
    return out


def read(run):
    if roof.dims(run["model_config"]) is None:
        return {}
    planes = readers.planes(run)
    return from_trace(run, planes) if planes else {}
