"""Mamba-2 mixers beside a dense SwiGLU in every layer, a state a row
beside the page pool (granitemoehybrid).

- `dev_share_mamba_pct`: device self time under the scopes `ssm_conv`,
  `ssm_scan`, `ssm_step`, `ssm_gate` and `ssm_state` over busy device
  time; `dev_share_mamba_proj_pct`: under `ssm_in` and `ssm_out` (the
  scopes are nemotron_h's: the mixer is that one, called;
  `harness/trace_spans.py` files the first group under `attn`, the
  projections under `qkv` and `o_proj`).
- `mamba_step_roofline`: the one-step update is XLA (no kernel of its
  own), so its time is the self time under `ssm_step` and `ssm_state`,
  against 2 x the float32 state of every (row, layer) that took the
  one-step form (`harness/mamba_roofline.py`, which hands
  `harness/ssm_roofline.py` this family's keys). The need of each
  execution of a step program is its OWN record's `ssm_tokens_stepped`,
  not the window's mean: an execution's record is read off the capture
  (the engine's `cake/fetch` span carries the record's number and ends
  after the step's module, as `mla_dense.py` reads it; one window a
  step, so a record is one execution), and only the ops inside an
  execution whose record was found are counted on either side.
- `mamba_scan_roofline`: the self time under `ssm_scan` against the
  greater of the chunked form's own operations at the bf16 peak and its
  least bytes, for each execution's own `ssm_tokens_scanned`.
- `decode_step_state_roofline`: a decode execution's device time (first
  to last op) against (the weights' bytes + 2 x the LIVE rows' state +
  the live rows' K and V) over the HBM rate: the share of the whole
  step. Live rows from the record's `ssm_state_rows`, live keys from its
  `attn_pages` as a floor (a row on p pages holds at least
  (p - 1) x page + 1 keys).
- `mamba_state_rows_per_step`: rows whose state a step touched
  (`cake_ssm_state_rows_total` / Mamba layers / step records): at most
  the rows busy, a guard that a row with no token costs nothing.

Every need is a floor, so no share passes 100 on a correct run. A
config of another family, a program without the counters or the scopes,
or a capture without fetch spans yields nothing for the metric
concerned.
"""

import bisect

from harness import mamba_roofline as roof
from harness import readers, trace_reduce as tr, trace_spans as ts
from harness.peaks import peaks
from harness.server import metric_sum

MAMBA_SCOPES = ("ssm_conv", "ssm_scan", "ssm_step", "ssm_gate", "ssm_state")
PROJ_SCOPES = ("ssm_in", "ssm_out")
STEP_SCOPES = ("ssm_step", "ssm_state")
SCAN_SCOPE = "ssm_scan"
FETCH_SPAN = ts.SPAN_PREFIX + "fetch"
PROGRAMS, KERNELS = "step programs", "kernels"

METRICS = [
    {"name": "dev_share_mamba_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_mamba_proj_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "mamba_step_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "mamba_scan_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "mamba_state_rows_per_step", "unit": "rows",
     "layer": "scheduler and page allocator", "moves": "out_tok_s",
     "source": "program_counter"},
    {"name": "decode_step_state_roofline", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
]


def counters(run) -> dict:
    layers = roof.mamba_layers(run["model_config"])
    rows = (metric_sum(run["metrics_1"], "cake_ssm_state_rows_total")
            - metric_sum(run["metrics_0"], "cake_ssm_state_rows_total"))
    steps = [s for s in run["steps"] if s.get("ssm_state_rows")]
    if not layers or rows <= 0 or not steps:
        return {}
    return {"mamba_state_rows_per_step": rows / layers / len(steps)}


def scopes_of(event) -> list:
    return str(event["stats"].get("tf_op") or "").rstrip(":").split("/")


def shares(ops, timed) -> dict:
    """timed: `tr.self_times(ops)`, made once for both passes."""
    groups = {"dev_share_mamba_pct": MAMBA_SCOPES,
              "dev_share_mamba_proj_pct": PROJ_SCOPES}
    self_ns = dict.fromkeys(groups, 0.0)
    for e, ns in timed:
        parts = scopes_of(e)
        for name, scopes in groups.items():
            if any(p in scopes for p in parts):
                self_ns[name] += ns
                break
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    return {name: 100.0 * ns / busy for name, ns in self_ns.items()
            if busy > 0 and ns > 0}


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
                        "record": record, "step_ns": 0.0, "scan_ns": 0.0,
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
    out = shares(ops, timed)
    cfg = run["model_config"]
    layers = roof.mamba_layers(cfg)
    ran = executions(run, planes, devices[0])
    if not layers or not ran:
        return out
    starts = [x["lo"] for x in ran]
    for e, ns in timed:
        i = bisect.bisect_right(starts, e["start_ns"]) - 1
        if i < 0 or e["start_ns"] >= ran[i]["hi"]:
            continue
        x, end = ran[i], e["start_ns"] + e["dur_ns"]
        x["first"] = e["start_ns"] if x["first"] is None else x["first"]
        x["last"] = end if x["last"] is None else max(x["last"], end)
        parts = scopes_of(e)
        if any(p in STEP_SCOPES for p in parts):
            x["step_ns"] += ns
        elif SCAN_SCOPE in parts:
            x["scan_ns"] += ns
    peak = peaks(run["device"]["kind"])
    shape = run["cell"].cell["shape"]
    page = int(run["server_args"]["kv-page-size"])

    def ratio(need_s, ns):
        return 100.0 * need_s / (ns / 1e9) if need_s > 0 and ns > 0 else None

    out["mamba_step_roofline"] = ratio(
        sum(roof.step_least_s(cfg, x["record"].get("ssm_tokens_stepped", 0),
                              peak) for x in ran),
        sum(x["step_ns"] for x in ran))
    out["mamba_scan_roofline"] = ratio(
        sum(roof.scan_least_s(cfg, x["record"].get("ssm_tokens_scanned", 0),
                              peak, shape.get("kv_bytes", 2)) for x in ran),
        sum(x["scan_ns"] for x in ran))
    need = dur = 0.0
    for x in ran:
        rec = x["record"]
        if (x["kind"] != "decode" or x["first"] is None
                or not rec.get("ssm_state_rows")):
            continue
        pages, rows = rec.get("attn_pages", 0), rec["rows"]
        keys = max(0, pages - rows) * page + rows if pages else 0
        need += roof.decode_step_least_s(
            cfg, rec["ssm_state_rows"], keys, peak,
            weight_bytes=shape["weight_bytes"], kv_bytes=shape["kv_bytes"])
        dur += x["last"] - x["first"]
    out["decode_step_state_roofline"] = ratio(need, dur)
    return out


def read(run):
    if roof.as_ssm_config(run["model_config"]) is None:
        return {}
    out = counters(run)
    planes = readers.planes(run)
    if planes:
        out.update(from_trace(run, planes))
    return out
