"""The sparse-expert FFN: its grouped matmuls' share of their roofline,
the device time of routing, dispatch and combine around them, tile
padding, and expert imbalance.

- `moe_experts_roofline`: the summed device time of the `cake_moe_gmm`
  kernel events of the capture (device 0) against what the routed rows
  needed (`harness/moe_roofline.py`). One event is one projection of one
  layer of one step; a decode step's events and a mixed step's are told
  apart by the rows of the printed result shape (16 * k against
  16 * width * k). What a step of each kind needs on average over the
  window comes from the step records' `moe_rows` and
  `moe_experts_touched`, which the step program counts on the device.
- `dev_share_moe_route_pct`: device self time under the scopes `router`,
  `moe_dispatch` and `moe_combine` over busy device time, read from the
  capture with `harness/trace_spans.py`'s functions and this file's own
  scope list (its fixed list files all of them under `ffn`).
- `moe_rows_padded_pct`: of the rows the kernel's tiles covered, the
  share that held no routed row (`cake_moe_rows_padded_total` against
  `cake_moe_rows_total` over the window).
- `moe_expert_load_max_over_mean`: tokens on a layer's busiest expert
  over tokens on its average expert, both summed over the window's steps.

A program without the counters, the scopes or the kernel (a dense model,
an older program) yields nothing for the metric concerned.
"""

import re

from harness import moe_roofline, readers, trace_reduce as tr
from harness import trace_spans as ts
from harness.peaks import peaks
from harness.server import metric_sum

KERNEL = "cake_moe_gmm"
ROUTE_SCOPES = ("router", "moe_dispatch", "moe_combine")
PROJECTIONS = 3          # gate, up, down: kernel events per layer and step

METRICS = [
    {"name": "moe_experts_roofline", "unit": "%", "layer": "kernels",
     "moves": "ttft_mean_ms", "source": "device_trace"},
    {"name": "dev_share_moe_route_pct", "unit": "%", "layer": "step programs",
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "moe_rows_padded_pct", "unit": "%", "layer": "step programs",
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "moe_expert_load_max_over_mean", "unit": "ratio",
     "layer": "scheduler and page allocator", "moves": "out_tok_s",
     "source": "program_counter"},
]


def result_rows(event_name: str):
    """Rows of the kernel's printed result, `bf16[rows,cols]`."""
    m = re.search(r"= [a-z0-9]+\[(\d+),\d+\]", event_name)
    return int(m.group(1)) if m else None


def counters(run) -> dict:
    def delta(family):
        return (metric_sum(run["metrics_1"], family)
                - metric_sum(run["metrics_0"], family))

    out = {}
    rows = delta("cake_moe_rows_total")
    padded = delta("cake_moe_rows_padded_total")
    if rows > 0 and padded > 0:
        out["moe_rows_padded_pct"] = 100.0 * (padded - rows) / padded
    mean = delta("cake_moe_expert_load_mean")
    if mean > 0:
        out["moe_expert_load_max_over_mean"] = (
            delta("cake_moe_expert_load_max") / mean)
    return out


def need_per_step(run, kind: str):
    """Mean least seconds of the grouped matmuls of one step of `kind`
    (all layers), from the window's step records."""
    cfg, shape = run["model_config"], run["cell"].cell["shape"]
    peak = peaks(run["device"]["kind"])
    L = cfg["num_hidden_layers"]
    per_step = [
        L * moe_roofline.experts_least_s(
            cfg, s["moe_rows"] / L, s["moe_experts_touched"] / L, peak,
            weight_bytes=shape["weight_bytes"])
        for s in run["steps"]
        if s["kind"] == kind and not s["compiled"] and s.get("moe_rows")]
    return sum(per_step) / len(per_step) if per_step else None


def experts_roofline(run):
    trace = run.get("trace")
    if not trace or not trace.get("kernels"):
        return None
    cfg = run["model_config"]
    slots = run["health"].get("decode_slots")
    k = cfg.get("num_experts_per_tok")
    if not slots or not k:
        return None
    events_per_step = PROJECTIONS * cfg["num_hidden_layers"]
    dur, count = 0.0, {}
    for ev in trace["kernels"]:
        if ev["device"] != 0 or KERNEL not in ev["name"]:
            continue
        rows = result_rows(ev["name"])
        if rows is None:
            continue
        kind = "decode" if rows <= slots * k else "mixed"
        dur += ev["dur_s"]
        count[kind] = count.get(kind, 0) + 1
    need = 0.0
    for kind, n in count.items():
        per_step = need_per_step(run, kind)
        if per_step is None:
            return None
        need += per_step * n / events_per_step
    return 100.0 * need / dur if dur > 0 else None


def route_share(run):
    devices = sorted((p for p in readers.planes(run) or ()
                      if tr.is_device_plane(p["name"])),
                     key=lambda p: p["name"])
    ops = tr._line(devices[0], (ts.OPS_LINE,)) if devices else None
    ops = [e for e in (ops["events"] if ops else []) if e["dur_ns"] > 0]
    if not ops:
        return None
    route = 0.0
    for e, self_ns in tr.self_times(ops):
        parts = str(e["stats"].get("tf_op") or "").rstrip(":").split("/")
        if any(p in ROUTE_SCOPES for p in parts):
            route += self_ns
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    if route == 0.0 or busy <= 0:
        return None
    return 100.0 * route / busy


def read(run):
    out = counters(run)
    out["moe_experts_roofline"] = experts_roofline(run)
    out["dev_share_moe_route_pct"] = route_share(run)
    return out
