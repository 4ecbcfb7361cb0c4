"""Recurrent blocks beside the page pool (nemotron_h) and LatentMoE.

- `dev_share_ssm_pct`: device self time under the scopes `ssm_conv`,
  `ssm_scan`, `ssm_step`, `ssm_gate` and `ssm_state` over busy device
  time; `dev_share_ssm_proj_pct`: under `ssm_in` and `ssm_out`;
  `dev_share_moe_latent_pct`: under `moe_latent` (this file's own scope
  lists, as moe.py and dsa.py have: `harness/trace_spans.py` files the
  first group under `attn`, the projections under `qkv` and `o_proj`,
  the latent under `ffn`).
- `ssm_step_roofline`: the one-step update is XLA (no kernel of its
  own), so its time is the self time under `ssm_step` and `ssm_state`
  in the capture, against 2 x the float32 state of every (row, block)
  the step records say took the one-step form
  (`harness/ssm_roofline.py`). What one execution of each step program
  needs is the window's mean, `ssm_tokens_stepped` over the records'
  dispatches; the capture's executions are counted from its module
  events.
- `ssm_scan_roofline`: the self time under `ssm_scan` against the
  greater of the chunked form's own operations at the bf16 peak and its
  least bytes, for the tokens the records say were scanned.
- `latent_moe_experts_roofline`: the summed time of the `cake_moe_gmm`
  events (device 0; a decode step's and a mixed dispatch's told apart by
  the rows of the printed result, as moe.py does) against the least
  time for the rows routed to HELD experts and the experts touched
  (`harness/latent_moe_roofline.py`), from the records' `moe_rows` and
  `moe_experts_touched`.
- `ssm_scanned_share_pct`: tokens through the chunked scan over all
  tokens through a Mamba block (`cake_ssm_tokens_scanned_total` against
  `..._stepped_total`); `ssm_state_rows_per_step`: rows whose state a
  step touched (`cake_ssm_state_rows_total` / Mamba blocks / steps): at
  most the rows busy, a guard that a row with no token costs nothing.

The cell's mixed step and client TTFT are `window_steps.py`'s
(`mixed_step_ms.tok`, `mixed_step_device_ms.tok`, `ttft_p50_ms.tok`).

A program without the counters, the scopes or the kernel yields nothing
for the metric concerned.
"""

import re

from harness import (latent_moe_roofline, readers, ssm_roofline,
                     trace_reduce as tr, trace_spans as ts)
from harness.peaks import peaks
from harness.server import metric_sum

KERNEL = "cake_moe_gmm"
SSM_SCOPES = ("ssm_conv", "ssm_scan", "ssm_step", "ssm_gate", "ssm_state")
PROJ_SCOPES = ("ssm_in", "ssm_out")
LATENT_SCOPES = ("moe_latent",)
STEP_SCOPES = ("ssm_step", "ssm_state")
PROJECTIONS = 2          # up, down: kernel events per block and dispatch
PROGRAMS, KERNELS = "step programs", "kernels"

METRICS = [
    {"name": "dev_share_ssm_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_ssm_proj_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_moe_latent_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "ssm_step_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "ssm_scan_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "latent_moe_experts_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "ssm_scanned_share_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "ssm_state_rows_per_step", "unit": "rows",
     "layer": "scheduler and page allocator", "moves": "out_tok_s",
     "source": "program_counter"},
]


def result_rows(event_name: str):
    """Rows of the kernel's printed result, `bf16[rows,cols]`."""
    m = re.search(r"= [a-z0-9]+\[(\d+),\d+\]", event_name)
    return int(m.group(1)) if m else None


def is_hybrid(run) -> bool:
    return "M" in str(run["model_config"].get("hybrid_override_pattern", ""))


def counters(run) -> dict:
    def delta(family):
        return (metric_sum(run["metrics_1"], family)
                - metric_sum(run["metrics_0"], family))

    out = {}
    scanned = delta("cake_ssm_tokens_scanned_total")
    stepped = delta("cake_ssm_tokens_stepped_total")
    if scanned + stepped > 0:
        out["ssm_scanned_share_pct"] = 100.0 * scanned / (scanned + stepped)
    rows = delta("cake_ssm_state_rows_total")
    steps = [s for s in run["steps"] if s.get("ssm_state_rows")]
    if rows > 0 and steps and is_hybrid(run):
        blocks = ssm_roofline.ssm_dims(run["model_config"])["L_M"]
        out["ssm_state_rows_per_step"] = rows / blocks / len(steps)
    return out


def dispatches(run, step: dict) -> float:
    """Program executions behind one step record: a mixed step of
    several dispatches says so by the positions it computed."""
    if step["kind"] != "mixed" or not step.get("tokens_computed"):
        return 1.0
    width = run["cell"].cell["shape"]["mixed_width"]
    slots = run["health"].get("decode_slots") or 0
    bucket = -(-(width + slots - 1) // 16) * 16
    return max(1.0, step["tokens_computed"] / bucket)


def per_execution(run, kind: str, key: str):
    """The window's mean of a step-record counter per execution of the
    step program of `kind`, or None."""
    steps = [s for s in run["steps"]
             if s["kind"] == kind and not s["compiled"] and key in s]
    n = sum(dispatches(run, s) for s in steps)
    return sum(s[key] for s in steps) / n if n else None


def executions(spans: dict) -> dict:
    """{step kind: executions of its program in the capture}."""
    out = {}
    for name, p in spans.get("programs", {}).items():
        kind = ts.step_kind(name)
        if kind:
            out[kind] = out.get(kind, 0) + p["executions"]
    return out


def from_trace(run) -> dict:
    planes = readers.planes(run)
    if not planes:
        return {}
    spans = readers.span_reduction(run)
    out = {}
    devices = sorted((p for p in planes if tr.is_device_plane(p["name"])),
                     key=lambda p: p["name"])
    ops = tr._line(devices[0], (ts.OPS_LINE,)) if devices else None
    ops = [e for e in (ops["events"] if ops else []) if e["dur_ns"] > 0]
    if not ops:
        return out
    groups = {"dev_share_ssm_pct": SSM_SCOPES,
              "dev_share_ssm_proj_pct": PROJ_SCOPES,
              "dev_share_moe_latent_pct": LATENT_SCOPES}
    self_ns = {name: 0.0 for name in groups}
    step_ns = scan_ns = 0.0
    for e, ns in tr.self_times(ops):
        parts = str(e["stats"].get("tf_op") or "").rstrip(":").split("/")
        for name, scopes in groups.items():
            if any(p in scopes for p in parts):
                self_ns[name] += ns
                break
        if any(p in STEP_SCOPES for p in parts):
            step_ns += ns
        elif "ssm_scan" in parts:
            scan_ns += ns
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    for name, ns in self_ns.items():
        if busy > 0 and ns > 0:
            out[name] = 100.0 * ns / busy
    if not is_hybrid(run):
        return out
    cfg = run["model_config"]
    peak = peaks(run["device"]["kind"])
    ran = executions(spans)
    stepped = scanned = 0.0
    for kind, n in ran.items():
        stepped += n * (per_execution(run, kind, "ssm_tokens_stepped") or 0.0)
        scanned += n * (per_execution(run, kind, "ssm_tokens_scanned") or 0.0)
    if step_ns > 0 and stepped > 0:
        out["ssm_step_roofline"] = (
            100.0 * ssm_roofline.step_least_s(cfg, stepped, peak)
            / (step_ns / 1e9))
    if scan_ns > 0 and scanned > 0:
        out["ssm_scan_roofline"] = (
            100.0 * ssm_roofline.scan_least_s(
                cfg, scanned, peak,
                act_bytes=run["cell"].cell["shape"].get("kv_bytes", 2))
            / (scan_ns / 1e9))
    return out


def experts_roofline(run):
    trace = run.get("trace")
    if not trace or not trace.get("kernels") or not is_hybrid(run):
        return None
    cfg, shape = run["model_config"], run["cell"].cell["shape"]
    slots = run["health"].get("decode_slots")
    k = cfg.get("num_experts_per_tok")
    if not slots or not k:
        return None
    peak = peaks(run["device"]["kind"])
    blocks = cfg["hybrid_override_pattern"].count("E")
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
        rows = per_execution(run, kind, "moe_rows")
        touched = per_execution(run, kind, "moe_experts_touched")
        if rows is None or touched is None:
            return None
        need += (n / (PROJECTIONS * blocks)) * blocks * \
            latent_moe_roofline.experts_least_s(
                cfg, rows / blocks, touched / blocks, peak,
                weight_bytes=shape["weight_bytes"])
    return 100.0 * need / dur if dur > 0 else None


def read(run):
    out = counters(run)
    out.update(from_trace(run))
    out["latent_moe_experts_roofline"] = experts_roofline(run)
    return out
