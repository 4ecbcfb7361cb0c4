"""Compressed convolutional attention beside the page pool, the MLP
router and top-1 experts (zaya).

- `dev_share_cca_mix_pct`: device self time under the scope `cca_mix`
  (the convolutions, the q-k mean, the value shift, the norm and the
  tail's reads and writes: everything between the projection into the
  latent and RoPE) over busy device time; `dev_share_router_pct`: under
  `router` (down-projection, state, MLP, softmax, choice). Read from the
  capture with `harness/trace_spans.py`'s functions and this file's own
  scope lists, as moe.py, dsa.py and ssm.py do (its fixed list files
  the first under `attn`, the second under `ffn`).
- `top1_moe_experts_roofline`: the summed time of the `cake_moe_gmm`
  events (device 0; a decode step's and a mixed dispatch's told apart
  by the rows of the printed result, as moe.py does) against the least
  time for the rows routed and the experts touched
  (`harness/zaya_roofline.py`), from the step records' `moe_rows` and
  `moe_experts_touched`.
- `moe_experts_touched_per_layer`: distinct experts a decode step
  touches a layer, of the config's `num_experts`
  (`moe_experts_touched` of the window's decode records over layers).

The cell's mixed step and client TTFT are `window_steps.py`'s
(`mixed_step_ms.tok`, `ttft_p50_ms.tok`): the one packed size makes a
dispatch with one prefilling row pay a second window's padding, and
`mixed_step_ms.tok` is where that cost reads.

A program without the counters, the scopes or the kernel, or a config
without `moe_intermediate_size`, yields nothing for the metric
concerned.
"""

import re

from harness import readers, trace_reduce as tr, trace_spans as ts
from harness import zaya_roofline
from harness.peaks import peaks
from harness.readers import steps_of

KERNEL = "cake_moe_gmm"
SCOPES = {"dev_share_cca_mix_pct": ("cca_mix",),
          "dev_share_router_pct": ("router",)}
PROJECTIONS = 3          # gate, up, down: kernel events per layer and step
PROGRAMS, KERNELS = "step programs", "kernels"

METRICS = [
    {"name": "dev_share_cca_mix_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_router_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "top1_moe_experts_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "moe_experts_touched_per_layer", "unit": "experts",
     "layer": PROGRAMS, "moves": "out_tok_s", "source": "program_counter"},
]


def result_rows(event_name: str):
    """Rows of the kernel's printed result, `bf16[rows,cols]`."""
    m = re.search(r"= [a-z0-9]+\[(\d+),\d+\]", event_name)
    return int(m.group(1)) if m else None


def counted(run, kind: str) -> list:
    return [s for s in steps_of(run, kind) if s.get("moe_rows")]


def touched_per_layer(run):
    steps = counted(run, "decode")
    if not steps:
        return None
    L = run["model_config"]["num_hidden_layers"]
    return sum(s["moe_experts_touched"] for s in steps) / len(steps) / L


def scope_shares(run) -> dict:
    devices = sorted((p for p in readers.planes(run) or ()
                      if tr.is_device_plane(p["name"])),
                     key=lambda p: p["name"])
    ops = tr._line(devices[0], (ts.OPS_LINE,)) if devices else None
    ops = [e for e in (ops["events"] if ops else []) if e["dur_ns"] > 0]
    if not ops:
        return {}
    self_ns = dict.fromkeys(SCOPES, 0.0)
    for e, ns in tr.self_times(ops):
        parts = str(e["stats"].get("tf_op") or "").rstrip(":").split("/")
        for name, scopes in SCOPES.items():
            if any(p in scopes for p in parts):
                self_ns[name] += ns
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    return {name: 100.0 * ns / busy for name, ns in self_ns.items()
            if busy > 0 and ns > 0}


def need_per_step(run, kind: str):
    """Mean least seconds of the grouped matmuls of one step of `kind`
    (all layers), from the window's step records."""
    cfg, shape = run["model_config"], run["cell"].cell["shape"]
    peak = peaks(run["device"]["kind"])
    L = cfg["num_hidden_layers"]
    per_step = [
        L * zaya_roofline.experts_least_s(
            cfg, s["moe_rows"] / L, s["moe_experts_touched"] / L, peak,
            weight_bytes=shape["weight_bytes"])
        for s in counted(run, kind)]
    return sum(per_step) / len(per_step) if per_step else None


def experts_roofline(run):
    trace = run.get("trace")
    cfg = run["model_config"]
    if (not trace or not trace.get("kernels")
            or "moe_intermediate_size" not in cfg):
        return None
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


def read(run):
    out = scope_shares(run)
    out["top1_moe_experts_roofline"] = experts_roofline(run)
    out["moe_experts_touched_per_layer"] = touched_per_layer(run)
    return out
