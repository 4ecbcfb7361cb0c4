"""Kernels: the ragged paged attention kernels' share of their
roofline, from the device trace. The trace names them only
`tpu_custom_call` until the `tracing` issue gives them scopes, so the
two step kinds are told apart by the printed result shape [slots,
query width, heads, hd]: width 1 is the decode kernel, a wider one the
mixed kernel. One event is one layer of one
step; what a step of that kind needs on average over the window
(`harness.roofline.attention_need`, rows from the step records and the
client's records) times the steps traced, over the events' summed time.
"""

import re

from harness.peaks import peaks
from harness.readers import live_tokens_at, mixed_step_rows, mono, steps_of
from harness.roofline import attention_need, least_s

METRICS = [
    {"name": "decode_attn_roofline", "unit": "%", "layer": "kernels",
     "moves": "tpot_p50_ms", "source": "device_trace"},
    {"name": "mixed_attn_roofline", "unit": "%", "layer": "kernels",
     "moves": "ttft_mean_ms", "source": "device_trace"},
]


def kind_of(event_name: str):
    m = re.search(r"[a-z0-9]+\[([0-9,]+)\]", event_name)
    if not m:
        return None
    shape = [int(x) for x in m.group(1).split(",")]
    if len(shape) == 3:
        return "decode"
    if len(shape) == 4:
        return "decode" if shape[1] == 1 else "mixed"
    return None


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("kernels"):
        return {}
    peak = peaks(run["device"]["kind"])
    cfg, shape = run["model_config"], run["cell"].cell["shape"]
    dur, count = {}, {}
    for k in trace["kernels"]:
        if k["device"] != 0:
            continue
        kind = kind_of(k["name"])
        if kind:
            dur[kind] = dur.get(kind, 0.0) + k["dur_s"]
            count[kind] = count.get(kind, 0) + 1
    need = {}
    decode = steps_of(run, "decode")
    if decode:
        per_step = []
        for s in decode:
            live = live_tokens_at(run, mono(run, s["ts"]))
            n = max(1, s["rows"])
            per_step.append(least_s(*attention_need(
                cfg, [(1, live / n)] * n, kv_bytes=shape["kv_bytes"]),
                peak)[0])
        need["decode"] = sum(per_step) / len(per_step)
    mixed = mixed_step_rows(run, int(shape.get("mixed_width", 128)))
    if mixed:
        per_step = [least_s(*attention_need(
            cfg, rows, kv_bytes=shape["kv_bytes"]), peak)[0]
            for rows in mixed]
        need["mixed"] = sum(per_step) / len(per_step)
    out = {}
    for kind in ("decode", "mixed"):
        if dur.get(kind) and kind in need:
            # need[kind] is one layer of one step: one event
            out[f"{kind}_attn_roofline"] = (
                100.0 * need[kind] * count[kind] / dur[kind])
    return out
