"""GQA in two kinds of layer over K/V pages by kind (exaone_moe).

- `gqa_window_attn_roofline`, `gqa_full_attn_roofline`: the device time
  of the two ragged paged attention kernels' events (device 0:
  `cake_decode_attn`, a row's single token, in every decode step and
  every mixed dispatch; `cake_mixed_attn`, the dispatch's window in
  entries) under the scope `gqa_window` (the banded calls, through the
  ring) or `gqa_full`, against the least time for what those calls
  attended (`harness/gqa_window_roofline.py`). An event belongs to the
  step record whose `cake/fetch` span is the first to end after it
  (step_device's rule, PR 45), and a record's need comes from its own
  counters: the single-token rows' keys by kind of layer
  (`gqa_window_keys_single`, `gqa_full_keys_single`: the decode
  kernel's), the rest of `swa_keys_attended` / `gqa_full_keys_attended`
  the window's. Distinct keys: the pairs themselves for the single
  rows (every row reads its own), the window's own tokens in a sliding
  layer (a floor: its band reaches 127 keys further back), its whole
  context in a full one. The decode kernel fetches whole pages (two of
  128 for a band of 128 keys that starts inside a page) and the window
  is handed over in 8 entries that each fetch their pages: the share
  reads low by design of the count.
- `dev_share_gqa_window_pct`, `dev_share_gqa_full_pct`: device self
  time under the scopes `gqa_window` and `gqa_full` (the K/V writes,
  both kernels and what lies between them) over busy device time.
- `gqa_window_pages_per_decode_row`: ring pages a single-token row
  walked a sliding layer (`cake_gqa_window_pages_walked_total` over
  `cake_gqa_rows_single_total` and the sliding layers): 2 at a window
  of one page that starts inside a page, whatever the context.

The cell's mixed step and client TTFT are `window_steps.py`'s
(`mixed_step_ms.tok`, `mixed_step_device_ms.tok`, `ttft_p50_ms.tok`);
its share of visible keys the sliding layers attend is `swa.py`'s
`swa_attended_share_pct`.

A program without the counters, the scopes or the kernels (the parent,
another family) yields nothing for the metric concerned.
"""

import bisect

from harness import gqa_window_roofline as gw
from harness import readers, trace_reduce as tr, trace_spans as ts
from harness.peaks import peaks
from harness.server import metric_sum

KERNELS_BY_NAME = ("cake_decode_attn", "cake_mixed_attn")
SCOPES = {"gqa_window": "dev_share_gqa_window_pct",
          "gqa_full": "dev_share_gqa_full_pct"}
ROOFLINES = {"gqa_window": "gqa_window_attn_roofline",
             "gqa_full": "gqa_full_attn_roofline"}
FETCH_SPAN = ts.SPAN_PREFIX + "fetch"
PROGRAMS, KERNELS = "step programs", "kernels"

METRICS = [
    {"name": "gqa_window_attn_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "gqa_full_attn_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_gqa_window_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_gqa_full_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "gqa_window_pages_per_decode_row", "unit": "pages",
     "layer": KERNELS, "moves": "out_tok_s", "source": "program_counter"},
]


def windowed_gqa(model_config: dict) -> bool:
    return (model_config.get("model_type") == "exaone_moe"
            and "sliding_attention" in (model_config.get("layer_types")
                                        or ()))


def counters(run) -> dict:
    def delta(family):
        return (metric_sum(run["metrics_1"], family)
                - metric_sum(run["metrics_0"], family))

    rows = delta("cake_gqa_rows_single_total")
    if rows <= 0 or not windowed_gqa(run["model_config"]):
        return {}
    layers = gw.gqa_dims(run["model_config"])["L_sliding"]
    return {"gqa_window_pages_per_decode_row":
            delta("cake_gqa_window_pages_walked_total") / rows / layers}


def record_need(rec: dict, kind: str, kernel: str, cfg: dict, peak: dict,
                kv_bytes: float):
    """Least seconds of ONE step record's calls of `kernel` in the
    layers of `kind`, all of them, from its counters; None where the
    record has none."""
    d = gw.gqa_dims(cfg)
    single = rec.get("gqa_rows_single")
    if single is None:
        return None
    if kind == "gqa_window":
        layers, alone = d["L_sliding"], rec["gqa_window_keys_single"]
        total = rec["swa_keys_attended"]
    else:
        layers, alone = d["L_full"], rec["gqa_full_keys_single"]
        total = rec["gqa_full_keys_attended"]
    if kernel == "cake_decode_attn":
        return gw.attn_least_s(cfg, alone, alone, single * layers, peak,
                               kv_bytes)
    pairs = total - alone
    n = rec.get("tokens_real", 0) - single      # the window's tokens
    if pairs <= 0 or n <= 0:
        return 0.0
    keys = (n if kind == "gqa_window"
            else gw.window_context(pairs / layers, n))
    return gw.attn_least_s(cfg, pairs, keys * layers, n * layers, peak,
                           kv_bytes)


def rooflines(run, planes, scoped: list) -> dict:
    """scoped: [(event, scope parts)] of device 0's kernel events."""
    fetches = sorted(
        (e["start_ns"] + e["dur_ns"], int(e["stats"]["step"]))
        for p in planes if ts.is_host_plane(p["name"])
        for line in p["lines"] for e in line["events"]
        if e["name"] == FETCH_SPAN and e["stats"].get("step") is not None)
    if len(fetches) < 2:
        return {}
    ends = [end for end, _step in fetches]
    records = {s["step"]: s for s in run.get("all_steps") or run["steps"]}
    took = {}           # (kind, kernel, step) -> seconds
    for e, parts in scoped:
        kind = next((p for p in parts if p in ROOFLINES), None)
        kernel = ts.KERNEL.match(e["name"])
        if kind is None or not kernel:
            continue
        i = bisect.bisect_left(ends, e["start_ns"] + e["dur_ns"])
        if 0 < i < len(ends):
            key = (kind, kernel.group(1), fetches[i][1])
            took[key] = took.get(key, 0.0) + e["dur_ns"] / 1e9
    cfg, peak = run["model_config"], peaks(run["device"]["kind"])
    kv_bytes = run["cell"].cell["shape"].get("kv_bytes", 2)
    need, spent = dict.fromkeys(ROOFLINES, 0.0), dict.fromkeys(ROOFLINES, 0.0)
    for (kind, kernel, step), seconds in took.items():
        rec = records.get(step)
        least = (None if rec is None
                 else record_need(rec, kind, kernel, cfg, peak, kv_bytes))
        if least is None:
            return {}
        need[kind] += least
        spent[kind] += seconds
    return {ROOFLINES[kind]: 100.0 * need[kind] / spent[kind]
            for kind in ROOFLINES if spent[kind] > 0 and need[kind] > 0}


def from_trace(run) -> dict:
    planes = (readers.planes(run)
              if windowed_gqa(run["model_config"]) else None)
    if not planes:
        return {}
    out = {}
    devices = sorted((p for p in planes if tr.is_device_plane(p["name"])),
                     key=lambda p: p["name"])
    ops = tr._line(devices[0], (ts.OPS_LINE,)) if devices else None
    ops = [e for e in (ops["events"] if ops else []) if e["dur_ns"] > 0]
    if not ops:
        return out
    self_ns = dict.fromkeys(SCOPES.values(), 0.0)
    kernels = []
    for e, ns in tr.self_times(ops):
        parts = str(e["stats"].get("tf_op") or "").rstrip(":").split("/")
        for scope, name in SCOPES.items():
            if scope in parts:
                self_ns[name] += ns
        m = ts.KERNEL.match(e["name"])
        if m and m.group(1) in KERNELS_BY_NAME:
            kernels.append((e, parts))
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    out.update({name: 100.0 * ns / busy for name, ns in self_ns.items()
                if busy > 0 and ns > 0})
    out.update(rooflines(run, planes, kernels))
    return out


def read(run):
    out = counters(run)
    out.update(from_trace(run))
    return out
