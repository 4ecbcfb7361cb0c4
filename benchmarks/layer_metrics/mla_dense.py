"""Latent attention over every live page of a row, group-limited
routing on a share (deepseek_v2; Ling-3.0's two latent layers join).

- `mla_decode_attn_roofline`: the summed device time of the
  `cake_mla_decode_attn` events of the capture (device 0; one event is
  one layer of one dispatch: every decode step and every mixed dispatch
  runs it over its single-token rows) against the least time for the
  keys those rows attended (`harness/mla_dense_roofline.py`: the greater
  of keys x 278,528 operations at the bf16 peak and keys x 1,152 B at
  the HBM rate). The keys are the step records' own (`mla_keys_attended`,
  all layers and dispatches of a record), and an event's record is read
  off the capture: the engine's `cake/fetch` span of a step carries the
  record's number (stat `step`) and ends when the step's results are on
  the host, so an event belongs to the first fetch that ends after it
  (an event past the last fetch, to the record after it). Exact where a
  record is one dispatch; a record of several gives each its mean.
- `mla_dense_window_roofline`: the `cake_mla_window_attn` events here
  (one a layer and mixed dispatch: the dispatch's one window over its
  row's pages under causality) against the (query, key) pairs s <= t of
  the window's prefill rows x 278,528 operations (the rows from the
  client's records: `harness/readers.mixed_step_rows`). The kernel
  computes whole pages up to the window's last position and absorbs the
  key up-projection (an up-projected window would need 81,920 a pair):
  the count is of the absorbed form, which is what runs.
- `dev_share_mla_attn_pct`: device self time under the scope `mla_attn`
  (both kernels) over busy device time.
- `mla_keys_per_decode_row`: `mla_keys_attended` over the decode
  records' rows and the layers: the mean context a decode row attends
  (a guard on the contexts: ~4.2k in the code cell).
- `moe_group_held_share_pct`: token-layers whose chosen groups include
  the group held here over all routed token-layers
  (`cake_moe_tokens_group_held_total` against
  `cake_moe_rows_routed_total` / experts a token): 100 * topk_group /
  n_group = 37.5 under even routing.

A record's keys are divided by the LATENT layers of the model
(`mla_dense_roofline.dims`: all of DeepSeek-V2's, one in
`layer_group_size` of Ling's). The cell's mixed step and client TTFT
are `window_steps.py`'s (`mixed_step_ms.tok`,
`mixed_step_device_ms.tok`, `ttft_p50_ms.tok`).

A program without the counters, the scope or the kernels yields nothing
for the metric concerned. The reader runs where `BENCHMARK.json` lists
one of its metrics for the cell, and nowhere else (`spec.Cell.per_layer`).
"""

import bisect

from harness import mla_dense_roofline as roof
from harness import readers, trace_reduce as tr, trace_spans as ts
from harness.peaks import peaks
from harness.readers import mixed_step_rows
from harness.server import metric_sum

DECODE_KERNEL, WINDOW_KERNEL = "cake_mla_decode_attn", "cake_mla_window_attn"
KERNELS_READ = (DECODE_KERNEL, WINDOW_KERNEL)
FETCH_SPAN = ts.SPAN_PREFIX + "fetch"
ATTN_SCOPE = "mla_attn"
PROGRAMS, KERNELS = "step programs", "kernels"

METRICS = [
    {"name": "mla_decode_attn_roofline", "unit": "%", "layer": KERNELS,
     "moves": "tpot_p50_ms", "source": "device_trace"},
    {"name": "mla_dense_window_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_mla_attn_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "mla_keys_per_decode_row", "unit": "keys", "layer": PROGRAMS,
     "moves": "tpot_p50_ms", "source": "program_counter"},
    {"name": "moe_group_held_share_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "program_counter"},
]


def dispatches(run, s) -> float:
    """Dispatches of the step programs a step record holds."""
    if s["kind"] == "mixed":
        one = min((x["tokens_computed"] for x in run["steps"]
                   if x["kind"] == "mixed" and x.get("tokens_computed")),
                  default=0)
        return s.get("tokens_computed", 0) / one if one else 0.0
    args = run["server_args"]
    table = (int(args["max-slots"]) * int(args["max-seq-len"])
             // int(args["kv-page-size"]))
    return (s.get("attn_pages_table") or table) / table


def device_ops(planes) -> list:
    """Device 0's ops that took time."""
    devices = sorted((p for p in planes if tr.is_device_plane(p["name"])),
                     key=lambda p: p["name"])
    ops = tr._line(devices[0], (ts.OPS_LINE,)) if devices else None
    return [e for e in (ops["events"] if ops else ()) if e["dur_ns"] > 0]


def kernel_ops(planes, kernel: str) -> list:
    out = []
    for e in device_ops(planes):
        m = ts.KERNEL.match(e["name"])
        if m and m.group(1) == kernel:
            out.append(e)
    return out


def fetched_steps(planes) -> list:
    """[(end_ns, step)] of the capture's `cake/fetch` spans, by end."""
    return sorted(
        (e["start_ns"] + e["dur_ns"], int(e["stats"]["step"]))
        for p in planes if ts.is_host_plane(p["name"])
        for line in p["lines"] for e in line["events"]
        if e["name"] == FETCH_SPAN and e["stats"].get("step") is not None)


def keys_attended(run, planes, events) -> float | None:
    """What the capture's `events` of the page-walking kernel attended,
    in keys of ONE layer: each event takes 1 / (layers x dispatches) of
    its record's `mla_keys_attended`. None where the capture holds no
    fetch span or a record is missing."""
    fetches = fetched_steps(planes)
    if not fetches:
        return None
    ends = [end for end, _step in fetches]
    records = {s["step"]: s for s in run.get("all_steps") or run["steps"]}
    L = roof.dims(run["model_config"])["L"]
    keys = 0.0
    for e in events:
        i = bisect.bisect_left(ends, e["start_ns"] + e["dur_ns"])
        # past the last fetch: the step in flight when the capture ended
        rec = (records.get(fetches[i][1]) if i < len(ends)
               else records.get(fetches[-1][1] + 1,
                                records.get(fetches[-1][1])))
        if rec is None or rec.get("mla_keys_attended") is None:
            return None
        keys += rec["mla_keys_attended"] / (
            L * max(1.0, dispatches(run, rec)))
    return keys


def decode_roofline(run, planes):
    events = kernel_ops(planes, DECODE_KERNEL)
    dur = sum(e["dur_ns"] for e in events) / 1e9
    if dur <= 0:
        return None
    keys = keys_attended(run, planes, events)
    if keys is None:
        return None
    need = roof.least_s(run["model_config"], keys, keys,
                        peaks(run["device"]["kind"]),
                        run["cell"].cell["shape"].get("kv_bytes", 2))
    return 100.0 * need / dur


def window_roofline(run, planes):
    cfg = run["model_config"]
    events = kernel_ops(planes, WINDOW_KERNEL)
    n, dur = len(events), sum(e["dur_ns"] for e in events) / 1e9
    if dur <= 0:
        return None
    width = run["cell"].cell["shape"]["mixed_width"]
    pairs, keys = [], []
    for rows in mixed_step_rows(run, width):
        for q, ctx in rows:
            if q > 1:
                pairs.append(q * ctx - q * (q - 1) / 2.0)
                keys.append(ctx)
    if not pairs:
        return None
    peak = peaks(run["device"]["kind"])
    kv_bytes = run["cell"].cell["shape"].get("kv_bytes", 2)
    each = sum(roof.least_s(cfg, p, k, peak, kv_bytes)
               for p, k in zip(pairs, keys)) / len(pairs)
    return 100.0 * n * each / dur


def counters(run) -> dict:
    def delta(family):
        return (metric_sum(run["metrics_1"], family)
                - metric_sum(run["metrics_0"], family))

    out = {}
    cfg = run["model_config"]
    routed = delta("cake_moe_rows_routed_total")
    held = delta("cake_moe_tokens_group_held_total")
    if routed > 0 and held > 0:
        out["moe_group_held_share_pct"] = (
            100.0 * held * cfg["num_experts_per_tok"] / routed)
    keys = rows = 0.0
    for s in run["steps"]:
        if (s["kind"] == "decode" and not s["compiled"]
                and s.get("mla_keys_attended")):
            keys += s["mla_keys_attended"]
            rows += s["rows"] * dispatches(run, s)
    if rows:
        out["mla_keys_per_decode_row"] = keys / rows / roof.dims(cfg)["L"]
    return out


def from_trace(planes) -> dict:
    out = {}
    ops = device_ops(planes)
    if not ops:
        return out
    attn = 0.0
    for e, self_ns in tr.self_times(ops):
        parts = str(e["stats"].get("tf_op") or "").rstrip(":").split("/")
        if ATTN_SCOPE in parts:
            attn += self_ns
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    if busy > 0 and attn > 0:
        out["dev_share_mla_attn_pct"] = 100.0 * attn / busy
    return out


def read(run):
    out = counters(run)
    planes = readers.planes(run)
    if planes:
        out.update(from_trace(planes))
        out["mla_decode_attn_roofline"] = decode_roofline(run, planes)
        out["mla_dense_window_roofline"] = window_roofline(run, planes)
    return out
