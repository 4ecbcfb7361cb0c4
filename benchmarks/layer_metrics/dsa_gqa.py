"""A learned sparse indexer over ORDINARY K/V pages (KeyeVL2: `sa_config`
beside GQA).

- `dsa_gqa_attn_roofline`: the device time of the two ragged paged
  attention kernels' events under the scope `gqa_full` (device 0:
  `cake_decode_attn`, a row's single token over its gathered rows, in
  every decode step and every mixed dispatch; `cake_mixed_attn`, the
  dispatch's window over its row's pages under the selection's mask)
  against the least time for the SELECTED keys' work, whatever
  implements it (`harness/dsa_gqa_roofline.py`). An event belongs to
  the step record whose `cake/fetch` span is the first to end after it
  (step_device's rule, PR 45), and a record's need comes from its OWN
  counters, not the window's mean: the single-token rows' pairs are
  `dsa_keys_single` (each row reads its own rows), the window's the
  rest of `dsa_keys_selected` over the rest of `dsa_rows_distinct`. The
  window kernel computes every VISIBLE key of every live page and masks
  what was not selected, so the share reads low by design of the count,
  as `mla_attn_roofline` says of itself.
- `dev_share_dsa_gather_pct`: device self time under the scope
  `dsa_gather` (the XLA gathers that move the single-token rows'
  selected K and V rows out of the pools) over busy device time;
  nothing, not 0, where the scope is absent.
- `dev_share_gqa_proj_pct`: device self time under `gqa_proj` (q, k, v,
  the norm a head, the rotation), which lies under `qkv` and so in none
  of `trace_spans`' four shares.
- `dsa_keys_per_decode_row`, `dsa_keys_scanned_per_decode_row`: keys a
  single-token row attended, and index keys its indexer scored, a layer
  (`cake_dsa_keys_single_total` / `cake_dsa_keys_scanned_single_total`
  over `cake_gqa_rows_single_total` and the layers): 2,048 against
  8k-33k says the regime at a glance.

The cell's mixed step and client TTFT are `window_steps.py`'s; its share
of visible keys selected and the indexer's device share are `dsa.py`'s.

A program without the counters, the scopes or the kernels (the parent,
another family) yields nothing for the metric concerned.
"""

import bisect

from harness import dsa_gqa_roofline as dg
from harness import readers, trace_reduce as tr, trace_spans as ts
from harness.peaks import peaks
from harness.server import metric_sum

KERNELS_BY_NAME = ("cake_decode_attn", "cake_mixed_attn")
ATTN_SCOPE = "gqa_full"
SCOPES = {"dsa_gather": "dev_share_dsa_gather_pct",
          "gqa_proj": "dev_share_gqa_proj_pct"}
FETCH_SPAN = ts.SPAN_PREFIX + "fetch"
PROGRAMS, KERNELS = "step programs", "kernels"

METRICS = [
    {"name": "dsa_gqa_attn_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_dsa_gather_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_gqa_proj_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dsa_keys_per_decode_row", "unit": "keys", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "dsa_keys_scanned_per_decode_row", "unit": "keys",
     "layer": PROGRAMS, "moves": "out_tok_s", "source": "program_counter"},
]


def counters(run, dims: dict) -> dict:
    def delta(family):
        return (metric_sum(run["metrics_1"], family)
                - metric_sum(run["metrics_0"], family))

    rows = delta("cake_gqa_rows_single_total") * dims["L"]
    attended = delta("cake_dsa_keys_single_total")
    if rows <= 0 or attended <= 0:
        return {}
    return {"dsa_keys_per_decode_row": attended / rows,
            "dsa_keys_scanned_per_decode_row":
            delta("cake_dsa_keys_scanned_single_total") / rows}


def record_need(rec: dict, kernel: str, dims: dict, peak: dict,
                kv_bytes: float):
    """Least seconds of ONE step record's calls of `kernel`, all layers,
    from its counters; None where the record has none."""
    alone, single = rec.get("dsa_keys_single"), rec.get("gqa_rows_single")
    if alone is None or single is None:
        return None
    if kernel == "cake_decode_attn":
        return dg.attn_least_s(dims, alone, alone, single * dims["L"], peak,
                               kv_bytes)
    pairs = rec.get("dsa_keys_selected", 0) - alone
    n = rec.get("tokens_real", 0) - single      # the window's tokens
    if pairs <= 0 or n <= 0:
        return 0.0
    distinct = max(rec.get("dsa_rows_distinct", 0) - alone, 0)
    return dg.attn_least_s(dims, pairs, distinct, n * dims["L"], peak,
                           kv_bytes)


def roofline(run, planes, kernels: list, dims: dict) -> dict:
    """kernels: device 0's attention kernel events under ATTN_SCOPE."""
    fetches = sorted(
        (e["start_ns"] + e["dur_ns"], int(e["stats"]["step"]))
        for p in planes if ts.is_host_plane(p["name"])
        for line in p["lines"] for e in line["events"]
        if e["name"] == FETCH_SPAN and e["stats"].get("step") is not None)
    if len(fetches) < 2:
        return {}
    ends = [end for end, _step in fetches]
    records = {s["step"]: s for s in run.get("all_steps") or run["steps"]}
    took = {}           # (kernel, step) -> seconds
    for e in kernels:
        kernel = ts.KERNEL.match(e["name"]).group(1)
        i = bisect.bisect_left(ends, e["start_ns"] + e["dur_ns"])
        if 0 < i < len(ends):
            key = (kernel, fetches[i][1])
            took[key] = took.get(key, 0.0) + e["dur_ns"] / 1e9
    peak = peaks(run["device"]["kind"])
    kv_bytes = run["cell"].cell["shape"].get("kv_bytes", 2)
    need = spent = 0.0
    for (kernel, step), seconds in took.items():
        rec = records.get(step)
        least = (None if rec is None
                 else record_need(rec, kernel, dims, peak, kv_bytes))
        if least is None:
            return {}
        need += least
        spent += seconds
    if spent <= 0 or need <= 0:
        return {}
    return {"dsa_gqa_attn_roofline": 100.0 * need / spent}


def from_trace(run, dims: dict) -> dict:
    planes = readers.planes(run)
    if not planes:
        return {}
    devices = sorted((p for p in planes if tr.is_device_plane(p["name"])),
                     key=lambda p: p["name"])
    ops = tr._line(devices[0], (ts.OPS_LINE,)) if devices else None
    ops = [e for e in (ops["events"] if ops else []) if e["dur_ns"] > 0]
    if not ops:
        return {}
    self_ns = dict.fromkeys(SCOPES.values(), 0.0)
    kernels = []
    for e, ns in tr.self_times(ops):
        parts = str(e["stats"].get("tf_op") or "").rstrip(":").split("/")
        for scope, name in SCOPES.items():
            if scope in parts:
                self_ns[name] += ns
        m = ts.KERNEL.match(e["name"])
        if m and m.group(1) in KERNELS_BY_NAME and ATTN_SCOPE in parts:
            kernels.append(e)
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    out = {name: 100.0 * ns / busy for name, ns in self_ns.items()
           if busy > 0 and ns > 0}
    out.update(roofline(run, planes, kernels, dims))
    return out


def read(run):
    dims = dg.dsa_gqa_dims(run["model_config"])
    if dims is None:
        return {}
    out = counters(run, dims)
    out.update(from_trace(run, dims))
    return out
