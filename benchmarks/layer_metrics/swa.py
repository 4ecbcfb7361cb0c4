"""Sliding-window latent layers beside full ones (dots3_note), and the
long-and-short cell's own readings.

- `swa_attended_share_pct`: keys the sliding layers attended over keys
  visible to them, summed over query tokens and sliding layers
  (`cake_swa_keys_attended_total` / `cake_swa_keys_visible_total` over
  the window): how far past the window the contexts are.
- `dev_share_swa_attn_pct`, `dev_share_swa_proj_pct`: device self time
  under the scopes `swa_gather` + `swa_attn`, and `swa_q` + `swa_kv` +
  `attn_gate` (the gate of BOTH kinds of layer), over busy device time
  (this file's own scope lists, as dsa.py has).
- `swa_attn_roofline`: the summed device time of the sliding layers'
  kernel events (device 0: `cake_swa_attn`, a row's single token over
  its gathered window, in every decode step and every mixed dispatch;
  `cake_swa_window_attn`, a window over its row's ring, in every mixed
  dispatch) against the least time for what the window's steps attended
  (`harness/swa_roofline.py`). One event is one sliding layer of one
  dispatch. What a dispatch of each kind needs on average comes from
  the step records' `swa_keys_attended`; the DISTINCT rows are the
  attended pairs themselves in a decode step (every query has its own
  rows) and attended / mixed width in a mixed one (a window's queries
  share their rows: a floor), so the need stays a floor. The window
  kernel computes the whole ring under a bias, and the one-pass kernel
  640 gathered rows for at most 513: the share reads low by design of
  the count.
- `dsa_full_attn_roofline`: the same for the FULL layers' kernel events
  (`cake_mla_attn`, `cake_mla_window_attn`), one event a full layer and
  dispatch, `mla_roofline`'s arithmetic at this model's 128 heads
  (`mla_attn_roofline` divides by `num_hidden_layers` and reads
  `indexer_types`: it yields nothing for this configuration).
- `ttft_p50_ms.longshort-s1k`, `ttft_p50_ms.longshort-d8k` (client
  TTFT by prompt class, plain medians, NOT judged: a 48 s window holds
  a few dozen first tokens), `tpot_p50_ms.longshort` (the end-to-end
  `tpot_p50_ms`'s arithmetic, NOT judged in this cell: the median of
  ~100 requests' means falls on one of two levels, 82.2 or 84.1 ms,
  and a set of six runs spread 2.27 % against half its bound, 1.5 %:
  my chip runs, PR 41). These three are readings BY CLASS or of this
  cell's own two levels; the cell's mixed step is `window_steps.py`'s
  (`mixed_step_ms.tok`, `mixed_step_device_ms.tok`).

`swa_attended_share_pct` is a counter any model with sliding-window
layers emits (K-EXAONE's step programs too: its cell lists it); the
scopes, kernels and rooflines are dots3_note's latent geometry, and a
config without it (`windowed`) yields nothing for them.

A program without the counters, the scopes or the kernels yields
nothing for the metric concerned.
"""

from harness import readers, swa_roofline, trace_reduce as tr
from harness import trace_spans as ts
from harness.e2e import median, tpot_samples, ttft_samples
from harness.peaks import peaks
from harness.server import metric_sum

SWA_KERNELS = ("cake_swa_attn", "cake_swa_window_attn")
FULL_KERNELS = ("cake_mla_attn", "cake_mla_window_attn")
SCOPES = {"dev_share_swa_attn_pct": ("swa_gather", "swa_attn"),
          "dev_share_swa_proj_pct": ("swa_q", "swa_kv", "attn_gate")}
CLASSES = {"ttft_p50_ms.longshort-s1k": "s1k",
           "ttft_p50_ms.longshort-d8k": "d8k"}
PROGRAMS, KERNELS = "step programs", "kernels"
ALLOCATOR = "scheduler and page allocator"

METRICS = [
    {"name": "swa_attended_share_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "dev_share_swa_attn_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_swa_proj_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "swa_attn_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dsa_full_attn_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "ttft_p50_ms.longshort-s1k", "unit": "ms", "layer": ALLOCATOR,
     "moves": "out_tok_s", "source": "host_clock"},
    {"name": "ttft_p50_ms.longshort-d8k", "unit": "ms", "layer": ALLOCATOR,
     "moves": "out_tok_s", "source": "host_clock"},
    {"name": "tpot_p50_ms.longshort", "unit": "ms", "layer": ALLOCATOR,
     "moves": "out_tok_s", "source": "host_clock"},
]


def counters(run) -> dict:
    def delta(family):
        return (metric_sum(run["metrics_1"], family)
                - metric_sum(run["metrics_0"], family))

    out = {}
    visible = delta("cake_swa_keys_visible_total")
    if visible > 0:
        out["swa_attended_share_pct"] = (
            100.0 * delta("cake_swa_keys_attended_total") / visible)
    return out


def windowed(model_config: dict) -> bool:
    """Sliding-window LATENT layers, with the keys `swa_roofline.swa_dims`
    reads: K-EXAONE's `layer_types` name `sliding_attention` too, over
    plain GQA pages (`gqa_window.py` reads those)."""
    return ("sliding_attention" in (model_config.get("layer_types") or ())
            and "swa_num_attention_heads" in model_config)


def need_per_dispatch(run, kind: str, which: str):
    """Mean least seconds of one dispatch's attention in the layers of
    kind `which` ("swa" | "full"; all of them), over the window's steps
    of `kind`. A step record holds `swa_layers / L_sliding` dispatches."""
    cfg = run["model_config"]
    d = swa_roofline.swa_dims(cfg)
    peak = peaks(run["device"]["kind"])
    shape = run["cell"].cell["shape"]
    kv_bytes = shape.get("kv_bytes", 2)
    need = dispatches = 0.0
    for s in run["steps"]:
        if s["kind"] != kind or s["compiled"] or not s.get("swa_layers"):
            continue
        n = s["swa_layers"] / d["L_sliding"]
        if which == "swa":
            events = s["swa_layers"]
            attended = s["swa_keys_attended"] / events
            distinct = (attended if kind == "decode"
                        else attended / shape["mixed_width"])
            need += events * swa_roofline.swa_least_s(
                cfg, attended, distinct, peak, cache_bytes=kv_bytes)
        else:
            events = d["L_full"] * n
            need += events * swa_roofline.full_least_s(
                cfg, s["dsa_keys_selected"] / events,
                s["dsa_rows_distinct"] / events, peak, cache_bytes=kv_bytes)
        dispatches += n
    return need / dispatches if dispatches else None


def kernels_roofline(run, names: tuple, which: str):
    """names: (the one-pass kernel, the window kernel) of one kind of
    layer; their events against the need of the dispatches that ran
    them."""
    trace = run.get("trace")
    if (not trace or not trace.get("kernels")
            or not windowed(run["model_config"])):
        return None
    d = swa_roofline.swa_dims(run["model_config"])
    per_dispatch = d["L_sliding"] if which == "swa" else d["L_full"]
    dur, events = 0.0, dict.fromkeys(names, 0)
    for ev in trace["kernels"]:
        if ev["device"] != 0:
            continue
        m = ts.KERNEL.match(ev["name"])
        if m and m.group(1) in events:
            dur += ev["dur_s"]
            events[m.group(1)] += 1
    one_pass, window = names
    mixed = events[window] / per_dispatch
    decode = max(0.0, events[one_pass] / per_dispatch - mixed)
    need = 0.0
    for kind, n in (("mixed", mixed), ("decode", decode)):
        if n:
            each = need_per_dispatch(run, kind, which)
            if each is None:
                return None
            need += each * n
    return 100.0 * need / dur if dur > 0 else None


def from_trace(run) -> dict:
    planes = readers.planes(run)
    if not planes or not windowed(run["model_config"]):
        return {}
    out = {}
    devices = sorted((p for p in planes if tr.is_device_plane(p["name"])),
                     key=lambda p: p["name"])
    ops = tr._line(devices[0], (ts.OPS_LINE,)) if devices else None
    ops = [e for e in (ops["events"] if ops else []) if e["dur_ns"] > 0]
    if not ops:
        return out
    self_ns = dict.fromkeys(SCOPES, 0.0)
    for e, ns in tr.self_times(ops):
        parts = str(e["stats"].get("tf_op") or "").rstrip(":").split("/")
        for name, scopes in SCOPES.items():
            if any(p in scopes for p in parts):
                self_ns[name] += ns
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    out.update({name: 100.0 * ns / busy for name, ns in self_ns.items()
                if busy > 0 and ns > 0})
    return out


def read(run):
    out = counters(run)
    out.update(from_trace(run))
    out["swa_attn_roofline"] = kernels_roofline(run, SWA_KERNELS, "swa")
    out["dsa_full_attn_roofline"] = kernels_roofline(run, FULL_KERNELS,
                                                     "full")
    first = ttft_samples(run["records"], run["t0"], run["t1"])
    for name, cls in CLASSES.items():
        if first.get(cls):
            out[name] = 1000.0 * median(first[cls])
    per_token = tpot_samples(run["records"], run["t0"], run["t1"])
    if per_token:
        out["tpot_p50_ms.longshort"] = 1000.0 * median(per_token)
    return out
