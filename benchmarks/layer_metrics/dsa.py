"""Latent attention with the learned sparse indexer (glm_moe_dsa).

- `dsa_selected_share_pct`: keys the indexer selected over keys visible,
  summed over query tokens and attention layers
  (`cake_dsa_keys_selected_total` / `cake_dsa_keys_visible_total` over
  the window).
- `dsa_index_reuse_pct`: of the attention layers that ran, the share
  that reused the key sets of the full layer below
  (`cake_dsa_index_reused_total` against `..._index_layers_total`):
  fixed by the configuration's `indexer_types`, a guard.
- `moe_held_rows_share_pct`: (token, expert) rows computed here over
  rows the routers chose among ALL their experts
  (`cake_moe_rows_total` / `cake_moe_rows_routed_total`): 100 * held /
  total under even routing.
- `dev_share_indexer_pct`, `dev_share_mla_proj_pct`: device self time
  under the scopes `indexer` + `index_topk`, and `mla_q` + `mla_kv`,
  over busy device time (this file's own scope lists, as moe.py has:
  `harness/trace_spans.py` files all of them under `attn`).
- `mla_attn_roofline`: the summed device time of the two attention
  kernels' events (device 0: `cake_mla_attn`, a row's single token over
  its gathered rows, in every decode step and every mixed dispatch;
  `cake_mla_window_attn`, a window over its row's pages, in every mixed
  dispatch) against the least time for what the window's steps selected
  (`harness/mla_roofline.py`). One event is one layer of one dispatch,
  so the capture held `window events / L` mixed dispatches and the rest
  of the `cake_mla_attn` events were decode steps; what a dispatch of
  each kind needs on average (all layers) comes from the step records'
  `dsa_keys_selected` and `dsa_rows_distinct`. The window kernel
  computes every VISIBLE key under a bias; only the SELECTED ones are
  needed, so its share reads low by design of the count.

The cell's mixed step and client TTFT are `window_steps.py`'s
(`mixed_step_ms.tok`, `mixed_step_device_ms.tok`, `ttft_p50_ms.tok`).

A program without the counters, the scopes or the kernel yields nothing
for the metric concerned.
"""

from harness import mla_roofline, readers, trace_reduce as tr
from harness import trace_spans as ts
from harness.peaks import peaks
from harness.server import metric_sum

KERNEL, WINDOW_KERNEL = "cake_mla_attn", "cake_mla_window_attn"
KERNELS_BY_NAME = (KERNEL, WINDOW_KERNEL)
INDEXER_SCOPES = ("indexer", "index_topk")
PROJ_SCOPES = ("mla_q", "mla_kv")
PROGRAMS, KERNELS = "step programs", "kernels"

METRICS = [
    {"name": "dsa_selected_share_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "dsa_index_reuse_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "moe_held_rows_share_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "dev_share_indexer_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_mla_proj_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "mla_attn_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
]


def counters(run) -> dict:
    def delta(family):
        return (metric_sum(run["metrics_1"], family)
                - metric_sum(run["metrics_0"], family))

    out = {}
    visible = delta("cake_dsa_keys_visible_total")
    if visible > 0:
        out["dsa_selected_share_pct"] = (
            100.0 * delta("cake_dsa_keys_selected_total") / visible)
    computed = delta("cake_dsa_index_layers_total")
    reused = delta("cake_dsa_index_reused_total")
    if computed + reused > 0:
        out["dsa_index_reuse_pct"] = 100.0 * reused / (computed + reused)
    routed = delta("cake_moe_rows_routed_total")
    if routed > 0:
        out["moe_held_rows_share_pct"] = (
            100.0 * delta("cake_moe_rows_total") / routed)
    return out


def need_per_dispatch(run, kind: str):
    """Mean least seconds of the attention of one dispatch (all layers)
    of the window's steps of `kind`; a step record holds
    `dsa_index_layers / L_full` dispatches."""
    cfg = run["model_config"]
    d = mla_roofline.mla_dims(cfg)
    peak = peaks(run["device"]["kind"])
    kv_bytes = run["cell"].cell["shape"].get("kv_bytes", 2)
    need = dispatches = 0.0
    for s in run["steps"]:
        if (s["kind"] != kind or s["compiled"]
                or not s.get("dsa_index_layers")):
            continue
        n = s["dsa_index_layers"] / d["L_full"]
        events = d["L"] * n
        need += events * mla_roofline.attn_least_s(
            cfg, s["dsa_keys_selected"] / events,
            s["dsa_rows_distinct"] / events, peak, cache_bytes=kv_bytes)
        dispatches += n
    return need / dispatches if dispatches else None


def attn_roofline(run):
    trace = run.get("trace")
    if not trace or not trace.get("kernels"):
        return None
    if "indexer_types" not in run["model_config"]:
        return None
    L = run["model_config"]["num_hidden_layers"]
    dur, events = 0.0, {k: 0 for k in KERNELS_BY_NAME}
    for ev in trace["kernels"]:
        if ev["device"] != 0:
            continue
        m = ts.KERNEL.match(ev["name"])
        if m and m.group(1) in events:
            dur += ev["dur_s"]
            events[m.group(1)] += 1
    mixed = events[WINDOW_KERNEL] / L
    decode = max(0.0, events[KERNEL] / L - mixed)
    need = 0.0
    for kind, n in (("mixed", mixed), ("decode", decode)):
        if n:
            per_dispatch = need_per_dispatch(run, kind)
            if per_dispatch is None:
                return None
            need += per_dispatch * n
    return 100.0 * need / dur if dur > 0 else None


def from_trace(run) -> dict:
    planes = readers.planes(run)
    if not planes:
        return {}
    out = {}
    devices = sorted((p for p in planes if tr.is_device_plane(p["name"])),
                     key=lambda p: p["name"])
    ops = tr._line(devices[0], (ts.OPS_LINE,)) if devices else None
    ops = [e for e in (ops["events"] if ops else []) if e["dur_ns"] > 0]
    if not ops:
        return out
    indexer = proj = 0.0
    for e, self_ns in tr.self_times(ops):
        parts = str(e["stats"].get("tf_op") or "").rstrip(":").split("/")
        if any(p in INDEXER_SCOPES for p in parts):
            indexer += self_ns
        elif any(p in PROJ_SCOPES for p in parts):
            proj += self_ns
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    if busy > 0 and indexer > 0:
        out["dev_share_indexer_pct"] = 100.0 * indexer / busy
    if busy > 0 and proj > 0:
        out["dev_share_mla_proj_pct"] = 100.0 * proj / busy
    return out


def read(run):
    out = counters(run)
    out.update(from_trace(run))
    out["mla_attn_roofline"] = attn_roofline(run)
    return out
