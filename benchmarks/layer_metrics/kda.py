"""Kimi Delta Attention beside the page pool (bailing_hybrid).

- `dev_share_kda_pct`: device self time under the scopes `kda_conv`,
  `kda_gate`, `kda_chunk`, `kda_step` and `kda_state` over busy device
  time; `dev_share_kda_proj_pct`: under `kda_in` and `kda_out` (this
  file's own scope lists, as ssm.py has: `harness/trace_spans.py` files
  the first group under `attn`, the projections under `qkv` and
  `o_proj`).
- `kda_step_roofline`: the one-step form is XLA (no kernel of its own),
  so its time is the self time under `kda_step` and `kda_state` in the
  capture, against 2 x the float32 state of every (row, layer) the step
  records say took the one-step form (`harness/kda_roofline.py`).
- `kda_chunk_roofline`: the self time under `kda_chunk` against the
  greater of the chunked form's own operations at a STATED chunk of 64
  (not the chunk the program picks) at the bf16 peak and its least
  bytes, for the tokens the records say were chunked.
  Both take each step's need from its OWN record, as mla_dense.py does:
  the engine's `cake/fetch` span of a step carries the record's number
  and ends when the step's results are on the host, so a device op
  belongs to the first fetch that ends after it. A step of this family
  is ONE dispatch (one window a step). The first record of the capture
  (whose ops may have begun before it) and the ops past the last fetch
  are left out, time and need alike.
- `kda_chunked_share_pct`: tokens through the chunked form over all
  tokens through a KDA layer (`cake_kda_tokens_chunked_total` against
  `..._stepped_total`); `kda_state_rows_per_step`: rows whose state a
  step touched (`cake_kda_state_rows_total` / KDA layers / steps): at
  most the rows busy, a guard that a row with no token costs nothing.

The cell's mixed step and client TTFT are `window_steps.py`'s
(`mixed_step_ms.tok`, `mixed_step_device_ms.tok`, `ttft_p50_ms.tok`).

A program without the counters, the scopes or the fetch spans yields
nothing for the metric concerned.
"""

import bisect

from harness import kda_roofline, readers, trace_reduce as tr
from harness import trace_spans as ts
from harness.peaks import peaks
from harness.server import metric_sum

KDA_SCOPES = ("kda_conv", "kda_gate", "kda_chunk", "kda_step", "kda_state")
PROJ_SCOPES = ("kda_in", "kda_out")
STEP_SCOPES = ("kda_step", "kda_state")
CHUNK_SCOPE = "kda_chunk"
FETCH_SPAN = ts.SPAN_PREFIX + "fetch"
PROGRAMS, KERNELS = "step programs", "kernels"

METRICS = [
    {"name": "dev_share_kda_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_kda_proj_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "kda_step_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "kda_chunk_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "kda_chunked_share_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "kda_state_rows_per_step", "unit": "rows",
     "layer": "scheduler and page allocator", "moves": "out_tok_s",
     "source": "program_counter"},
]


def has_kda(run) -> bool:
    return "layer_group_size" in run["model_config"]


def counters(run) -> dict:
    def delta(family):
        return (metric_sum(run["metrics_1"], family)
                - metric_sum(run["metrics_0"], family))

    out = {}
    chunked = delta("cake_kda_tokens_chunked_total")
    stepped = delta("cake_kda_tokens_stepped_total")
    if chunked + stepped > 0:
        out["kda_chunked_share_pct"] = 100.0 * chunked / (chunked + stepped)
    rows = delta("cake_kda_state_rows_total")
    steps = [s for s in run["steps"] if s.get("kda_state_rows")]
    if rows > 0 and steps and has_kda(run):
        layers = kda_roofline.kda_dims(run["model_config"])["L_kda"]
        out["kda_state_rows_per_step"] = rows / layers / len(steps)
    return out


def fetched_steps(planes) -> list:
    """[(end_ns, step)] of the capture's `cake/fetch` spans, by end."""
    return sorted(
        (e["start_ns"] + e["dur_ns"], int(e["stats"]["step"]))
        for p in planes if ts.is_host_plane(p["name"])
        for line in p["lines"] for e in line["events"]
        if e["name"] == FETCH_SPAN and e["stats"].get("step") is not None)


def rooflines(run, planes, scoped: list) -> dict:
    """scoped: [(event, self_ns, scope parts)] of device 0's ops. Each
    op under the step's or the chunk's scopes goes to its own record
    (module docstring); a record's need is its own counters'."""
    fetches = fetched_steps(planes)
    if len(fetches) < 2:
        return {}
    ends = [end for end, _step in fetches]
    records = {s["step"]: s for s in run.get("all_steps") or run["steps"]}
    step_ns, chunk_ns = {}, {}
    for e, ns, parts in scoped:
        into = (step_ns if any(p in STEP_SCOPES for p in parts)
                else chunk_ns if CHUNK_SCOPE in parts else None)
        if into is None:
            continue
        i = bisect.bisect_left(ends, e["start_ns"] + e["dur_ns"])
        if 0 < i < len(ends):
            step = fetches[i][1]
            into[step] = into.get(step, 0.0) + ns
    cfg, peak = run["model_config"], peaks(run["device"]["kind"])
    act = run["cell"].cell["shape"].get("kv_bytes", 2)
    out = {}
    for name, spent, key, least in (
            ("kda_step_roofline", step_ns, "kda_tokens_stepped",
             lambda n: kda_roofline.step_least_s(cfg, n, peak)),
            ("kda_chunk_roofline", chunk_ns, "kda_tokens_chunked",
             lambda n: kda_roofline.chunk_least_s(cfg, n, peak, act))):
        need = took = 0.0
        for step, ns in spent.items():
            rec = records.get(step)
            if rec is None or rec.get(key) is None:
                return {}
            if rec[key] > 0:
                need += least(rec[key])
                took += ns / 1e9
        if took > 0 and need > 0:
            out[name] = 100.0 * need / took
    return out


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
    groups = {"dev_share_kda_pct": KDA_SCOPES,
              "dev_share_kda_proj_pct": PROJ_SCOPES}
    self_ns = {name: 0.0 for name in groups}
    scoped = []
    for e, ns in tr.self_times(ops):
        parts = str(e["stats"].get("tf_op") or "").rstrip(":").split("/")
        scoped.append((e, ns, parts))
        for name, scopes in groups.items():
            if any(p in scopes for p in parts):
                self_ns[name] += ns
                break
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    for name, ns in self_ns.items():
        if busy > 0 and ns > 0:
            out[name] = 100.0 * ns / busy
    if has_kda(run):
        out.update(rooflines(run, planes, scoped))
    return out


def read(run):
    out = counters(run)
    out.update(from_trace(run))
    return out
