"""The sampler inside the step programs.

- `dev_share_sample_pct`: device self time under the scope `sample`
  (`engine._masked_sample` around `ops/sampling.sample_tokens_ragged`:
  the key split, the penalty's scatter, the nucleus threshold, the
  draw, the log-softmax, the top alternatives and the ring's write,
  traced into every sampled step program and run eagerly after a
  synchronous step) over busy device time, device 0. Read from the
  capture with `harness/trace_spans.py`'s functions and this file's own
  scope list, as zaya.py, moe.py, dsa.py and ssm.py do: its fixed list
  knows `sample`, but no accepted metric reports it.

Nothing in an untraced run; a program without the scope yields nothing.
"""

from harness import readers, trace_reduce as tr, trace_spans as ts

SCOPES = {"dev_share_sample_pct": ("sample",)}

METRICS = [
    {"name": "dev_share_sample_pct", "unit": "%", "layer": "step programs",
     "moves": "out_tok_s", "source": "device_trace"},
]


def shares(planes: list) -> dict:
    """{metric: 100 x self time under its scopes / busy time} on the
    first device plane of `planes` (`trace_spans.read_xspace`'s dicts)."""
    devices = sorted((p for p in planes if tr.is_device_plane(p["name"])),
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


def read(run):
    planes = readers.planes(run)
    return shares(planes) if planes else {}
