"""Shortcut-connected layers with zero-compute experts (longcat_flash).

- `scmoe_decode_attn_roofline`: the summed device time of the capture's
  `cake_mla_decode_attn` events against the least time for the keys
  their step records say they attended (`mla_keys_attended` over the 2 x
  `num_layers` latent layers: 139,264 operations and 1,152 B a key at 64
  heads), each event matched to its record through the `cake/fetch`
  spans: `mla_dense.py`'s reading, on `harness/scmoe_roofline.py`'s
  mapping of this config's key names.
- `scmoe_window_attn_roofline`: the `cake_mla_window_attn` events
  against the (query, key) pairs s <= t of the windows' prefill rows, as
  `mla_dense_window_roofline` counts them.
- `moe_zero_pairs_pct`: routed (token, expert) pairs that chose a zero
  expert over all routed pairs (`cake_moe_pairs_zero_total` /
  `cake_moe_rows_routed_total`): 100 x zero_expert_num / the router's
  width = 33.3 under an even router.
- `dev_share_shortcut_moe_pct`: device self time under the scope
  `shortcut_moe` (the router, the dispatch, the grouped matmuls, the
  combine and the identity part) over busy device time.

A config without this family's keys, a program without the counter, the
scope or the kernels, or a run without a capture yields nothing for the
metric concerned.
"""

import importlib.util
import os

from harness import readers, scmoe_roofline, trace_reduce as tr
from harness import trace_spans as ts
from harness.server import metric_sum

SCOPE = "shortcut_moe"
PROGRAMS, KERNELS = "step programs", "kernels"

METRICS = [
    {"name": "scmoe_decode_attn_roofline", "unit": "%", "layer": KERNELS,
     "moves": "tpot_p50_ms", "source": "device_trace"},
    {"name": "scmoe_window_attn_roofline", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "moe_zero_pairs_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "dev_share_shortcut_moe_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
]


def dense_reader():
    """`mla_dense.py`, whose two rooflines read these kernels."""
    spec = importlib.util.spec_from_file_location(
        "layer_metric_mla_dense_for_scmoe", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "mla_dense.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def zero_pairs(run) -> dict:
    def delta(family):
        return (metric_sum(run["metrics_1"], family)
                - metric_sum(run["metrics_0"], family))

    routed = delta("cake_moe_rows_routed_total")
    zero = delta("cake_moe_pairs_zero_total")
    if routed > 0 and zero > 0:
        return {"moe_zero_pairs_pct": 100.0 * zero / routed}
    return {}


def scope_share(ops) -> dict:
    """ops: device 0's ops that took time (`mla_dense.device_ops`)."""
    under = sum(
        self_ns for e, self_ns in tr.self_times(ops)
        if SCOPE in str(e["stats"].get("tf_op") or "").rstrip(":").split("/"))
    busy = sum(e - s for s, e in ts.merge(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops))
    if busy > 0 and under > 0:
        return {"dev_share_shortcut_moe_pct": 100.0 * under / busy}
    return {}


def read(run):
    mapped = scmoe_roofline.as_mla_dense_config(run["model_config"])
    if mapped is None:
        return {}
    out = zero_pairs(run)
    planes = readers.planes(run)
    if planes:
        dense, as_dense = dense_reader(), dict(run, model_config=mapped)
        out.update(scope_share(dense.device_ops(planes)))
        out["scmoe_decode_attn_roofline"] = dense.decode_roofline(
            as_dense, planes)
        out["scmoe_window_attn_roofline"] = dense.window_roofline(
            as_dense, planes)
    return out
