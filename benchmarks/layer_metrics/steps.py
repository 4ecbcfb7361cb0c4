"""Scheduler and step dispatch, from the step records of the window
(`GET /api/v1/steps`) and the compile counter on `/metrics`."""

from harness.readers import median_wall_ms
from harness.server import metric_sum

SCHED = "scheduler and page allocator"
DISPATCH = "step dispatch"

METRICS = [
    {"name": "rows_busy_pct", "unit": "%", "layer": SCHED,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "pages_in_use_pct", "unit": "%", "layer": SCHED,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "prefill_rows_per_mixed_step", "unit": "rows", "layer": SCHED,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "mixed_step_share_pct", "unit": "%", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_counter"},
    {"name": "decode_step_ms", "unit": "ms", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_span"},
    {"name": "mixed_step_ms", "unit": "ms", "layer": DISPATCH,
     "moves": "ttft_mean_ms", "source": "program_span"},
    {"name": "compiles_in_window", "unit": "count", "layer": DISPATCH,
     "moves": "out_tok_s", "source": "program_counter"},
]


def read(run):
    steps = run["steps"]
    out = {"compiles_in_window":
           metric_sum(run["metrics_1"], "cake_jit_compiles_total")
           - metric_sum(run["metrics_0"], "cake_jit_compiles_total")}
    if not steps:
        return out
    slots = run["health"].get("decode_slots")
    # a mixed or decode step carries every occupied row; a dense-engine
    # prefill step carries one admission and says nothing of the rest
    rows = [s["rows"] for s in steps if s["kind"] != "prefill"]
    if slots and rows:
        out["rows_busy_pct"] = 100.0 * sum(rows) / (len(rows) * slots)
    paged = [s for s in steps if s.get("pages_total")]
    if paged:
        out["pages_in_use_pct"] = 100.0 * sum(
            1.0 - s["pages_free"] / s["pages_total"] for s in paged) / len(paged)
    mixed = [s for s in steps if s["kind"] == "mixed"]
    if mixed:
        out["mixed_step_share_pct"] = 100.0 * len(mixed) / len(steps)
        # how many prompts share a mixed step: the more, the fewer
        # mixed steps the same prompts cost every decoding row
        shared = [s["rows_prefill"] for s in mixed
                  if s.get("rows_prefill") is not None]
        if shared:
            out["prefill_rows_per_mixed_step"] = sum(shared) / len(shared)
    out["decode_step_ms"] = median_wall_ms(run, "decode")
    out["mixed_step_ms"] = median_wall_ms(run, "mixed")
    return out
