"""The plain readings of a cell that is judged by tokens: its mixed
step on the host clock and on the device, and its client TTFT. One name
each for every such cell; a new cell joins them by list.

`mixed_step_ms` and `mixed_step_device_ms` move `ttft_mean_ms`, which
only the chat cells report, and `harness/spec.py` refuses a cell that
lists a metric whose `moves` it does not report. These three are the
same readings under names that move `out_tok_s`: `.tok` says that the
cell is judged by tokens, never which cell it is (a copy a cell, as
`mixed_step_ms.longdoc`, is what filled `per_layer` to its cap of 128
by PR 53; the map old name -> new name is in PERF.md section 3).

- `mixed_step_ms.tok`: the median `wall_s` of the window's `mixed` step
  records that did not compile (`readers.median_wall_ms`: what
  `mixed_step_ms` reads).
- `mixed_step_device_ms.tok`: the median over the capture's executions
  of a mixed step program of first to last device-0 op inside one `XLA
  Modules` event: `mixed_step_device_ms` of `trace_spans.reduce_spans`,
  taken from the one reduction a traced run makes
  (`readers.span_reduction`), never from a read of its own. Not listed
  for a cell whose capture can fall inside one decode stretch (ZAYA).
- `ttft_p50_ms.tok`: the plain median of the client's TTFT over every
  prompt class (`e2e.ttft_samples`: requests sent inside the window
  whose first token arrived in it). Reported, NOT judged: a 48 s window
  holds a few dozen first tokens, and a dense-slot engine's TTFT is
  mostly the prefills a request queued behind. A reading BY CLASS keeps
  a name of its own (`ttft_p50_ms.longshort-s1k`).

A run with no mixed step, no capture or no first token yields nothing
for the metric concerned.
"""

from harness.e2e import median, ttft_samples
from harness.readers import median_wall_ms, span_reduction

METRICS = [
    {"name": "mixed_step_ms.tok", "unit": "ms", "layer": "step dispatch",
     "moves": "out_tok_s", "source": "program_span"},
    {"name": "mixed_step_device_ms.tok", "unit": "ms",
     "layer": "step programs", "moves": "out_tok_s",
     "source": "device_trace"},
    {"name": "ttft_p50_ms.tok", "unit": "ms",
     "layer": "scheduler and page allocator", "moves": "out_tok_s",
     "source": "host_clock"},
]


def read(run):
    out = {"mixed_step_ms.tok": median_wall_ms(run, "mixed")}
    spans = span_reduction(run)
    if spans:
        out["mixed_step_device_ms.tok"] = spans["metrics"].get(
            "mixed_step_device_ms")
    first = [x for v in ttft_samples(run["records"], run["t0"],
                                     run["t1"]).values() for x in v]
    if first:
        out["ttft_p50_ms.tok"] = 1000.0 * median(first)
    return out
