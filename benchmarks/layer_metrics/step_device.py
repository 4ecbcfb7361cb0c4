"""Step programs, kernels and device, from the capture's `.xplane.pb`
by way of `harness/trace_spans.py` (`readers.span_reduction`: made once
a run, over the one read of the capture the readers share): one
execution of a step program on device 0, busy device time by the
program's named scopes, and idle device time by the `cake/<phase>`
span of the engine thread it lay under. The full tables go to
`benchmarks/.run/<cell>/trace_spans.json` and one `spans: {...}` line
on stderr. Nothing in an untraced run; a program without scopes or
spans yields no share and no attribution. `mixed_step_device_ms` is
the chat cells' name (it moves `ttft_mean_ms`); the cells judged by
tokens read the same number as `mixed_step_device_ms.tok`
(`window_steps.py`)."""

from harness.readers import span_reduction

KERNELS, PROGRAMS, DEVICE = "kernels", "step programs", "device"

METRICS = [
    {"name": "decode_step_device_ms", "unit": "ms", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "mixed_step_device_ms", "unit": "ms", "layer": PROGRAMS,
     "moves": "ttft_mean_ms", "source": "device_trace"},
    {"name": "dev_share_attn_pct", "unit": "%", "layer": KERNELS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_ffn_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_kv_pct", "unit": "%", "layer": PROGRAMS,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "dev_share_unscoped_pct", "unit": "%", "layer": DEVICE,
     "moves": "out_tok_s", "source": "device_trace"},
    {"name": "idle_attributed_pct", "unit": "%", "layer": DEVICE,
     "moves": "out_tok_s", "source": "device_trace"},
]


def read(run):
    spans = span_reduction(run)
    return dict(spans["metrics"]) if spans else {}
