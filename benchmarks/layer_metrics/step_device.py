"""Step programs, kernels and device, from the capture's `.xplane.pb`
by way of `harness/trace_spans.py` (a process of its own, held to the
CPU): one execution of a step program on device 0, busy device time by
the program's named scopes, and idle device time by the `cake/<phase>`
span of the engine thread it lay under. The full tables go to
`benchmarks/.run/<cell>/trace_spans.json` and one `spans: {...}` line
on stderr. Nothing in an untraced run; a program without scopes or
spans yields no share and no attribution."""

import os
import subprocess
import sys

from harness import spec

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
    xplane = (run.get("trace") or {}).get("xplane")
    if not xplane or not os.path.isfile(xplane):
        return {}
    out_path = os.path.join(spec.BENCH_DIR, ".run", run["cell"].name,
                            "trace_spans.json")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(spec.BENCH_DIR, "harness", "trace_spans.py"),
         xplane, out_path],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    sys.stderr.write(proc.stderr[-4000:])
    if proc.returncode != 0:
        return {}
    return spec.load_json(out_path)["metrics"]
