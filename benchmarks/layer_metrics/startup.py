"""Entry and loader, on the program's own clock (`cake_tpu/startup.py`,
published by `cake_tpu/obs/startup.py`), read from the `/metrics` scrape
taken as the window opens.

`setup_programs_s`: the seconds between the process's start and the
window's opening that went into MAKING programs, wherever the harness's
requests made them fall (before the first healthy answer, in the
calibration request that lies between `healthy_s` and `warmup_s`, or in
the warm-up): Python traced, jaxprs lowered, the backend (XLA's compile
on a cache miss, the cache's read and the executable's deserialisation
on a hit) and the step accountant's cost analysis. The program counts
each of the four less the spans inside it, so they add up to wall
seconds.

`setup_weights_s`: the start-up phases that make the weights: `weights`
(the draw from the seed or the load), `quantize` where it runs apart
from it, `weights_ready` where the host first waits for the device. The
two names overlap: a program made inside these phases (the draw's own
jit) is in both. In the accepted cells the draw is launched and never
waited for, so `weights` is almost wholly its program's making and the
name moves only with that (PERF.md, section 3).

A program whose scrape has none of these families reports nothing."""

LAYER = "entry and loader"

METRICS = [
    {"name": "setup_programs_s", "unit": "s", "layer": LAYER,
     "moves": "setup_s", "source": "program_span"},
    {"name": "setup_weights_s", "unit": "s", "layer": LAYER,
     "moves": "setup_s", "source": "program_span"},
]

PROGRAM_PARTS = ("cake_jit_trace_seconds_total",
                 "cake_jit_lower_seconds_total",
                 "cake_jit_backend_seconds_total",
                 "cake_jit_cost_analysis_seconds_total")
WEIGHT_PHASES = ("weights", "quantize", "weights_ready")
PHASE_SERIES = 'cake_startup_phase_seconds{phase="%s"}'


def read(run):
    scrape = run.get("metrics_0") or {}
    out = {}
    parts = [scrape[name] for name in PROGRAM_PARTS if name in scrape]
    if parts:
        out["setup_programs_s"] = sum(parts)
    phases = [scrape[PHASE_SERIES % p] for p in WEIGHT_PHASES
              if PHASE_SERIES % p in scrape]
    if phases:
        out["setup_weights_s"] = sum(phases)
    return out
