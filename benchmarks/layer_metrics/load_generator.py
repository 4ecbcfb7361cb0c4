"""Load generator: how long a client took from one request's end to
its next send. A starved generator shows here, not as a fast server."""

from harness.e2e import median

METRICS = [{"name": "client_turnaround_p50_ms", "unit": "ms",
            "layer": "load generator", "moves": "out_tok_s",
            "source": "host_clock"}]


def read(run):
    xs = [d for t, d in run["turnarounds"] if run["t0"] <= t < run["t1"]]
    return {"client_turnaround_p50_ms": 1000.0 * median(xs)} if xs else {}
