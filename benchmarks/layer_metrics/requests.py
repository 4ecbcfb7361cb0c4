"""HTTP front door and scheduler, from the server's request spans
(`GET /api/v1/requests`) joined to the client's records by rid."""

from harness.e2e import median, ttft_s

METRICS = [
    {"name": "http_ttft_overhead_p50_ms", "unit": "ms",
     "layer": "HTTP front door", "moves": "ttft_mean_ms",
     "source": "program_span"},
    {"name": "queue_wait_p50_ms", "unit": "ms",
     "layer": "scheduler and page allocator", "moves": "ttft_mean_ms",
     "source": "program_span"},
]


def read(run):
    over, waits = [], []
    for r in run["records"]:
        tr = run["traces"].get(r["rid"])
        if (tr is None or r["failed"] or not r["token_t"]
                or not run["t0"] <= r["t_send"] < run["t1"]):
            continue
        if tr.get("ttft_s") is not None:
            over.append(ttft_s(r) - tr["ttft_s"])
        if tr.get("queue_wait_s") is not None:
            waits.append(tr["queue_wait_s"])
    out = {}
    if over:
        out["http_ttft_overhead_p50_ms"] = 1000.0 * median(over)
    if waits:
        out["queue_wait_p50_ms"] = 1000.0 * median(waits)
    return out
