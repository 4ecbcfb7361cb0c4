"""The dense-slot engine's TTFT, plain median, NOT judged: it prefills
one request at a time, so a request's TTFT is mostly the prefills it
queued behind (0.08 s alone, 2.04 s queued: chip run, PR 21)."""

from harness.e2e import median, ttft_samples

METRICS = [{"name": "ttft_p50_ms.dense", "unit": "ms",
            "layer": "scheduler and page allocator", "moves": "out_tok_s",
            "source": "host_clock"}]


def read(run):
    xs = [x for v in ttft_samples(run["records"], run["t0"],
                                  run["t1"]).values() for x in v]
    return {"ttft_p50_ms.dense": 1000.0 * median(xs)} if xs else {}
