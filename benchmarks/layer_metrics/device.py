"""Device: peak HBM on the fullest chip, from `/metrics`
(`Device.memory_stats` in the process that holds the chip). The idle
share is in the result line's `device` object."""

from harness.server import metric_max

METRICS = [{"name": "peak_hbm_gib", "unit": "GiB", "layer": "device",
            "moves": "out_tok_s", "source": "program_counter"}]


def read(run):
    peak = metric_max(run["metrics_2"], "cake_device_hbm_peak_bytes")
    return {"peak_hbm_gib": peak / 2**30} if peak else {}
