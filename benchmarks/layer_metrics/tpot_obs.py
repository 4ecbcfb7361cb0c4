"""`tpot_p50_ms` where it is observed and not judged. In the chat cell
two untraced runs in twenty of the same code read 10 % high while the
rate fell 3 % and the step times stayed, and every traced run reads so
too; the cause is not established (PERF.md, section 7). So there it
stands beside the judged metrics under this name."""

from harness.e2e import MetricError, tpot_p50_ms

METRICS = [{"name": "tpot_p50_ms.obs", "unit": "ms", "layer": "step dispatch",
            "moves": "out_tok_s", "source": "host_clock"}]


def read(run):
    try:
        return {"tpot_p50_ms.obs":
                tpot_p50_ms(run["records"], run["t0"], run["t1"])}
    except MetricError:
        return {}
