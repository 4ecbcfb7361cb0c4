"""Step programs: one decode step against the least time the chips
could take to stream its weights and live KV once (bandwidth bound).
On a host clock that ends in a fetch, so host gaps inside the step
count against it; the device's idle share stands beside it."""

from harness.e2e import median
from harness.peaks import peaks
from harness.readers import live_tokens_at, mono, steps_of
from harness.roofline import decode_step_least_s

METRICS = [{"name": "decode_step_roofline", "unit": "%",
            "layer": "step programs", "moves": "tpot_p50_ms",
            "source": "program_span"}]


def read(run):
    steps = steps_of(run, "decode")
    if not steps:
        return {}
    peak = peaks(run["device"]["kind"])
    shape = run["cell"].cell["shape"]
    live = [live_tokens_at(run, mono(run, s["ts"])) for s in steps]
    least = decode_step_least_s(
        run["model_config"], sum(live) / len(live), peak,
        weight_bytes=shape["weight_bytes"], kv_bytes=shape["kv_bytes"],
        stages=shape.get("stages", 1), tp=shape.get("tp", 1))
    return {"decode_step_roofline":
            100.0 * least / median(s["wall_s"] for s in steps)}
