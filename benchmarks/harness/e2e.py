"""End-to-end metric arithmetic over the client's records.

Every function takes the list of request records (`client.stream_chat`)
and the window [t0, t1) on the same host clock. What a metric measures
is fixed here: a rate is over all the work and all the time of the
window, a tail is the tail of all samples.
"""

from __future__ import annotations

MIN_GAPS_CUT = 32      # a request cut by a window edge counts from here


class MetricError(Exception):
    """The run cannot yield the metric as defined; the run fails."""


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise MetricError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def start_time(rec: dict) -> float:
    """What a request's latency counts from: when it was due in an open
    loop, when it was sent in a closed one."""
    return rec.get("t_due", rec["t_send"])


def ttft_s(rec: dict):
    return rec["token_t"][0] - start_time(rec) if rec["token_t"] else None


def ttft_samples(records, t0: float, t1: float) -> dict:
    """{class: [TTFT seconds]} of the requests sent inside the window
    whose first token arrived before its end."""
    out = {}
    for r in records:
        if r["failed"] or not r["token_t"]:
            continue
        if t0 <= start_time(r) < t1 and r["token_t"][0] < t1:
            out.setdefault(r["class"], []).append(ttft_s(r))
    return out


def stratified_ttft_mean_ms(records, t0: float, t1: float,
                            classes: list) -> float:
    """The mean TTFT of the stated mix: the mean within each prompt
    class, combined with the traffic file's class weights. Which
    requests happened to finish in the window cannot move it; a class
    with no finished request is a failed run, never a re-weighting."""
    by_class = ttft_samples(records, t0, t1)
    total, weight_sum = 0.0, 0.0
    for c in classes:
        xs = by_class.get(c["name"])
        if not xs:
            raise MetricError(
                f"no request of class {c['name']!r} got its first token "
                "inside the window: ttft_mean_ms is undefined")
        total += c["weight"] * (sum(xs) / len(xs))
        weight_sum += c["weight"]
    return 1000.0 * total / weight_sum


def tokens_in_window(rec: dict, t0: float, t1: float) -> list:
    return [t for t in rec["token_t"] if t0 <= t < t1]


def tpot_samples(records, t0: float, t1: float) -> list:
    """Per request, (last - first) / (tokens - 1) over the tokens that
    arrived inside the window. A request wholly inside counts with any
    gap; one cut by an edge of the window counts what lies inside if
    that is at least MIN_GAPS_CUT gaps."""
    out = []
    for r in records:
        if r["failed"]:
            continue
        ts = tokens_in_window(r, t0, t1)
        gaps = len(ts) - 1
        whole = r["finished"] and len(ts) == len(r["token_t"])
        if gaps >= (1 if whole else MIN_GAPS_CUT):
            out.append((ts[-1] - ts[0]) / gaps)
    return out


def tpot_p50_ms(records, t0: float, t1: float) -> float:
    xs = tpot_samples(records, t0, t1)
    if not xs:
        raise MetricError("no request had enough tokens in the window")
    return 1000.0 * median(xs)


def gaps_in_window(records, t0: float, t1: float) -> list:
    out = []
    for r in records:
        if r["failed"]:
            continue
        ts = tokens_in_window(r, t0, t1)
        out += [b - a for a, b in zip(ts, ts[1:])]
    return out


def itl_p95_ms(records, t0: float, t1: float) -> float:
    xs = gaps_in_window(records, t0, t1)
    if len(xs) < 200:
        raise MetricError(f"only {len(xs)} inter-token gaps in the window")
    return 1000.0 * percentile(xs, 95.0)


def out_tok_s(records, t0: float, t1: float) -> float:
    n = sum(len(tokens_in_window(r, t0, t1))
            for r in records if not r["failed"])
    return n / (t1 - t0)


def end_to_end(names, records, t0, t1, traffic: dict, setup_s: float) -> dict:
    """{name: value} for the cell's judged metrics. An unknown name is
    an error: a new end-to-end metric is new arithmetic, added here by
    a benchmark PR."""
    table = {
        "ttft_mean_ms": lambda: stratified_ttft_mean_ms(
            records, t0, t1, traffic["prompt_classes"]),
        "tpot_p50_ms": lambda: tpot_p50_ms(records, t0, t1),
        "itl_p95_ms": lambda: itl_p95_ms(records, t0, t1),
        "out_tok_s": lambda: out_tok_s(records, t0, t1),
        "setup_s": lambda: setup_s,
    }
    out = {}
    for name in names:
        if name not in table:
            raise MetricError(f"no arithmetic for end-to-end metric {name!r}")
        out[name] = table[name]()
    return out
