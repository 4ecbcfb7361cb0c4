"""From the profiler's `.xplane.pb` to numbers.

Run as a process of its own, after the server has exited (it imports
JAX for `jax.profiler.ProfileData`, and one process holds the chip at a
time):

    python benchmarks/harness/trace_reduce.py <dir or .xplane.pb> <out.json>

The arithmetic (`reduce_planes`) works on plain dicts, so it is tested
without a chip: planes = [{"name", "lines": [{"name", "events":
[{"name", "start_ns", "dur_ns", "stats": {}}]}]}].
"""

from __future__ import annotations

import json
import os
import re
import sys

TOP_N = 10
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
KERNEL_TARGET = "tpu_custom_call"      # a Pallas kernel's custom call


def find_xplane(path: str):
    if os.path.isfile(path):
        return path
    newest, newest_m = None, -1.0
    for root, _dirs, files in os.walk(path):
        for name in files:
            if name.endswith(".xplane.pb"):
                p = os.path.join(root, name)
                m = os.path.getmtime(p)
                if m > newest_m:
                    newest, newest_m = p, m
    return newest


def load_planes(xplane_path: str, device_only: bool = True) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    planes = []
    for plane in data.planes:
        if device_only and not is_device_plane(plane.name):
            continue
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                stats = {}
                try:
                    for k, v in ev.stats:
                        if isinstance(v, (int, float, str)):
                            stats[k] = v
                except Exception:  # noqa: BLE001 — stats are optional
                    pass
                events.append({"name": ev.name,
                               "start_ns": float(ev.start_ns),
                               "dur_ns": float(ev.duration_ns),
                               "stats": stats})
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def union_ns(intervals) -> float:
    """Total length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals) -> list:
    """[(gap start, gap end)] between merged busy intervals."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def self_times(events: list) -> list:
    """[(event, self nanoseconds)]: an event's duration minus what the
    events nested directly inside it cover (a `while` holds its body's
    ops on the same line, and would otherwise count them twice)."""
    out, stack = [], []
    order = sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"]))
    for e in order:
        end = e["start_ns"] + e["dur_ns"]
        while stack and stack[-1][1] <= e["start_ns"]:
            stack.pop()
        if stack and end <= stack[-1][1] + 1:
            stack[-1][2][1] -= e["dur_ns"]
        cell = [e, e["dur_ns"]]
        out.append(cell)
        stack.append((e, end, cell))
    return [(e, max(0.0, s)) for e, s in out]


def op_label(ev: dict) -> str:
    """The name the trace prints, with the printed result shape where
    the event carries one, in the characters a metric name may have."""
    name = ev["name"]
    m = re.search(r"= *\(?([a-z0-9]+\[[0-9,]*\])", name)
    head = name.split(" = ")[0].strip().lstrip("%")
    op = re.search(r"= *\(?[a-z0-9]+\[[0-9,]*\][^ ]* ([a-z\-_]+)\(", name)
    parts = [head]
    if op:
        parts.append(op.group(1))
    if m:
        parts.append(m.group(1))
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", "_".join(parts))[:96].strip("_")


def gap_label(modules: list, t0: float, t1: float) -> str:
    """`after_<program>_before_<program>`: the XLA modules (jitted
    programs) that ran on the device on either side of an idle gap,
    without their run ids, in the characters a name may have."""
    before = after = "none"
    for m in modules:
        if m["start_ns"] + m["dur_ns"] <= t0 + 1:
            before = m["name"]
        elif m["start_ns"] >= t1 - 1:
            after = m["name"]
            break
    before, after = (re.sub(r"\(\d+\)$", "", n) for n in (before, after))
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", f"after_{before}_before_{after}")


def _line(plane: dict, names) -> dict | None:
    for line in plane["lines"]:
        if line["name"] in names:
            return line
    return None


def reduce_planes(planes: list, window_s: float | None = None) -> dict:
    """busy_s (union of device-op intervals, averaged over the device
    planes), window_s (the traced span, first op start to last op end
    unless given), per-op sums, the longest idle gaps on the first
    device labelled by the programs on either side, and every
    custom-call (Pallas kernel) event for the per-kernel readers."""
    devices = [p for p in planes if is_device_plane(p["name"])]
    busy, spans, per_op, kernels = [], [], {}, []
    gaps_out = []
    for di, plane in enumerate(devices):
        ops = _line(plane, OPS_LINES)
        if ops is None:
            continue
        iv = [(e["start_ns"], e["start_ns"] + e["dur_ns"])
              for e in ops["events"] if e["dur_ns"] > 0]
        if not iv:
            continue
        busy.append(union_ns(iv) / 1e9)
        spans.append((min(s for s, _ in iv), max(e for _, e in iv)))
        if di == 0:
            for e, self_ns in self_times(ops["events"]):
                label = op_label(e)
                per_op[label] = per_op.get(label, 0.0) + self_ns / 1e9
            mods = _line(plane, MODULE_LINES)
            mod_ev = sorted(mods["events"], key=lambda e: e["start_ns"]) \
                if mods else []
            longest = sorted(gaps_of(iv), key=lambda g: g[0] - g[1])[:TOP_N]
            gaps_out = [[gap_label(mod_ev, s, e), (e - s) / 1e9]
                        for s, e in longest]
        for e in ops["events"]:
            if KERNEL_TARGET in e["name"]:
                kernels.append({"device": di, "name": e["name"],
                                "label": op_label(e),
                                "dur_s": e["dur_ns"] / 1e9})
    if not busy:
        return {"busy_s": 0.0, "window_s": window_s or 0.0,
                "device_ops": [], "idle_gaps": [], "kernels": [],
                "devices": len(devices)}
    span_s = (max(e for _, e in spans) - min(s for s, _ in spans)) / 1e9
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP_N]
    return {"busy_s": sum(busy) / len(busy),
            "window_s": window_s if window_s else span_s,
            "span_s": span_s,
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": gaps_out, "kernels": kernels,
            "devices": len(devices)}


def describe(planes: list) -> dict:
    """What the trace holds, for a human: planes, lines, event counts
    and a few names with their stats. Written beside the reduction."""
    out = []
    for p in planes:
        lines = []
        for line in p["lines"]:
            sample = [{"name": e["name"][:300], "stats": e["stats"]}
                      for e in line["events"][:3]]
            lines.append({"name": line["name"],
                          "events": len(line["events"]), "sample": sample})
        out.append({"name": p["name"], "lines": lines})
    return {"planes": out}


def main(argv) -> int:
    src, dst = argv[1], argv[2]
    xplane = find_xplane(src)
    if xplane is None:
        print(f"no .xplane.pb under {src}", file=sys.stderr)
        return 1
    planes = load_planes(xplane)
    result = reduce_planes(planes)
    result["xplane"] = xplane
    with open(dst, "w") as f:
        json.dump(result, f)
    with open(dst + ".describe.json", "w") as f:
        json.dump(describe(planes), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
