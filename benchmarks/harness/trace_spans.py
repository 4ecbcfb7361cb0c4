"""From the profiler's `.xplane.pb` to device time by named scope, a
step program's device time, and idle device time by host span.

Run as a process of its own (it needs no JAX and touches no chip; the
reader `layer_metrics/step_device.py` starts it held to the CPU all the
same, like `trace_reduce.py`):

    python benchmarks/harness/trace_spans.py <dir or .xplane.pb> <out.json>

What it reads that `trace_reduce.py` does not:

- **Scopes.** The program's `jax.named_scope`s (`embed`, `layers`,
  `attn_norm`, `qkv`, `attn`, `kv`, `o_proj`, `ffn`, `head`, `sample`)
  reach the trace as the HLO op_name, which the TPU profiler stores as
  the stat `tf_op` of an op's EVENT METADATA
  (`jit(decode_step_ragged_paged)/layers/while/body/closed_call/attn/...`),
  beside `program_id`, `hlo_category` and `source`.
  `jax.profiler.ProfileData` shows only an event's own stats, so the
  protobuf is read directly (`read_xspace`: the wire format of
  tsl/profiler/protobuf/xplane.proto, six messages).
- **Host spans.** `cake/<phase>` TraceAnnotations of the engine thread
  (`obs/steps.StepTelemetry.span`) on the `/host:CPU` plane, each with
  the stat `step`.

The arithmetic (`reduce_spans`) works on plain dicts, so it is tested
without a chip: planes = [{"name", "lines": [{"name", "events":
[{"name", "start_ns", "dur_ns", "stats": {}}]}]}], an event's stats
being its own merged over its metadata's.
"""

from __future__ import annotations

import bisect
import json
import re
import struct
import sys

try:                                # imported as harness.trace_spans
    from . import trace_reduce as tr
    from .e2e import median
except ImportError:                 # run as a script, beside it
    import trace_reduce as tr
    from e2e import median

SCOPES = ("embed", "layers", "attn_norm", "qkv", "attn", "kv", "o_proj",
          "ffn", "head", "sample")
# host spans under which an idle device is explained by the engine
# thread's own work (dispatch and fetch are the device's side: a gap
# under `fetch` is the device finishing, not the host holding it back)
HOST_WORK = ("admin", "schedule", "build", "sample", "emit")
SPAN_PREFIX = "cake/"
OPS_LINE, MODULES_LINE = tr.OPS_LINES[0], tr.MODULE_LINES[0]
STEP_PROGRAMS = {"decode": "jit_decode_step", "mixed": "jit_mixed_step",
                 "prefill": "jit_prefill"}
KERNEL = re.compile(r"%?(cake_[a-z0-9_]+?)(?:\.\d+)? = ")


# -- the protobuf, read directly ---------------------------------------------


def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; a
    length-delimited value is a memoryview slice."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield field, wire, val


def _text(val) -> str:
    return bytes(val).decode("utf-8", "replace")


def _signed(x: int) -> int:
    return x - (1 << 64) if x >= 1 << 63 else x


def _stat(buf, stat_names):
    """XStat -> (name, value); a ref_value resolves to the string it
    refers to."""
    key, val = 0, None
    for f, _w, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = struct.unpack("<d", bytes(v))[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f == 5:
            val = _text(v)
        elif f == 7:
            val = stat_names.get(v, v)
    return stat_names.get(key, str(key)), val


def _map_entry(buf):
    key, val = None, b""
    for f, _w, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf, want_line, want_event) -> dict:
    name, lines, event_meta, stat_meta = "", [], [], []
    for f, _w, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            event_meta.append(v)
        elif f == 5:
            stat_meta.append(v)
    stat_names = {}
    for v in stat_meta:
        key, msg = _map_entry(v)
        for f, _w, x in _fields(msg):
            if f == 2:
                stat_names[key] = _text(x)
    metas = {}
    for v in event_meta:
        key, msg = _map_entry(v)
        ev_name, stats = "", {}
        for f, _w, x in _fields(msg):
            if f == 2:
                ev_name = _text(x)
            elif f == 5:
                k, val = _stat(x, stat_names)
                stats[k] = val
        metas[key] = (ev_name, stats)
    out_lines = []
    for v in lines:
        line_name, ts_ns, raw_events = "", 0, []
        for f, _w, x in _fields(v):
            if f == 2:
                line_name = _text(x)
            elif f == 3:
                ts_ns = x
            elif f == 4:
                raw_events.append(x)
        if not want_line(name, line_name):
            continue
        events = []
        for x in raw_events:
            mid = off_ps = dur_ps = 0
            own = []
            for f, _w, y in _fields(x):
                if f == 1:
                    mid = y
                elif f == 2:
                    off_ps = y
                elif f == 3:
                    dur_ps = y
                elif f == 4:
                    own.append(y)
            ev_name, meta_stats = metas.get(mid, ("", {}))
            if not want_event(name, ev_name):
                continue
            stats = dict(meta_stats)
            for y in own:
                k, val = _stat(y, stat_names)
                stats[k] = val
            events.append({"name": ev_name,
                           "start_ns": ts_ns + off_ps / 1000.0,
                           "dur_ns": dur_ps / 1000.0, "stats": stats})
        out_lines.append({"name": line_name, "events": events})
    return {"name": name, "lines": out_lines}


def is_host_plane(name: str) -> bool:
    return name.startswith("/host:CPU")


def read_xspace(path: str) -> list:
    """The planes `reduce_spans` needs, as plain dicts: the device
    planes' op and module lines whole, and of the host plane only the
    `cake/` events (it holds every runtime call of every thread)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())

    def want_line(plane, line):
        if tr.is_device_plane(plane):
            return line in (OPS_LINE, MODULES_LINE)
        return is_host_plane(plane)

    def want_event(plane, event):
        return tr.is_device_plane(plane) or event.startswith(SPAN_PREFIX)

    planes = []
    for f, _w, v in _fields(buf):
        if f != 1:
            continue
        # a plane's name comes before its lines; skip the others early
        name = next((_text(x) for ff, _ww, x in _fields(v) if ff == 2), "")
        if tr.is_device_plane(name) or is_host_plane(name):
            planes.append(_plane(v, want_line, want_event))
    return planes


# -- arithmetic on plain dicts -----------------------------------------------


def merge(intervals) -> list:
    """Sorted, disjoint [start, end) intervals covering the same set."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap_ns(a: list, b: list) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def program_of(name: str) -> str:
    """`jit_decode_step_ragged_paged(1590...)` -> the program's name."""
    return re.sub(r"\(\d+\)$", "", name)


def _ident(program_id):
    """A program id as the unsigned number a module's name prints (the
    stat may be stored signed)."""
    try:
        return int(program_id) % (1 << 64)
    except (TypeError, ValueError):
        return None


def step_kind(program: str):
    for kind, prefix in STEP_PROGRAMS.items():
        if program.startswith(prefix):
            return kind
    return None


def scope_of(ev: dict, programs: dict) -> str:
    """The key an op's self time is summed under: the innermost named
    scope of its op_name; `program_copies` for data movement the
    compiler inserted into a step program with no op_name at all (the
    copies of a scan's carried buffers); else `unscoped`."""
    op_name = str(ev["stats"].get("tf_op") or "")
    for part in reversed(op_name.rstrip(":").split("/")):
        if part in SCOPES:
            return part
    program = programs.get(_ident(ev["stats"].get("program_id")), "")
    if (not op_name and step_kind(program)
            and ev["stats"].get("hlo_category") == "data formatting"):
        return "program_copies"
    return "unscoped"


def kernel_of(ev: dict):
    """The `name=` of the pallas_call behind a Pallas custom-call
    event ("" for an unnamed one), or None for any other event."""
    if tr.KERNEL_TARGET not in ev["name"]:
        return None
    m = KERNEL.match(ev["name"])
    return m.group(1) if m else ""


def reduce_spans(planes: list) -> dict:
    """Device 0's busy time by scope, each step program's device time
    per execution, its kernels by name, and its idle time by the host
    span it lay under."""
    devices = sorted((p for p in planes if tr.is_device_plane(p["name"])),
                     key=lambda p: p["name"])
    out = {"scopes_s": {}, "kernels_s": {}, "unnamed_custom_calls": 0,
           "programs": {}, "idle_s": {}, "metrics": {}}
    if not devices:
        return out
    dev = devices[0]
    ops = tr._line(dev, (OPS_LINE,))
    ops = [e for e in (ops["events"] if ops else []) if e["dur_ns"] > 0]
    modules = tr._line(dev, (MODULES_LINE,))
    modules = sorted(modules["events"] if modules else [],
                     key=lambda e: e["start_ns"])
    programs = {}
    for m in modules:
        ident = re.search(r"\((\d+)\)$", m["name"])
        if ident:
            programs[_ident(ident.group(1))] = program_of(m["name"])
    if not ops:
        return out

    # busy seconds by scope, by self time
    scopes, kernels, top = {}, {}, {}
    for e, self_ns in tr.self_times(ops):
        key = scope_of(e, programs)
        scopes[key] = scopes.get(key, 0.0) + self_ns / 1e9
        label = e["name"].split(" = ")[0].lstrip("%")
        top.setdefault(key, {})
        top[key][label] = top[key].get(label, 0.0) + self_ns / 1e9
        kern = kernel_of(e)
        if kern is None:
            continue
        if kern:
            kernels[kern] = kernels.get(kern, 0.0) + e["dur_ns"] / 1e9
        else:
            out["unnamed_custom_calls"] += 1
    busy = merge((e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops)
    busy_s = sum(e - s for s, e in busy) / 1e9
    out["busy_s"] = busy_s
    out["scopes_s"] = scopes
    out["kernels_s"] = kernels
    out["top_ops_s"] = {k: sorted(v.items(), key=lambda kv: -kv[1])[:5]
                        for k, v in top.items()}

    # a step program's device time: first to last op of one execution
    starts = sorted(e["start_ns"] for e in ops)
    ends = sorted(e["start_ns"] + e["dur_ns"] for e in ops)
    by_program = {}
    for m in modules:
        lo, hi = m["start_ns"], m["start_ns"] + m["dur_ns"]
        i = bisect.bisect_left(starts, lo - 1)     # first op inside
        j = bisect.bisect_right(ends, hi + 1)      # one past the last
        dur = m["dur_ns"]
        if i < len(starts) and j and starts[i] < ends[j - 1]:
            dur = ends[j - 1] - starts[i]
        by_program.setdefault(program_of(m["name"]), []).append(dur / 1e6)
    out["programs"] = {
        name: {"executions": len(d), "median_ms": median(d),
               "total_s": sum(d) / 1e3}
        for name, d in sorted(by_program.items(),
                              key=lambda kv: -sum(kv[1]))}
    metrics = out["metrics"]
    for kind in ("decode", "mixed"):
        durs = [d for name, ds in by_program.items()
                if step_kind(name) == kind for d in ds]
        if durs:
            metrics[f"{kind}_step_device_ms"] = median(durs)

    if any(k in SCOPES for k in scopes) and busy_s > 0:
        def share(*keys):
            return 100.0 * sum(scopes.get(k, 0.0) for k in keys) / busy_s
        metrics["dev_share_attn_pct"] = share("attn")
        metrics["dev_share_ffn_pct"] = share("ffn")
        # the whole-pool passes: the KV writes, what the layer scan
        # itself does to its operands (slicing the stacked pool in,
        # stacking it out), and the copies of its carried buffers
        metrics["dev_share_kv_pct"] = share("kv", "layers",
                                            "program_copies")
        metrics["dev_share_unscoped_pct"] = share("unscoped")

    # idle device time by the host span it lay under
    spans = {}
    for p in planes:
        if not is_host_plane(p["name"]):
            continue
        for ln in p["lines"]:
            for e in ln["events"]:
                if e["name"].startswith(SPAN_PREFIX) and e["dur_ns"] > 0:
                    spans.setdefault(e["name"][len(SPAN_PREFIX):], []).append(
                        (e["start_ns"], e["start_ns"] + e["dur_ns"]))
    idle = [list(g) for g in tr.gaps_of(busy)]
    idle_s = sum(e - s for s, e in idle) / 1e9
    out["idle_total_s"] = idle_s
    out["window_s"] = (busy[-1][1] - busy[0][0]) / 1e9
    if spans and idle_s > 0:
        covered = []
        for name, iv in sorted(spans.items()):
            m = merge(iv)
            out["idle_s"][name] = overlap_ns(idle, m) / 1e9
            covered += m
        out["idle_s"]["none"] = idle_s - overlap_ns(idle, merge(covered)) / 1e9
        work = merge(iv for name in HOST_WORK for iv in spans.get(name, []))
        metrics["idle_attributed_pct"] = (
            100.0 * overlap_ns(idle, work) / 1e9 / idle_s)
        out["span_events"] = {k: len(v) for k, v in spans.items()}
    return out


def write(result: dict, dst: str) -> None:
    """The full tables to `dst`, one `spans: {...}` line to stderr."""
    with open(dst, "w") as f:
        json.dump(result, f, indent=1)
    brief = {"scopes_s": {k: round(v, 4)
                          for k, v in result["scopes_s"].items()},
             "idle_s": {k: round(v, 4) for k, v in result["idle_s"].items()},
             "kernels_s": {k: round(v, 4)
                           for k, v in result["kernels_s"].items()},
             "unnamed_custom_calls": result["unnamed_custom_calls"]}
    print("spans: " + json.dumps(brief), file=sys.stderr, flush=True)


def main(argv) -> int:
    src, dst = argv[1], argv[2]
    xplane = tr.find_xplane(src)
    if xplane is None:
        print(f"no .xplane.pb under {src}", file=sys.stderr)
        return 1
    write(reduce_spans(read_xspace(xplane)), dst)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
