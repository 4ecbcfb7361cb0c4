"""The readers of the engine thread's clock (layer_metrics/
emit_parts.py, loop_clock.py, idle_names.py) on hand-made step records
in the shape `/api/v1/steps` gives them, and on hand-made planes.
Found by name; nothing here pins a list's end."""

import importlib.util
import json
import os

import pytest

from harness import spec

EMIT = ["emit_us_per_token", "emit_trace_us_per_token",
        "emit_report_us_per_token", "emit_stream_us_per_token",
        "emit_retire_us_per_token", "emit_rows_us_per_token"]
CLOCK = ["loop_uncovered_pct", "host_offcpu_pct", "gc_pause_share_pct",
         "gc_pause_max_ms", "host_pause_max_ms", "host_room_p50_ms"]
IDLE = ["idle_unnamed_pct", "idle_gc_pct"]
MS = 1e6     # nanoseconds


def flown(step=2, tokens=32, loop_s=0.017, wait_s=0.004, emit=0.008,
          parts=None, gc=(0.0, 0, 0.0), **extra):
    """A chained decode record of the change."""
    phases = {"record": 0.0001, "emit": emit, "gate": 0.00005,
              "build": 0.0003, "dispatch": 0.0012,
              "fetch": wait_s}
    rec = {"step": step, "kind": "decode", "compiled": False,
           "chained": True, "tokens": tokens, "rows": tokens,
           "wall_s": loop_s, "ts": 100.0 + step * loop_s, "gap_s": 0.0,
           "fetch_wait_s": wait_s, "late": wait_s < 0.00125,
           "phases": phases, "loop_s": loop_s,
           "offcpu": {k: 0.0 for k in phases} | {"fetch": wait_s * 0.9},
           "gc_s": gc[0], "gc_n": gc[1], "gc_max_s": gc[2],
           "parts": {"dispatch.launch": 0.001, "emit.rows": 0.0016,
                     "emit.trace": 0.0008, "emit.report": 0.0012,
                     "emit.detok": 0.0004, "emit.stream": 0.0036,
                     **(parts or {})}}
    rec.update(extra)
    return rec


def parent_style(n=6):
    """Records of the program before this one: phases, `emit.detok`,
    `fetch_wait_s`, and none of the clock's fields."""
    out = []
    for i in range(n):
        r = flown(step=i + 1, wait_s=0.003 + 0.001 * i)
        for key in ("loop_s", "offcpu", "gc_s", "gc_n", "gc_max_s"):
            del r[key]
        r["phases"] = {k: v for k, v in r["phases"].items()
                       if k not in ("gate", "record")}
        r["parts"] = {"dispatch.launch": 0.001, "emit.detok": 0.0004}
        out.append(r)
    return out


@pytest.fixture(scope="module")
def found():
    return spec.discover_layer_metrics()


@pytest.mark.parametrize("name", EMIT + CLOCK + IDLE)
def test_found_by_name_in_every_cell_and_agrees_with_benchmark_json(
        found, name):
    decl, _read = found[name]
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in doc["per_layer"] if m["name"] == name]
    for key in ("unit", "layer", "moves", "source"):
        assert decl[key] == entry[key], key
    assert entry["moves"] == "out_tok_s"
    assert entry["better"] == ("higher" if name == "host_room_p50_ms"
                               else "lower")
    assert entry["layer"] == ("device" if name in IDLE
                              else "step dispatch")
    assert entry["source"] == (
        "device_trace" if name in IDLE
        else "program_counter" if name.startswith("gc_")
        else "program_span")
    cells = [w["name"] for w in doc["workloads"]]
    assert set(cells[:10]) <= set(entry["workloads"])
    for cell in entry["workloads"]:
        assert name in spec.Cell(cell).names("per_layer")


@pytest.mark.parametrize("name", EMIT + CLOCK)
def test_a_parent_style_run_reports_host_room_alone(found, name, capsys):
    _decl, read = found[name]
    got = read({"steps": parent_style(), "seconds": 48.0})
    if name in CLOCK:
        # `fetch_wait_s` is PR 35's: the one reading both sides have
        assert got == {"host_room_p50_ms": pytest.approx(5.5)}
    else:
        assert got == {}
    assert read({"steps": [], "seconds": 48.0}) == {}
    assert read({}) == {}
    assert "pauses:" not in capsys.readouterr().err


def test_emit_parts_are_window_sums_over_the_tokens(found):
    _decl, read = found["emit_us_per_token"]
    steps = [flown(step=i + 2) for i in range(9)]
    # a record whose emit retired two rows, and a mixed record that
    # emitted one first token; a sum takes them as they come
    steps.append(flown(step=11, emit=0.0095,
                       parts={"emit.retire": 0.0012}))
    steps.append(flown(step=12, tokens=1, emit=0.0005, kind="mixed",
                       parts={"emit.rows": 0.0001, "emit.trace": 0.0001,
                              "emit.report": 0.0001, "emit.detok": 0.0,
                              "emit.stream": 0.0001}))
    tokens = 10 * 32 + 1
    got = read({"steps": steps, "seconds": 48.0})
    span = 9 * 0.008 + 0.0095 + 0.0005
    assert got["emit_us_per_token"] == pytest.approx(1e6 * span / tokens)
    assert got["emit_stream_us_per_token"] == pytest.approx(
        1e6 * (10 * 0.0036 + 0.0001) / tokens)
    assert got["emit_retire_us_per_token"] == pytest.approx(
        1e6 * 0.0012 / tokens)
    assert got["emit_rows_us_per_token"] == pytest.approx(
        1e6 * (10 * 0.0016 + 0.0001) / tokens)
    # no name for what the parts leave of the span (PR 55: in a real
    # record they leave nothing, `test_retired_metrics.py`)
    assert set(got) == set(EMIT)
    # where most records lack a part a median reads 0.0; the sum does not
    assert got["emit_retire_us_per_token"] > 0


def test_the_loop_closes_by_sums_and_a_window_of_chained_records_reads(
        found):
    _decl, read = found["loop_uncovered_pct"]
    steps = [flown(step=i + 2, wait_s=0.002 + 0.001 * i) for i in range(5)]
    assert all(s["chained"] for s in steps)
    got = read({"steps": steps, "seconds": 48.0})
    spans = sum(sum(s["phases"].values()) for s in steps)
    assert got["loop_uncovered_pct"] == pytest.approx(
        100.0 * (5 * 0.017 - spans) / (5 * 0.017))
    assert got["host_room_p50_ms"] == pytest.approx(4.0)
    assert got["host_offcpu_pct"] == 0.0
    assert got["gc_pause_share_pct"] == 0.0 and got["gc_pause_max_ms"] == 0.0
    # the longest host interval: the loop less the wait for the device
    assert got["host_pause_max_ms"] == pytest.approx(
        1000.0 * (0.017 - 0.002))
    # a compiled step is no sample
    slow = flown(step=9, loop_s=3.0, compiled=True)
    assert read({"steps": steps + [slow], "seconds": 48.0}) == got


def test_offcpu_counts_the_host_work_spans_alone(found):
    _decl, read = found["host_offcpu_pct"]
    a, b = flown(step=2), flown(step=3)
    a["offcpu"].update(emit=0.002, gate=0.00001)
    b["offcpu"].update(record=0.00005, dispatch=0.0011)  # the runtime's
    work = 2 * (0.0001 + 0.008 + 0.00005 + 0.0003)
    got = read({"steps": [a, b], "seconds": 48.0})
    assert got["host_offcpu_pct"] == pytest.approx(
        100.0 * (0.002 + 0.00001 + 0.00005) / work)


def test_collections_are_a_share_of_the_window_and_a_longest_one(found):
    _decl, read = found["gc_pause_share_pct"]
    steps = [flown(step=2), flown(step=3, gc=(0.0004, 3, 0.0002)),
             flown(step=4, loop_s=0.125, emit=0.116,
                   gc=(0.108, 1, 0.108))]
    got = read({"steps": steps, "seconds": 24.0})
    assert got["gc_pause_share_pct"] == pytest.approx(
        100.0 * 0.1084 / 24.0)
    assert got["gc_pause_max_ms"] == pytest.approx(108.0)
    assert got["host_pause_max_ms"] == pytest.approx(121.0)


def test_the_longest_host_intervals_come_decomposed(found, capsys,
                                                    tmp_path, monkeypatch):
    decl, read = found["host_pause_max_ms"]
    steps = [flown(step=i + 2, loop_s=0.017 + 0.001 * i) for i in range(8)]
    stall = flown(step=20, loop_s=0.140, emit=0.130,
                  gc=(0.110, 1, 0.110), chain_break="row_finished")
    stall["offcpu"]["emit"] = 0.110          # on another thread
    steps.insert(3, stall)
    monkeypatch.setattr(spec, "BENCH_DIR", str(tmp_path))

    class Cell:
        name = "a.cell"

    os.makedirs(tmp_path / ".run" / "a.cell")
    got = read({"steps": steps, "seconds": 48.0, "cell": Cell()})
    assert got["host_pause_max_ms"] == pytest.approx(136.0)
    doc = json.load(open(tmp_path / ".run" / "a.cell" / "host_pauses.json"))
    longest = doc["longest"]
    assert len(longest) == 5
    assert [p["step"] for p in longest[:3]] == [20, 9, 8]
    first = longest[0]
    assert first["chain_break"] == "row_finished" and first["chained"]
    assert first["gc_s"] == 0.110 and first["offcpu"]["emit"] == 0.110
    # the decomposition sums to the interval: the host's phases, and
    # what no span held
    host = sum(v for k, v in first["phases"].items() if k != "fetch")
    assert host + first["unnamed_s"] == pytest.approx(first["host_s"])
    assert first["host_s"] == pytest.approx(0.136)
    # every interval of 30 ms or more is listed by step, the five
    # longest or not
    assert [p["step"] for p in doc["long"]] == [20]
    # and what an ordinary record holds outside every span, by kind
    table = doc["unnamed_us"]["decode"]
    assert table["records"] == 9 and table["offcpu_mean"] == 0.0
    assert table["p50"] == pytest.approx(
        1e6 * (0.020 - sum(steps[0]["phases"].values())))
    line = [ln for ln in capsys.readouterr().err.splitlines()
            if ln.startswith("pauses: ")]
    assert len(line) == 1
    assert json.loads(line[0][8:])["longest"][0]["step"] == 20


def idle_module():
    path = os.path.join(spec.BENCH_DIR, "layer_metrics", "idle_names.py")
    s = importlib.util.spec_from_file_location("layer_metric_idle", path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def op(start_ms, dur_ms):
    return {"name": "%fusion = f32[] fusion()", "start_ns": start_ms * MS,
            "dur_ns": dur_ms * MS, "stats": {}}


def span(name, start_ms, dur_ms, step=None):
    return {"name": "cake/" + name, "start_ns": start_ms * MS,
            "dur_ns": dur_ms * MS,
            "stats": {} if step is None else {"step": step}}


def planes(host_events, other_thread=()):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                op(0, 10), op(12, 10), op(62, 10), op(74, 10)]},
            {"name": "XLA Modules", "events": []}]},
        {"name": "/host:CPU", "lines": [
            {"name": "engine", "events": list(host_events)},
            {"name": "handler", "events": list(other_thread)}]}]


@pytest.mark.parametrize("case", ["named", "gc", "parent", "no_device"])
def test_idle_time_by_the_ends_of_the_table(case):
    mod = idle_module()
    # idle: 10-12, 22-62, 72-74 = 44 ms
    events = [span("emit", 10, 1.5, 7), span("gate", 21.5, 1, 7),
              span("build", 23, 2, 7), span("record", 60, 3, 7)]
    if case == "parent":
        events = [e for e in events
                  if e["name"] not in ("cake/gate", "cake/record")]
    gc = [span("gc", 26, 30)] if case == "gc" else []
    ps = planes(events, gc)
    if case == "no_device":
        ps = ps[1:]
    idle, spans = mod.idle_and_spans(ps)
    got = mod.shares(idle, spans)
    if case in ("parent", "no_device"):
        assert got == {}
        return
    named = 1.5 + 0.5 + 2 + 2          # emit, gate, build, record
    if case == "gc":
        named += 30
        assert got["idle_gc_pct"] == pytest.approx(100.0 * 30 / 44)
    else:
        assert got["idle_gc_pct"] == 0.0
    assert got["idle_unnamed_pct"] == pytest.approx(
        100.0 * (44 - named) / 44)
    # the one gap of 30 ms or more, with what it lay under
    (gap,) = mod.long_gaps(idle, spans)
    assert gap["ms"] == pytest.approx(40.0)
    assert gap["under"]["build"] == [7] and gap["under"]["record"] == [7]
    assert ("gc" in gap["under"]) == (case == "gc")
    assert gap["before"] == ["emit", 7] and gap["after"] is None


def test_idle_names_read_nothing_without_a_capture(found, tmp_path):
    for name in IDLE:
        _decl, read = found[name]
        assert read({}) == {}
        assert read({"trace": None}) == {}
        assert read({"trace": {"xplane": str(tmp_path / "gone.pb")}}) == {}
