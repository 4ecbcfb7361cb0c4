"""`harness/trace_spans.py` on hand-made planes and on a hand-encoded
`.xplane.pb`; the two metric files that read the step phases and the
spans, found by name."""

import json
import os
import struct

import pytest

from harness import spec, trace_spans as ts

MS = 1e6     # nanoseconds
STEP = "jit_decode_step_ragged_paged(77)"


def op(name, start_ms, dur_ms, tf_op=None, category=None, program=77):
    stats = {"program_id": program}
    if tf_op:
        stats["tf_op"] = tf_op
    if category:
        stats["hlo_category"] = category
    return {"name": name, "start_ns": start_ms * MS, "dur_ns": dur_ms * MS,
            "stats": stats}


def span(name, start_ms, dur_ms, step=1):
    return {"name": "cake/" + name, "start_ns": start_ms * MS,
            "dur_ns": dur_ms * MS, "stats": {"step": step}}


def planes(ops, modules, spans):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [op("%x = f32[] add()", 0, 999)]}]},
        {"name": "/host:CPU", "lines": [{"name": "", "events": spans}]},
    ]


SCAN = "jit(decode_step_ragged_paged)/layers/while/body/"


def two_steps():
    """Two executions of a decode step, 10 ms each, 4 ms apart, and
    2 ms of eager glue in the gap."""
    ops, modules = [], []
    for t0 in (0.0, 14.0):
        modules.append({"name": STEP, "start_ns": t0 * MS,
                        "dur_ns": 10 * MS, "stats": {}})
        ops += [
            op("%copy.69 = bf16[32,256] copy(%gte)", t0, 1.0,
               category="data formatting"),
            # the while holds its body's ops: 8 ms long, 0.5 ms its own
            op("%while.5 = (s32[]) while(%t)", t0 + 1, 8.0),
            op("%slice_fusion = bf16[256] fusion(%p)", t0 + 1, 1.5,
               tf_op=SCAN + "squeeze:"),
            op("%fusion.1 = bf16[16] fusion(%p)", t0 + 2.5, 1.0,
               tf_op=SCAN + "closed_call/qkv/dot_general:"),
            op("%scatter = bf16[256] scatter(%p)", t0 + 3.5, 0.5,
               tf_op=SCAN + "closed_call/attn/kv/scatter:"),
            op("%cake_decode_attn.9 = bf16[16,1,32,128] custom-call(%q), "
               'custom_call_target="tpu_custom_call"', t0 + 4, 2.0,
               tf_op=SCAN + "closed_call/attn/cake_decode_attn/pallas_call:"),
            op("%fusion.2 = bf16[16] fusion(%p)", t0 + 6, 2.5,
               tf_op=SCAN + "closed_call/ffn/dot_general:"),
            op("%fusion.90 = f32[16] fusion(%p)", t0 + 9, 1.0,
               tf_op="jit(decode_step_ragged_paged)/head/dot_general:"),
        ]
    ops.append(op("%add = s32[16] add(%a)", 11.0, 2.0,
                  tf_op="jit(add)/add:", program=5))
    modules.append({"name": "jit_add(5)", "start_ns": 11 * MS,
                    "dur_ns": 2 * MS, "stats": {}})
    return ops, modules


def test_self_time_by_scope_and_the_shares():
    ops, modules = two_steps()
    r = ts.reduce_spans(planes(ops, modules, []))
    s = r["scopes_s"]
    assert s["layers"] == pytest.approx(2 * 1.5e-3)
    assert s["qkv"] == pytest.approx(2 * 1.0e-3)
    assert s["kv"] == pytest.approx(2 * 0.5e-3)     # innermost scope wins
    assert s["attn"] == pytest.approx(2 * 2.0e-3)
    assert s["ffn"] == pytest.approx(2 * 2.5e-3)
    assert s["head"] == pytest.approx(2 * 1.0e-3)
    # compiler-inserted data movement inside a step program
    assert s["program_copies"] == pytest.approx(2 * 1.0e-3)
    # the while's own half millisecond and the eager add
    assert s["unscoped"] == pytest.approx(2 * 0.5e-3 + 2.0e-3)
    assert r["busy_s"] == pytest.approx(22e-3)
    assert sum(s.values()) == pytest.approx(r["busy_s"])
    m = r["metrics"]
    assert m["dev_share_attn_pct"] == pytest.approx(100 * 4 / 22)
    assert m["dev_share_ffn_pct"] == pytest.approx(100 * 5 / 22)
    assert m["dev_share_kv_pct"] == pytest.approx(100 * (1 + 3 + 2) / 22)
    assert m["dev_share_unscoped_pct"] == pytest.approx(100 * 3 / 22)
    # device 1 is not device 0
    assert r["window_s"] == pytest.approx(24e-3)


def test_a_steps_device_time_is_first_to_last_op_of_one_execution():
    ops, modules = two_steps()
    # a third execution whose module event outlasts its ops (waiting on
    # another chip): the ops bound the step
    modules.append({"name": STEP, "start_ns": 30 * MS, "dur_ns": 20 * MS,
                    "stats": {}})
    ops.append(op("%fusion.2 = bf16[16] fusion(%p)", 31, 12,
                  tf_op=SCAN + "closed_call/ffn/dot_general:"))
    r = ts.reduce_spans(planes(ops, modules, []))
    prog = r["programs"]["jit_decode_step_ragged_paged"]
    assert prog["executions"] == 3
    assert r["metrics"]["decode_step_device_ms"] == pytest.approx(10.0)
    assert prog["median_ms"] == pytest.approx(10.0)
    assert prog["total_s"] == pytest.approx(32e-3)
    assert "mixed_step_device_ms" not in r["metrics"]
    assert r["programs"]["jit_add"]["median_ms"] == pytest.approx(2.0)


def test_idle_time_by_the_host_span_it_lay_under():
    ops, modules = two_steps()
    # idle: 10-11 and 13-14 (the eager add runs 11-13)
    spans = [span("fetch", 5.0, 5.2, step=1),      # ends inside the gap
             span("emit", 10.2, 0.6, step=2),
             span("build", 13.0, 0.9, step=2),
             span("dispatch", 13.9, 0.3, step=2),
             span("wait", 40.0, 5.0, step=3)]
    r = ts.reduce_spans(planes(ops, modules, spans))
    assert r["idle_total_s"] == pytest.approx(2e-3)
    idle = r["idle_s"]
    assert idle["fetch"] == pytest.approx(0.2e-3)
    assert idle["emit"] == pytest.approx(0.6e-3)
    assert idle["build"] == pytest.approx(0.9e-3)
    assert idle["dispatch"] == pytest.approx(0.1e-3)
    assert idle["wait"] == 0.0
    assert idle["none"] == pytest.approx(0.2e-3)
    # host WORK explains emit + build; fetch and dispatch are the
    # device's side of the hand-over
    assert r["metrics"]["idle_attributed_pct"] == pytest.approx(
        100 * 1.5 / 2.0)
    assert r["span_events"]["emit"] == 1


def test_a_program_without_scopes_or_spans_reports_no_share():
    ops, modules = two_steps()
    for e in ops:
        e["stats"].pop("tf_op", None)
    r = ts.reduce_spans(planes(ops, modules, []))
    assert set(r["metrics"]) == {"decode_step_device_ms"}
    assert ts.reduce_spans([])["metrics"] == {}


def test_kernels_are_matched_by_name():
    ops, modules = two_steps()
    ops.append(op("%custom-call.3 = bf16[16,8,4,128] custom-call(%q), "
                  'custom_call_target="tpu_custom_call"', 26, 1.0))
    # XLA's own custom calls are no kernels
    ops.append(op("%custom-call.12 = bf16[32,256] custom-call(), "
                  'custom_call_target="AllocateBuffer"', 27, 0.0))
    r = ts.reduce_spans(planes(ops, modules, []))
    assert r["kernels_s"] == {"cake_decode_attn": pytest.approx(4e-3)}
    assert r["unnamed_custom_calls"] == 1
    assert ts.kernel_of(op("%fusion.1 = f32[] fusion(%p)", 0, 1)) is None
    assert ts.kernel_of(op(
        "%cake_mixed_attn = bf16[16,128,32,128] custom-call(%q), "
        'custom_call_target="tpu_custom_call"', 0, 1)) == "cake_mixed_attn"


# -- the protobuf reader, against bytes encoded by hand -----------------------


def varint(x):
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def field(num, wire, payload):
    if wire == 0:
        return varint(num << 3) + varint(payload)
    return varint(num << 3 | 2) + varint(len(payload)) + payload


def msg(*fields):
    return b"".join(fields)


def test_read_xspace_merges_metadata_stats_into_events(tmp_path):
    stat_meta = lambda i, name: field(5, 2, msg(                 # noqa: E731
        field(1, 0, i), field(2, 2, msg(field(1, 0, i),
                                        field(2, 2, name.encode())))))
    tf_op = msg(field(1, 0, 1), field(5, 2, b"jit(f)/layers/attn/dot:"))
    program = msg(field(1, 0, 2), field(3, 0, 2**64 - 5))
    ev_meta = field(4, 2, msg(field(1, 0, 9), field(2, 2, msg(
        field(1, 0, 9), field(2, 2, b"%fusion.1 = f32[] fusion()"),
        field(5, 2, tf_op), field(5, 2, program)))))
    event = msg(field(1, 0, 9), field(2, 0, 3_000_000), field(3, 0, 500_000),
                field(4, 2, msg(field(1, 0, 3), field(4, 0, 12))))
    line = field(3, 2, msg(field(2, 2, b"XLA Ops"), field(3, 0, 1000),
                           field(4, 2, event)))
    device = msg(field(2, 2, b"/device:TPU:0"), line, ev_meta,
                 stat_meta(1, "tf_op"), stat_meta(2, "program_id"),
                 stat_meta(3, "run_id"))
    cake_meta = field(4, 2, msg(field(1, 0, 1), field(2, 2, msg(
        field(1, 0, 1), field(2, 2, b"cake/emit")))))
    other_meta = field(4, 2, msg(field(1, 0, 2), field(2, 2, msg(
        field(1, 0, 2), field(2, 2, b"PjitFunction(f)")))))
    host_line = field(3, 2, msg(
        field(2, 2, b"engine"), field(3, 0, 2000),
        field(4, 2, msg(field(1, 0, 1), field(2, 0, 1000), field(3, 0, 7000),
                        field(4, 2, msg(field(1, 0, 1), field(4, 0, 41))))),
        field(4, 2, msg(field(1, 0, 2), field(2, 0, 0), field(3, 0, 10)))))
    host = msg(field(2, 2, b"/host:CPU"), host_line, cake_meta, other_meta,
               stat_meta(1, "step"))
    skipped = msg(field(2, 2, b"/host:metadata"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(msg(field(1, 2, device), field(1, 2, host),
                         field(1, 2, skipped)))
    got = ts.read_xspace(str(path))
    assert [p["name"] for p in got] == ["/device:TPU:0", "/host:CPU"]
    (ev,) = got[0]["lines"][0]["events"]
    assert ev["name"].startswith("%fusion.1")
    assert ev["start_ns"] == pytest.approx(1000 + 3000.0)
    assert ev["dur_ns"] == pytest.approx(500.0)
    assert ev["stats"] == {"tf_op": "jit(f)/layers/attn/dot:",
                           "program_id": 2**64 - 5, "run_id": 12}
    assert ts.scope_of(ev, {}) == "attn"
    # of the host plane only the engine's spans are kept
    (sp,) = got[1]["lines"][0]["events"]
    assert sp["name"] == "cake/emit" and sp["stats"] == {"step": 41}
    assert ts.tr.find_xplane(str(tmp_path)) == str(path)


# -- the metric files, found by name ------------------------------------------


NEW = {"host_emit_p50_ms", "host_build_p50_ms",
       "decode_step_device_ms", "mixed_step_device_ms",
       "dev_share_attn_pct", "dev_share_ffn_pct", "dev_share_kv_pct",
       "dev_share_unscoped_pct", "idle_attributed_pct"}
# retired at PR 55: three medians that read 0.0 since the median step is
# chained (PR 32), and the complement of `loop_uncovered_pct`
RETIRED = {"step_gap_p50_ms", "host_schedule_p50_ms", "host_sample_p50_ms",
           "loop_covered_pct"}
# `mixed_step_device_ms` moves `ttft_mean_ms`: the chat cells' alone (the
# others read it as `mixed_step_device_ms.tok`); a capture of these two
# cells holds mixed dispatches and no decode step
CHAT = {"mistral7b.chat-closed", "olmoe7b.chat-closed"}
NO_DECODE_IN_CAPTURE = {"dots3.longshort-closed", "dsv2.code-closed"}


def test_the_new_metric_files_are_found_and_belong_to_their_cells():
    found = spec.discover_layer_metrics()
    assert NEW <= set(found) and not RETIRED & set(found)
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert not RETIRED & {m["name"] for m in doc["per_layer"]}
    for w in doc["workloads"]:
        names = set(spec.Cell(w["name"]).names("per_layer"))
        want = set(NEW)
        if w["name"] not in CHAT:
            want.discard("mixed_step_device_ms")
        if w["name"] in NO_DECODE_IN_CAPTURE:
            want.discard("decode_step_device_ms")
        assert names & NEW == want, w["name"]


def step(kind, gap_ms, compiled=False, ts=0.0, **phases_ms):
    rec = {"kind": kind, "compiled": compiled, "ts": ts,
           "phases": {k: v / 1e3 for k, v in phases_ms.items()}}
    if gap_ms is not None:
        rec["gap_s"] = gap_ms / 1e3
    return rec


def test_step_phases_are_medians_over_the_windows_steady_steps():
    _decl, read = spec.discover_layer_metrics()["host_emit_p50_ms"]
    steps = [    # newest first, as /api/v1/steps gives them
        step("decode", 900.0, compiled=True, ts=11.2, emit=1, dispatch=800),
        step("mixed", None, ts=10.35, admin=2, schedule=9, build=4,
             dispatch=1, sample=5, fetch=200),
        step("decode", 10.0, ts=10.12, emit=7, admin=1, schedule=1,
             build=2, dispatch=1, sample=3, fetch=42),
        step("decode", 12.0, ts=10.06, emit=5, admin=1, schedule=2,
             build=3, dispatch=1, sample=4, fetch=40),
    ]
    got = read({"steps": steps, "seconds": 2.0})
    # the compiled step is left out: 5, 7, absent=0 and 3, 2, 4
    assert got == {"host_emit_p50_ms": pytest.approx(5.0),
                   "host_build_p50_ms": pytest.approx(3.0)}
    # a program without the spans (the parent commit) reports nothing
    assert read({"steps": [{"kind": "decode", "compiled": False, "ts": 1.0,
                            "wall_s": 0.06}], "seconds": 2.0}) == {}


def test_step_device_reads_nothing_without_a_capture(tmp_path):
    _decl, read = spec.discover_layer_metrics()["decode_step_device_ms"]
    assert read({"trace": None}) == {}
    assert read({"trace": {"xplane": str(tmp_path / "gone.pb")}}) == {}
