"""`layer_metrics/window_steps.py`: the three plain readings of a cell
judged by tokens, against the seven per-cell formulas they replaced at
PR 55 (computed by hand here, since that code went), on the records a
closed loop takes from `fake_server.py`, on hand-made step records and
on hand-made planes; and the one read of a capture the readers share."""

import os
import statistics
import time

import pytest

import fake_server
from harness import readers, spec, trace_spans as ts, traffic as tfc

NAMES = ("mixed_step_ms.tok", "mixed_step_device_ms.tok", "ttft_p50_ms.tok")
MS = 1e6     # nanoseconds
# what each of the three took the place of, by cell (PERF.md section 3)
OLD = {
    "mixed_step_ms.tok": {
        "glm52.longdoc-closed": "mixed_step_ms.longdoc",
        "nemotron3s.agent-closed": "mixed_step_ms.agent",
        "zaya1.reason-closed": "mixed_step_ms.reason",
        "dots3.longshort-closed": "mixed_step_ms.longshort",
        "dsv2.code-closed": "mixed_step_ms.code",
        "ling3.longreply-closed": "mixed_step_ms.longreply",
        "kexaone.longreply-closed": "mixed_step_ms.kexaone"},
    "mixed_step_device_ms.tok": {
        "glm52.longdoc-closed": "mixed_step_device_ms.longdoc",
        "nemotron3s.agent-closed": "mixed_step_device_ms.agent",
        "dots3.longshort-closed": "mixed_step_device_ms.longshort",
        "dsv2.code-closed": "mixed_step_device_ms.code",
        "ling3.longreply-closed": "mixed_step_device_ms.longreply",
        "kexaone.longreply-closed": "mixed_step_device_ms.kexaone"},
    "ttft_p50_ms.tok": {
        "qwen32b.chat-closed-4chip": "ttft_p50_ms.dense",
        "glm52.longdoc-closed": "ttft_p50_ms.longdoc",
        "nemotron3s.agent-closed": "ttft_p50_ms.agent",
        "zaya1.reason-closed": "ttft_p50_ms.reason",
        "dsv2.code-closed": "ttft_p50_ms.code",
        "ling3.longreply-closed": "ttft_p50_ms.longreply",
        "kexaone.longreply-closed": "ttft_p50_ms.kexaone"},
}
TWO_CLASSES = {
    "loop": "closed", "clients": 3, "ramp_s": 0,
    "prompt_classes": [{"name": "short", "lo": 4, "hi": 8, "weight": 0.75},
                       {"name": "long", "lo": 24, "hi": 32, "weight": 0.25}],
    "multiset": [{"class": "short", "out": 4, "n": 3},
                 {"class": "long", "out": 6, "n": 1}]}


@pytest.fixture(scope="module")
def found():
    return spec.discover_layer_metrics()


@pytest.fixture(scope="module")
def served():
    """(records, t0, t1) of a closed loop against the stand-in server."""
    httpd, _state = fake_server.start(0.04, 0.01)
    try:
        loop = tfc.ClosedLoop(httpd.server_address[1],
                              tfc.Mix(TWO_CLASSES, 5, 64), clients=3)
        loop.start()
        time.sleep(0.15)
        t0 = time.monotonic()
        time.sleep(1.0)
        t1 = time.monotonic()
        loop.stop()
    finally:
        httpd.shutdown()
    return sorted(loop.records, key=lambda r: r["t_send"]), t0, t1


def step(kind, wall_ms, compiled=False):
    return {"kind": kind, "compiled": compiled, "wall_s": wall_ms / 1e3,
            "ts": 1.0}


def module(name, start_ms, dur_ms):
    return {"name": name, "start_ns": start_ms * MS, "dur_ns": dur_ms * MS,
            "stats": {}}


def op(start_ms, dur_ms):
    return {"name": "%fusion.1 = bf16[16] fusion(%p)",
            "start_ns": start_ms * MS, "dur_ns": dur_ms * MS, "stats": {}}


def capture():
    """Three mixed dispatches whose first-to-last op spans 40, 44 and
    60 ms inside modules of 41, 45 and 61, and two decode steps."""
    modules, ops = [], []
    for t0, dur in ((0, 40), (100, 44), (200, 60)):
        modules.append(module("jit_mixed_step_sampled(11)", t0, dur + 1))
        ops += [op(t0 + 0.5, 10), op(t0 + 0.5 + dur - 5, 5)]
    for t0 in (300, 320):
        modules.append(module("jit_decode_step_sampled(12)", t0, 10))
        ops.append(op(t0, 9))
    return [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}]


def test_declared_once_with_the_lists_of_the_names_they_replaced(found):
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in doc["per_layer"]}
    cells = [w["name"] for w in doc["workloads"]]
    for name in NAMES:
        decl, _read = found[name]
        m = entries[name]
        for key in ("unit", "layer", "moves", "source"):
            assert decl[key] == m[key], (name, key)
        assert (m["unit"], m["better"], m["moves"]) == ("ms", "lower",
                                                        "out_tok_s")
        # the cells the old names listed, in the order of `workloads`; a
        # later cell appends itself
        want = sorted(OLD[name], key=cells.index)
        assert m["workloads"][:len(want)] == want
        # the twenty are gone, from the file and from every reader
        for old in OLD[name].values():
            assert old not in entries and old not in found
    assert {n: entries[n]["layer"] for n in NAMES} == {
        "mixed_step_ms.tok": "step dispatch",
        "mixed_step_device_ms.tok": "step programs",
        "ttft_p50_ms.tok": "scheduler and page allocator"}
    assert {n: entries[n]["source"] for n in NAMES} == {
        "mixed_step_ms.tok": "program_span",
        "mixed_step_device_ms.tok": "device_trace",
        "ttft_p50_ms.tok": "host_clock"}
    assert sum(len(v) for v in OLD.values()) == 20
    # the chat cells keep the names that move `ttft_mean_ms`
    for name in ("mistral7b.chat-closed", "olmoe7b.chat-closed"):
        layers = spec.Cell(name).names("per_layer")
        assert not set(NAMES) & set(layers)
        assert {"mixed_step_ms", "mixed_step_device_ms"} <= set(layers)
    # ZAYA's capture can fall inside one decode stretch: no device name
    assert "zaya1.reason-closed" not in entries[
        "mixed_step_device_ms.tok"]["workloads"]
    assert not os.path.exists(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                           "dense_ttft.py"))


def test_the_new_reader_gives_what_the_seven_old_formulas_gave(found,
                                                               served):
    _decl, read = found["ttft_p50_ms.tok"]
    records, t0, t1 = served
    assert {r["class"] for r in records} == {"short", "long"}
    assert not any(r["failed"] for r in records)
    steps = [step("mixed", 52.0), step("decode", 14.0),
             step("mixed", 48.0), step("mixed", 900.0, compiled=True),
             step("mixed", 45.0), step("decode", 13.0)]
    run = {"records": records, "t0": t0, "t1": t1, "steps": steps,
           "trace": {"xplane": "kept-in-memory"},
           readers.PLANES_KEY: capture()}
    got = read(run)
    # 1. `median_wall_ms(run, "mixed")`, the formula of mixed_step_ms.
    #    longdoc, .agent, .reason, .longshort, .code, .longreply and
    #    .kexaone: the median wall of the mixed records that did not
    #    compile, 45, 48 and 52 ms
    assert got["mixed_step_ms.tok"] == pytest.approx(48.0)
    # 2. `reduce_spans(planes)["metrics"]["mixed_step_device_ms"]`, the
    #    formula of the six mixed_step_device_ms.*: the median over the
    #    mixed program's executions of first to last op, 40, 44, 60
    assert got["mixed_step_device_ms.tok"] == pytest.approx(44.0)
    assert got["mixed_step_device_ms.tok"] == ts.reduce_spans(
        capture())["metrics"]["mixed_step_device_ms"]
    # 3. the plain median of `ttft_samples` over all classes, the
    #    formula of ttft_p50_ms.dense and the six others: requests sent
    #    inside the window whose first token arrived inside it
    by_hand = [r["token_t"][0] - r["t_send"] for r in records
               if r["token_t"] and t0 <= r["t_send"] < t1
               and r["token_t"][0] < t1]
    assert len(by_hand) >= 10
    assert got["ttft_p50_ms.tok"] == pytest.approx(
        1000.0 * statistics.median(by_hand))
    assert got["ttft_p50_ms.tok"] == pytest.approx(40.0, abs=25.0)
    # a request sent before the window, and one whose first token came
    # after it, count in neither
    assert len(by_hand) < sum(bool(r["token_t"]) for r in records)
    # through the harness, in a cell that lists all three
    cell = spec.Cell("ling3.longreply-closed")
    line = spec.read_layer_metrics(cell, dict(
        run, cell=None, metrics_0={}, metrics_1={}, metrics_2={},
        model_config=cell.model_config, server_args={}, all_steps=steps,
        device={"kind": "TPU v5 lite"}), found)
    assert {n: line[n]["value"] for n in NAMES} == {n: got[n] for n in NAMES}
    assert all(line[n]["unit"] == "ms" for n in NAMES)


def test_nothing_to_read_yields_nothing(found):
    _decl, read = found["mixed_step_ms.tok"]
    empty = {"records": [], "t0": 0.0, "t1": 48.0, "steps": [],
             "trace": None}
    assert {k: v for k, v in read(empty).items() if v is not None} == {}
    # an untraced run: the host's two, nothing of the device
    def run():     # anew each time: a run dict keeps what was read
        return {"records": [{"class": "a", "t_send": 1.0, "token_t": [1.25],
                             "failed": False}],
                "t0": 0.0, "t1": 48.0, "steps": [step("mixed", 30.0)],
                "trace": None}
    assert read(run()) == {"mixed_step_ms.tok": pytest.approx(30.0),
                           "ttft_p50_ms.tok": pytest.approx(250.0)}
    # a capture with no mixed dispatch in it (ZAYA's, inside one decode
    # stretch): no device reading, and never a 0
    decode_only = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules",
         "events": [module("jit_decode_step_sampled(12)", 0, 10)]},
        {"name": "XLA Ops", "events": [op(0, 9)]}]}]
    got = read(dict(run(), trace={"xplane": "kept-in-memory"},
                    **{readers.PLANES_KEY: decode_only}))
    assert got["mixed_step_device_ms.tok"] is None
    # a capture that is gone
    gone = dict(run(), trace={"xplane": "/nonexistent.xplane.pb"})
    assert "mixed_step_device_ms.tok" not in read(gone)


def test_the_capture_is_read_once_and_reduced_once(found, tmp_path,
                                                   monkeypatch):
    """Every reader that looks at the capture takes `readers.planes`,
    and the three that want `reduce_spans` take the one reduction
    (`step_device.py`, `ssm.py`, `window_steps.py`): before PR 55 a
    traced run of Ling's cell read the `.xplane.pb` seven times in this
    process and reduced it twice."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    reads, reductions = [], []
    monkeypatch.setattr(ts, "read_xspace",
                        lambda p: reads.append(p) or capture())
    reduce_spans = ts.reduce_spans
    monkeypatch.setattr(ts, "reduce_spans",
                        lambda p: reductions.append(1) or reduce_spans(p))
    monkeypatch.setattr(spec, "BENCH_DIR", str(tmp_path))
    for name in ("ling3.longreply-closed", "nemotron3s.agent-closed",
                 "kexaone.longreply-closed", "dots3.longshort-closed"):
        del reads[:], reductions[:]
        cell = spec.Cell(name)
        os.makedirs(tmp_path / ".run" / cell.name, exist_ok=True)
        run = {"cell": cell, "model_config": cell.model_config,
               "server_args": dict(cell.cell["server_args"]),
               "device": {"kind": "TPU v5 lite"}, "records": [],
               "turnarounds": [], "t0": 0.0, "t1": 48.0, "wall_0": 0.0,
               "wall_1": 48.0, "seconds": 48.0, "steps": [],
               "all_steps": [], "traces": {}, "health": {},
               "metrics_0": {}, "metrics_1": {}, "metrics_2": {},
               "healthy_s": 1.0, "warmup_s": 1.0, "setup_s": 3.0,
               "trace": {"xplane": str(path), "kernels": [],
                         "busy_s": 1.0, "window_s": 3.0}}
        failed = []
        line = spec.read_layer_metrics(cell, run, found, failed.append)
        assert not failed, failed
        assert reads == [str(path)] and reductions == [1], name
        assert line["mixed_step_device_ms.tok"]["value"] == \
            pytest.approx(44.0)
        assert line["decode_step_device_ms" if "dots3" not in name
                    else "mixed_step_device_ms.tok"]["value"] > 0
        assert os.path.isfile(tmp_path / ".run" / cell.name /
                              "trace_spans.json")
    # an untraced run reads nothing
    del reads[:]
    assert readers.planes({"trace": None}) is None
    assert readers.span_reduction({"trace": None}) is None
    assert reads == []
