"""obs/steps.py: step flight recorder, MFU/cost accounting, recompile
counters, the shared JSONL log, and the /api/v1/steps + /api/v1/profile
endpoint contracts.

The acceptance contract (ISSUE 3): a ~20-step tiny-engine run yields
>= 20 flight records with monotonic step ids, nonzero dispatch times
and a computed MFU in (0, 1]; the recompile counter stays flat across
steady-state decode and increments exactly when a new prompt bucket
forces a retrace; GET /api/v1/steps serves the ring; POST
/api/v1/profile is single-flight (second concurrent capture -> 409).
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

import jax
import jax.numpy as jnp

from cake_tpu.obs import metrics as m
from cake_tpu.obs import steps as obs_steps
from cake_tpu.obs.jsonl import JsonlAppender, read_jsonl
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import ByteTokenizer
from cake_tpu.models.llama.params import init_params
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve.engine import InferenceEngine

TINY = LlamaConfig.tiny(num_hidden_layers=2)


def _make_engine(**kw):
    params = init_params(TINY, jax.random.PRNGKey(0), dtype=jnp.float32)
    return InferenceEngine(
        TINY, params, ByteTokenizer(TINY.vocab_size), max_slots=2,
        max_seq_len=256, sampling=SamplingConfig(temperature=0.0),
        cache_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def engine():
    eng = _make_engine()
    with eng:
        # the acceptance run: prompt (bucket 32) + 24 decode steps
        h = eng.submit(list(range(3, 3 + 16)), max_new_tokens=24)
        assert h.wait(180)
        yield eng


# -- unit: recorder / accountant ---------------------------------------------


def test_flight_recorder_ring_bounds():
    st = obs_steps.StepTelemetry(impl="t", capacity=8,
                                 peak_flops=1e12, hbm_bps=1e11)
    for _ in range(20):
        st.record("decode", rows=1, tokens=1, wall_s=0.001)
    recs = st.dump()
    assert len(recs) == 8                      # ring bound holds
    ids = [r["step"] for r in recs]
    assert ids == list(range(20, 12, -1))      # newest first, monotonic
    assert st.summary()["recorded_steps"] == 20
    assert len(st.dump(limit=3)) == 3


@pytest.mark.parametrize("kind,tiles", [
    ("mixed", {"attn_q_tiles": 30, "attn_q_tiles_window": 128}),
    ("mixed", {}), ("decode", {}),
    ("decode", {"attn_pages": 46, "attn_pages_table": 256}),
    ("mixed", {"window_pages": 270, "window_folds": 75}),
    ("decode", {"mla_decode_pages": 15840, "mla_decode_folds": 4320}),
    ("mixed", {"window_pages": 270, "window_folds": 75,
               "mla_decode_pages": 495, "mla_decode_folds": 135}),
    ("mixed", {"mixed_attn_pages": 149, "mixed_attn_pages_table": 256,
               "mixed_attn_folds": 45})])
def test_attention_tile_counts_ride_the_records_that_carry_them(kind,
                                                                tiles):
    """A mixed step whose rows go through the mixed attention kernel
    records the query tiles it folds and those of the rows' windows, a
    decode step whose rows go through the decode kernel the pages it
    streams and the entries of its page table, a mixed step of a latent
    family the pages its window kernel walks and the softmax updates
    they take, a decode or mixed step of a family whose single-token
    rows walk their latent pages the pages those walk and their softmax
    updates, a mixed step whose family tells the host its call of the
    mixed kernel the pages that walks, its tables' entries and the
    softmax updates; the record of any other step has none of the keys,
    and the eleven /metrics series move with the records that have
    them."""
    series = {"cake_mixed_attn_q_tiles_total": "attn_q_tiles",
              "cake_mixed_attn_q_tiles_window_total": "attn_q_tiles_window",
              "cake_decode_attn_pages_total": "attn_pages",
              "cake_decode_attn_pages_table_total": "attn_pages_table",
              "cake_mla_window_pages_total": "window_pages",
              "cake_mla_window_folds_total": "window_folds",
              "cake_mla_decode_pages_total": "mla_decode_pages",
              "cake_mla_decode_folds_total": "mla_decode_folds",
              "cake_mixed_attn_pages_total": "mixed_attn_pages",
              "cake_mixed_attn_pages_table_total": "mixed_attn_pages_table",
              "cake_mixed_attn_folds_total": "mixed_attn_folds"}

    def read():
        return [sum(float(ln.split()[-1])
                    for ln in m.REGISTRY.render().splitlines()
                    if ln.startswith(name + " ")) for name in series]

    st = obs_steps.StepTelemetry(impl="t", capacity=4)
    before = read()
    rec = st.record(kind, rows=16, tokens=16, wall_s=0.01, **tiles)
    got = {k: v for k, v in rec.to_dict().items()
           if k.startswith(("attn_", "window_", "mixed_attn_",
                            "mla_decode_"))}
    assert got == tiles
    assert [b - a for a, b in zip(before, read())] == [
        tiles.get(key, 0) for key in series.values()]


def test_mfu_math_against_hand_computed_matmul():
    """MFU = cost_analysis FLOPs / (peak x step seconds), with the
    matmul's FLOPs hand-computable: 2*M*K*N."""
    M, K, N = 8, 16, 4
    f = jax.jit(lambda a, b: a @ b)
    a, b = jnp.ones((M, K)), jnp.ones((K, N))
    st = obs_steps.StepTelemetry(impl="t", peak_flops=1e6, hbm_bps=1e6,
                                 key_prefix=("mfu-hand-test",))
    js = st.jit_step("hand_mm", ((M, K, N),),
                     lambda: obs_steps.lower_cost(f, (a, b)))
    assert js.new
    assert js.cost is not None
    assert js.cost.flops == 2 * M * K * N
    wall = 0.004
    rec = st.record("decode", rows=1, tokens=1, wall_s=wall,
                    cost=js.cost, compiled=js.new)
    assert rec.mfu == pytest.approx(
        min(1.0, 2 * M * K * N / (1e6 * wall)))
    assert rec.hbm_util == pytest.approx(
        min(1.0, js.cost.bytes_accessed / (1e6 * wall)))
    assert 0 < rec.mfu <= 1.0
    # same signature again: not a new compile
    assert not st.jit_step("hand_mm", ((M, K, N),),
                           lambda: None).new
    # MFU clamps at 1.0 for an impossibly fast step
    rec2 = st.record("decode", rows=1, tokens=1, wall_s=1e-12,
                     cost=js.cost)
    assert rec2.mfu == 1.0


def test_unknown_device_kind_yields_no_utilization():
    """A kind that is not in the peak table has no peak — no default,
    no CPU stand-in — so its records and aggregates carry no
    mfu/hbm_util at all (this lane's device kind is "cpu")."""
    assert obs_steps.peak_flops_for("cpu") is None
    assert obs_steps.hbm_bps_for("Some Future Chip") is None
    assert obs_steps.peak_flops_for("TPU v5 lite") == 197e12
    assert obs_steps.hbm_bps_for("TPU v5 lite") == 819e9
    st = obs_steps.StepTelemetry(impl="t")     # peaks from the table
    rec = st.record("decode", rows=1, tokens=1, wall_s=0.01,
                    cost=obs_steps.CostInfo(flops=1e6,
                                            bytes_accessed=1e6))
    assert rec.mfu is None and rec.hbm_util is None
    assert "mfu" not in rec.to_dict()
    assert "hbm_util" not in rec.to_dict()
    assert st.utilization() == {}
    assert "mfu" not in st.summary()


def test_recompile_counter_increments_on_new_static_shape():
    ctr = m.REGISTRY.get("cake_jit_compiles_total")
    f = jax.jit(lambda x: x * 2)
    st = obs_steps.StepTelemetry(impl="t", key_prefix=("shape-probe",),
                                 peak_flops=1e12, hbm_bps=1e11)

    def probe(n):
        x = jnp.ones((n,))
        return st.jit_step("shape_probe", ((n,),),
                           lambda: obs_steps.lower_cost(f, (x,)))

    base = ctr.labels(fn="shape_probe").value
    assert probe(8).new                         # first shape compiles
    assert ctr.labels(fn="shape_probe").value == base + 1
    assert not probe(8).new                     # steady state: flat
    assert ctr.labels(fn="shape_probe").value == base + 1
    assert probe(16).new                        # new shape: retrace
    assert ctr.labels(fn="shape_probe").value == base + 2


def test_lower_cost_unwraps_partials_and_wrappers():
    import functools
    f = jax.jit(lambda a, s: a * s)
    x = jnp.ones((4, 4))
    direct = obs_steps.lower_cost(f, (x, 2.0))
    assert direct is not None
    part = functools.partial(f, s=2.0)
    assert obs_steps.lower_cost(part, (x,)) is not None

    @functools.wraps(f)
    def wrapper(*a, **k):
        return f(*a, **k)
    assert obs_steps.lower_cost(wrapper, (x, 2.0)) is not None
    # a plain function without .lower degrades to None, never raises
    assert obs_steps.lower_cost(lambda y: y, (x,)) is None


# -- engine integration -------------------------------------------------------


def test_engine_run_yields_flight_records(engine):
    """Acceptance: >= 20 records, monotonic ids, nonzero dispatch
    walls; no utilization on this lane (a CPU has no peak in the
    table — the MFU math is pinned by the hand-computed test above)."""
    recs = engine.flight.dump()
    assert len(recs) >= 20
    ids = [r["step"] for r in recs]
    assert ids == sorted(ids, reverse=True)     # monotonic (newest first)
    assert all(r["dispatch_s"] > 0 for r in recs)
    kinds = {r["kind"] for r in recs}
    assert "prefill" in kinds and "decode" in kinds
    assert not any("mfu" in r or "hbm_util" in r for r in recs)
    # NOTE: no `any(compiled)` assertion here — the accountant is
    # process-global (it mirrors the process-global jit cache), so when
    # an earlier test module already compiled this config's signatures,
    # this engine's run truthfully reports zero new compiles. The
    # compiled-flag plumbing is unit-tested above instead.
    assert engine.flight.utilization() == {}
    summary = engine.flight.summary()
    assert summary["kinds"]["decode"]["count"] >= 19
    assert summary["impl"] == "dense"


def test_recompile_flat_in_steady_state_and_bumps_on_new_bucket(engine):
    ctr = m.REGISTRY.get("cake_jit_compiles_total")
    decode_before = ctr.labels(fn="decode_step").value
    prefill_before = ctr.labels(fn="prefill_slot").value
    # same prompt bucket (16 -> 32), steady-state decode: both flat
    h = engine.submit(list(range(3, 3 + 16)), max_new_tokens=4)
    assert h.wait(120)
    assert ctr.labels(fn="decode_step").value == decode_before
    assert ctr.labels(fn="prefill_slot").value == prefill_before
    # a longer prompt forces a NEW prefill bucket (40 -> 64): exactly
    # one prefill retrace, decode still flat
    h = engine.submit(list(range(3, 3 + 40)), max_new_tokens=4)
    assert h.wait(120)
    assert ctr.labels(fn="prefill_slot").value == prefill_before + 1
    assert ctr.labels(fn="decode_step").value == decode_before


def test_step_log_jsonl_and_truncated_tail(tmp_path):
    path = tmp_path / "steps.jsonl"
    eng = _make_engine(step_log=str(path), step_ring=64)
    with eng:
        h = eng.submit(list(range(3, 3 + 16)), max_new_tokens=6)
        assert h.wait(120)
    # engine.stop() closed the appender (flush + fsync)
    recs = read_jsonl(str(path))
    assert len(recs) >= 6
    assert all("step" in r and "kind" in r and "dispatch_s" in r
               for r in recs)
    # simulate a killed writer: torn half-line at the tail must not
    # wedge the reader — complete records still parse
    with open(path, "a") as f:
        f.write('{"step": 999, "kind": "dec')
    again = read_jsonl(str(path))
    assert len(again) == len(recs)
    assert read_jsonl(str(path), limit=2) == recs[-2:]
    # missing file reads empty, never raises
    assert read_jsonl(str(tmp_path / "nope.jsonl")) == []


def test_jsonl_appender_fail_open(tmp_path):
    ap = JsonlAppender(str(tmp_path))  # a DIRECTORY: open() fails
    # falsy on failure (0 — append reports bytes written so the
    # request journal can account growth without re-serializing)
    assert not ap.append({"a": 1})
    assert ap.failed
    ap.close()  # no-op, no raise
    good = JsonlAppender(str(tmp_path / "x.jsonl"))
    n = good.append({"a": 1})
    assert n == len('{"a": 1}') + 1
    good.close()
    assert read_jsonl(str(tmp_path / "x.jsonl")) == [{"a": 1}]


# -- HTTP endpoints -----------------------------------------------------------


@pytest.fixture(scope="module")
def server_url():
    from cake_tpu.api.server import start
    from cake_tpu.args import Args
    from cake_tpu.master import Master
    from cake_tpu.models.llama.generator import LlamaGenerator
    params = init_params(TINY, jax.random.PRNGKey(0), dtype=jnp.float32)
    gen = LlamaGenerator(TINY, params, ByteTokenizer(TINY.vocab_size),
                         max_seq_len=256,
                         sampling=SamplingConfig(temperature=0.0),
                         cache_dtype=jnp.float32)
    master = Master(Args(sample_len=4), text_generator=gen)
    httpd = start(master, address="127.0.0.1:0", block=False)
    host, port = httpd.server_address[:2]
    yield f"http://{host}:{port}"
    httpd.shutdown()


def _post(url, path, body, timeout=60):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req, timeout=timeout).read())


def test_steps_endpoint_contract(server_url):
    _post(server_url, "/api/v1/chat/completions",
          {"messages": [{"role": "user", "content": "hi"}],
           "max_tokens": 3}, timeout=120)
    obj = json.loads(urllib.request.urlopen(
        server_url + "/api/v1/steps", timeout=10).read())
    assert obj["steps"], obj
    rec = obj["steps"][0]
    for key in ("step", "kind", "impl", "rows", "tokens", "dispatch_s",
                "wall_s", "compiled"):
        assert key in rec, rec
    assert obj["summary"]["recorded_steps"] >= len(obj["steps"])
    capped = json.loads(urllib.request.urlopen(
        server_url + "/api/v1/steps?limit=1", timeout=10).read())
    assert len(capped["steps"]) == 1
    # the exposition carries the new series and passes the lint tool
    text = urllib.request.urlopen(server_url + "/metrics",
                                  timeout=10).read().decode()
    assert "cake_steps_total" in text
    assert "cake_jit_compiles_total" in text
    import importlib.util
    import pathlib
    spec = importlib.util.spec_from_file_location(
        "lint_metrics",
        pathlib.Path(__file__).resolve().parents[1] / "tools"
        / "lint_metrics.py")
    lm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lm)
    assert lm.lint(text) == []


def test_profile_endpoint_single_flight(server_url, monkeypatch):
    """Contract test with a stubbed capture (the real jax.profiler
    pays ~10s one-time init; the slow-lane test below covers it):
    200 with artifact paths, second concurrent POST 409, bad seconds
    400, and the capture still works while health is failed."""
    started = threading.Event()
    release = threading.Event()

    def fake_capture(seconds, out_dir=None, perfetto=False):
        started.set()
        release.wait(30)
        return {"dir": "/tmp/fake", "perfetto_trace": None,
                "seconds": seconds}

    monkeypatch.setattr("cake_tpu.utils.profiling.capture_trace",
                        fake_capture)
    results = {}

    def first():
        results["first"] = _post(server_url, "/api/v1/profile",
                                 {"seconds": 1.0})

    t = threading.Thread(target=first, daemon=True)
    t.start()
    assert started.wait(30)
    # second concurrent capture: single-flight guard -> 409
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(server_url, "/api/v1/profile", {"seconds": 0.5})
    assert exc.value.code == 409
    release.set()
    t.join(30)
    assert results["first"]["seconds"] == 1.0
    assert results["first"]["dir"]
    # invalid seconds: client error, not a server fault
    for bad in (-1, 0, "soon", 1e9):
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(server_url, "/api/v1/profile", {"seconds": bad})
        assert exc.value.code == 400, bad


@pytest.mark.parametrize("body,want", [
    ({"seconds": 0.5}, False),                    # the benchmark's request
    ({"seconds": 0.5, "perfetto": False}, False),
    ({"seconds": 0.5, "perfetto": True}, True),
])
def test_profile_endpoint_perfetto_only_when_asked(server_url, monkeypatch,
                                                   body, want):
    """The Perfetto conversion runs inside the serving process at stop,
    so it is opt-in; the reply keeps its `perfetto_trace` key (null
    otherwise) and names the `.xplane.pb`."""
    seen = {}

    def fake_capture(seconds, out_dir=None, perfetto=False):
        seen["perfetto"] = perfetto
        return {"dir": "/tmp/fake", "xplane": "/tmp/fake/x.xplane.pb",
                "perfetto_trace": "/tmp/fake/p.json.gz" if perfetto
                else None, "seconds": seconds}

    monkeypatch.setattr("cake_tpu.utils.profiling.capture_trace",
                        fake_capture)
    out = _post(server_url, "/api/v1/profile", body)
    assert seen["perfetto"] is want
    assert out["xplane"].endswith(".xplane.pb")
    assert (out["perfetto_trace"] is not None) is want
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(server_url, "/api/v1/profile",
              {"seconds": 0.5, "perfetto": "yes"})
    assert exc.value.code == 400


# -- step phases (StepTelemetry.span) ----------------------------------------


class _Clock:
    """perf_counter under the test's control."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _span(st, clock, name, seconds):
    with st.span(name):
        clock.t += seconds


def test_span_seconds_and_gap_land_in_the_record(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(obs_steps.time, "perf_counter", clock)
    st = obs_steps.StepTelemetry(impl="t")
    for name, dt in (("admin", 0.001), ("schedule", 0.002),
                     ("build", 0.003), ("dispatch", 0.004),
                     ("sample", 0.005), ("fetch", 0.050)):
        _span(st, clock, name, dt)
    first = st.record("decode", rows=1, tokens=1, wall_s=0.062)
    assert first.phases == pytest.approx(
        {"admin": 0.001, "schedule": 0.002, "build": 0.003,
         "dispatch": 0.004, "sample": 0.005, "fetch": 0.050})
    assert first.gap_s is None            # no fetch before this step
    assert st.open_phase("dispatch") is None      # record cleared it
    # what follows a record belongs to the NEXT one: the emit of the
    # step just recorded, then the next step's own phases; a phase
    # entered twice adds up
    _span(st, clock, "emit", 0.006)
    clock.t += 0.0005                     # uncovered loop time
    _span(st, clock, "admin", 0.001)
    _span(st, clock, "build", 0.002)
    _span(st, clock, "dispatch", 0.004)
    _span(st, clock, "dispatch", 0.001)
    assert st.open_phase("dispatch") == pytest.approx(0.005)
    _span(st, clock, "fetch", 0.040)
    second = st.record("decode", rows=1, tokens=1, wall_s=0.05)
    assert second.phases["emit"] == pytest.approx(0.006)
    assert second.phases["dispatch"] == pytest.approx(0.005)
    # end of the previous fetch -> start of this step's FIRST dispatch
    assert second.gap_s == pytest.approx(0.006 + 0.0005 + 0.001 + 0.002)
    d = second.to_dict()
    assert d["gap_s"] == pytest.approx(second.gap_s, abs=1e-6)
    assert set(d["phases"]) == {"emit", "admin", "build", "dispatch",
                                "fetch"}
    assert "phases" not in st.record("decode", wall_s=0.01).to_dict()


def test_wait_span_belongs_to_no_step(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(obs_steps.time, "perf_counter", clock)
    st = obs_steps.StepTelemetry(impl="t")
    _span(st, clock, "dispatch", 0.004)
    _span(st, clock, "fetch", 0.050)
    st.record("decode", wall_s=0.054)
    _span(st, clock, "emit", 0.006)
    _span(st, clock, "wait", 0.050)       # nothing to run
    _span(st, clock, "dispatch", 0.004)
    _span(st, clock, "fetch", 0.050)
    rec = st.record("decode", wall_s=0.054)
    assert "emit" not in rec.phases and "wait" not in rec.phases
    assert rec.gap_s is None              # an idle engine is no gap


def test_span_annotation_carries_the_records_step_number(monkeypatch):
    made = []

    class FakeAnnotation:
        def __init__(self, name, **kw):
            made.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    st = obs_steps.StepTelemetry(impl="t")
    for _ in range(2):
        with st.span("dispatch"):
            pass
        with st.span("fetch"):
            pass
        rec = st.record("decode", wall_s=0.01)
        assert made[-2:] == [("cake/dispatch", {"step": rec.step}),
                             ("cake/fetch", {"step": rec.step})]
    assert set(obs_steps.PHASES) >= {"admin", "schedule", "build",
                                     "dispatch", "sample", "fetch",
                                     "emit", "wait"}


# -- stretch boundaries: chain_break, parts, late ----------------------------


@pytest.mark.parametrize("what", ["break", "part", "add_part"])
def test_boundary_vocabularies_refuse_unknown_names(what):
    st = obs_steps.StepTelemetry(impl="t")
    with pytest.raises(ValueError, match="vocabulary"):
        if what == "break":
            st.chain_broke("tired")
        elif what == "part":
            with st.span("schedule"):
                st.part("launch")      # dispatch's part, not schedule's
        else:
            st.add_part("emit.think", 0.001)
    assert len(set(obs_steps.BREAKS)) == len(obs_steps.BREAKS) == 10
    # every part names its phase
    assert {p.split(".")[0] for p in obs_steps.PARTS} <= set(obs_steps.PHASES)


def test_part_outside_an_open_span_raises():
    st = obs_steps.StepTelemetry(impl="t")
    with pytest.raises(ValueError, match="open span"):
        st.part("plan")
    with st.span("schedule"):
        with st.part("plan"):
            pass
    with pytest.raises(ValueError, match="open span"):
        st.part("plan")                # the span closed behind it


def test_part_seconds_land_in_parts_and_once_in_phases(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(obs_steps.time, "perf_counter", clock)
    st = obs_steps.StepTelemetry(impl="t")
    with st.span("schedule"):
        clock.t += 0.001
        with st.part("plan"):
            clock.t += 0.002
        with st.part("admit_pages"):
            clock.t += 0.003
        with st.part("admit_pages"):       # a second admission adds up
            clock.t += 0.003
    with st.span("emit"):
        clock.t += 0.004
        st.add_part("emit.detok", 0.0015)
        st.add_part("emit.detok", 0.0015)
    # outside its span a measured part counts no more than the span
    st.add_part("emit.detok", 0.5)
    with st.span("dispatch"):
        with st.part("launch"):
            clock.t += 0.005
    rec = st.record("mixed", wall_s=0.02, chained=False)
    assert rec.parts == pytest.approx(
        {"schedule.plan": 0.002, "schedule.admit_pages": 0.006,
         "emit.detok": 0.003, "dispatch.launch": 0.005,
         # what an emit span's row seams (add_emit) do not claim is its
         # rows' own work: here the whole span
         "emit.rows": 0.004})
    # the span's seconds are its own, parts included ONCE
    assert rec.phases == pytest.approx(
        {"schedule": 0.009, "emit": 0.004, "dispatch": 0.005})
    d = rec.to_dict()
    assert set(d["parts"]) == set(rec.parts)
    assert set(d["phases"]) <= set(obs_steps.PHASES)
    # a record carries `parts` only when it has any
    assert "parts" not in st.record("decode", wall_s=0.01).to_dict()


@pytest.mark.parametrize("case", ["summed", "absent", "emit_only",
                                  "discarded"])
def test_detok_ids_since_the_record_before(case):
    """The ids the detokenisation handed to `decode` (PR 47): summed
    over the emit span's rows since the record before, absent where
    nothing was emitted, counted inside `emit` only, dropped with an
    open step that belongs to no record."""
    st = obs_steps.StepTelemetry(impl="t")
    if case == "summed":
        with st.span("emit"):
            for ids in (3, 3, 5):          # three rows' tokens
                st.add_detok_ids(ids)
        with st.span("emit"):              # a second emit, same record
            st.add_detok_ids(4)
        rec = st.record("decode", rows=3, tokens=3, wall_s=0.01)
        assert rec.detok_ids == 15 and rec.to_dict()["detok_ids"] == 15
        # taken by the record: the next one starts from nothing
        assert st.record("decode", wall_s=0.01).detok_ids is None
    elif case == "absent":
        with st.span("emit"):
            st.add_detok_ids(0)            # a flush with nothing held
        with st.span("dispatch"):
            pass
        rec = st.record("decode", wall_s=0.01)
        assert rec.detok_ids is None and "detok_ids" not in rec.to_dict()
    elif case == "emit_only":
        st.add_detok_ids(7)                # no span open
        with st.span("admin"):
            st.add_detok_ids(7)            # a recovered row's flush
        with st.span("emit"):
            st.add_detok_ids(2)
        assert st.record("decode", wall_s=0.01).detok_ids == 2
    else:
        with st.span("emit"):
            st.add_detok_ids(9)
        st.discard_open()                  # the warm-up, the idle loop
        assert st.record("decode", wall_s=0.01).detok_ids is None


@pytest.mark.parametrize("case", ["one_wake_a_span", "direct", "absent",
                                  "outside_a_span", "kept"])
def test_stream_counts_since_the_record_before(case):
    """What `emit` did with its deltas (PR 54): those left for the API
    server's stream writer (`stream_chunks`), the one signal a span
    that left any (`stream_wakes`, sent where the span closes), those
    handed to a per-token callback (`stream_direct`); and the series
    that count them."""
    from cake_tpu.obs import metrics as obs_metrics

    def series():
        chunks = obs_metrics.REGISTRY.get("cake_stream_chunks_total")
        return (chunks.labels(path="writer").value,
                chunks.labels(path="handler").value,
                obs_metrics.REGISTRY.get(
                    "cake_stream_writer_wakes_total").value)

    st = obs_steps.StepTelemetry(impl="t")
    woken = []
    st.stream_wake = lambda: woken.append(st._open)
    before = series()
    if case == "one_wake_a_span":
        with st.span("emit"):
            for _row in range(3):
                st.add_stream(True)
            assert not woken              # never inside the rows' loop
        assert woken == [None]            # once, after the span's clock
        with st.span("emit"):             # a second emit, same record
            st.add_stream(True)
            st.add_stream(False)          # a stream given back
        rec = st.record("decode", rows=3, tokens=3, wall_s=0.01)
        assert (rec.stream_chunks, rec.stream_wakes,
                rec.stream_direct) == (4, 2, 1)
        d = rec.to_dict()
        assert (d["stream_chunks"], d["stream_wakes"],
                d["stream_direct"]) == (4, 2, 1)
        assert len(woken) == 2
        assert series() == (before[0] + 4, before[1] + 1, before[2] + 2)
        # taken by the record: the next one starts from nothing
        assert st.record("decode", wall_s=0.01).stream_chunks is None
    elif case == "direct":
        with st.span("emit"):
            st.add_stream(False)
            st.add_stream(False)
        rec = st.record("decode", wall_s=0.01)
        assert (rec.stream_chunks, rec.stream_wakes,
                rec.stream_direct) == (None, None, 2)
        assert not woken
        assert series() == (before[0], before[1] + 2, before[2])
    elif case == "absent":
        with st.span("emit"):
            pass                           # rows that stream nothing
        d = st.record("decode", wall_s=0.01).to_dict()
        assert not {"stream_chunks", "stream_wakes",
                    "stream_direct"} & set(d)
        assert not woken and series() == before
    elif case == "outside_a_span":
        # a speculative round, an adopted first token, a recovered
        # row's flush: the writer is signalled at once, nothing counted
        st.add_stream(True)
        with st.span("admin"):
            st.add_stream(True)
            st.add_stream(False)
        assert woken == [None, "admin"]
        assert st.record("decode", wall_s=0.01).stream_chunks is None
        st.stream_wake = None              # no server: nothing to call
        st.add_stream(True)
        with st.span("emit"):
            st.add_stream(True)
        assert st.record("decode", wall_s=0.01).stream_wakes == 1
    else:
        with st.span("emit"):
            st.add_stream(True)
        st.discard_open()                  # the idle loop: still sent
        rec = st.record("decode", wall_s=0.01)
        assert (rec.stream_chunks, rec.stream_wakes) == (1, 1)


def test_chain_break_rides_the_next_unchained_record_only():
    breaks = m.REGISTRY.get("cake_chain_breaks_total")
    before = breaks.labels(cause="queue").value
    st = obs_steps.StepTelemetry(impl="t")
    first = st.record("mixed", wall_s=0.01, chained=False)
    assert first.chain_break is None and first.rows_admitted == 0
    assert "chain_break" not in first.to_dict()
    st.chain_broke("queue")
    st.admitted()
    st.admitted(2)
    flown = st.record("mixed", wall_s=0.01, chained=True)
    assert flown.chain_break is None and flown.rows_admitted is None
    assert "rows_admitted" not in flown.to_dict()
    nxt = st.record("mixed", wall_s=0.01, chained=False)
    assert nxt.to_dict()["chain_break"] == "queue"
    assert nxt.to_dict()["rows_admitted"] == 3
    # cleared by the record that took it; a record with no `chained`
    # at all (the dense engine's prefill) counts as not chained
    st.chain_broke("stretch_cap")
    plain = st.record("prefill", wall_s=0.01)
    assert plain.chain_break == "stretch_cap"
    after = st.record("decode", wall_s=0.01, chained=False)
    assert after.chain_break is None and after.rows_admitted == 0
    assert breaks.labels(cause="queue").value - before == 1


def test_wait_span_yields_idle(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(obs_steps.time, "perf_counter", clock)
    st = obs_steps.StepTelemetry(impl="t")
    _span(st, clock, "wait", 0.05)        # before the engine's first step
    assert st.record("mixed", wall_s=0.01, chained=False).chain_break is None
    st.chain_broke("row_finished")        # the last row of the plan
    with st.span("schedule"):
        with st.part("plan"):
            clock.t += 0.001
    _span(st, clock, "wait", 0.05)        # then nothing to run
    rec = st.record("mixed", wall_s=0.01, chained=False)
    assert rec.chain_break == "idle" and rec.gap_s is None
    assert rec.parts is None              # what led up to the wait is nobody's


@pytest.mark.parametrize("wait_s,late", [
    (0.0, True), (obs_steps.LATE_FETCH_S / 2, True),
    (obs_steps.LATE_FETCH_S, False), (0.012, False)])
def test_late_follows_the_steps_own_fetch(wait_s, late):
    counter = m.REGISTRY.get("cake_chained_steps_late_total")
    before = counter.value
    st = obs_steps.StepTelemetry(impl="t")
    rec = st.record("decode", wall_s=0.015, chained=True,
                    fetch_wait_s=wait_s)
    assert rec.late is late
    d = rec.to_dict()
    assert d["late"] is late
    assert d["fetch_wait_s"] == pytest.approx(wait_s, abs=1e-6)
    assert counter.value - before == int(late)
    # a stretch's first step waits for the whole step: never `late`
    head = st.record("decode", wall_s=0.015, chained=False,
                     fetch_wait_s=wait_s).to_dict()
    assert "late" not in head and "fetch_wait_s" not in head
    assert 1e-4 <= obs_steps.LATE_FETCH_S <= 2e-3


@pytest.fixture(scope="module")
def paged_engine():
    eng = _make_engine(kv_pages=16, kv_page_size=16)
    with eng:
        # the prompt rides mixed steps as chunk rows, then decode steps
        h = eng.submit(list(range(3, 3 + 40)), max_new_tokens=10)
        assert h.wait(180)
        yield eng


@pytest.mark.parametrize("kind", ["mixed", "decode"])
def test_paged_step_timings_are_the_spans(paged_engine, kind):
    """Both kinds of step are kept in flight: dispatch_s is a step's
    own dispatch's seconds; a step with nothing in flight before it has
    device_s = its `fetch` span and wall_s the whole step; a chained
    step wall_s = device_s = the period between two fetches. No
    `sample` span anywhere (the programs sample)."""
    recs = [r for r in paged_engine.flight.dump() if r["kind"] == kind]
    assert recs, paged_engine.flight.summary()
    for r in recs:
        ph = r["phases"]
        assert "sample" not in ph and "fetch" in ph, r
        if r["chained"]:
            # (its dispatch ran inside the period before its own)
            assert r["device_s"] == r["wall_s"] and r["gap_s"] == 0.0
        else:
            assert {"build", "dispatch"} <= set(ph), r
            assert r["dispatch_s"] < r["wall_s"]
            assert r["device_s"] < r["wall_s"]
            # (its `dispatch` span holds the next step's dispatch too)
            assert r["dispatch_s"] <= ph["dispatch"] + 1e-6
            assert r["device_s"] == pytest.approx(ph["fetch"], abs=2e-6)
    # two spans of a few hundred microseconds, recorded to the
    # microsecond, coincide in one record now and then (0.000224 ==
    # 0.000224 failed the driver's run at PR 26): they are two
    # measurements if they differ anywhere
    assert any(r["dispatch_s"] != r["device_s"] for r in recs)
    # one request, nobody else arriving: one stretch from the prompt's
    # first window to the last token, whose first step alone was
    # dispatched with nothing in flight
    flags = [r["chained"] for r in reversed(paged_engine.flight.dump())]
    assert flags == [False] + [True] * (len(flags) - 1)


def test_paged_decode_steps_carry_gap_and_emit(paged_engine):
    recs = [r for r in reversed(paged_engine.flight.dump())
            if r["kind"] == "decode"]
    # the stretch went on from the prompt's last window into the decode
    # steps: every one of them was queued behind the step before it
    assert recs and all(r["gap_s"] == 0.0 for r in recs)
    # a record holds the spans since the record before it: the emit of
    # the step before, while this one ran
    assert all(r["phases"]["emit"] > 0 for r in recs)


def test_dense_engine_steps_carry_phases(engine):
    recs = list(reversed(engine.flight.dump()))
    prefill = [r for r in recs if r["kind"] == "prefill"]
    decode = [r for r in recs if r["kind"] == "decode"]
    assert prefill and decode
    assert {"schedule", "build", "dispatch", "sample", "fetch"} \
        <= set(prefill[0]["phases"])
    # a decode step samples inside its program; a stretch's first
    # record holds its own build and dispatch, a chained one the emit
    # of the step before it
    assert all("fetch" in r["phases"] and "sample" not in r["phases"]
               for r in decode)
    assert all({"build", "dispatch"} <= set(r["phases"])
               for r in decode if not r["chained"])
    assert all("emit" in r["phases"] for r in decode if r["chained"])
    assert any(r["chained"] for r in decode)
    assert any("gap_s" in r for r in decode)


def test_steps_endpoint_carries_phases_and_gap(server_url):
    _post(server_url, "/api/v1/chat/completions",
          {"messages": [{"role": "user", "content": "hi"}],
           "max_tokens": 4}, timeout=120)
    steps = json.loads(urllib.request.urlopen(
        server_url + "/api/v1/steps", timeout=10).read())["steps"]
    assert all("phases" in s for s in steps), steps
    assert any("gap_s" in s for s in steps)
    for s in steps:
        assert set(s["phases"]) <= set(obs_steps.PHASES) - {"wait"}


def test_capture_holds_cake_spans_joined_by_step_number(tmp_path):
    """A jax.profiler capture of a toy paged engine holds the
    `cake/<phase>` events on the host plane, each carrying the step
    number of the /api/v1/steps record its seconds went into."""
    from cake_tpu.utils.profiling import capture_trace
    eng = _make_engine(kv_pages=16, kv_page_size=16)
    got = {}
    with eng:
        h = eng.submit(list(range(3, 3 + 20)), max_new_tokens=4)
        assert h.wait(180)                # compile outside the capture
        t = threading.Thread(target=lambda: got.update(
            capture_trace(0.8, str(tmp_path))))
        t.start()
        import time
        time.sleep(0.25)
        h = eng.submit(list(range(3, 3 + 20)), max_new_tokens=6)
        assert h.wait(180)
        t.join(120)
        recs = {r["step"]: r for r in eng.flight.dump()}
    assert got["xplane"] and got["perfetto_trace"] is None
    from jax.profiler import ProfileData
    seen, causes = {}, {}
    for plane in ProfileData.from_file(got["xplane"]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("cake/"):
                    stats = dict(ev.stats)
                    step = int(stats["step"])
                    seen.setdefault(step, {}).setdefault(
                        ev.name[5:], 0.0)
                    seen[step][ev.name[5:]] += ev.duration_ns / 1e9
                    if "chain_break" in stats:
                        assert ev.name == "cake/dispatch"
                        causes[step] = stats["chain_break"]
    joined = [n for n in seen if n in recs and "dispatch" in seen[n]]
    assert len(joined) >= 4, (sorted(seen), sorted(recs))
    for n in joined:
        for name in ("dispatch", "fetch"):
            # the same span on two clocks
            assert seen[n][name] == pytest.approx(
                recs[n]["phases"][name], abs=2e-3)
    # the boundary: the request arrived at an idle engine, so its first
    # step's first dispatch says `idle`, where the record says it; the
    # admission's parts lie on the host plane under that step's number
    (first,) = causes
    assert causes[first] == recs[first]["chain_break"] == "idle"
    for part in ("schedule.plan", "schedule.admit_pages",
                 "schedule.admit_ring", "dispatch.launch"):
        assert seen[first][part] == pytest.approx(
            recs[first]["parts"][part], abs=2e-3), part
    assert recs[first]["rows_admitted"] == 1
    assert any("dispatch.launch" in seen[n] for n in joined if n != first)
    # the loop's two other spans lie on the host plane too: the stretch's
    # gate under the number of the record being put together, the
    # writing of a record under that record's own
    assert any("gate" in seen[n] for n in joined)
    assert all("record" in seen[n] for n in joined)



# -- the engine thread's clock: loop_s, offcpu, emit by part, collections ------

NEW_PHASES = ("gate", "record", "release")
NEW_PARTS = ("emit.rows", "emit.trace", "emit.report", "emit.stream",
             "emit.retire")
CLOCK_FIELDS = ("loop_s", "offcpu", "gc_s", "gc_n", "gc_max_s")


def _clocks(monkeypatch):
    """perf_counter and thread_time under the test's control: `wall`
    moves alone while the thread is off the CPU."""
    wall, cpu = _Clock(), _Clock()
    cpu.t = 7.0
    monkeypatch.setattr(obs_steps.time, "perf_counter", wall)
    monkeypatch.setattr(obs_steps.time, "thread_time", cpu)
    return wall, cpu


@pytest.mark.parametrize("name", NEW_PHASES + NEW_PARTS
                         + ("phase?", "part?"))
def test_the_clock_vocabulary(monkeypatch, name):
    # clocks that stand still: a span's own microseconds are 0 whatever
    # else the machine runs (on the wall clock a loaded host read 1 ms
    # inside the `emit.rows` case's span and failed it: PR 54)
    _clocks(monkeypatch)
    st = obs_steps.StepTelemetry(impl="t")
    if name in NEW_PHASES:
        assert name in obs_steps.PHASES
        with st.span(name):
            pass
    elif name in NEW_PARTS:
        assert name in obs_steps.PARTS and name in obs_steps.EMIT_SEAMS
        with st.span("emit"):
            st.add_part(name, 0.002)
        # (and, for emit.rows, the microseconds of this span itself)
        assert st.record("decode", wall_s=0.01).parts[name] == \
            pytest.approx(0.002, abs=1e-4)
    elif name == "phase?":
        with pytest.raises(ValueError, match="vocabulary"):
            st.span("think")
    else:
        with pytest.raises(ValueError, match="vocabulary"):
            st.add_part("emit.think", 0.001)
        with pytest.raises(TypeError):
            with st.span("emit"):
                st.add_emit(1.0, 2.0)          # six seams, no fewer
    st.close()
    # the seams are parts of emit, in the order they run
    assert obs_steps.EMIT_SEAMS == ("emit.rows", "emit.trace",
                                    "emit.report", "emit.detok",
                                    "emit.stream", "emit.retire")
    assert set(obs_steps.EMIT_SEAMS) <= set(obs_steps.PARTS)


@pytest.mark.parametrize("case", ["loop", "record_span", "wait",
                                  "discard", "first"])
def test_loop_s_runs_from_record_to_record(monkeypatch, case):
    wall, cpu = _clocks(monkeypatch)
    st = obs_steps.StepTelemetry(impl="t")
    if case == "first":
        # nothing to count from: a recorder's first record has none
        assert st.record("decode", wall_s=0.01).loop_s is None
        assert "loop_s" not in st.dump()[0]
        return
    st.record("decode", wall_s=0.01)
    if case == "loop":
        _span(st, wall, "emit", 0.006)
        wall.t += 0.0005                   # between two spans
        _span(st, wall, "gate", 0.001)
        _span(st, wall, "fetch", 0.010)
        wall.t += 0.00025
        rec = st.record("decode", wall_s=0.01)
        assert rec.loop_s == pytest.approx(0.01775)
        # of ONE record: what lay outside every span in it
        assert rec.loop_s - sum(rec.phases.values()) == pytest.approx(
            0.00075)
        assert rec.to_dict()["loop_s"] == pytest.approx(0.01775, abs=1e-6)
    elif case == "record_span":
        # the clock turns over where the `record` span starts; its
        # seconds, the record's own writing included, go to the NEXT one
        _span(st, wall, "fetch", 0.010)
        with st.span("record"):
            wall.t += 0.0002
            mid = st.record("decode", wall_s=0.01)
            wall.t += 0.0001
        assert mid.loop_s == pytest.approx(0.010)
        assert "record" not in mid.phases
        _span(st, wall, "emit", 0.004)
        nxt = st.record("decode", wall_s=0.01)
        assert nxt.phases == pytest.approx({"record": 0.0003,
                                            "emit": 0.004})
        assert nxt.loop_s == pytest.approx(0.0043)
    elif case == "wait":
        _span(st, wall, "emit", 0.006)
        wall.t += 0.002
        _span(st, wall, "wait", 0.050)     # the origin moves to its end
        wall.t += 0.0004
        _span(st, wall, "dispatch", 0.003)
        rec = st.record("decode", wall_s=0.01)
        assert rec.loop_s == pytest.approx(0.0034)
        assert rec.phases == pytest.approx({"dispatch": 0.003})
        # (the test's thread_time stands still: all of it off the CPU,
        # the 0.4 ms outside every span under `none`)
        assert rec.offcpu == pytest.approx({"dispatch": 0.003,
                                            "none": 0.0004})
    else:
        _span(st, wall, "dispatch", 5.0)   # the warm-up
        st.discard_open()
        _span(st, wall, "dispatch", 0.003)
        rec = st.record("decode", wall_s=0.01)
        assert rec.loop_s == pytest.approx(0.003) and rec.offcpu == \
            pytest.approx({"dispatch": 0.003, "none": 0.0})


def test_offcpu_is_a_spans_wall_less_its_threads_cpu(monkeypatch):
    wall, cpu = _clocks(monkeypatch)
    st = obs_steps.StepTelemetry(impl="t")
    with st.span("emit"):                  # 6 ms, of which 4 on the CPU
        wall.t += 0.006
        cpu.t += 0.004
    with st.span("emit"):                  # a second one adds up
        wall.t += 0.001
        cpu.t += 0.001
    with st.span("fetch"):                 # waiting for the device
        wall.t += 0.012
        cpu.t += 0.0005
    with st.span("gate"):                  # the two clocks' own jitter
        wall.t += 0.0001
        cpu.t += 0.00011
    first = st.record("decode", wall_s=0.02)
    # (signed: a clamp a span would bias the sums on a coarse CPU clock)
    assert first.offcpu == pytest.approx(
        {"emit": 0.002, "fetch": 0.0115, "gate": -0.00001})
    assert set(first.offcpu) == set(first.phases)
    d = first.to_dict()
    assert d["offcpu"] == pytest.approx(first.offcpu, abs=1e-6)
    # from the second record on the loop has an origin, and `none`
    # holds what of it was off the CPU outside every span
    with st.span("emit"):
        wall.t += 0.004
        cpu.t += 0.003
    wall.t += 0.0030                       # between two spans: 1 ms of
    cpu.t += 0.0020                        # it waiting
    with st.span("fetch"):
        wall.t += 0.010
    rec = st.record("decode", wall_s=0.02)
    assert rec.loop_s == pytest.approx(0.017)
    assert rec.offcpu == pytest.approx(
        {"emit": 0.001, "fetch": 0.010, "none": 0.001})
    assert set(rec.offcpu) == set(rec.phases) | {"none"}
    quiet = st.record("decode", wall_s=0.01).to_dict()
    assert quiet["offcpu"] == {"none": 0.0} and "phases" not in quiet


@pytest.mark.parametrize("case", ["streamed", "silent", "finished",
                                  "two_rows", "outside"])
def test_a_rows_seams_are_the_parts_of_emit(monkeypatch, case):
    """add_emit takes the clock reads at a row's seams: each part is the
    difference of two neighbours, a part that did not run ends where
    the one before it did, and `emit.rows` runs from the span's start
    (or the row before) to the row's entry, then from the last row to
    the span's end."""
    wall, _cpu = _clocks(monkeypatch)
    st = obs_steps.StepTelemetry(impl="t")
    t = wall.t
    if case == "outside":
        st.add_emit(t, t + 1, t + 2, t + 3, t + 4, t + 5)
        st.add_part("emit.stream", 0.5)    # no span open: nobody's
        with st.span("admin"):
            st.add_emit(t, t + 1, t + 2, t + 3, t + 4, t + 5)
            st.add_part("emit.stream", 0.5)
        assert st.record("decode", wall_s=0.01).parts is None
        return
    with st.span("emit"):
        wall.t += 0.0010                   # the mirrors, the zip
        t = wall.t
        if case == "streamed":
            st.add_emit(t, t + .0002, t + .0005, t + .0006, t + .0009,
                        t + .0009)
            wall.t += 0.0009
            want = {"emit.rows": 0.0010, "emit.trace": 0.0002,
                    "emit.report": 0.0003, "emit.detok": 0.0001,
                    "emit.stream": 0.0003}
        elif case == "silent":             # no stream: three reads
            st.add_emit(t, t + .0002, t + .0005, t + .0005, t + .0005,
                        t + .0005)
            wall.t += 0.0005
            want = {"emit.rows": 0.0010, "emit.trace": 0.0002,
                    "emit.report": 0.0003}
        elif case == "finished":
            st.add_emit(t, t + .0002, t + .0005, t + .0006, t + .0009,
                        t + .0019)
            wall.t += 0.0019
            want = {"emit.rows": 0.0010, "emit.trace": 0.0002,
                    "emit.report": 0.0003, "emit.detok": 0.0001,
                    "emit.stream": 0.0003, "emit.retire": 0.0010}
        else:
            st.add_emit(t, t + .0002, t + .0005, t + .0006, t + .0009,
                        t + .0009)
            wall.t += 0.0009 + 0.0004      # the second row's own work
            t = wall.t
            st.add_emit(t, t + .0001, t + .0002, t + .0003, t + .0004,
                        t + .0004)
            wall.t += 0.0004
            want = {"emit.rows": 0.0014, "emit.trace": 0.0003,
                    "emit.report": 0.0004, "emit.detok": 0.0002,
                    "emit.stream": 0.0004}
        wall.t += 0.0003                   # after the last row
        want["emit.rows"] += 0.0003
    rec = st.record("decode", wall_s=0.01)
    assert rec.parts == pytest.approx(want)
    # the seams leave nothing of the span unnamed
    assert sum(rec.parts.values()) == pytest.approx(rec.phases["emit"])


def _collect(generation: int) -> None:
    import gc
    gc.collect(generation)


@pytest.mark.parametrize("case", ["delta", "by_generation", "close",
                                  "engine", "annotation"])
def test_collections_are_events(monkeypatch, case):
    import gc
    # only the collections this test asks for (the hook hears those too)
    gc.disable()
    try:
        _collections_are_events(monkeypatch, case)
    finally:
        gc.enable()


def _collections_are_events(monkeypatch, case):
    import gc
    watch = obs_steps.GC_WATCH
    users = watch._users
    if case == "annotation":
        made = []

        class FakeAnnotation:
            def __init__(self, name, **kw):
                made.append(name)

            def __enter__(self):
                made.append("enter")

            def __exit__(self, *exc):
                made.append("exit")

        st = obs_steps.StepTelemetry(impl="t")
        # (the hook's annotation is its first opener's)
        monkeypatch.setattr(watch, "_annotation", FakeAnnotation)
        _collect(0)
        assert made == []                  # a young collection: sums only
        _collect(2)
        assert made == ["cake/gc", "enter", "exit"]
        st.close()
        return
    if case == "engine":
        eng = _make_engine()
        with eng:
            assert watch._users == users + 1
            assert watch._hook in gc.callbacks
        # no hook left behind by an engine that stopped
        assert watch._users == users
        assert (watch._hook in gc.callbacks) == bool(users)
        return
    st = obs_steps.StepTelemetry(impl="t")
    assert watch._users == users + 1
    assert gc.callbacks.count(watch._hook) == 1    # one, however many
    if case == "close":
        st.close()
        assert watch._users == users
        assert (watch._hook in gc.callbacks) == bool(users)
        st.close()                          # once, however often
        assert watch._users == users
        # a recorder nobody closed lets go when it is collected
        obs_steps.StepTelemetry(impl="t")
        assert watch._users == users
        return
    st.record("decode", wall_s=0.01)
    if case == "delta":
        before = watch.mark()
        _collect(0)
        _collect(2)
        rec = st.record("decode", wall_s=0.01)
        after = watch.mark()
        assert rec.gc_n == after[1] - before[1] >= 2
        assert rec.gc_s == pytest.approx(after[0] - before[0])
        assert rec.gc_s > 0 and rec.gc_max_s == watch.longest(rec.gc_n)
        assert 0 < rec.gc_max_s <= rec.gc_s
        d = rec.to_dict()
        assert d["gc_n"] == rec.gc_n and d["gc_max_s"] > 0
        # taken by the record: the next one starts from nothing...
        quiet = st.record("decode", wall_s=0.01)
        if quiet.gc_n == 0:                 # (...unless one just ran)
            assert quiet.gc_s == 0.0 and quiet.gc_max_s == 0.0
        assert "gc_s" in quiet.to_dict()    # 0.0, not absent
    else:
        count, seconds = list(watch.count), list(watch.seconds)
        _collect(1)
        _collect(2)
        _collect(2)
        assert watch.count[1] - count[1] >= 1
        assert watch.count[2] - count[2] == 2
        assert watch.seconds[2] > seconds[2]
        obs_steps.refresh_gc_series()
        for name, sums in (("cake_gc_collections_total", watch.count),
                           ("cake_gc_pause_seconds_total", watch.seconds)):
            fam = m.REGISTRY.get(name)
            assert fam.labels(generation="2").value == pytest.approx(
                sums[2])
    st.close()


@pytest.mark.parametrize("kind", ["mixed", "decode"])
def test_engine_records_carry_the_clock(paged_engine, kind):
    recs = [r for r in paged_engine.flight.dump() if r["kind"] == kind]
    assert recs
    later = [r for r in recs if r["step"] > 1]
    for r in later:
        for field in CLOCK_FIELDS:
            assert field in r, (field, r)
        ph = r["phases"]
        assert set(r["offcpu"]) == set(ph) | {"none"}
        assert "record" in ph and "fetch" in ph, r
        emit = sum(v for k, v in r.get("parts", {}).items()
                   if k.startswith("emit."))
        # (each rounded to the microsecond)
        assert emit <= ph.get("emit", 0.0) + 1e-5, r
        assert sum(ph.values()) <= r["loop_s"] + 1e-5, r
        # (two clocks: a span that ran throughout reads a hair under 0)
        assert all(-1e-3 <= v <= ph.get(k, r["loop_s"]) + 1e-3
                   for k, v in r["offcpu"].items()), r
    # the stretch asks its gate before every dispatch but its first,
    # and names what an emit span did for its row
    if kind == "decode":
        assert any("gate" in r["phases"] for r in later)
        # a completed step's device outputs die under a span of their own
        assert sum("release" in r["phases"] for r in later) >= len(later) - 1
        assert all({"emit.rows", "emit.trace", "emit.report"}
                   <= set(r["parts"]) for r in later)


def test_step_log_holds_the_clock(tmp_path):
    path = tmp_path / "steps.jsonl"
    eng = _make_engine(step_log=str(path), step_ring=64)
    seen = []
    with eng:
        h = eng.submit(list(range(3, 3 + 16)), max_new_tokens=6,
                       stream=lambda *a: seen.append(a))
        assert h.wait(120)
        ring = {r["step"]: r for r in eng.flight.dump()}
    recs = read_jsonl(str(path))
    assert len(recs) >= 6
    for r in recs[1:]:
        assert set(CLOCK_FIELDS) <= set(r), r
        assert r == ring[r["step"]]
    last = recs[-1]["parts"]
    # the request's last token: the row was retired in that emit... which
    # follows the last record, so the retire shows in no record of this
    # run; the rows before it show the seams a streamed row has
    assert {"emit.rows", "emit.trace", "emit.report", "emit.detok"} \
        <= set(last), last
