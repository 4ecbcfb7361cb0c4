"""obs/: metrics registry semantics + request-lifecycle tracing.

Registry: label sets, histogram bucket math, concurrency, exposition.
Tracer: span ordering, queue-wait under a full batch, the bounded ring,
the JSONL event log — driven through the REAL engine (dense and sp
paths), because the tracer's value is the seams it is wired into."""

import json
import threading

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.obs import metrics as m
from cake_tpu.obs.tracing import RequestTracer


# -- registry ----------------------------------------------------------------


def test_counter_labels_and_values():
    reg = m.Registry()
    c = m.Counter("c_total", "requests", labelnames=("route", "status"),
                  registry=reg)
    c.labels(route="/a", status="200").inc()
    c.labels(route="/a", status="200").inc(2)
    c.labels("/b", "500").inc()
    text = reg.render()
    assert 'c_total{route="/a",status="200"} 3' in text
    assert 'c_total{route="/b",status="500"} 1' in text
    assert "# TYPE c_total counter" in text
    with pytest.raises(ValueError):
        c.labels(route="/a").inc()          # missing label
    with pytest.raises(ValueError):
        c.labels(route="/a", status="1", extra="x")
    with pytest.raises(ValueError):
        c.inc()                             # labeled family needs labels
    with pytest.raises(ValueError):
        c.labels(route="/a", status="200").inc(-1)


def test_gauge_set_function_and_escaping():
    reg = m.Registry()
    g = m.Gauge("g", "gauge", labelnames=("who",), registry=reg)
    g.labels(who='a"b\\c\nd').set(1)
    g2 = m.Gauge("g_fn", "fn gauge", registry=reg)
    g2.set_function(lambda: 42.5)
    text = reg.render()
    assert 'g{who="a\\"b\\\\c\\nd"} 1' in text
    assert "g_fn 42.5" in text


def test_invalid_names_rejected():
    reg = m.Registry()
    with pytest.raises(ValueError):
        m.Counter("bad-name", registry=reg)
    with pytest.raises(ValueError):
        m.Counter("ok", labelnames=("bad-label",), registry=reg)
    with pytest.raises(ValueError):
        m.Counter("ok2", labelnames=("__reserved",), registry=reg)


def test_histogram_bucket_math():
    reg = m.Registry()
    h = m.Histogram("h_seconds", "lat", buckets=(0.1, 1.0, 10.0),
                    registry=reg)
    for v in (0.05, 0.1, 0.5, 5.0, 100.0):
        h.observe(v)
    lines = reg.render().splitlines()
    # cumulative: le=0.1 catches 0.05 AND the boundary value 0.1
    assert 'h_seconds_bucket{le="0.1"} 2' in lines
    assert 'h_seconds_bucket{le="1"} 3' in lines
    assert 'h_seconds_bucket{le="10"} 4' in lines
    assert 'h_seconds_bucket{le="+Inf"} 5' in lines
    assert "h_seconds_count 5" in lines
    assert h.count == 5
    assert abs(h.sum - 105.65) < 1e-9
    with pytest.raises(ValueError):
        m.Histogram("h2", buckets=(), registry=reg)
    with pytest.raises(ValueError):
        m.Histogram("h3", buckets=(1.0, 1.0), registry=reg)


def test_get_or_create_semantics():
    reg = m.Registry()
    a = m.counter("x_total", "x", registry=reg)
    assert m.counter("x_total", registry=reg) is a
    with pytest.raises(ValueError):
        m.gauge("x_total", registry=reg)        # type mismatch
    with pytest.raises(ValueError):
        m.counter("x_total", labelnames=("l",), registry=reg)
    with pytest.raises(ValueError):
        m.Counter("x_total", registry=reg)      # raw ctor collides


def test_counter_set_total_is_monotonic():
    reg = m.Registry()
    c = m.counter("mirror_total", registry=reg)
    c.set_total(10)
    c.set_total(4)       # a restarted source must not move it backwards
    assert c.value == 10
    c.set_total(12)
    assert c.value == 12


def test_concurrent_increments_are_exact():
    reg = m.Registry()
    c = m.Counter("cc_total", registry=reg)
    h = m.Histogram("ch_seconds", buckets=(0.5,), registry=reg)
    N, T = 2000, 8

    def work():
        for _ in range(N):
            c.inc()
            h.observe(0.1)

    ts = [threading.Thread(target=work) for _ in range(T)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == N * T
    assert h.count == N * T
    assert f'ch_seconds_bucket{{le="0.5"}} {N * T}' in reg.render()


# -- tracer (unit) -----------------------------------------------------------


def test_tracer_ring_is_bounded_and_ordered(tmp_path):
    ev = tmp_path / "events.jsonl"
    tr = RequestTracer(capacity=3, events_path=str(ev),
                       observe_metrics=False)
    for rid in range(1, 6):
        tr.admit(rid, prompt_tokens=4, max_new_tokens=2)
        tr.prefill_start(rid)
        tr.first_token(rid)
        tr.token(rid)
        tr.finish(rid, "retired", output_tokens=2)
    recs = tr.dump()
    assert [r["rid"] for r in recs] == [5, 4, 3]     # ring of 3, newest first
    for r in recs:
        names = [s["name"] for s in r["spans"]]
        assert names == ["admitted", "queued", "prefill", "first_token",
                         "decode", "retired"]
        ts = [s["t"] for s in r["spans"]]
        assert ts == sorted(ts)
        assert r["queue_wait_s"] >= 0
        assert r["e2e_s"] >= r["ttft_s"] >= 0
        assert r["inter_token"]["count"] == 1
    # double-finish is idempotent; unknown rids are ignored
    tr.finish(5, "error", error="late")
    tr.token(99)
    assert tr.dump()[0]["status"] == "retired"
    tr.close()
    events = [json.loads(line) for line in ev.read_text().splitlines()]
    assert len(events) == 5 * 4      # admitted/prefill/first_token/retired
    assert {e["event"] for e in events} == {
        "admitted", "prefill", "first_token", "retired"}
    assert all("ts" in e and "rid" in e for e in events)


def test_tracer_annotate_and_error_status():
    tr = RequestTracer(capacity=4, observe_metrics=False)
    tr.admit(1, 3, 5)
    tr.annotate(1, resumed=True, truncated=True, nonsense_key=1)
    tr.finish(1, "error", error="boom")
    rec = tr.dump()[0]
    assert rec["status"] == "error" and rec["error"] == "boom"
    assert rec["resumed"] and rec["truncated"]
    with pytest.raises(ValueError):
        tr.finish(1, "nope")


# -- tracer through the real engine ------------------------------------------


@pytest.fixture(scope="module")
def tiny_engine_setup():
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.models.llama.params import init_params
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params, ByteTokenizer(cfg.vocab_size)


def _greedy():
    from cake_tpu.ops.sampling import SamplingConfig
    return SamplingConfig(temperature=0.0, repeat_penalty=1.0)


def test_engine_lifecycle_queue_wait_under_full_batch(tiny_engine_setup,
                                                     tmp_path):
    """max_slots=1: the second request queues behind the first's whole
    generation, so its trace shows a strictly larger queue wait and a
    complete, ordered span sequence."""
    from cake_tpu.serve.engine import InferenceEngine
    cfg, params, tok = tiny_engine_setup
    ev = tmp_path / "ev.jsonl"
    eng = InferenceEngine(cfg, params, tok, max_slots=1, max_seq_len=96,
                          sampling=_greedy(), cache_dtype=jnp.float32,
                          trace_events=str(ev))
    with eng:
        ha = eng.submit(list(range(3, 12)), max_new_tokens=6)
        hb = eng.submit(list(range(4, 14)), max_new_tokens=3)
        assert ha.wait(300) and hb.wait(300)
    recs = {r["rid"]: r for r in eng.tracer.dump()}
    a = recs[ha._req.rid]
    b = recs[hb._req.rid]
    for r in (a, b):
        names = [s["name"] for s in r["spans"]]
        assert names == ["admitted", "queued", "prefill", "first_token",
                         "decode", "retired"], names
        offs = [s["offset_s"] for s in r["spans"]]
        assert offs == sorted(offs)
        assert r["status"] == "retired"
    assert a["output_tokens"] == len(ha._req.out_tokens)
    # b could only prefill after a retired: queue wait covers a's e2e
    assert b["queue_wait_s"] > 0
    assert b["queue_wait_s"] > a["queue_wait_s"]
    assert b["queue_wait_s"] >= a["e2e_s"] - a["queue_wait_s"] - 1.0
    events = [json.loads(line) for line in ev.read_text().splitlines()]
    assert [e["event"] for e in events
            if e["rid"] == b["rid"]] == ["admitted", "prefill",
                                         "first_token", "retired"]


def test_request_histograms_populate_from_engine(tiny_engine_setup):
    from cake_tpu.obs.tracing import (
        REQUEST_E2E, REQUEST_QUEUE_WAIT, REQUEST_TTFT,
    )
    from cake_tpu.serve.engine import InferenceEngine
    cfg, params, tok = tiny_engine_setup
    before = {h.name: h.count for h in (REQUEST_TTFT, REQUEST_E2E,
                                        REQUEST_QUEUE_WAIT)}
    eng = InferenceEngine(cfg, params, tok, max_slots=2, max_seq_len=96,
                          sampling=_greedy(), cache_dtype=jnp.float32)
    with eng:
        h = eng.submit(list(range(5, 15)), max_new_tokens=3)
        assert h.wait(300)
    for hist in (REQUEST_TTFT, REQUEST_E2E, REQUEST_QUEUE_WAIT):
        assert hist.count == before[hist.name] + 1, hist.name
    assert m.REGISTRY.get("cake_request_ttft_seconds") is not None


def test_cancelled_request_is_traced(tiny_engine_setup):
    from cake_tpu.serve.engine import InferenceEngine
    cfg, params, tok = tiny_engine_setup
    eng = InferenceEngine(cfg, params, tok, max_slots=1, max_seq_len=96,
                          sampling=_greedy(), cache_dtype=jnp.float32)
    with eng:
        h1 = eng.submit(list(range(3, 12)), max_new_tokens=4)
        h2 = eng.submit(list(range(3, 13)), max_new_tokens=4)
        eng.cancel(h2)
        assert h1.wait(300) and h2.wait(300)
    recs = {r["rid"]: r for r in eng.tracer.dump()}
    assert recs[h2._req.rid]["status"] == "cancelled"
    assert [s["name"] for s in recs[h2._req.rid]["spans"]][-1] == \
        "cancelled"


def test_sp_engine_lifecycle_traces(tiny_engine_setup):
    """The sp (sequence-parallel) engine path produces the same complete
    span records as the dense path — the acceptance criterion's 'both
    engine paths'."""
    from cake_tpu.parallel.context_parallel import (
        create_sp_engine_cache, make_sp_engine_step_fns, place_sp_params,
    )
    from cake_tpu.serve.engine import InferenceEngine
    cfg, params, tok = tiny_engine_setup
    from jax.sharding import Mesh
    CTX, TAIL = 32, 16
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    params_p = place_sp_params(mesh, cfg, params, tp=False)
    fns = make_sp_engine_step_fns(mesh, cfg, CTX, TAIL,
                                  kv_dtype=jnp.float32, params=params_p)
    cache = create_sp_engine_cache(mesh, cfg, 2, CTX, TAIL,
                                   kv_dtype=jnp.float32)
    eng = InferenceEngine(cfg, params_p, tok, max_slots=2,
                          max_seq_len=CTX + TAIL, sampling=_greedy(),
                          cache_dtype=jnp.float32, step_fns=fns,
                          cache=cache, prompt_limit=CTX,
                          decode_budget=TAIL)
    with eng:
        h = eng.submit(list(range(3, 15)), max_new_tokens=4)
        assert h.wait(600)
        assert len(h.token_ids) > 0
    rec = eng.tracer.dump()[0]
    assert rec["status"] == "retired"
    names = [s["name"] for s in rec["spans"]]
    assert names == ["admitted", "queued", "prefill", "first_token",
                     "decode", "retired"]
    assert rec["ttft_s"] > 0 and rec["e2e_s"] >= rec["ttft_s"]
    # the sp dispatch counters saw the prefill and decode programs
    disp = m.REGISTRY.get("cake_sp_dispatch_total")
    assert disp is not None
    assert disp.labels(op="prefill", mode="sp").value >= 1
    # (a single-host engine's decode steps run the sampled one-step
    # program of the third step fn)
    assert disp.labels(op="decode_scan", mode="sp").value >= 1


def test_engine_reset_failure_counter(tiny_engine_setup):
    """Satellite: a post-error reset that itself raises must stop the
    engine cleanly and bump cake_engine_reset_failures_total."""
    from cake_tpu.serve import engine as engine_mod
    from cake_tpu.serve.engine import InferenceEngine
    cfg, params, tok = tiny_engine_setup
    eng = InferenceEngine(cfg, params, tok, max_slots=1, max_seq_len=96,
                          sampling=_greedy(), cache_dtype=jnp.float32)
    before = engine_mod._RESET_FAILURES.value

    def bad_prefill(*a, **k):
        raise RuntimeError("injected iteration failure")

    def bad_reset():
        raise RuntimeError("injected reset failure")

    eng._prefill_slot = bad_prefill
    eng._do_prefill_batch = bad_prefill
    eng._reset_after_error = bad_reset
    with eng:
        h = eng.submit([5, 6, 7], max_new_tokens=2)
        assert h.wait(60)
        with pytest.raises(RuntimeError):
            h.text()
        # the engine thread must EXIT (cleanly stopped), not serve on
        eng._thread.join(30)
        assert not eng._thread.is_alive()
        assert eng._stop.is_set()
    assert engine_mod._RESET_FAILURES.value == before + 1
    assert eng.tracer.dump()[0]["status"] == "error"
