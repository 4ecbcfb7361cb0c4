"""Per-request explain (obs/timeline.py + engine.request_timeline).

THE acceptance pin: one timeline call on a request that was preempted,
had its pages spilled to the host tier and restored, and crossed a
live config switch shows all three causes in time order — the PR 5
(sched), PR 7 (kv tiering) and PR 9 (autotune) machinery stitched into
one view. Plus the TTFT original-arrival regression pins: a
recovery/switch resubmit re-enters prefill but must NOT reset the
TTFT/attainment clock."""

import re
import time

import pytest

import jax.numpy as jnp

from cake_tpu.obs.timeline import build_timeline

T = 64
PAGE = 16


@pytest.fixture(scope="module")
def params(tiny_config):
    import jax
    from cake_tpu.models.llama.params import init_params
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


def _engine(tiny_config, params, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.sched import SchedConfig
    from cake_tpu.serve.engine import InferenceEngine

    kw.setdefault("max_slots", 1)
    return InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        max_seq_len=T,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        cache_dtype=jnp.float32,
        sched_config=SchedConfig(preempt_budget=8),
        **kw)


def _wait_tokens(handle, n, timeout=120.0):
    t0 = time.perf_counter()
    while (len(handle._req.out_tokens) < n
           and time.perf_counter() - t0 < timeout):
        time.sleep(0.002)
    assert len(handle._req.out_tokens) >= n, "stream never got going"


# -- pure stitcher units ------------------------------------------------------


def _trace(spans, **over):
    t0 = 1000.0
    d = {"rid": 5, "status": "retired", "priority": "interactive",
         "config_epoch": 0, "prompt_tokens": 4, "max_new_tokens": 8,
         "output_tokens": 8, "queue_wait_s": 0.01, "ttft_s": 0.4,
         "e2e_s": 0.6,
         "spans": [{"name": n, "t": t0 + dt, "offset_s": dt}
                   for n, dt in spans]}
    d.update(over)
    return d


def test_build_timeline_merges_time_ordered():
    trace = _trace([("admitted", 0.0), ("queued", 0.0),
                    ("prefill", 0.1), ("first_token", 0.4),
                    ("retired", 0.6)])
    events = [
        {"seq": 2, "ts": 1000.3, "type": "kv_restore", "rid": 5,
         "pages": 2},
        {"seq": 1, "ts": 1000.05, "type": "preempted", "rid": 5,
         "reason": "slots"},
    ]
    steps = [{"step": 9, "ts": 1000.2, "kind": "mixed", "rows": 2,
              "wall_s": 0.01, "compiled": True, "rids": [5]}]
    tl = build_timeline(trace, events, steps)
    ts = [e["t"] for e in tl["timeline"]]
    assert ts == sorted(ts)
    names = [e["event"] for e in tl["timeline"]]
    assert names.index("preempted") < names.index("step:mixed") \
        < names.index("kv_restore") < names.index("first_token")
    assert tl["summary"]["causes"] == {
        "preempted": 1, "kv_restore": 1, "compiled_steps": 1}
    # both events landed before first_token: TTFT-attributable
    assert tl["summary"]["ttft_causes"] == {
        "preempted": 1, "kv_restore": 1}
    assert tl["rid"] == 5 and tl["summary"]["ttft_s"] == 0.4


def test_build_timeline_ttft_causes_window():
    trace = _trace([("admitted", 0.0), ("first_token", 0.2),
                    ("retired", 0.9)])
    events = [
        {"seq": 1, "ts": 1000.1, "type": "preempted", "rid": 5},
        {"seq": 2, "ts": 1000.5, "type": "reconfigured", "rid": 5},
    ]
    tl = build_timeline(trace, events)
    assert tl["summary"]["causes"] == {"preempted": 1,
                                       "reconfigured": 1}
    # the post-first-token switch is an e2e cause, not a TTFT cause
    assert tl["summary"]["ttft_causes"] == {"preempted": 1}


def test_build_timeline_no_events_no_steps():
    tl = build_timeline(_trace([("admitted", 0.0)]), [])
    assert tl["summary"]["causes"] == {}
    assert [e["source"] for e in tl["timeline"]] == ["trace"]


# -- THE acceptance: preempt + spill/restore + switch, one call --------------


def test_timeline_explains_preempt_spill_restore_switch(
        tiny_config, params):
    """Drive the PR 5/7/9 machinery against one batch request on a
    1-slot paged engine with a host tier, then explain it: the
    timeline must show preempted -> kv_spill -> kv_restore ->
    reconfigured in time order, with every entry wall-stamped."""
    eng = _engine(tiny_config, params, priority_classes=True,
                  preemption=True, kv_pages=8, kv_page_size=PAGE,
                  kv_host_pages=8)
    with eng:
        hb = eng.submit([5] * 9, max_new_tokens=24, temperature=0.0,
                        repeat_penalty=1.0, priority="batch")
        _wait_tokens(hb, 4)
        hi = eng.submit([2, 9, 4], max_new_tokens=3, temperature=0.0,
                        repeat_penalty=1.0, priority="interactive")
        assert hi.wait(timeout=300)
        # victim re-admitted and restored from the host tier (polled:
        # with a decode step in flight the victim may hold its eighth
        # token before it is preempted, so a token count says nothing)
        t0 = time.perf_counter()
        while (eng.stats.kv_restores < 1
               and time.perf_counter() - t0 < 120.0):
            time.sleep(0.002)
        assert eng.stats.kv_restores >= 1, "victim was not restored"
        # live config switch mid-stream (PR 9): fold + requeue
        assert eng.reconfigure({"slots": 2, "kv_pages": 8,
                                "kv_page_size": PAGE,
                                "paged_attn": "fold"})
        assert hb.wait(timeout=300)
        rid = hb._req.rid
        tl = eng.request_timeline(rid)

    assert tl is not None and tl["rid"] == rid
    causes = tl["summary"]["causes"]
    assert causes.get("preempted", 0) >= 1
    assert causes.get("kv_spill", 0) >= 1
    assert causes.get("kv_restore", 0) >= 1
    assert causes.get("reconfigured", 0) >= 1
    # one merged chronology, globally time-ordered
    ts = [e["t"] for e in tl["timeline"]]
    assert ts == sorted(ts)
    names = [e["event"] for e in tl["timeline"]]
    assert (names.index("preempted") < names.index("kv_restore")
            < names.index("reconfigured"))
    assert names.index("kv_spill") <= names.index("kv_restore")
    # the three streams all contributed entries
    sources = {e["source"] for e in tl["timeline"]}
    assert sources == {"trace", "events", "steps"}
    # unknown rid -> None (the API's 404)
    assert eng.request_timeline(999_999) is None


# -- TTFT original-arrival pins ----------------------------------------------


def _sched_ttft_count(cls="standard"):
    from cake_tpu.obs import metrics as m
    pat = re.compile(
        r'cake_sched_ttft_seconds_count\{class="%s"\} (\S+)' % cls)
    got = pat.findall(m.REGISTRY.render())
    return float(got[0]) if got else 0.0


def test_switch_resubmit_keeps_original_arrival(tiny_config, params):
    """A request queued across a config switch re-enters prefill via
    the fold, but TTFT keeps counting from the ORIGINAL admission —
    the requeue must not reset the clock (and must not re-admit: one
    admitted span, one first_token span, ONE cake_sched_ttft
    observation)."""
    n0 = _sched_ttft_count()
    eng = _engine(tiny_config, params)   # not started: submit queues
    h = eng.submit([5] * 6, max_new_tokens=4, temperature=0.0,
                   repeat_penalty=1.0)
    pause = 0.25
    time.sleep(pause)
    # sync path (no engine thread yet): folds/requeues the queued
    # request under the new slot count
    assert eng.reconfigure({"slots": 2})
    with eng:
        assert h.wait(timeout=300)
        rec = eng.tracer.get(h._req.rid)
    spans = [s["name"] for s in rec["spans"]]
    assert spans.count("admitted") == 1
    assert spans.count("first_token") == 1
    assert rec["ttft_s"] >= pause, \
        f"switch resubmit reset the TTFT clock: {rec['ttft_s']}"
    assert _sched_ttft_count() - n0 == 1.0
    # the SLO accountant judged it against the SAME original-arrival
    # TTFT (obs/slo.py rides the tracer record)
    assert eng.slo.requests["standard"] == 1


def test_recovery_resubmit_keeps_original_arrival(tiny_config, params):
    """A crash-recovery resubmit (PR 8 fold) re-enters prefill with
    tokens already emitted: no second admitted/first_token span, no
    second cake_sched_ttft observation, and the recovered request's
    e2e keeps counting from the original admission."""
    from cake_tpu.serve.errors import RecoveryConfig
    n0 = _sched_ttft_count()
    eng = _engine(tiny_config, params,
                  fault_plan="seed=5;engine.decode:nth=3:transient",
                  recovery_config=RecoveryConfig(backoff_base_s=0.05))
    with eng:
        h = eng.submit([7] * 6, max_new_tokens=8, temperature=0.0,
                       repeat_penalty=1.0)
        assert h.wait(timeout=300)
        assert h._req.error is None
        assert eng.stats.recoveries >= 1, "no crash was recovered"
        rec = eng.tracer.get(h._req.rid)
        evs = eng.events.dump(rid=h._req.rid, type="recovered")
    spans = [s["name"] for s in rec["spans"]]
    assert spans.count("admitted") == 1
    assert spans.count("first_token") == 1
    assert "crash_recovered" in spans
    assert len(evs) >= 1 and evs[0]["rid"] == h._req.rid
    assert _sched_ttft_count() - n0 == 1.0
    assert rec["e2e_s"] >= rec["ttft_s"]
