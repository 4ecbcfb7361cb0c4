"""Checkpoint/resume and failure-detection subsystems.

Checkpoint correctness target: a greedy generation interrupted mid-flight
and resumed in a NEW engine instance produces exactly the transcript the
uninterrupted run produces (re-prefill of prompt+generated rebuilds the KV
deterministically).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.params import init_params
from cake_tpu.ops.sampling import SamplingConfig

CFG = LlamaConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _engine(params, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.serve.engine import InferenceEngine
    return InferenceEngine(
        CFG, params, ByteTokenizer(CFG.vocab_size), max_slots=2,
        max_seq_len=128,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0), **kw)


PROMPT = [5, 6, 7, 8, 9]
N_TOK = 12


def _interrupt_at(eng, n):
    """Arm `eng` to stop once a request holds n tokens; call before the
    submit, then _stopped(eng). The flag is set on the engine thread
    itself (a hook on _emit), so the interrupt lands mid-generation
    however fast the loop decodes: a 10 ms poll from the test's thread
    lost that race once a decode step was kept in flight (PR 29: twelve
    tokens of the tiny model take a few milliseconds)."""
    emit = eng._emit

    def hooked(req, *a, **kw):
        emit(req, *a, **kw)
        if len(req.out_tokens) >= n:
            eng._stop.set()

    eng._emit = hooked


def _stopped(eng):
    deadline = time.time() + 60
    while not eng._stop.is_set() and time.time() < deadline:
        time.sleep(0.001)
    eng.stop()


def test_checkpoint_resume_matches_uninterrupted(params, tmp_path):
    from cake_tpu.serve import checkpoint

    # uninterrupted reference transcript
    with _engine(params).start() as eng:
        h = eng.submit(PROMPT, max_new_tokens=N_TOK)
        assert h.wait(60)
        want = h.token_ids

    # interrupted run: stop mid-generation, snapshot, restore elsewhere
    eng1 = _engine(params).start()
    _interrupt_at(eng1, 4)
    h1 = eng1.submit(PROMPT, max_new_tokens=N_TOK)
    _stopped(eng1)
    got_before = h1.token_ids
    assert 0 < len(got_before) < N_TOK, "expected a mid-flight interrupt"
    path = str(tmp_path / "engine.ckpt")
    checkpoint.save(eng1, path)

    eng2 = _engine(params).start()
    try:
        handles, finished = checkpoint.restore(eng2, path)
        assert len(handles) == 1 and not finished
        assert handles[0].wait(60)
        assert got_before + handles[0].token_ids == want
    finally:
        eng2.stop()


def test_snapshot_empty_after_completion_and_finished_records_skip(params):
    """Completed requests leave the engine (transcripts live with their
    callers), so a quiesced idle engine snapshots empty; records marked
    finished in a snapshot are returned, not resubmitted."""
    from cake_tpu.serve import checkpoint

    with _engine(params).start() as eng:
        h = eng.submit(PROMPT, max_new_tokens=4)
        assert h.wait(60)
        snap = checkpoint.snapshot(eng)
    assert snap["requests"] == []

    done_rec = {"rid": 1, "prompt_ids": PROMPT, "out_tokens": [1, 2],
                "remaining": 0, "temperature": 0.0, "top_p": 1.0,
                "repeat_penalty": 1.0, "finished": True, "error": None}
    snap["requests"] = [done_rec]
    with _engine(params).start() as eng2:
        handles, finished = checkpoint.resume(eng2, snap)
    assert handles == [] and finished == [done_rec]


def test_checkpoint_fingerprint_mismatch_raises(params, tmp_path):
    from cake_tpu.serve import checkpoint

    eng = _engine(params)
    snap = checkpoint.snapshot(eng)
    snap["engine"]["hidden_size"] = 999
    with pytest.raises(ValueError):
        checkpoint.resume(eng, snap)
    # non-strict downgrade to warning
    handles, _ = checkpoint.resume(eng, snap, strict=False)
    assert handles == []


def test_server_restores_checkpoint_on_start(params, tmp_path):
    """api.start(checkpoint_path=...) resumes a previous shutdown's
    in-flight requests into the fresh engine."""
    from cake_tpu.api.server import start
    from cake_tpu.args import Args
    from cake_tpu.master import Master
    from cake_tpu.serve import checkpoint

    # produce a genuine interrupted-run snapshot (v2 fingerprints include a
    # params digest, so hand-written records can't fake one)
    eng0 = _engine(params).start()
    # a budget the engine cannot finish between polls: with the jit
    # cache warm from earlier modules, a 6-token request could retire
    # inside one 10ms sleep, leaving nothing in flight to snapshot
    h0 = eng0.submit(PROMPT, max_new_tokens=40)
    deadline = time.time() + 60
    while len(h0.token_ids) < 2 and time.time() < deadline:
        time.sleep(0.001)
    eng0.stop()
    assert 0 < len(h0.token_ids) < 40
    path = tmp_path / "server.ckpt"
    checkpoint.save(eng0, str(path))

    engine = _engine(params)
    from cake_tpu.models.llama.generator import ByteTokenizer, LlamaGenerator
    from cake_tpu.ops.sampling import SamplingConfig as SC
    gen = LlamaGenerator(CFG, params, ByteTokenizer(CFG.vocab_size),
                         max_seq_len=128, batch_size=1,
                         sampling=SC(temperature=0.0, repeat_penalty=1.0))
    master = Master(Args(), text_generator=gen)
    httpd = start(master, address="127.0.0.1:0", block=False,
                  engine=engine, checkpoint_path=str(path))
    try:
        deadline = time.time() + 60
        while engine.stats.requests_completed < 1 and time.time() < deadline:
            time.sleep(0.05)
        assert engine.stats.requests_completed == 1
    finally:
        httpd.shutdown()
        engine.stop()


def test_probe_devices_ok_on_cpu():
    from cake_tpu.parallel.health import probe_devices

    reports = probe_devices(timeout_s=30.0)
    assert reports and all(r.ok for r in reports)


def test_heartbeat_detects_lost_worker():
    from cake_tpu.parallel.health import HeartbeatMonitor, HeartbeatSender

    lost = []
    mon = HeartbeatMonitor(on_failure=lost.append, stale_after_s=0.6,
                           sweep_interval_s=0.1)
    try:
        a = HeartbeatSender(mon.address, "worker-a", interval_s=0.1)
        b = HeartbeatSender(mon.address, "worker-b", interval_s=0.1)
        deadline = time.time() + 5
        while (len(mon.last_seen) < 2) and time.time() < deadline:
            time.sleep(0.05)
        assert set(mon.last_seen) == {"worker-a", "worker-b"}
        assert mon.stale() == []

        b.close()  # worker-b dies
        deadline = time.time() + 5
        while "worker-b" not in lost and time.time() < deadline:
            time.sleep(0.05)
        assert lost == ["worker-b"]
        assert mon.stale() == ["worker-b"]
        a.close()
    finally:
        mon.close()


def test_watchdog_fires_on_stall_and_rearms():
    from cake_tpu.parallel.health import Watchdog

    value = [0]
    active = [False]
    stalls = []
    wd = Watchdog(lambda: value[0], stall_after_s=0.3,
                  on_stall=lambda: stalls.append(time.monotonic()),
                  active=lambda: active[0],
                  poll_interval_s=0.05)
    try:
        # idle (no active work), never-advanced counter -> no stall
        time.sleep(0.6)
        assert stalls == []
        # progress -> no stall
        active[0] = True
        for _ in range(5):
            value[0] += 1
            time.sleep(0.05)
        assert stalls == []
        # stop advancing -> exactly one firing
        time.sleep(0.8)
        assert len(stalls) == 1
        # progress resumes, then stalls again -> re-arms
        value[0] += 1
        time.sleep(0.8)
        assert len(stalls) == 2
    finally:
        wd.close()


def test_watchdog_fires_before_first_token():
    """A request that hangs before the counter EVER advances (wedged
    compile, dead device — the exact failure the watchdog exists for)
    must still fire: the stall clock starts when active() flips on, not
    at the first counter advance (round-4 advisor finding)."""
    from cake_tpu.parallel.health import Watchdog

    value = [0]
    active = [False]
    stalls = []
    wd = Watchdog(lambda: value[0], stall_after_s=0.3,
                  on_stall=lambda: stalls.append(time.monotonic()),
                  active=lambda: active[0], poll_interval_s=0.05)
    try:
        time.sleep(0.5)   # idle: the deadline keeps refreshing
        assert stalls == []
        active[0] = True  # request admitted; first token never comes
        time.sleep(0.8)
        assert len(stalls) == 1
        # the idle interval between requests ends the stall episode: a
        # SECOND request that also wedges pre-first-token (counter still
        # never advanced) must fire again, not be eaten by the latch
        active[0] = False
        time.sleep(0.3)
        active[0] = True
        time.sleep(0.8)
        assert len(stalls) == 2
    finally:
        wd.close()


# -- round-3 regression tests (round-1 advisor findings) ----------------------

def test_checkpoint_fingerprint_detects_different_weights(params, tmp_path):
    """Shape-only fingerprints let a snapshot resume into any model with
    identical dims; the digest must reject different weights."""
    from cake_tpu.serve import checkpoint

    with _engine(params).start() as eng:
        h = eng.submit(PROMPT, max_new_tokens=4)
        assert h.wait(60)
    path = str(tmp_path / "fp.ckpt")
    checkpoint.save(eng, path)

    other = init_params(CFG, jax.random.PRNGKey(99), dtype=jnp.float32)
    eng2 = _engine(other).start()
    try:
        with pytest.raises(ValueError, match="fingerprint"):
            checkpoint.restore(eng2, path, strict=True)
    finally:
        eng2.stop()


def test_resume_primes_repeat_penalty_ring(params, tmp_path):
    """Greedy + repeat_penalty: interrupted-and-resumed transcript must
    equal the uninterrupted one (the ring is reconstructed, not emptied)."""
    from cake_tpu.serve import checkpoint

    sampling = SamplingConfig(temperature=0.0, repeat_penalty=1.3,
                              repeat_last_n=8)

    def mk():
        from cake_tpu.models.llama.generator import ByteTokenizer
        from cake_tpu.serve.engine import InferenceEngine
        return InferenceEngine(
            CFG, params, ByteTokenizer(CFG.vocab_size), max_slots=2,
            max_seq_len=128, sampling=sampling)

    with mk().start() as eng:
        h = eng.submit(PROMPT, max_new_tokens=N_TOK, repeat_penalty=1.3)
        assert h.wait(60)
        want = h.token_ids

    eng1 = mk().start()
    _interrupt_at(eng1, 5)
    h1 = eng1.submit(PROMPT, max_new_tokens=N_TOK, repeat_penalty=1.3)
    _stopped(eng1)
    assert 0 < len(h1.token_ids) < N_TOK
    path = str(tmp_path / "ring.ckpt")
    checkpoint.save(eng1, path)

    eng2 = mk().start()
    try:
        handles, _ = checkpoint.restore(eng2, path)
        assert len(handles) == 1
        assert handles[0].wait(60)
        got = h1.token_ids + handles[0].token_ids
        assert got == want, (got, want)
    finally:
        eng2.stop()


def test_heartbeat_detects_never_started_worker():
    """A worker registered as expected but never beating must be reported
    (health.py roster gap: last_seen-only iteration misses it)."""
    from cake_tpu.parallel.health import HeartbeatMonitor, HeartbeatSender

    failures = []
    mon = HeartbeatMonitor(on_failure=failures.append,
                           stale_after_s=0.4, sweep_interval_s=0.1,
                           expected=["alive", "neverstarted"])
    try:
        s = HeartbeatSender(mon.address, "alive", interval_s=0.1)
        deadline = time.time() + 10
        while "neverstarted" not in failures and time.time() < deadline:
            time.sleep(0.05)
        assert "neverstarted" in failures
        assert "alive" not in failures
        s.close()
    finally:
        mon.close()


def test_sigterm_handler_chains_previous(params, tmp_path, monkeypatch):
    """start()'s SIGTERM hook must invoke the previously-installed handler
    instead of clobbering it (api/server.py round-1 finding)."""
    import signal

    from cake_tpu.api.server import start
    from cake_tpu.master import Master
    from cake_tpu.args import Args

    calls = []
    prev = lambda signum, frame: calls.append("prev")  # noqa: E731
    old = signal.signal(signal.SIGTERM, prev)
    try:
        from cake_tpu.models.llama.generator import (
            ByteTokenizer, LlamaGenerator,
        )
        gen = LlamaGenerator(
            CFG, params, ByteTokenizer(CFG.vocab_size), max_seq_len=128,
            sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0))
        master = Master(Args(), text_generator=gen)
        path = str(tmp_path / "sig.ckpt")
        httpd = start(master, address="127.0.0.1:0", block=False,
                      checkpoint_path=path)
        handler = signal.getsignal(signal.SIGTERM)
        assert handler is not prev, "hook not installed"
        handler(signal.SIGTERM, None)  # simulate delivery
        assert calls == ["prev"], "previous handler was not chained"
        assert np.asarray([1]).size  # keep np import used
        httpd.shutdown()
    finally:
        signal.signal(signal.SIGTERM, old)


def test_double_interrupt_preserves_penalty_window(params, tmp_path):
    """A request interrupted and resumed TWICE still reconstructs the
    penalty ring over its whole transcript (snapshot records
    penalty_context = prime + out, not just the latest leg)."""
    from cake_tpu.serve import checkpoint

    sampling = SamplingConfig(temperature=0.0, repeat_penalty=1.3,
                              repeat_last_n=8)

    def mk():
        from cake_tpu.models.llama.generator import ByteTokenizer
        from cake_tpu.serve.engine import InferenceEngine
        return InferenceEngine(
            CFG, params, ByteTokenizer(CFG.vocab_size), max_slots=2,
            max_seq_len=128, sampling=sampling)

    with mk().start() as eng:
        h = eng.submit(PROMPT, max_new_tokens=N_TOK, repeat_penalty=1.3)
        assert h.wait(60)
        want = h.token_ids

    transcript = []
    eng1 = mk().start()
    _interrupt_at(eng1, 4)
    h1 = eng1.submit(PROMPT, max_new_tokens=N_TOK, repeat_penalty=1.3)
    _stopped(eng1)
    assert 4 <= len(h1.token_ids) < N_TOK
    transcript += h1.token_ids
    p1 = str(tmp_path / "leg1.ckpt")
    checkpoint.save(eng1, p1)

    eng2 = mk().start()
    _interrupt_at(eng2, 2)
    h2s, _ = checkpoint.restore(eng2, p1)
    _stopped(eng2)
    assert len(h2s[0].token_ids) >= 2
    transcript += h2s[0].token_ids
    p2 = str(tmp_path / "leg2.ckpt")
    checkpoint.save(eng2, p2)

    eng3 = mk().start()
    try:
        h3s, _ = checkpoint.restore(eng3, p2)
        if h3s:  # leg 2 may already have finished the budget
            assert h3s[0].wait(60)
            transcript += h3s[0].token_ids
    finally:
        eng3.stop()
    assert transcript == want, (transcript, want)


def test_serving_health_fails_engine_on_heartbeat_loss(params):
    """The verdict-#7 wiring: a lapsed worker heartbeat flips serving
    health, drains (fails) in-flight requests, and the API starts
    returning 503s instead of hanging on a dead mesh."""
    import json
    import urllib.error
    import urllib.request

    from cake_tpu.api.server import start
    from cake_tpu.args import Args
    from cake_tpu.master import Master
    from cake_tpu.parallel.health import HeartbeatSender, ServingHealth

    eng = _engine(params)
    health = ServingHealth(eng, stall_after_s=3600)  # watchdog idle here
    hb = health.expect_workers(["w1"], stale_after_s=0.6)
    sender = HeartbeatSender(hb, "w1", interval_s=0.1)

    master = Master(Args(sample_len=4), text_generator=None)
    master.llm = object()  # present but unused: engine passed explicitly
    httpd = start(master, address="127.0.0.1:0", block=False, engine=eng,
                  health=health)
    base = "http://%s:%d" % httpd.server_address[:2]
    try:
        h = json.loads(urllib.request.urlopen(
            base + "/api/v1/health", timeout=10).read())
        assert h["status"] == "ok"

        # an in-flight request held open by a slow stream consumer
        slow = eng.submit(PROMPT, max_new_tokens=64,
                          stream=lambda d, f: time.sleep(0.25))

        sender.close()              # the worker "dies"
        deadline = time.time() + 10
        while time.time() < deadline:
            h = json.loads(urllib.request.urlopen(
                base + "/api/v1/health", timeout=10).read())
            if h["status"] == "failed":
                break
            time.sleep(0.2)
        assert h["status"] == "failed"
        assert "w1" in h["reason"]

        # the in-flight request was drained with an error, not left hanging
        assert slow.wait(timeout=10)
        with pytest.raises(RuntimeError, match="heartbeat lost"):
            slow.text()

        # new work is rejected with 503 + the reason
        with pytest.raises(urllib.error.HTTPError) as e:
            req = urllib.request.Request(
                base + "/api/v1/chat/completions",
                data=json.dumps({"messages": [
                    {"role": "user", "content": "x"}]}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 503
        assert b"heartbeat lost" in e.value.read()

        # metrics reflect the flip
        body = urllib.request.urlopen(base + "/metrics",
                                      timeout=10).read().decode()
        assert "cake_serving_healthy 0" in body
    finally:
        httpd.shutdown()
        eng.stop()
        health.close()
