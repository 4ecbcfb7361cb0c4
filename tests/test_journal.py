"""Durable serving (serve/journal.py): the write-ahead request journal,
kill -9 crash drills via the fault-plan `abort` kind, cold-restart
replay token identity (dense AND paged with shared prefixes, pool
conserved), idempotent submits, SSE Last-Event-ID resume across a
restart, the drain endpoint, atomic checkpoint writes, and the
tools/journal_check.py rc contract."""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.serve.journal import (
    RequestJournal, read_records, recover, replay_state,
)

REPO = pathlib.Path(__file__).resolve().parents[1]
TOOLS = REPO / "tools"
T = 64
PAGE = 16
P1 = [5] * 9
P2 = [2, 9, 4, 7, 3]
GEN = 12


def _load_tool():
    spec = importlib.util.spec_from_file_location(
        "journal_check", TOOLS / "journal_check.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params(tiny_config):
    from cake_tpu.models.llama.params import init_params
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


def _engine(tiny_config, params, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", T)
    return InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        # f32 KV: greedy token identity must exercise the replay fold,
        # not bf16 tie-breaks
        cache_dtype=jnp.float32,
        **kw)


def _abandon_at(engine, hs, n):
    """Start the engine on what is queued, and _abandon it once every
    stream of `hs` holds n tokens. The stop is
    set on the engine thread itself, from a hook on _emit, so it lands
    mid-generation however fast the loop decodes: a poll from the
    test's thread lost that race now and then once a decode step was
    kept in flight (PR 29: twelve tokens take a few milliseconds)."""
    assert engine._thread is None, "queue the requests, then abandon"
    emit = engine._emit

    def hooked(req, *a, **kw):
        emit(req, *a, **kw)
        if min(len(h._req.out_tokens) for h in hs) >= n:
            engine._stop.set()

    engine._emit = hooked
    # started here, with everything queued: the streams are admitted
    # in one iteration and advance together
    engine.start()
    engine._thread.join(120)
    assert not engine._thread.is_alive()
    _abandon(engine)


def _abandon(engine):
    """Simulate a hard death for an in-process engine: stop the loop
    WITHOUT any retire/teardown path running (no tombstones, no
    snapshot) and flush what the journal already buffered — the state
    a kill -9 leaves behind, minus the current iteration's batch."""
    engine._stop.set()
    engine._wake.set()
    if engine._thread is not None:
        engine._thread.join(10)
    engine._journal.close()


@pytest.fixture(scope="module")
def dense_clean(tiny_config, params):
    eng = _engine(tiny_config, params)
    with eng:
        hs = [eng.submit(list(p), max_new_tokens=GEN) for p in (P1, P2)]
        assert all(h.wait(timeout=600) for h in hs)
        return [list(h._req.out_tokens) for h in hs]


# -- record grammar / replay_state (pure, no engine) -------------------------

def _admit(rid, ids, max_new=GEN, key=None):
    return {"rec": "admit", "rid": rid, "ids": list(ids),
            "max_new": max_new, "temp": 0.0, "top_p": 1.0, "pen": 1.0,
            "prime": [], "prio": "standard", "key": key, "epoch": 0}


def test_replay_state_reconstructs_and_finalizes():
    recs, findings, header = replay_state([
        {"rec": "start", "v": 1, "fp": None},
        _admit(1, P1, key="k"),
        {"rec": "emit", "rid": 1, "toks": [7, 8], "n": 2},
        {"rec": "emit", "rid": 1, "toks": [9], "n": 3},
        _admit(2, P2),
        {"rec": "retire", "rid": 2, "status": "cancelled"},
    ])
    assert not findings and header["v"] == 1
    by = {r["rid"]: r for r in recs}
    assert by[1]["out_tokens"] == [7, 8, 9]
    assert by[1]["remaining"] == GEN - 3
    assert by[1]["idempotency_key"] == "k"
    assert by[1]["penalty_context"] == [7, 8, 9]
    assert not by[1]["finished"]
    assert by[2]["finished"]
    from cake_tpu.serve.checkpoint import is_resumable
    assert is_resumable(by[1]) and not is_resumable(by[2])


def test_replay_state_emit_overlap_reconciles_by_cumulative_count():
    # a re-flushed batch overlapping the previous one (crash between
    # append and buffer clear) reconciles via n, not blind extend
    recs, findings, _ = replay_state([
        _admit(1, P1),
        {"rec": "emit", "rid": 1, "toks": [7, 8], "n": 2},
        {"rec": "emit", "rid": 1, "toks": [8, 9], "n": 3},
    ])
    assert recs[0]["out_tokens"] == [7, 8, 9]
    assert not findings


def test_replay_state_findings():
    recs, findings, _ = replay_state([
        {"rec": "emit", "rid": 9, "toks": [1], "n": 1},      # orphan
        _admit(1, P1),
        _admit(1, P1),                                       # duplicate
        {"rec": "emit", "rid": 1, "toks": [5], "n": 4},      # gap
        {"rec": "retire", "rid": 1, "status": "retired"},
        {"rec": "emit", "rid": 1, "toks": [6], "n": 5},      # post-retire
        {"rec": "bogus", "rid": 1},                          # unknown
    ])
    text = "\n".join(findings)
    assert "orphaned emit" in text
    assert "duplicate admit" in text
    assert "does not extend" in text
    assert "emit after retire" in text
    assert "unknown record type" in text


def test_read_records_torn_tail_vs_midfile_corruption(tmp_path):
    p = tmp_path / "j.journal"
    good = json.dumps(_admit(1, P1))
    p.write_text(good + "\n{broken mid}\n" + good + "\n" + '{"rec": "em')
    records, bad, torn = read_records(str(p))
    assert len(records) == 2
    assert bad == 1            # the mid-file line only
    assert torn is True        # the unterminated tail is separate
    assert read_records(str(tmp_path / "missing"))[0] == []


def test_journal_fsync_mode_validated(tmp_path):
    with pytest.raises(ValueError, match="journal-fsync"):
        RequestJournal(str(tmp_path / "j"), fsync="sometimes")
    from cake_tpu.args import Args
    with pytest.raises(ValueError, match="journal_fsync"):
        Args(journal_fsync="sometimes").validate()


# -- journal_check CLI (satellite: rc 0/1/2 contract) ------------------------

def test_journal_check_rc_contract(tmp_path, capsys):
    tool = _load_tool()
    clean = tmp_path / "clean.journal"
    clean.write_text(
        json.dumps({"rec": "start", "v": 1, "fp": None}) + "\n"
        + json.dumps(_admit(1, P1, key="k")) + "\n"
        + json.dumps({"rec": "emit", "rid": 1, "toks": [7], "n": 1})
        + "\n" + '{"rec": "emi')      # torn tail: tolerated, rc 0
    assert tool.main([str(clean)]) == 0
    out = capsys.readouterr().out
    assert "torn tail tolerated" in out and "1 request(s) would resume" in out

    dirty = tmp_path / "dirty.journal"
    dirty.write_text(
        json.dumps({"rec": "emit", "rid": 9, "toks": [1], "n": 1}) + "\n")
    assert tool.main([str(dirty), "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["rc"] == 1 and any("orphaned" in f
                                  for f in doc["findings"])

    assert tool.main([str(tmp_path / "nope.journal")]) == 2
    assert tool.main([]) == 2      # usage


# -- fault plane: abort kind + journal sites ---------------------------------

def test_abort_error_kind_and_journal_sites_parse():
    from cake_tpu.faults import ABORT_EXIT_CODE, ERRORS, SITES, FaultPlan
    assert "abort" in ERRORS and ABORT_EXIT_CODE == 86
    for site in ("journal.append", "journal.fsync", "journal.replay"):
        assert site in SITES
    plan = FaultPlan.parse("journal.append:nth=3:abort")
    assert plan.rules[0].error == "abort"


def test_journal_fault_sites_fire(tmp_path):
    from cake_tpu.faults import build_injector
    from cake_tpu.faults.plan import InjectedTransient
    j = RequestJournal(str(tmp_path / "j.journal"), fsync="always")
    j.faults = build_injector("journal.fsync:nth=1:transient")

    class _Req:
        rid, prompt_ids, max_new_tokens = 1, P1, GEN
        temperature, top_p, repeat_penalty = 0.0, 1.0, 1.0
        prime_tokens, priority = [], "standard"
        idempotency_key, replayed_tokens = None, []
    with pytest.raises(InjectedTransient):
        j.note_admit(_Req())       # fsync=always syncs per append
    j2 = RequestJournal(str(tmp_path / "j2.journal"))
    j2.faults = build_injector("journal.append:nth=1:transient")
    with pytest.raises(InjectedTransient):
        j2.note_admit(_Req())


def test_journal_call_sites_are_attribute_guarded():
    """The PR 8 injector discipline extended to the journal: every
    engine call into self._journal, and every fault-site check inside
    journal.py, sits behind an `is not None` guard."""
    import cake_tpu.serve.engine as engine
    import cake_tpu.serve.journal as journal
    src = open(engine.__file__).readlines()
    needles = [i for i, ln in enumerate(src)
               if "self._journal." in ln and "self._journal = " not in ln]
    assert needles, "no journal call sites found in engine.py"
    for i in needles:
        window = "".join(src[max(0, i - 8):i + 1])
        # the construction block (`if journal:` in __init__) is the one
        # legitimate unguarded touch — it CREATES the attribute
        assert ("_journal is not None" in window
                or "self._journal = RequestJournal" in window), (
            f"engine.py:{i + 1} touches self._journal without an "
            "`is not None` guard — the disabled journal must stay a "
            "single attribute test")
    jsrc = open(journal.__file__).readlines()
    jneedles = [i for i, ln in enumerate(jsrc) if "faults.check(" in ln]
    assert jneedles, "no fault sites found in journal.py"
    for i in jneedles:
        window = "".join(jsrc[max(0, i - 4):i + 1])
        assert "faults is not None" in window, (
            f"journal.py:{i + 1} calls faults.check() without an "
            "`is not None` guard")


# -- engine acceptance: replay token identity --------------------------------

def test_dense_replay_token_identical_after_abandon(
        tiny_config, params, tmp_path, dense_clean):
    jpath = str(tmp_path / "dense.journal")
    engA = _engine(tiny_config, params, journal=jpath)
    hs = [engA.submit(list(P1), max_new_tokens=GEN,
                      idempotency_key="key-1"),
          engA.submit(list(P2), max_new_tokens=GEN)]
    _abandon_at(engA, hs, 4)

    engB = _engine(tiny_config, params, journal=jpath)
    engB.start()
    try:
        handles, finished = recover(engB)
        assert len(handles) == 2 and not finished
        assert all(h.wait(timeout=600) for h in handles)
        full = [list(h._req.replayed_tokens) + list(h._req.out_tokens)
                for h in handles]
        assert full == dense_clean
        # the key survived the restart: a retry attaches to the
        # completed stream, no third admission
        before = engB.stats.requests_completed
        h2 = engB.submit([1, 2, 3], max_new_tokens=4,
                         idempotency_key="key-1")
        assert getattr(h2, "attached", False)
        assert (list(h2._req.replayed_tokens)
                + list(h2._req.out_tokens)) == dense_clean[0]
        assert engB.stats.requests_completed == before
        # health-block state reports the replay
        st = engB._journal.state()
        assert st["last_replay"]["replayed"] == 2
        assert st["last_replay"]["dropped"] == 0
    finally:
        engB.stop()


def test_paged_shared_prefix_replay_identical_and_pool_conserved(
        tiny_config, params, tmp_path):
    prefix = [7] * PAGE
    kw = dict(kv_pages=16, kv_page_size=PAGE, paged_attn="fold")

    def submit_wave(eng):
        pid = eng.register_prefix(prefix)
        hs = [eng.submit(prefix + list(P1), max_new_tokens=GEN),
              eng.submit(list(P2), max_new_tokens=GEN)]
        return pid, hs

    clean_eng = _engine(tiny_config, params, **kw)
    with clean_eng:
        _, hs = submit_wave(clean_eng)
        assert all(h.wait(timeout=600) for h in hs)
        clean = [list(h._req.out_tokens) for h in hs]

    jpath = str(tmp_path / "paged.journal")
    engA = _engine(tiny_config, params, journal=jpath, **kw)
    _, hs = submit_wave(engA)
    _abandon_at(engA, hs, 3)

    engB = _engine(tiny_config, params, journal=jpath, **kw)
    engB.start()
    try:
        # the prefix registration is NOT journaled (it holds no client
        # work); re-register like a restarted operator/auto-prefix does
        engB.register_prefix(prefix)
        handles, _ = recover(engB)
        assert len(handles) == 2
        assert all(h.wait(timeout=600) for h in handles)
        full = [list(h._req.replayed_tokens) + list(h._req.out_tokens)
                for h in handles]
        assert full == clean
        # pool conserved: all non-registry pages free after drain
        pager = engB._pager
        assert pager.free_pages + len(prefix) // PAGE == engB.cache.n_pages
    finally:
        engB.stop()


def test_checkpoint_handshake_truncates_journal(
        tiny_config, params, tmp_path):
    jpath = str(tmp_path / "hs.journal")
    ck = str(tmp_path / "hs.ckpt")
    eng = _engine(tiny_config, params, journal=jpath)
    eng.start()
    h = eng.submit(list(P1), max_new_tokens=GEN)
    assert h.wait(timeout=600)
    eng.stop()
    assert os.path.getsize(jpath) > 0
    eng.shutdown_save(ck)
    # the snapshot owns everything journaled before it: truncated
    assert os.path.getsize(jpath) == 0
    assert os.path.exists(ck)


def test_size_triggered_compaction_preserves_replay(
        tiny_config, params, tmp_path, dense_clean):
    jpath = str(tmp_path / "compact.journal")
    engA = _engine(tiny_config, params, journal=jpath)
    # force a compaction on nearly every iteration
    engA._journal.compact_bytes = 1
    hs = [engA.submit(list(P1), max_new_tokens=GEN),
          engA.submit(list(P2), max_new_tokens=GEN)]
    _abandon_at(engA, hs, 4)
    assert engA._journal.compactions > 0
    records, bad, _torn = read_records(jpath)
    assert bad == 0
    # compacted: one admit (+ optional emit) per live request + header
    engB = _engine(tiny_config, params, journal=jpath)
    engB.start()
    try:
        handles, _ = recover(engB)
        assert all(h.wait(timeout=600) for h in handles)
        full = [list(h._req.replayed_tokens) + list(h._req.out_tokens)
                for h in handles]
        assert full == dense_clean
    finally:
        engB.stop()


def test_idempotent_submit_never_double_admits(tiny_config, params):
    eng = _engine(tiny_config, params)
    with eng:
        h1 = eng.submit(list(P1), max_new_tokens=GEN,
                        idempotency_key="dup")
        h2 = eng.submit(list(P1), max_new_tokens=GEN,
                        idempotency_key="dup")
        assert getattr(h2, "attached", False)
        assert h2._req is h1._req
        assert h1.wait(timeout=600)
        # post-retirement retry attaches to the finished transcript
        h3 = eng.submit(list(P1), max_new_tokens=GEN,
                        idempotency_key="dup")
        assert getattr(h3, "attached", False)
        assert h3._req.out_tokens == h1._req.out_tokens
        assert eng.stats.requests_completed == 1


def test_stale_consumed_sideline_does_not_truncate_live_journal(
        tiny_config, params, tmp_path, dense_clean):
    """Review regression: a consumed `.replaying` whose removal failed
    must NOT make the next startup discard the live journal — the
    replay_done marker (written into the fresh journal at recovery)
    disambiguates it from a crashed-mid-recovery sideline."""
    jpath = str(tmp_path / "stale.journal")
    engA = _engine(tiny_config, params, journal=jpath)
    hs = [engA.submit(list(P1), max_new_tokens=GEN),
          engA.submit(list(P2), max_new_tokens=GEN)]
    _abandon_at(engA, hs, 4)
    # simulate "removal failed": plant a STALE sideline (old state)
    # next to a live journal that carries the consumed marker
    stale = json.dumps(_admit(999, [1, 2, 3])) + "\n"
    (tmp_path / "stale.journal.replaying").write_text(stale)
    live = (tmp_path / "stale.journal").read_text()
    (tmp_path / "stale.journal").write_text(
        json.dumps({"rec": "replay_done"}) + "\n" + live)
    engB = _engine(tiny_config, params, journal=jpath)
    engB.start()
    try:
        handles, _ = recover(engB)
        # the LIVE journal replayed (2 real streams), the stale
        # sideline's rid 999 did not
        assert len(handles) == 2
        assert all(h.wait(timeout=600) for h in handles)
        full = [list(h._req.replayed_tokens) + list(h._req.out_tokens)
                for h in handles]
        assert full == dense_clean
    finally:
        engB.stop()


def test_wal_order_admit_precedes_registration(tiny_config, params,
                                               tmp_path):
    """Review regression: the admit record is on disk BEFORE the
    request becomes engine-visible, and a queue-full refusal after the
    write-ahead admit compensates with a cancel tombstone so the
    refused admission never replays."""
    jpath = str(tmp_path / "wal.journal")
    eng = _engine(tiny_config, params, journal=jpath, max_queue=1)
    # engine NOT started: the queue fills without being drained
    h1 = eng.submit(list(P1), max_new_tokens=GEN)
    with pytest.raises(Exception, match="queue full"):
        eng.submit(list(P2), max_new_tokens=GEN)
    eng._journal.close()
    recs, findings, _ = replay_state(read_records(jpath)[0])
    assert not findings
    by = {r["rid"]: r for r in recs}
    assert not by[h1._req.rid]["finished"]
    refused = [r for r in recs if r["rid"] != h1._req.rid]
    assert len(refused) == 1 and refused[0]["finished"]
    assert refused[0]["status"] == "cancelled"


# -- kill -9 subprocess drill (fault-plan abort) -----------------------------

DRILL = """
import sys
import jax, jax.numpy as jnp
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import ByteTokenizer
from cake_tpu.models.llama.params import init_params
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve.engine import InferenceEngine

cfg = LlamaConfig.tiny()
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
eng = InferenceEngine(
    cfg, params, ByteTokenizer(cfg.vocab_size),
    max_slots=2, max_seq_len=64,
    sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
    cache_dtype=jnp.float32, journal=sys.argv[1],
    fault_plan="engine.step:step=8:abort")
# submit BEFORE start: the engine plans from a fully-populated queue,
# so the step the abort fires on is deterministic across runs
hs = [eng.submit([5] * 9, max_new_tokens=12, idempotency_key="k1"),
      eng.submit([2, 9, 4, 7, 3], max_new_tokens=12)]
eng.start()
for h in hs:
    h.wait(timeout=600)
sys.exit(3)  # the abort never fired: a drill misconfiguration
"""


def _run_drill(tmp_path, tag):
    from cake_tpu.faults import ABORT_EXIT_CODE
    script = tmp_path / "drill.py"
    script.write_text(DRILL)
    jpath = str(tmp_path / f"drill-{tag}.journal")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, str(script), jpath],
                          env=env, capture_output=True, text=True,
                          timeout=600, cwd=str(REPO))
    assert proc.returncode == ABORT_EXIT_CODE, (
        f"drill {tag}: rc={proc.returncode}, wanted planned abort\n"
        f"{proc.stderr[-2000:]}")
    return jpath


def _drill_state(jpath):
    """The journal's view of the world at death, normalized for
    comparison across runs (drop wall-clock t)."""
    recs, findings, _ = replay_state(read_records(jpath)[0])
    assert not findings
    return [(r["rid"], tuple(r["prompt_ids"]), tuple(r["out_tokens"]),
             r["finished"]) for r in recs]


def test_kill9_drill_fires_deterministically_and_replays_identical(
        tiny_config, params, tmp_path, dense_clean):
    """THE crash drill: a subprocess serving with --journal dies by a
    fault-plan `abort` (os._exit — a staged kill -9). Two runs of the
    same plan die with identical journal state (the abort fires on
    the same step), and replaying the journal in a fresh engine
    completes every stream token-identical to the uninterrupted run."""
    j1 = _run_drill(tmp_path, "a")
    j2 = _run_drill(tmp_path, "b")
    s1, s2 = _drill_state(j1), _drill_state(j2)
    assert s1 == s2, "abort fired on different steps across runs"
    assert any(out for _rid, _p, out, _f in s1), \
        "drill died before any emitted-token batch was journaled"

    engB = _engine(tiny_config, params, journal=j1)
    engB.start()
    try:
        handles, _ = recover(engB)
        assert len(handles) == 2
        assert all(h.wait(timeout=600) for h in handles)
        full = [list(h._req.replayed_tokens) + list(h._req.out_tokens)
                for h in handles]
        assert full == dense_clean
    finally:
        engB.stop()


# -- atomic checkpoint satellite ---------------------------------------------

def test_corrupt_checkpoint_degrades_to_no_checkpoint(tmp_path, caplog):
    from cake_tpu.serve import checkpoint
    p = tmp_path / "snap.json"
    p.write_text('{"version": 3, "requests": [{"rid"')   # torn write
    import logging
    with caplog.at_level(logging.WARNING):
        assert checkpoint.load(str(p)) is None
    assert any("corrupt" in r.message for r in caplog.records)
    # restore() of the same file restores nothing instead of raising
    class _E:   # never touched: load fails first
        pass
    assert checkpoint.restore(_E(), str(p)) == ([], [])
    # a non-object JSON document is equally not a snapshot
    p.write_text("[1, 2]")
    assert checkpoint.load(str(p)) is None
    # version mismatch stays a LOUD error (intact file, explicit)
    p.write_text('{"version": 1, "requests": []}')
    with pytest.raises(ValueError, match="version"):
        checkpoint.load(str(p))


def test_checkpoint_write_is_atomic_and_cleans_tmp(tmp_path,
                                                   monkeypatch):
    from cake_tpu.serve import checkpoint
    path = tmp_path / "snap.json"
    snap = {"version": 3, "engine": {}, "requests": []}
    checkpoint.write(snap, str(path))
    assert json.loads(path.read_text()) == snap
    assert list(tmp_path.glob("*.tmp")) == []
    # a failing rename must not leave tmp litter either
    real_replace = os.replace

    def boom(src, dst):
        raise OSError("disk gone")
    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError):
        checkpoint.write(snap, str(path))
    monkeypatch.setattr(os, "replace", real_replace)
    assert list(tmp_path.glob("*.tmp")) == []
    assert json.loads(path.read_text()) == snap   # previous good kept


# -- drain drill (cheap, no model compile) -----------------------------------

def test_drain_drill_429_then_typed_reset(tiny_config, params):
    """One ordered drill: wedged engine holds 2 queued requests ->
    POST /api/v1/drain -> health reports draining + depth -> a new
    submit 429s with Retry-After -> after the (timed-out) drain stops
    the engine, submits get the typed reset 503, not a hang. The
    wedge fires at the top of every iteration, so nothing compiles."""
    from http.server import ThreadingHTTPServer

    from cake_tpu.api.server import ApiServer, make_handler
    from cake_tpu.args import Args
    from cake_tpu.master import Master
    from cake_tpu.serve.errors import RecoveryConfig

    eng = _engine(
        tiny_config, params,
        # 256: the rendered chat template (~120 tokens) must be a
        # VALID new admission, so the refusal we see is the drain 429,
        # not a prompt-length 400
        max_seq_len=256,
        fault_plan="engine.step:always:wedge:secs=1.5:times=99",
        recovery_config=RecoveryConfig(backoff_base_s=5.0,
                                       storm_resets=99))
    master = Master(Args(sample_len=4), text_generator=None)
    master.llm = object()   # chat goes through the engine path
    api = ApiServer(master, engine=eng)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(api))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"

    def post(path, body, headers=None):
        req = urllib.request.Request(
            url + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json",
                     **(headers or {})})
        return urllib.request.urlopen(req, timeout=30)

    try:
        eng.submit(list(P1), max_new_tokens=8)
        eng.submit(list(P2), max_new_tokens=8)
        resp = post("/api/v1/drain", {"timeout_s": 2})
        st = json.loads(resp.read())
        assert st["draining"] is True and st["pending_requests"] == 2

        health = json.loads(urllib.request.urlopen(
            url + "/api/v1/health", timeout=30).read())
        assert health["draining"] is True
        assert health["drain"]["pending_requests"] == 2

        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/api/v1/chat/completions",
                 {"messages": [{"role": "user", "content": "hi"}]})
        assert ei.value.code == 429
        assert int(ei.value.headers["Retry-After"]) >= 1
        assert "draining" in json.loads(ei.value.read())["error"]

        # malformed timeout is a 400, not an armed drain
        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/api/v1/drain", {"timeout_s": -1})
        assert ei.value.code == 400

        # the drain times out (the wedge never lets the wave finish),
        # stops the engine, and post-drain submits map to the typed
        # retryable reset -> 503 + Retry-After, never a hang
        deadline = time.monotonic() + 30
        code = None
        while time.monotonic() < deadline:
            try:
                post("/api/v1/chat/completions",
                     {"messages": [{"role": "user", "content": "hi"}]})
            except urllib.error.HTTPError as e:
                code = e.code
                if code == 503:
                    assert int(e.headers["Retry-After"]) >= 1
                    assert json.loads(e.read())["retryable"] is True
                    break
            time.sleep(0.1)
        assert code == 503, f"post-drain submit never 503'd (last {code})"
    finally:
        httpd.shutdown()
        eng.stop(timeout=5)


# -- SSE ids + Last-Event-ID resume across a restart -------------------------

def test_sse_resume_across_restart_exact_suffix(
        tiny_config, params, tmp_path, dense_clean):
    """Acceptance: a client that saw N events before a kill -9
    reconnects (same idempotency key, Last-Event-ID: N) against the
    REPLAYED server and receives exactly the missing suffix — no
    duplicates, no gaps — then [DONE]."""
    from http.server import ThreadingHTTPServer

    from cake_tpu.api.server import ApiServer, make_handler
    from cake_tpu.args import Args
    from cake_tpu.master import Master
    from cake_tpu.models.llama.generator import ByteTokenizer

    jpath = str(tmp_path / "sse.journal")
    seen = []

    def client_stream(delta, final, n_done=0):
        seen.append(n_done)

    client_stream.wants_count = True
    engA = _engine(tiny_config, params, journal=jpath)
    h = engA.submit(list(P1), max_new_tokens=GEN, stream=client_stream,
                    idempotency_key="sse-key")
    _abandon_at(engA, [h], 4)
    last_seen = max(seen)    # the client's Last-Event-ID
    assert 0 < last_seen < GEN

    engB = _engine(tiny_config, params, journal=jpath)
    master = Master(Args(sample_len=GEN), text_generator=None)
    master.llm = object()
    api = ApiServer(master, engine=engB)     # starts the engine
    handles, _ = recover(engB)
    assert len(handles) == 1
    assert handles[0].wait(timeout=600)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(api))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        req = urllib.request.Request(
            url + "/api/v1/chat/completions",
            data=json.dumps({
                "messages": [{"role": "user", "content": "ignored"}],
                "stream": True}).encode(),
            headers={"Content-Type": "application/json",
                     "x-cake-idempotency-key": "sse-key",
                     "Last-Event-ID": str(last_seen)})
        resp = urllib.request.urlopen(req, timeout=60)
        body = resp.read().decode()
        # parse SSE frames: (id, data) pairs
        events, cur_id = [], None
        for line in body.splitlines():
            if line.startswith("id: "):
                cur_id = int(line[4:])
            elif line.startswith("data: ") and line != "data: [DONE]":
                events.append((cur_id, json.loads(line[6:])))
        assert "data: [DONE]" in body
        # the replay chunk covers exactly (last_seen, total]: its id is
        # the total and its text is the re-decoded missing suffix
        text_events = [(i, e) for i, e in events
                       if e.get("choices", [{}])[0].get("delta", {})
                       .get("content")]
        assert text_events, f"no replayed suffix in {body!r}"
        replay_id, replay_ev = text_events[0]
        total = len(dense_clean[0])
        assert replay_id == total
        tok = ByteTokenizer(tiny_config.vocab_size)
        eos = tiny_config.eos_token_ids
        want = tok.decode([t for t in dense_clean[0][last_seen:]
                           if t not in eos])
        got = replay_ev["choices"][0]["delta"]["content"]
        assert got == want
        # no event at or below the client's Last-Event-ID: no dups
        assert all(i is None or i > last_seen for i, _ in events)
        # a plain retry (no stream) attaches too: never double-admits
        before = engB.stats.requests_completed
        req2 = urllib.request.Request(
            url + "/api/v1/chat/completions",
            data=json.dumps({
                "messages": [{"role": "user",
                              "content": "ignored"}]}).encode(),
            headers={"Content-Type": "application/json",
                     "x-cake-idempotency-key": "sse-key"})
        out = json.loads(urllib.request.urlopen(req2, timeout=60).read())
        assert out["choices"][0]["message"]["content"] == tok.decode(
            [t for t in dense_clean[0] if t not in eos])
        assert engB.stats.requests_completed == before
    finally:
        httpd.shutdown()
        engB.stop(timeout=5)
