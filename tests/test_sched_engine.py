"""Engine-level SLO scheduling: preemption token equality + shed.

The recompute-preemption contract: a preempted-then-resumed request
emits tokens IDENTICAL to an uninterrupted greedy run (f32 KV cache —
bf16 storage flips greedy near-ties and would test tie-breaks, not the
fold), on the dense AND the paged engine, and the paged path leaves the
refcounted page pool conserved (shared prefix pages decref, never
free another slot's live context).
"""

import time

import pytest

import jax.numpy as jnp

from cake_tpu.sched import SchedConfig, ShedController, ShedError
from cake_tpu.sched.shed import ShedDecision

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
    from cake_tpu.serve.engine import InferenceEngine

    kw.setdefault("max_slots", 1)
    kw.setdefault("priority_classes", True)
    return InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        max_seq_len=T,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        # f32 KV to match the f32 params fixture: greedy equality must
        # exercise the preemption fold, not bf16 tie-breaks
        cache_dtype=jnp.float32,
        # token-equality runs preempt exactly once mid-stream; the
        # budget must not silently exempt the victim
        sched_config=SchedConfig(preempt_budget=8),
        **kw)


def _wait_tokens(handle, n, timeout=120.0):
    t0 = time.perf_counter()
    while (len(handle._req.out_tokens) < n
           and time.perf_counter() - t0 < timeout):
        time.sleep(0.002)
    assert len(handle._req.out_tokens) >= n, "victim never got going"


BATCH_PROMPT = [5] * 9
INTER_PROMPT = [2, 9, 4, 7, 3]
GEN = 24


def _uninterrupted(tiny_config, params, **kw):
    eng = _engine(tiny_config, params, **kw)
    with eng:
        h = eng.submit(BATCH_PROMPT, max_new_tokens=GEN,
                       temperature=0.0, repeat_penalty=1.0,
                       priority="batch")
        assert h.wait(timeout=300)
        assert eng.stats.preemptions == 0
        return list(h._req.out_tokens)


def _preempted(tiny_config, params, stream=None, **kw):
    """Batch request preempted mid-decode by an interactive arrival on
    a 1-slot engine, then resumed; returns its final token stream."""
    eng = _engine(tiny_config, params, preemption=True, **kw)
    with eng:
        hb = eng.submit(BATCH_PROMPT, max_new_tokens=GEN,
                        temperature=0.0, repeat_penalty=1.0,
                        priority="batch", stream=stream)
        _wait_tokens(hb, 4)
        hi = eng.submit(INTER_PROMPT, max_new_tokens=4,
                        temperature=0.0, repeat_penalty=1.0,
                        priority="interactive")
        assert hi.wait(timeout=300) and hb.wait(timeout=300)
        assert eng.stats.preemptions >= 1, "no preemption happened"
        assert hb._req.preemptions >= 1
        # the interactive request was served while batch was parked
        assert len(hi._req.out_tokens) >= 1
        return list(hb._req.out_tokens), eng


def test_preemption_token_equality_dense(tiny_config, params):
    want = _uninterrupted(tiny_config, params)
    got, _eng = _preempted(tiny_config, params)
    assert got == want


def test_preemption_token_equality_paged(tiny_config, params):
    paged_kw = dict(kv_pages=8, kv_page_size=PAGE)
    want = _uninterrupted(tiny_config, params, **paged_kw)
    got, eng = _preempted(tiny_config, params, **paged_kw)
    assert got == want
    # every page released: retire AND the preemption release both
    # returned their references (free + live == n_pages, live == 0)
    assert eng._pager.free_pages == eng.cache.n_pages


@pytest.mark.parametrize("engine", ["dense", "paged"])
def test_preempted_stream_continues_its_text(tiny_config, params, engine):
    """A streaming row that is preempted and requeued goes on where its
    text stopped (PR 47: the detokeniser's place lives on the request):
    no chunk twice, none missing, a character split across the
    preemption whole."""
    from cake_tpu.models.llama.generator import ByteTokenizer
    kw = dict(kv_pages=8, kv_page_size=PAGE) if engine == "paged" else {}
    chunks = []

    def stream(delta, final, n_done=0):
        chunks.append((delta, final, n_done))
    stream.wants_count = True

    got, _eng = _preempted(tiny_config, params, stream=stream, **kw)
    assert got == _uninterrupted(tiny_config, params, **kw)
    tok = ByteTokenizer(tiny_config.vocab_size)
    assert "".join(c[0] for c in chunks) == tok.decode(got)
    counts = [c[2] for c in chunks]
    assert counts == sorted(set(counts)) and counts[-1] == GEN
    # chunks from both sides of the preemption (it came after 4 tokens)
    assert counts[0] <= 4 < counts[-2]
    assert [c[1] for c in chunks] == [False] * (len(chunks) - 1) + [True]
    sent = ""
    for delta, final, n in chunks[:-1]:
        sent += delta
        assert sent == tok.decode(got[:n])


def test_paged_page_starvation_preempts_lower_class(tiny_config, params):
    """3 slots but a pool only big enough for two batch residents: the
    interactive admission is page-starved, the youngest batch slot is
    preempted (reason=pages), its pages free, and everyone still
    completes with the pool conserved."""
    eng = _engine(tiny_config, params, max_slots=3, preemption=True,
                  kv_pages=4, kv_page_size=PAGE)
    with eng:
        # each needs pages_for(9 + 23) = 2 pages -> pool exhausted
        hb = [eng.submit([5 + i] * 9, max_new_tokens=23,
                         temperature=0.0, repeat_penalty=1.0,
                         priority="batch") for i in range(2)]
        for h in hb:
            _wait_tokens(h, 2)
        hi = eng.submit(INTER_PROMPT, max_new_tokens=7,
                        temperature=0.0, repeat_penalty=1.0,
                        priority="interactive")
        assert hi.wait(timeout=300)
        assert all(h.wait(timeout=600) for h in hb)
        assert eng.stats.preemptions >= 1
        assert eng._pager.free_pages == eng.cache.n_pages


def test_preemption_with_shared_prefix_pages(tiny_config, params):
    """Preempting a slot that maps shared prefix pages decrefs them
    (registry + sibling slots keep them alive); resume re-maps the
    prefix and the tokens still match the unpreempted shared run."""
    prefix = [(3 * j) % 50 + 3 for j in range(2 * PAGE)]
    suffix = [7, 11, 13]

    def run(preempt_mid: bool):
        eng = _engine(tiny_config, params, max_slots=2, preemption=True,
                      kv_pages=8, kv_page_size=PAGE)
        with eng:
            pid = eng.register_prefix(prefix)
            h = eng.submit(prefix + suffix, max_new_tokens=16,
                           temperature=0.0, repeat_penalty=1.0,
                           priority="batch")
            if preempt_mid:
                _wait_tokens(h, 3)
                # 1 free slot remains but scheduling is slot-granular
                # here; fill the other slot first so the interactive
                # arrival must preempt
                h2 = eng.submit(prefix + [19, 23], max_new_tokens=16,
                                temperature=0.0, repeat_penalty=1.0,
                                priority="batch")
                _wait_tokens(h2, 1)
                hi = eng.submit(INTER_PROMPT, max_new_tokens=3,
                                temperature=0.0, repeat_penalty=1.0,
                                priority="interactive")
                assert hi.wait(timeout=300)
                assert h2.wait(timeout=300)
            assert h.wait(timeout=300)
            toks = list(h._req.out_tokens)
            preempts = eng.stats.preemptions
            eng.unregister_prefix(pid)
        assert eng._pager.free_pages == eng.cache.n_pages
        return toks, preempts

    want, _ = run(preempt_mid=False)
    got, preempts = run(preempt_mid=True)
    assert preempts >= 1
    assert got == want


def test_shed_rejects_with_honest_retry_after(tiny_config, params):
    eng = _engine(tiny_config, params, shed=True)

    class _AlwaysShed:
        def decide(self, cls, depth, now=None):
            return ShedDecision(False, 7.0, 0.0, 9.0)

        def observe_retire(self, now=None):
            pass

        def estimate_retry_after(self, cls, depth, now=None):
            return 7.0

    assert isinstance(eng._shed, ShedController)
    eng._shed = _AlwaysShed()
    with pytest.raises(ShedError) as ei:
        eng.submit([5] * 4, max_new_tokens=2, priority="interactive")
    assert ei.value.retry_after == 7.0
    assert ei.value.priority == "interactive"
    assert eng.stats.shed == 1
    # nothing entered the queue
    assert eng.queue_depth == 0


def test_queue_full_carries_retry_after(tiny_config, params):
    from cake_tpu.serve.engine import QueueFullError
    eng = _engine(tiny_config, params)
    eng.scheduler.max_queue = 0
    with pytest.raises(QueueFullError) as ei:
        eng.submit([5] * 4, max_new_tokens=2)
    assert ei.value.retry_after >= 1.0


def test_unknown_priority_rejected(tiny_config, params):
    eng = _engine(tiny_config, params)
    with pytest.raises(ValueError, match="priority"):
        eng.submit([5] * 4, max_new_tokens=2, priority="vip")
