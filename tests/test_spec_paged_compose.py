"""What a speculating row composes with on the paged engine.

A row reaches speculation at its decode frontier whatever brought it
there (engine._spec_row_ready): a chunked prefill, a registered prefix,
a preemption's resume, a crash recovery's replay. Each case here puts a
`--spec-draft` engine through one of those and holds its greedy streams
to a plain paged engine's, token for token, with the page pool whole
afterwards. f32 KV throughout: equality has to exercise the fold and
the round, not bf16 tie-breaks.
"""

import time

import pytest

import jax
import jax.numpy as jnp

from cake_tpu.sched import SchedConfig
from cake_tpu.serve.errors import RecoveryConfig

T = 64
PAGE = 8
PAGES = 32
GAMMA = 3
GEN = 24

LONG = [(3 * j) % 50 + 3 for j in range(20)]        # > one chunk of 8
BATCH_PROMPT = [5] * 9
INTER_PROMPT = [2, 9, 4, 7, 3]
PREFIX = [(5 * j) % 40 + 2 for j in range(2 * PAGE)]
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)


@pytest.fixture(scope="module")
def params(tiny_config):
    from cake_tpu.models.llama.params import init_params
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


@pytest.fixture(scope="module")
def spec(tiny_config, params):
    """Self-draft: near-full acceptance, so most tokens arrive through
    the round and a row that lost its place would show at once."""
    return dict(spec_draft_params=params, spec_draft_config=tiny_config,
                spec_gamma=GAMMA)


def _engine(tiny_config, params, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("recovery_config", RecoveryConfig(backoff_base_s=0.01))
    return InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        max_seq_len=T, kv_pages=PAGES, kv_page_size=PAGE,
        sampling=SamplingConfig(**GREEDY), cache_dtype=jnp.float32, **kw)


def _tokens(eng, *prompts, gen=GEN, **kw):
    hs = [eng.submit(list(p), max_new_tokens=gen, **GREEDY, **kw)
          for p in prompts]
    assert all(h.wait(timeout=600) for h in hs), "wave timed out"
    assert all(h._req.error is None for h in hs)
    return [list(h._req.out_tokens) for h in hs]


def _wait_tokens(handle, n, timeout=120.0):
    t0 = time.perf_counter()
    while (len(handle._req.out_tokens) < n
           and time.perf_counter() - t0 < timeout):
        time.sleep(0.002)
    assert len(handle._req.out_tokens) >= n, "the row never got going"


def _pool_whole(eng, registry_pages=0):
    pg = eng._pager
    assert pg.free_pages + pg.live_pages == pg.n_pages
    assert pg.live_pages == registry_pages
    assert not eng._specp.spec_streams


def test_chunked_prefill_then_speculation(tiny_config, params, spec):
    """Prompts of three windows walk the mixed step chunk by chunk
    beside a row that is already decoding, then speculate with it."""
    prompts = (BATCH_PROMPT, LONG, LONG[::-1])
    with _engine(tiny_config, params, max_slots=3,
                 prefill_chunk=8) as plain:
        want = _tokens(plain, *prompts)
    eng = _engine(tiny_config, params, max_slots=3, prefill_chunk=8,
                  **spec)
    with eng:
        got = _tokens(eng, *prompts)
    assert got == want
    assert eng._mixed_chunk == 8
    rounds = eng.events.dump(type="spec_round")
    assert max(e["rows"] for e in rounds) >= 2
    _pool_whole(eng)


def test_prefix_registered_while_rows_speculate(tiny_config, params, spec):
    """register_prefix is served by a --spec-draft engine (the dense
    speculative engine refused it: its draft cache had no way in for a
    prefix), mid-serving; a request that hits the prefix maps the
    registry's pages, prefills its suffix alone and speculates."""
    prompts = [PREFIX + [3, 9, 4], PREFIX + [8, 2, 6, 1]]
    with _engine(tiny_config, params) as plain:
        plain.register_prefix(PREFIX)
        want = _tokens(plain, *prompts)
    eng = _engine(tiny_config, params, **spec)
    with eng:
        first = eng.submit(BATCH_PROMPT, max_new_tokens=GEN, **GREEDY)
        _wait_tokens(first, 2 * (GAMMA + 1))
        assert eng.register_prefix(PREFIX) >= 1
        before = eng.stats.spec_proposed
        got = _tokens(eng, *prompts)
        assert first.wait(timeout=600)
    assert got == want
    assert eng.stats.prefix_hits == len(prompts)
    assert eng.stats.spec_proposed > before
    _pool_whole(eng, registry_pages=len(PREFIX) // PAGE)


def test_preemption_resumes_a_speculating_row(tiny_config, params, spec):
    """--priority-classes --preemption on a --spec-draft engine (the
    dense speculative engine turned preemption off with a warning): a
    batch row that speculates is preempted for an interactive arrival,
    its draft row's and suffix pages go back with its own, and the
    resumed row, speculating again from its folded prompt, ends on the
    uninterrupted plain stream."""
    sched = dict(priority_classes=True, max_slots=1,
                 sched_config=SchedConfig(preempt_budget=8))
    with _engine(tiny_config, params, **sched) as plain:
        want, = _tokens(plain, BATCH_PROMPT, gen=2 * GEN, priority="batch")
    eng = _engine(tiny_config, params, preemption=True, **sched, **spec)
    assert eng._preemption
    with eng:
        hb = eng.submit(BATCH_PROMPT, max_new_tokens=2 * GEN, **GREEDY,
                        priority="batch")
        _wait_tokens(hb, 2)     # the first is the prefill's, then rounds
        proposed = eng.stats.spec_proposed
        assert proposed > 0, "the victim was not speculating"
        hi = eng.submit(INTER_PROMPT, max_new_tokens=4, **GREEDY,
                        priority="interactive")
        assert hi.wait(timeout=300) and hb.wait(timeout=300)
    assert eng.stats.preemptions >= 1 and hb._req.preemptions >= 1
    assert len(hi._req.out_tokens) == 4
    assert list(hb._req.out_tokens) == want
    assert eng.stats.spec_proposed > proposed, "no round after the resume"
    _pool_whole(eng)


def test_crash_recovery_replays_speculating_rows(tiny_config, params,
                                                 spec):
    """A step fails while both rows speculate: the reset rebuilds both
    pools, the rows are resubmitted with their tokens folded into their
    prompts, and they finish on the plain streams, speculating again."""
    with _engine(tiny_config, params) as plain:
        want = _tokens(plain, BATCH_PROMPT, INTER_PROMPT)
    eng = _engine(tiny_config, params,
                  fault_plan="seed=3;engine.step:step=5:transient", **spec)
    assert eng._recover
    with eng:
        got = _tokens(eng, BATCH_PROMPT, INTER_PROMPT)
    assert eng._faults.total == 1, "the planned fault never fired"
    assert eng.stats.recoveries == 1
    assert eng.stats.requests_recovered == 2
    assert got == want
    rounds = eng.events.dump(type="spec_round")
    recovered = eng.events.dump(type="recovered")[-1]["seq"]
    assert any(e["seq"] < recovered for e in rounds), \
        "no round before the fault"
    assert any(e["seq"] > recovered for e in rounds), \
        "no round after the recovery"
    _pool_whole(eng)


def test_decode_scan_carries_the_rows_a_round_leaves(tiny_config, params,
                                                     spec):
    """--decode-scan 4 beside --spec-draft: the rows a round covers
    ride it, the row it leaves (a repeat penalty has no place in a
    round) takes the scan, and both are the plain engine's streams."""
    def run(**kw):
        with _engine(tiny_config, params, decode_scan_steps=4,
                     **kw) as eng:
            hs = [eng.submit(BATCH_PROMPT, max_new_tokens=GEN, **GREEDY),
                  eng.submit(INTER_PROMPT, max_new_tokens=GEN,
                             temperature=0.0, repeat_penalty=1.3)]
            assert all(h.wait(timeout=600) for h in hs)
            return [list(h._req.out_tokens) for h in hs], eng

    want, _ = run()
    got, eng = run(**spec)
    assert got == want
    assert eng.stats.spec_proposed > 0
    _pool_whole(eng)
