"""Paged speculative decoding (cake_tpu/spec) as a ROW KIND of the
paged engine.

The acceptance bars from the issue, pinned:
  * greedy spec-paged serving is token-identical to plain greedy paged
    decode at f32 KV — dense prompts AND shared-prefix rows — for a
    self-draft (near-full acceptance exercises the emit/truncate fast
    path) and a mismatched draft (near-zero acceptance exercises the
    resample + degrade path); verify is authoritative either way;
  * the page allocator's `free + live == n_pages` invariant holds
    after every wave, including waves where `spec.verify` faults force
    whole rounds to reject — zero leaked draft or suffix pages;
  * forced acceptance collapse (spec.verify:always) degrades each
    stream to plain decode with a typed `spec_degraded` event — the
    stream completes correct greedy tokens, never wedges;
  * the gamma tuner narrows (never widens) with warmup/hold/cooldown
    hysteresis, round-counted so this file stays deterministic.
"""

import pytest

import jax
import jax.numpy as jnp

from cake_tpu.serve.errors import RecoveryConfig

T = 64            # max_seq_len
PAGE = 8
PAGES = 32
GAMMA = 3
GEN = 16

P1 = [5, 6, 7, 8, 9]
P2 = [11, 12, 13]
PREFIX = [7] * (2 * PAGE)           # page-granular shared head
SUFFIXES = ([3, 9, 4], [8, 2, 6, 1])


@pytest.fixture(scope="module")
def params(tiny_config):
    from cake_tpu.models.llama.params import init_params
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


@pytest.fixture(scope="module")
def mismatched_draft():
    """A draft that shares nothing with the target but the vocabulary:
    acceptance collapses organically (random agreement over 256 ids)."""
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.params import init_params
    dcfg = LlamaConfig.tiny(num_hidden_layers=1)
    return init_params(dcfg, jax.random.PRNGKey(42),
                       dtype=jnp.float32), dcfg


def _engine(tiny_config, params, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("kv_pages", PAGES)
    kw.setdefault("kv_page_size", PAGE)
    kw.setdefault("recovery_config",
                  RecoveryConfig(backoff_base_s=0.01))
    return InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        max_seq_len=T,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        # f32 KV: greedy equality must exercise accept/truncate, not
        # bf16 tie-breaks (the PR 2 lesson)
        cache_dtype=jnp.float32,
        **kw)


def _spec_kw(draft_params, draft_config, **kw):
    kw.setdefault("spec_gamma", GAMMA)
    return dict(spec_draft_params=draft_params,
                spec_draft_config=draft_config, **kw)


def _run_wave(eng, prompts=(P1, P2), gen=GEN, prefix=None):
    with eng:
        if prefix is not None:
            eng.register_prefix(list(prefix))
        hs = [eng.submit(list(p), max_new_tokens=gen, temperature=0.0,
                         repeat_penalty=1.0) for p in prompts]
        assert all(h.wait(timeout=600) for h in hs), "wave timed out"
        assert all(h._req.error is None for h in hs)
        return [list(h._req.out_tokens) for h in hs]


def _pool_conserved(eng, registry_pages=0):
    pg = eng._pager
    assert pg.free_pages + pg.live_pages == pg.n_pages
    assert pg.live_pages == registry_pages, (
        f"leaked pages: live={pg.live_pages}, "
        f"expected {registry_pages} (registry)")
    # every SpecState retired with its slot — no draft/suffix residue
    if eng._specp is not None:
        assert not eng._specp.spec_streams


@pytest.fixture(scope="module")
def plain_dense(tiny_config, params):
    return _run_wave(_engine(tiny_config, params))


# -- greedy token identity -----------------------------------------------------


def test_self_draft_token_identical_and_conserves_pool(
        tiny_config, params, plain_dense):
    """Self-draft (draft == target): near-full acceptance, so the
    accepted-suffix emit + truncate path carries most tokens — and the
    stream is still byte-identical to plain greedy decode."""
    eng = _engine(tiny_config, params,
                  **_spec_kw(params, tiny_config))
    toks = _run_wave(eng)
    assert toks == plain_dense
    st = eng.stats
    assert st.spec_proposed > 0, "spec rows never engaged"
    assert st.spec_accepted > 0
    # >1 token per round on average is the whole point
    assert st.spec_accepted / max(st.spec_proposed, 1) > 0.5
    _pool_conserved(eng)


def test_mismatched_draft_token_identical_despite_collapse(
        tiny_config, params, mismatched_draft, plain_dense):
    """A useless draft costs throughput, never correctness: verify is
    authoritative, rejected rounds emit the target's own resample, and
    the collapsed streams degrade to plain decode rather than wedge."""
    d_params, d_cfg = mismatched_draft
    eng = _engine(tiny_config, params, **_spec_kw(d_params, d_cfg))
    toks = _run_wave(eng)
    assert toks == plain_dense
    assert eng.stats.spec_proposed > 0
    _pool_conserved(eng)


def test_shared_prefix_token_identical(tiny_config, params):
    """Spec rows compose with page-granular prefix sharing: the draft
    pool prefills its own whole-context copy, the target row maps
    registry pages + its suffix, and greedy output matches plain
    shared-prefix serving token for token."""
    prompts = [PREFIX + list(s) for s in SUFFIXES]
    plain_eng = _engine(tiny_config, params)
    want = _run_wave(plain_eng, prompts=prompts, prefix=PREFIX)
    eng = _engine(tiny_config, params,
                  **_spec_kw(params, tiny_config))
    toks = _run_wave(eng, prompts=prompts, prefix=PREFIX)
    assert toks == want
    assert eng.stats.prefix_hits == len(prompts)
    assert eng.stats.spec_proposed > 0, "prefix rows never engaged spec"
    # only the registry's prefix pages stay live after the wave
    _pool_conserved(eng, registry_pages=len(PREFIX) // PAGE)


# -- page conservation under forced rejections --------------------------------


def test_forced_rejections_leak_no_pages(tiny_config, params,
                                         plain_dense):
    """The regression bar from the issue: N rounds with spec.verify
    faults forcing rejected rounds, then `free + live == n_pages` and
    zero surviving SpecStates — the pre-round row extensions were all
    truncated back."""
    eng = _engine(tiny_config, params,
                  fault_plan="seed=5;spec.verify:p=0.5:transient",
                  **_spec_kw(params, tiny_config))
    toks = _run_wave(eng)
    assert eng._faults.total >= 1, "the planned faults never fired"
    assert toks == plain_dense, "a faulted round corrupted the stream"
    assert eng.stats.recoveries == 0, (
        "injected spec.verify faults must be absorbed, not recovered")
    _pool_conserved(eng)


def test_verify_fault_storm_degrades_with_event(tiny_config, params,
                                                plain_dense):
    """spec.verify:always — every round faults, so each stream's
    verify_fails budget trips and it degrades to plain decode with a
    typed spec_degraded event; the wave still completes token-identical
    and no stream is lost or wedged."""
    from cake_tpu.spec.state import DISABLE_AFTER_FAILS
    eng = _engine(tiny_config, params,
                  fault_plan="seed=1;spec.verify:always:transient"
                             ":times=12",
                  **_spec_kw(params, tiny_config))
    toks = _run_wave(eng)
    assert toks == plain_dense
    assert eng._faults.total >= DISABLE_AFTER_FAILS
    deg = eng.events.dump(type="spec_degraded")
    assert deg, "no spec_degraded event for the collapsed streams"
    assert all(e["action"] == "disabled" for e in deg)
    assert {e["reason"] for e in deg} == {"verify_faults"}
    # every submitted stream degraded (both shared each faulted round)
    assert {e["rid"] for e in deg} == {1, 2}
    # the faulted rounds were still published (fault=True aggregates)
    faulted = [e for e in eng.events.dump(type="spec_round")
               if e.get("fault")]
    assert len(faulted) >= DISABLE_AFTER_FAILS
    assert all(e["accepted"] == 0 for e in faulted)
    _pool_conserved(eng)


# -- the closed loop: gamma tuner ---------------------------------------------


def test_gamma_tuner_narrows_with_hysteresis():
    from cake_tpu.autotune.spec import SpecGammaTuner, SpecTunerConfig
    cfg = SpecTunerConfig(shrink_below=0.3, warmup_rounds=4, hold=2,
                          cooldown_rounds=3)
    t = SpecGammaTuner(8, cfg)
    # warmup: even sustained collapse may not move gamma yet
    for _ in range(3):
        t.note_round(0.0)
        assert t.maybe_shrink() is None
    t.note_round(0.0)                      # round 4: warmup met, hold met
    assert t.maybe_shrink() == 4
    assert (t.gamma, t.shrinks) == (4, 1)
    # cooldown: the next two rounds of collapse make no second move...
    for _ in range(2):
        t.note_round(0.0)
        assert t.maybe_shrink() is None
    # ...the streak keeps building through cooldown, so the round that
    # retires it moves again
    t.note_round(0.0)
    assert t.maybe_shrink() == 2
    # a healthy round resets the below-threshold streak
    t.note_round(0.0)
    t.note_round(0.0)
    t.note_round(0.9)
    t.note_round(0.0)
    assert t.maybe_shrink() is None
    # never below 1, and a gamma-1 tuner never moves
    t2 = SpecGammaTuner(1, cfg)
    for _ in range(10):
        t2.note_round(0.0)
    assert t2.maybe_shrink() is None
    assert t2.gamma == 1


def test_spec_paged_rejects_incompatible_flavors(tiny_config, params):
    """Constructor refusals name their reason: quantized KV pools and
    missing paging are incompatible."""
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    def build(**kw):
        base = dict(max_slots=2, max_seq_len=T,
                    sampling=SamplingConfig(temperature=0.0,
                                            repeat_penalty=1.0),
                    spec_draft_params=params,
                    spec_draft_config=tiny_config)
        base.update(kw)
        return InferenceEngine(
            tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
            **base)

    with pytest.raises(ValueError, match="paged"):
        build()                                  # no kv_pages
    with pytest.raises(ValueError, match="int8|quant"):
        build(kv_pages=PAGES, kv_page_size=PAGE, kv_dtype="int8")
    with pytest.raises(ValueError, match="gamma"):
        build(kv_pages=PAGES, kv_page_size=PAGE, spec_gamma=0)


# -- a row's sampling decides whether it speculates, never whether it is served


def _tokens(eng, *subs, gen=GEN):
    """Submit (prompt, sampling options) pairs to a started engine, in
    order; every stream's tokens once all are done."""
    hs = [eng.submit(list(p), max_new_tokens=gen, **kw) for p, kw in subs]
    assert all(h.wait(timeout=600) for h in hs), "wave timed out"
    assert all(h._req.error is None for h in hs)
    return [list(h._req.out_tokens) for h in hs]


GREEDY_ROW = dict(temperature=0.0, repeat_penalty=1.0)
HOT_ROW = dict(temperature=0.9, repeat_penalty=1.0)


def test_more_requests_than_slots_chain_and_the_queue_drains(
        tiny_config, params):
    """Three requests on two slots: the third waits behind full slots
    that speculate round after round, is admitted when one retires,
    and all three are the plain engine's streams to the token."""
    subs = [([5] * 9, GREEDY_ROW)] * 3
    with _engine(tiny_config, params) as plain:
        want = _tokens(plain, *subs, gen=30)
    eng = _engine(tiny_config, params, **_spec_kw(params, tiny_config))
    with eng:
        got = _tokens(eng, *subs, gen=30)
    assert got == want and [len(t) for t in got] == [30] * 3
    # fewer rounds than tokens: rows rode rounds of more than one token
    rounds = eng.events.dump(type="spec_round")
    assert 0 < sum(e["rows"] for e in rounds) < 90
    _pool_conserved(eng)


def test_greedy_row_beside_a_temperature_row_is_the_plain_stream(
        tiny_config, params, mismatched_draft, plain_dense):
    """One round holds a greedy and a sampled row: the greedy row's
    stream is the one it has alone and the one a plain engine gives it
    (its key never advances, its acceptance is exact-match), whatever
    the hot row beside it draws."""
    d_params, d_cfg = mismatched_draft

    def cold(*beside):
        eng = _engine(tiny_config, params, **_spec_kw(d_params, d_cfg))
        with eng:
            out = _tokens(eng, (P1, GREEDY_ROW), *beside)
        _pool_conserved(eng)
        return out[0]

    assert cold() == cold((P2, HOT_ROW)) == plain_dense[0]


def test_temperature_row_repeats_under_its_seed_and_differs_under_another(
        tiny_config, params, mismatched_draft):
    """A sampled row speculates through rejection sampling: the same
    engine seed gives the same stream twice, another seed another."""
    d_params, d_cfg = mismatched_draft

    def hot(seed):
        eng = _engine(tiny_config, params, seed=seed,
                      **_spec_kw(d_params, d_cfg))
        with eng:
            out = _tokens(eng, (P1, HOT_ROW))
        assert eng.stats.spec_proposed > 0
        return out[0]

    a = hot(7)
    assert len(a) == GEN and all(0 <= t < tiny_config.vocab_size
                                 for t in a)
    assert hot(7) == a
    assert hot(8) != a


@pytest.mark.parametrize("option", [
    dict(temperature=0.8, top_p=0.9, repeat_penalty=1.0),
    dict(temperature=0.0, repeat_penalty=1.3),
    dict(temperature=0.8, repeat_penalty=1.0, want_top_logprobs=True),
], ids=["top_p", "repeat_penalty", "top_logprobs"])
def test_row_that_cannot_speculate_is_served_plain(tiny_config, params,
                                                  option):
    """Nucleus sampling, a repeat penalty and top-logprobs have no
    accept/resample identity, so such a row rides no round: it is
    served on the plain path, token for token what a plain engine
    samples for it, while the greedy row beside it speculates."""
    subs = [(P1, GREEDY_ROW), (P2, option)]
    with _engine(tiny_config, params) as plain:
        want = _tokens(plain, *subs)
    eng = _engine(tiny_config, params, **_spec_kw(params, tiny_config))
    with eng:
        got = _tokens(eng, *subs)
    assert got == want
    rounds = eng.events.dump(type="spec_round")
    assert rounds and all(e["rows"] == 1 for e in rounds)
    _pool_conserved(eng)


def test_row_near_the_windows_end_finishes_plain_with_its_tail(
        tiny_config, params):
    """Within gamma + 1 positions of max_seq_len a round's writes no
    longer fit: the row leaves speculation and the plain path carries
    it to the cap, every tail token a plain engine emits included."""
    prompt = [(3 * j) % 50 + 3 for j in range(40)]
    with _engine(tiny_config, params) as plain:
        want = _tokens(plain, (prompt, GREEDY_ROW), gen=T)
    eng = _engine(tiny_config, params, **_spec_kw(params, tiny_config))
    with eng:
        got = _tokens(eng, (prompt, GREEDY_ROW), gen=T)
    assert got == want and len(got[0]) == T - len(prompt)
    # it did speculate on the way, and not over the last gamma + 1
    by_rounds = sum(e["tokens"] for e in eng.events.dump(type="spec_round"))
    assert 0 < by_rounds <= len(got[0]) - (GAMMA + 1)
    _pool_conserved(eng)


def test_perfect_draft_accepts_every_proposal_and_the_series_move(
        tiny_config, params):
    """draft == target at f32: every round keeps all gamma drafts (the
    proof of the plumbing: a draft row one position out of step with
    its target row would crater it), the engine's counters say so, and
    the plane's four series are the ones that report it."""
    from cake_tpu.spec.state import (
        SPEC_ACCEPT_RATIO, SPEC_ROUNDS, SPEC_TOKENS_PER_ROUND,
    )
    rounds0 = SPEC_ROUNDS.value
    eng = _engine(tiny_config, params, max_slots=1,
                  **_spec_kw(params, tiny_config))
    with eng:
        _tokens(eng, (P1, GREEDY_ROW), gen=1 + 3 * (GAMMA + 1))
    st = eng.stats
    assert st.spec_proposed == 3 * GAMMA
    assert st.spec_accepted == st.spec_proposed
    assert st.spec_acceptance == 1.0
    assert SPEC_ROUNDS.value - rounds0 == 3
    assert SPEC_ACCEPT_RATIO.value == 1.0
    assert SPEC_TOKENS_PER_ROUND.value == GAMMA + 1
    _pool_conserved(eng)


# -- from the command line to the engine ---------------------------------------


def _master(tmp_path, draft_config=None, **kw):
    """A Master for `--spec-draft <dir>` over the weightless tiny
    target; the draft directory holds a config.json or nothing."""
    import json

    from cake_tpu.args import Args
    from cake_tpu.context import Context
    from cake_tpu.master import Master
    d_dir = tmp_path / "draft"
    d_dir.mkdir()
    if draft_config is not None:
        (d_dir / "config.json").write_text(json.dumps(draft_config))
    args = Args(model="", spec_draft=str(d_dir), spec_gamma=2,
                kv_pages=2 * PAGES, kv_page_size=PAGE, max_seq_len=4 * T,
                dtype="f32", temperature=0.0, repeat_penalty=1.0,
                flash_attention=False, max_slots=2, **kw).validate()
    return Master(args,
                  text_generator=Context.from_args(args).load_text_model())


def test_master_wires_spec_draft_from_args(tmp_path, tiny_config):
    """--spec-draft from Args: the draft loads (drawn where the
    directory holds no weights), the engine gets its plane at
    --spec-gamma, and a request speculates."""
    master = _master(tmp_path)
    kw = master._spec_kwargs()
    assert set(kw) == {"spec_draft_params", "spec_draft_config",
                       "spec_gamma"}
    assert kw["spec_gamma"] == 2
    assert kw["spec_draft_config"].vocab_size == tiny_config.vocab_size
    eng = master.make_engine()
    assert eng._specp is not None and eng._specp.live_gamma == 2
    with eng:
        out, = _tokens(eng, (P1, GREEDY_ROW), gen=8)
    assert len(out) == 8 and eng.stats.spec_proposed > 0


def test_master_refuses_a_draft_of_another_vocabulary(tmp_path,
                                                      tiny_config):
    import dataclasses
    cfg = {k: v for k, v in dataclasses.asdict(tiny_config).items()
           if isinstance(v, (int, float, str)) or v is None}
    cfg.update(vocab_size=tiny_config.vocab_size + 64,
               architectures=["LlamaForCausalLM"])
    master = _master(tmp_path, draft_config=cfg)
    with pytest.raises(ValueError, match="share a tokenizer"):
        master._spec_kwargs()


def test_spec_draft_beside_a_topology_is_refused_by_name(tiny_config,
                                                         params):
    """A topology's step programs run no paged pool, and speculation
    lives in one: the constructor says which option cannot stay."""
    with pytest.raises(ValueError, match="--kv-pages requires the "
                                         "built-in dense single-device"):
        _engine(tiny_config, params, step_fns=(print, print),
                **_spec_kw(params, tiny_config))


@pytest.fixture(scope="module")
def spec_server(tmp_path_factory):
    from cake_tpu.api.server import start
    master = _master(tmp_path_factory.mktemp("spec_api"), sample_len=6)
    engine = master.make_engine()
    httpd = start(master, address="127.0.0.1:0", block=False,
                  engine=engine.start())
    yield "http://%s:%d" % httpd.server_address[:2], engine
    httpd.shutdown()
    engine.stop()


@pytest.mark.parametrize("stream", [False, True],
                         ids=["buffered", "streamed"])
def test_http_api_serves_a_spec_draft_engine(spec_server, stream):
    """Two callers at once through the HTTP API of a --spec-draft
    server, buffered and streamed: both are answered and their rows
    rode speculative rounds."""
    import json
    import threading
    import urllib.request
    base, engine = spec_server
    before = engine.stats.spec_proposed
    answers = []

    def one(msg):
        req = urllib.request.Request(
            base + "/api/v1/chat/completions",
            data=json.dumps({
                "messages": [{"role": "user", "content": msg}],
                "max_tokens": 6, "stream": stream}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            answers.append(r.read().decode())

    ts = [threading.Thread(target=one, args=(m,)) for m in ("hi", "yo")]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=300)
    assert len(answers) == 2
    for body in answers:
        if stream:
            events = [ln[6:] for ln in body.splitlines()
                      if ln.startswith("data: ")]
            assert events[-1] == "[DONE]"
            last = json.loads(events[-2])
            assert last["object"] == "chat.completion.chunk"
            assert last["choices"][0]["finish_reason"] in ("stop",
                                                           "length")
        else:
            obj = json.loads(body)
            assert obj["choices"][0]["message"]["role"] == "assistant"
    assert engine.stats.spec_proposed > before
    assert 0.0 <= engine.stats.spec_acceptance <= 1.0
