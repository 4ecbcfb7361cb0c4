"""Speculative decoding: exact greedy equivalence and mechanics.

The invariant that makes speculation safe to ship: with temperature=0 the
emitted stream equals the target-only greedy stream TOKEN FOR TOKEN, no
matter how bad the draft is (a wrong draft only costs speed). The oracle
is LlamaGenerator on the same target weights.
"""

import numpy as np
import pytest

import jax

from cake_tpu.models.chat import Message
from cake_tpu.models.llama.generator import ByteTokenizer, LlamaGenerator
from cake_tpu.models.llama.params import init_params
from cake_tpu.models.llama.speculative import SpeculativeGenerator
from cake_tpu.ops.sampling import SamplingConfig


GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)


@pytest.fixture(scope="module")
def target(tiny_config):
    return init_params(tiny_config, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def draft(tiny_config):
    # a DIFFERENT model (other seed): drafts will frequently be wrong
    return init_params(tiny_config, jax.random.PRNGKey(42))


def _spec(tiny_config, target, draft, gamma=3, **kw):
    return SpeculativeGenerator(
        tiny_config, target, tiny_config, draft,
        ByteTokenizer(tiny_config.vocab_size),
        gamma=gamma, max_seq_len=256, sampling=GREEDY, **kw)


def _oracle(tiny_config, target):
    return LlamaGenerator(
        tiny_config, target, ByteTokenizer(tiny_config.vocab_size),
        max_seq_len=256, sampling=GREEDY)


def test_greedy_equivalence_bad_draft(tiny_config, target, draft):
    """Wrong drafts must never change the output, only the speed."""
    prompt = np.full((1, 9), 5, np.int32)
    plen = np.full((1,), 9, np.int32)
    want = _oracle(tiny_config, target).generate_on_device(prompt, plen, 14)
    got = _spec(tiny_config, target, draft).generate_on_device(
        prompt, plen, 14)
    np.testing.assert_array_equal(got, want)


def test_greedy_equivalence_perfect_draft(tiny_config, target):
    """draft == target: every draft accepted, output still identical."""
    prompt = np.full((1, 7), 11, np.int32)
    plen = np.full((1,), 7, np.int32)
    want = _oracle(tiny_config, target).generate_on_device(prompt, plen, 13)
    spec = _spec(tiny_config, target, target)
    got = spec.generate_on_device(prompt, plen, 13)
    np.testing.assert_array_equal(got, want)
    assert spec.acceptance_rate == 1.0


def test_spec_scan_rounds_match_single_round(tiny_config, target, draft):
    """spec_rounds=4 (on-device chained rounds, one fetch per 4) must
    emit the same greedy stream as spec_rounds=1 (host-stepped) and the
    oracle — the scan chains _spec_round with the identical rng
    sequence, so this is exact, not approximate."""
    prompt = np.full((1, 9), 5, np.int32)
    plen = np.full((1,), 9, np.int32)
    want = _oracle(tiny_config, target).generate_on_device(prompt, plen, 20)
    one = _spec(tiny_config, target, draft, spec_rounds=1)
    scan = _spec(tiny_config, target, draft, spec_rounds=4)
    np.testing.assert_array_equal(
        one.generate_on_device(prompt, plen, 20), want)
    np.testing.assert_array_equal(
        scan.generate_on_device(prompt, plen, 20), want)


def test_spec_scan_window_edge_falls_back(tiny_config, target, draft):
    """Near max_seq_len the R-round window does not fit; the generator
    must fall back to single rounds and still emit the same stream a
    spec_rounds=1 generator does. (Comparison is spec-vs-spec, not
    vs the oracle: both paths trace the identical _spec_round, so the
    equality is bitwise — an oracle comparison can flake on fp
    near-ties between the batched verify pass and step-by-step decode,
    e.g. a 0.005 logit gap on this prompt.)"""
    def make(R):
        return SpeculativeGenerator(
            tiny_config, target, tiny_config, draft,
            ByteTokenizer(tiny_config.vocab_size),
            gamma=3, max_seq_len=48, sampling=GREEDY, spec_rounds=R)
    prompt = np.full((1, 20), 5, np.int32)
    plen = np.full((1,), 20, np.int32)
    want = make(1).generate_on_device(prompt, plen, 8)
    got = make(4).generate_on_device(prompt, plen, 8)
    np.testing.assert_array_equal(got, want)


def test_interactive_session_matches_oracle(tiny_config, target, draft):
    """next_token protocol (the CLI/API path) equals the oracle stream.

    Prompt chosen tie-free: when the target's top-2 logits tie within
    bf16 accumulation noise, the batched verify pass and stepwise decode
    may break the tie differently (both are valid greedy streams — see
    the speculative.py module docstring); random-weight fixtures make
    such exact ties possible, so the fixed prompt here avoids one."""
    oracle = _oracle(tiny_config, target)
    spec = _spec(tiny_config, target, draft)
    for g in (oracle, spec):
        g.add_message(Message.user("hi"))
    want = [oracle.next_token(i).id for i in range(10)]
    got = [spec.next_token(i).id for i in range(10)]
    assert got == want
    # reset then regenerate: same stream again
    spec.reset()
    spec.add_message(Message.user("hi"))
    assert [spec.next_token(i).id for i in range(10)] == want


def test_interactive_session_streams_the_decode_of_its_ids(
        tiny_config, target, draft):
    """The speculative generator's token texts come from the one
    detokeniser (PR 47): they are the oracle's, token for token, and
    concatenate to the decode of the ids short of an incomplete tail."""
    oracle = _oracle(tiny_config, target)
    spec = _spec(tiny_config, target, draft)
    for g in (oracle, spec):
        g.add_message(Message.user("hi"))
    want = [oracle.next_token(i) for i in range(16)]
    got = [spec.next_token(i) for i in range(16)]
    assert [t.id for t in got] == [t.id for t in want]
    assert [t.text for t in got] == [t.text for t in want]
    whole = spec.tokenizer.decode([t.id for t in got])
    text = "".join(t.text for t in got)
    assert text and whole.startswith(text)
    assert not whole[len(text):].strip("\ufffd")


def test_acceptance_stats_track(tiny_config, target, draft):
    spec = _spec(tiny_config, target, draft)
    prompt = np.full((1, 5), 3, np.int32)
    spec.generate_on_device(prompt, np.full((1,), 5, np.int32), 12)
    assert spec.proposed > 0
    assert 0.0 <= spec.acceptance_rate <= 1.0


def test_sampling_path_generates(tiny_config, target, draft):
    """temperature > 0: accept/resample path produces tokens and is
    deterministic for a fixed seed."""
    spec = SpeculativeGenerator(
        tiny_config, target, tiny_config, draft,
        ByteTokenizer(tiny_config.vocab_size), gamma=3, max_seq_len=128,
        sampling=SamplingConfig(temperature=0.8, repeat_penalty=1.0),
        seed=7)
    prompt = np.full((1, 6), 9, np.int32)
    plen = np.full((1,), 6, np.int32)
    a = spec.generate_on_device(prompt, plen, 10)
    spec2 = SpeculativeGenerator(
        tiny_config, target, tiny_config, draft,
        ByteTokenizer(tiny_config.vocab_size), gamma=3, max_seq_len=128,
        sampling=SamplingConfig(temperature=0.8, repeat_penalty=1.0),
        seed=7)
    b = spec2.generate_on_device(prompt, plen, 10)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (1, 10)
    assert (a >= 0).all()


def test_repeat_penalty_rejected(tiny_config, target, draft):
    with pytest.raises(ValueError, match="repeat_penalty"):
        SpeculativeGenerator(
            tiny_config, target, tiny_config, draft,
            ByteTokenizer(tiny_config.vocab_size), max_seq_len=128,
            sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.1))


def test_top_kp_rejected(tiny_config, target, draft):
    with pytest.raises(ValueError, match="top_k/top_p"):
        SpeculativeGenerator(
            tiny_config, target, tiny_config, draft,
            ByteTokenizer(tiny_config.vocab_size), max_seq_len=128,
            sampling=SamplingConfig(temperature=0.8, repeat_penalty=1.0,
                                    top_k=40))


def test_sampled_calls_advance_rng(tiny_config, target, draft):
    """Two sampled generate_on_device calls on ONE generator must differ
    (the PRNG stream persists across calls, like LlamaGenerator)."""
    spec = SpeculativeGenerator(
        tiny_config, target, tiny_config, draft,
        ByteTokenizer(tiny_config.vocab_size), gamma=3, max_seq_len=256,
        sampling=SamplingConfig(temperature=0.8, repeat_penalty=1.0))
    prompt = np.full((1, 6), 9, np.int32)
    plen = np.full((1,), 6, np.int32)
    a = spec.generate_on_device(prompt, plen, 10)
    b = spec.generate_on_device(prompt, plen, 10)
    assert not np.array_equal(a, b)


def test_api_serves_draft_via_engine(tiny_config):
    """--draft-model + --api now serves through the BATCHING engine
    (round-4 verdict item 4: speculation was a single-request island):
    make_engine builds a spec-mode engine, concurrent requests all
    speculate, and the engine's acceptance counters advance."""
    import json
    import threading
    import urllib.request

    from cake_tpu.api.server import start
    from cake_tpu.args import Args
    from cake_tpu.context import Context
    from cake_tpu.master import Master

    args = Args(model="", draft_model="", max_seq_len=256,
                temperature=0.0, repeat_penalty=1.0,
                flash_attention=False).validate()
    gen = Context.from_args(args).load_text_model()
    from cake_tpu.models.llama.speculative import SpeculativeGenerator
    assert isinstance(gen, SpeculativeGenerator)
    master = Master(args, text_generator=gen)
    engine = master.make_engine(max_slots=2)
    assert engine is not None and engine._spec

    httpd = start(master, address="127.0.0.1:0", block=False,
                  engine=engine.start())
    base = "http://%s:%d" % httpd.server_address[:2]
    try:
        results = []

        def one(msg):
            req = urllib.request.Request(
                base + "/api/v1/chat/completions",
                data=json.dumps({
                    "messages": [{"role": "user", "content": msg}],
                    "max_tokens": 6}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as r:
                results.append(json.loads(r.read()))

        # two CONCURRENT requests — the island could never do this
        ts = [threading.Thread(target=one, args=(m,))
              for m in ("hi", "yo")]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        assert len(results) == 2
        for obj in results:
            assert obj["choices"][0]["message"]["role"] == "assistant"
        assert engine.stats.spec_proposed > 0
        assert 0.0 <= engine.stats.spec_acceptance <= 1.0
    finally:
        httpd.shutdown()
        engine.stop()


def test_engine_spec_matches_plain_engine(tiny_config, target):
    """Engine spec mode with a PERFECT (target==draft) structured draft:
    the greedy stream equals the plain engine's, and acceptance is ~1.0
    (every draft verified correct — the plumbing proof the verdict asks
    for: a broken cache alignment or position bookkeeping would crater
    it)."""
    from cake_tpu.serve.engine import InferenceEngine

    prompts = [[5] * 9, [11] * 7, [3, 7, 9, 11]]

    def run(spec):
        kw = dict(draft_params=target, draft_config=tiny_config,
                  spec_gamma=3) if spec else {}
        eng = InferenceEngine(
            tiny_config, target, ByteTokenizer(tiny_config.vocab_size),
            max_slots=2, max_seq_len=256, sampling=GREEDY, **kw)
        with eng:
            hs = [eng.submit(p, max_new_tokens=12, temperature=0.0,
                             repeat_penalty=1.0) for p in prompts]
            assert all(h.wait(timeout=300) for h in hs)
            out = [list(h._req.out_tokens) for h in hs]
        return out, eng.stats

    want, _ = run(spec=False)
    got, stats = run(spec=True)
    assert got == want
    assert stats.spec_proposed > 0
    assert stats.spec_acceptance >= 0.9, stats.spec_acceptance


def test_engine_spec_burst_chains_and_queue_progress(tiny_config, target):
    """The double-buffered spec burst must (a) chain rounds device-side
    for a long request — more than one dispatch per _do_decode_spec
    call — and (b) still make progress when a request is QUEUED behind
    full slots (the chain gate must not suppress the first round, or
    the loop spins forever: regression for the burst deadlock)."""
    from cake_tpu.serve.engine import InferenceEngine

    eng = InferenceEngine(
        tiny_config, target, ByteTokenizer(tiny_config.vocab_size),
        max_slots=2, max_seq_len=256, sampling=GREEDY,
        draft_params=target, draft_config=tiny_config, spec_gamma=3)
    calls = {"rounds": 0, "bursts": 0}
    orig = eng._do_decode_spec
    from cake_tpu.models.llama import speculative as spec_mod
    orig_round = spec_mod.spec_round_batched

    def count_round(*a, **k):
        calls["rounds"] += 1
        return orig_round(*a, **k)

    def count_burst(plan):
        calls["bursts"] += 1
        return orig(plan)

    spec_mod.spec_round_batched = count_round
    eng._do_decode_spec = count_burst
    try:
        with eng:
            # 3 requests, 2 slots: the third queues until a slot frees
            hs = [eng.submit([5] * 9, max_new_tokens=30)
                  for _ in range(3)]
            assert all(h.wait(timeout=300) for h in hs), "burst deadlock"
    finally:
        spec_mod.spec_round_batched = orig_round
    # perfect draft (target==draft): 30 tokens at gamma=3 -> ~8 rounds
    # per request; chaining means fewer burst calls than rounds
    assert calls["rounds"] > calls["bursts"], calls
    for h in hs:
        assert len(h._req.out_tokens) == 30


def test_engine_spec_mixed_sampling_isolation(tiny_config, target, draft):
    """The batched round runs greedy and temperature>0 rows in ONE
    program; a hot row sharing rounds with a greedy row must not change
    the greedy row's stream (per-row key masks: greedy rows never
    advance their PRNG, sampled rows draw per-row uniforms)."""
    from cake_tpu.serve.engine import InferenceEngine

    def run(with_hot):
        eng = InferenceEngine(
            tiny_config, target, ByteTokenizer(tiny_config.vocab_size),
            max_slots=2, max_seq_len=256, sampling=GREEDY,
            draft_params=draft, draft_config=tiny_config, spec_gamma=3)
        with eng:
            cold = eng.submit([5] * 9, max_new_tokens=10,
                              temperature=0.0, repeat_penalty=1.0)
            hot = (eng.submit([11] * 7, max_new_tokens=10,
                              temperature=0.9, repeat_penalty=1.0)
                   if with_hot else None)
            assert cold.wait(300)
            if hot is not None:
                assert hot.wait(300)
            return list(cold._req.out_tokens)

    assert run(with_hot=False) == run(with_hot=True)


def test_engine_spec_bad_draft_still_exact(tiny_config, target, draft):
    """A wrong draft must never change the engine's output — only the
    acceptance rate."""
    from cake_tpu.serve.engine import InferenceEngine

    def run(dp):
        kw = dict(draft_params=dp, draft_config=tiny_config,
                  spec_gamma=3) if dp is not None else {}
        eng = InferenceEngine(
            tiny_config, target, ByteTokenizer(tiny_config.vocab_size),
            max_slots=2, max_seq_len=256, sampling=GREEDY, **kw)
        with eng:
            h = eng.submit([5] * 9, max_new_tokens=10, temperature=0.0,
                           repeat_penalty=1.0)
            assert h.wait(timeout=300)
            return list(h._req.out_tokens)

    assert run(draft) == run(None)


def test_engine_spec_rejects_incompatible_sampling(tiny_config, target):
    from cake_tpu.serve.engine import InferenceEngine

    eng = InferenceEngine(
        tiny_config, target, ByteTokenizer(tiny_config.vocab_size),
        max_slots=2, max_seq_len=256, sampling=GREEDY,
        draft_params=target, draft_config=tiny_config, spec_gamma=2)
    with eng:
        with pytest.raises(ValueError, match="temperature-only"):
            eng.submit([5] * 6, max_new_tokens=4, repeat_penalty=1.3)
        with pytest.raises(ValueError, match="temperature-only"):
            eng.submit([5] * 6, max_new_tokens=4, top_p=0.9)
        with pytest.raises(ValueError, match="logprobs"):
            eng.submit([5] * 6, max_new_tokens=4,
                       want_top_logprobs=True)


def test_prefill_chunk_rejected_with_draft(tiny_config):
    from cake_tpu.args import Args
    from cake_tpu.context import Context

    args = Args(model="", draft_model="", prefill_chunk=32,
                max_seq_len=256, temperature=0.0, repeat_penalty=1.0,
                flash_attention=False).validate()
    with pytest.raises(ValueError, match="prefill-chunk"):
        Context.from_args(args).load_text_model()


def test_context_wires_draft_model(tiny_config):
    """--draft-model from the Args/Context path builds the speculative
    generator (random-init draft when no weights exist)."""
    from cake_tpu.args import Args
    from cake_tpu.context import Context

    args = Args(model="", draft_model="", spec_gamma=2, max_seq_len=128,
                temperature=0.0, repeat_penalty=1.0,
                flash_attention=False).validate()
    gen = Context.from_args(args).load_text_model()
    assert isinstance(gen, SpeculativeGenerator)
    gen.add_message(Message.user("hi"))
    toks = [gen.next_token(i).id for i in range(4)]
    assert len(toks) == 4


def test_draft_does_not_compose_with_topology(tmp_path, tiny_config):
    from cake_tpu.args import Args
    from cake_tpu.context import Context

    topo = tmp_path / "topology.yml"
    topo.write_text(
        "w0:\n  host: a:1\n  layers: [model.layers.0-1]\n"
        "w1:\n  host: b:1\n  layers: [model.layers.2-3]\n")
    args = Args(model="", draft_model="", topology=str(topo),
                max_seq_len=128, temperature=0.0, repeat_penalty=1.0,
                flash_attention=False).validate()
    with pytest.raises(ValueError, match="single-device"):
        Context.from_args(args).load_text_model()
