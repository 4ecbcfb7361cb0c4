"""One decode step in flight (PR 29).

A pure-decode iteration of a single-host engine dispatches the sampled
one-step program (`decode_step_sampled`: forward + `_masked_sample` + the
EOS and budget freeze), chains step k+1 from step k's carry while k is
still on the device, and fetches and emits k while k+1 runs
(`InferenceEngine._decode_burst` at one step per dispatch). What is
pinned here:

  * the in-flight path equals the synchronous `_decode_device` path
    token for token, logprob for logprob, and in the final PRNG keys and
    penalty rings, greedy and sampled, on every engine flavour;
  * a row that ends (budget, EOS, the window) while its successor is in
    flight emits nothing further and writes nothing past its end;
  * the host is seen within one step: a submit and a cancel end the
    stretch, and STRETCH_STEPS ends it when nothing else does;
  * the records: kind `decode`, the engine's impl, `chained`, `gap_s`,
    `wall_s`, a sparse model's `moe_*`, and the chained counter;
  * which rows keep the synchronous step;
  * why each stretch ended (PR 35): `chain_break` on the next record
    that is not chained, `cake_chain_breaks_total{cause}`;
  * the streamed text (PR 47): a token's text comes from a detokeniser
    that keeps its place on the request; the chunks are the buffered
    text, a held token's logprob entry rides the chunk with its text,
    EOS never reaches the tokenizer, and the records count the ids
    decoded (`detok_ids`).
"""

import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.models.llama.generator import ByteTokenizer
from cake_tpu.models.llama.params import init_params
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import engine as engine_mod
from cake_tpu.obs.steps import LATE_FETCH_S
from cake_tpu.serve.engine import STRETCH_STEPS, InferenceEngine

T = 96
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)
SAMPLED = dict(temperature=0.8, top_p=0.9, repeat_penalty=1.2,
               want_top_logprobs=True)
PAGED = dict(kv_pages=40, kv_page_size=8)
# every engine flavour with a sampled program, float32 and int8 pools
FLAVOURS = {
    "paged-fold": dict(PAGED, paged_attn="fold"),
    "paged-fold-int8": dict(PAGED, paged_attn="fold", kv_dtype="int8"),
    "paged-pallas": dict(PAGED, paged_attn="pallas"),
    "paged-pallas-int8": dict(PAGED, paged_attn="pallas", kv_dtype="int8"),
    "dense": {},
    "ring": dict(window=16),
}


@pytest.fixture(scope="module")
def params(tiny_config):
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


def make_engine(cfg, params, *, sync=False, window=None, max_seq_len=T,
                **kw):
    if window is not None:
        cfg = dataclasses.replace(cfg, sliding_window=window)
        kw.setdefault("prefill_chunk", 8)
    eng = InferenceEngine(
        cfg, params, ByteTokenizer(cfg.vocab_size), max_slots=4,
        max_seq_len=max_seq_len,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        cache_dtype=jnp.float32, **kw)
    if sync:
        # step fns that bring no sampled program: one of the reasons the
        # engine observes for the synchronous step
        eng._decode_scan_impl = None
    return eng


def serve(eng, requests, wait=300):
    """Queue everything before the loop starts (both engines of a
    comparison then admit in the same iteration), run to the end, and
    return the handles with the engine's final sampling state."""
    hs = [eng.submit(p, **kw) for p, kw in requests]
    with eng:
        for h in hs:
            assert h.wait(wait)
        state = (np.asarray(eng._keys), np.asarray(eng._ring))
    return hs, state


def breaks(cause):
    return obs_metrics.REGISTRY.get("cake_chain_breaks_total").labels(
        cause=cause).value


def spy_breaks(eng):
    """Every cause the driver hands the recorder, in order."""
    told, chain_broke = [], eng.flight.chain_broke
    eng.flight.chain_broke = lambda c: (told.append(c), chain_broke(c))[1]
    return told


def decode_records(eng):
    return [r for r in reversed(eng.flight.dump()) if r["kind"] == "decode"]


# -- the in-flight path is the synchronous path --------------------------------


@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
@pytest.mark.parametrize("flavour", list(FLAVOURS))
def test_in_flight_equals_synchronous(tiny_config, params, flavour,
                                      sampling):
    opts = GREEDY if sampling == "greedy" else SAMPLED
    # different lengths: a row runs out of budget while the others'
    # next step is already in flight
    requests = [([5] * 9, dict(opts, max_new_tokens=14)),
                ([11, 3, 7], dict(opts, max_new_tokens=6)),
                ([2] * 12, dict(opts, max_new_tokens=23))]
    got, got_state = serve(
        make_engine(tiny_config, params, **FLAVOURS[flavour]), requests)
    ref_eng = make_engine(tiny_config, params, sync=True,
                          **FLAVOURS[flavour])
    want, want_state = serve(ref_eng, requests)
    assert all(not r["chained"] for r in decode_records(ref_eng))
    for g, w in zip(got, want):
        assert g.token_ids == w.token_ids
        assert len(g.token_ids) == g._req.max_new_tokens
        np.testing.assert_allclose(g._req.out_logprobs,
                                   w._req.out_logprobs, atol=1e-5)
        if sampling == "sampled":
            for tg, tw in zip(g._req.out_top, w._req.out_top):
                assert [i for i, _ in tg] == [i for i, _ in tw]
                np.testing.assert_allclose([l for _, l in tg],
                                           [l for _, l in tw], atol=1e-5)
    for g, w in zip(got_state, want_state):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("end", ["budget", "eos"])
@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_row_that_ends_in_flight_writes_and_emits_no_more(
        tiny_config, params, attn, end):
    """The step after a row's last is dispatched before the host knows
    the row ended; the program froze the row (budget 0, or EOS in the
    carry), so the pool holds what the synchronous engine's holds."""
    kw = dict(PAGED, paged_attn=attn)
    short = 7
    requests = [([5] * 9, dict(GREEDY, max_new_tokens=20)),
                ([3, 7, 9, 4], dict(GREEDY, max_new_tokens=short))]
    cfg = tiny_config
    if end == "eos":
        # the token the short request emits fourth becomes the EOS
        probe, _ = serve(make_engine(cfg, params, sync=True, **kw),
                         requests[1:])
        cfg = dataclasses.replace(
            cfg, eos_token_ids=(probe[0].token_ids[3],))
    pools = []
    outs = []
    for sync in (False, True):
        eng = make_engine(cfg, params, sync=sync, **kw)
        hs, _ = serve(eng, requests)
        outs.append([list(h._req.out_tokens) for h in hs])
        pools.append((np.asarray(eng.cache.k), np.asarray(eng.cache.v)))
    assert outs[0] == outs[1]
    if end == "eos":
        assert len(outs[0][1]) < short
        assert outs[0][1][-1] in cfg.eos_token_ids
    else:
        assert len(outs[0][1]) == short
    for got, want in zip(*pools):
        np.testing.assert_array_equal(got != 0, want != 0)
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_window_end_matches_synchronous(tiny_config, params):
    """A budget far past the window: the cap ends the request where
    the synchronous engine's ends, and the stretch never chains a row
    onto max_seq_len (the carry has no window freeze; the guard
    projects the row by its unfetched tokens)."""
    requests = [(list(range(3, 11)), dict(GREEDY, max_new_tokens=1000))]
    eng = make_engine(tiny_config, params, max_seq_len=48, **PAGED)
    got, _ = serve(eng, requests)
    want, _ = serve(make_engine(tiny_config, params, sync=True,
                                max_seq_len=48, **PAGED), requests)
    assert got[0].token_ids == want[0].token_ids
    assert int(np.max(eng._pos)) <= 48
    recs = decode_records(eng)
    assert any(r["chained"] for r in recs)
    assert len(recs) == len(got[0]._req.out_tokens) - 1


# -- the host is seen within one step ------------------------------------------


def _spy_dispatches(eng):
    """[(perf_counter, chained from the device carry)] of every sampled
    dispatch, decode and (a paged engine's) mixed."""
    seen = []
    orig, orig_mixed = eng._dispatch_scan_device, eng._run_mixed_step

    def spy(rows, n, n_top, budget, state=None):
        seen.append((time.perf_counter(), state is not None))
        return orig(rows, n, n_top, budget, state=state)

    def spy_mixed(step, carry, size):
        seen.append((time.perf_counter(), carry is not None))
        return orig_mixed(step, carry, size)

    eng._dispatch_scan_device, eng._run_mixed_step = spy, spy_mixed
    return seen


def _wait_tokens(h, n):
    t0 = time.perf_counter()
    while len(h._req.out_tokens) < n and time.perf_counter() - t0 < 120:
        time.sleep(0.001)
    assert len(h._req.out_tokens) >= n


@pytest.mark.parametrize("event", ["submit", "cancel"])
def test_host_event_ends_the_stretch_within_one_step(tiny_config, params,
                                                     event):
    cause = {"submit": "queue", "cancel": "cancel"}[event]
    counted = breaks(cause)
    eng = make_engine(tiny_config, params, max_seq_len=512,
                      kv_pages=80, kv_page_size=8)
    seen = _spy_dispatches(eng)
    told = spy_breaks(eng)
    with eng:
        long = eng.submit([5] * 9, max_new_tokens=400, **GREEDY)
        _wait_tokens(long, 5)
        if event == "submit":
            other = eng.submit([7, 8, 9], max_new_tokens=4, **GREEDY)
        else:
            eng.cancel(long)
        t_event = time.perf_counter()
        after = None
        if event == "submit":
            assert other.wait(120)
            # the newcomer got its first token from the very next step:
            # the long request advanced by at most the one step that
            # was queued when the submit landed, plus the mixed step
            after = len(long._req.out_tokens)
            eng.cancel(long)
        assert long.wait(120)
    # of the dispatches chained after the event returned, only the one
    # whose gate was passed before it can exist (a cancel ends the
    # request; a submit is followed by new stretches, whose first
    # dispatch is not chained)
    chained_after = [t for t, chained in seen if chained and t > t_event]
    if event == "cancel":
        assert len(chained_after) <= 1
        assert len(long._req.out_tokens) < 400
    else:
        first_unchained = min(t for t, chained in seen
                              if not chained and t > t_event)
        assert sum(t < first_unchained for t in chained_after) <= 1
        assert after is not None and other.token_ids
        # the newcomer's first step is the one that says `queue`, and
        # admitted one row
        joined = next(r for r in reversed(eng.flight.dump())
                      if other._req.rid in r["rids"])
        assert joined["chain_break"] == "queue"
        assert joined["rows_admitted"] == 1 and not joined["chained"]
        assert breaks("queue") - counted >= 1
    # the event ended the stretch by its own name (after a cancel
    # nothing runs: the cause waits, the counter counts what landed)
    assert told[0] == cause


@pytest.mark.parametrize("flavour", ["dense", "paged-fold"])
def test_admitted_row_joins_the_step_after_its_prefill(tiny_config, params,
                                                       flavour):
    """The scheduler plans a wave's admissions and its decode rows
    apart, so the decode plan of the iteration that prefills a prompt
    does not hold its row: that iteration runs one decode dispatch and
    goes back to the planner. A stretch on the stale plan left every
    new row idle for STRETCH_STEPS steps (my chip run, PR 29: 6.3 of 8
    rows busy on four chips)."""
    eng = make_engine(tiny_config, params, max_seq_len=256,
                      **dict(FLAVOURS[flavour], **(
                          dict(kv_pages=80) if "paged" in flavour else {})))
    with eng:
        a = eng.submit([5] * 9, max_new_tokens=150, **GREEDY)
        _wait_tokens(a, 5)
        b = eng.submit([7, 8, 9], max_new_tokens=40, **GREEDY)
        assert b.wait(120)
        eng.cancel(a)
        assert a.wait(120)
    recs = list(reversed(eng.flight.dump()))
    rid_b = b._req.rid
    first = next(i for i, r in enumerate(recs) if rid_b in r["rids"])
    after = [r for r in recs[first + 1:] if r["kind"] == "decode"]
    # at most one decode step without the newcomer, then both rows
    alone = [r for r in after[:3] if rid_b not in r["rids"]]
    assert len(alone) <= 1, [(r["rows"], r["rids"]) for r in after[:4]]
    joined = [r for r in after if rid_b in r["rids"]]
    assert joined and joined[0]["rows"] == 2
    assert len(joined) >= 38 and any(r["chained"] for r in joined)
    if flavour == "dense":
        # the one dispatch on the stale plan is a chain of one by the
        # caller's word (chain=False): the step after it says `sync`
        # (a paged engine's decode steps ride the mixed stretch on)
        assert "sync" in [r.get("chain_break") for r in after[:3]]


def test_stretch_length_returns_to_the_loop(tiny_config, params):
    """Nobody arrives, nothing ends: the stretch still hands the thread
    back to _run_loop every STRETCH_STEPS dispatches."""
    n = 2 * STRETCH_STEPS + 9
    counted = breaks("stretch_cap")
    eng = make_engine(tiny_config, params, max_seq_len=256,
                      kv_pages=40, kv_page_size=8)
    serve(eng, [([5] * 9, dict(GREEDY, max_new_tokens=n + 1))])
    recs = list(reversed(eng.flight.dump()))
    assert [r["kind"] for r in recs] == ["mixed"] + ["decode"] * n
    # the first stretch is the prompt's one window and the decode steps
    # chained onto it
    starts = [i for i, r in enumerate(recs) if not r["chained"]]
    assert starts == [0, STRETCH_STEPS, 2 * STRETCH_STEPS]
    assert [recs[i].get("chain_break") for i in starts] == [
        None, "stretch_cap", "stretch_cap"]
    assert breaks("stretch_cap") - counted == 2
    # a step whose successor was sent in time waited for the device;
    # `late` is the host clock's word on the rest
    chained = [r for r in recs if r["chained"]]
    assert all("fetch_wait_s" in r and "late" in r for r in chained)
    # (decided from the wait as the record holds it, to a microsecond)
    assert all(r["late"] == (r["fetch_wait_s"] < LATE_FETCH_S)
               for r in chained)
    assert not any("late" in recs[i] for i in starts)


def test_finished_row_ends_the_stretch(tiny_config, params):
    """A row's last token is emitted with its successor step already in
    flight; nothing is chained onto that step, so whoever takes the
    free slot waits for one step and not two (on the chip the arrival
    that waited out two met the next one: PERF.md, PR 29)."""
    eng = make_engine(tiny_config, params, max_seq_len=256,
                      kv_pages=40, kv_page_size=8)
    serve(eng, [([5] * 9, dict(GREEDY, max_new_tokens=30)),
                ([3, 7, 9, 4], dict(GREEDY, max_new_tokens=7))])
    recs = decode_records(eng)
    rows = [r["rows"] for r in recs]
    k = rows.index(1)          # dispatched before the host saw the end
    assert rows[:k] == [2] * k and k >= 5
    assert recs[k]["chained"] and not recs[k + 1]["chained"]
    assert all(r["chained"] for r in recs[k + 2:])
    assert len(recs) == 29
    assert recs[k + 1]["chain_break"] == "row_finished"
    assert recs[k + 1]["rows_admitted"] == 0


# -- the records ---------------------------------------------------------------


@pytest.mark.parametrize("flavour", ["paged-pallas", "dense"])
def test_records_of_a_stretch(tiny_config, params, flavour):
    chained_total = obs_metrics.REGISTRY.get(
        "cake_decode_steps_chained_total")
    before = chained_total.value
    eng = make_engine(tiny_config, params, **FLAVOURS[flavour])
    hs, _ = serve(eng, [([5] * 9, dict(GREEDY, max_new_tokens=20)),
                        ([4] * 5, dict(GREEDY, max_new_tokens=20))])
    recs = decode_records(eng)
    assert len(recs) == 19 and not any(
        r["kind"] == "decode_scan" for r in eng.flight.dump())
    assert all(r["impl"] == flavour for r in recs)
    # a paged engine's first decode step is chained onto the mixed step
    # that ended the prompts
    first = flavour != "dense"
    assert [r["chained"] for r in recs] == [first] + [True] * 18
    assert chained_total.value - before == 18 + first
    rids = sorted(h._req.rid for h in hs)
    for r in recs:
        assert r["rows"] == 2 and r["tokens"] == 2
        assert sorted(r["rids"]) == rids and r["ts"] > 0
        assert "sample" not in r["phases"]
    assert all(r["gap_s"] == 0.0 for r in recs[1:])
    assert (recs[0]["gap_s"] > 0.0) != first
    # a chained step's wall_s is the time it added to the loop: from
    # the fetch before it to its own, so the stretch's wall_s add up to
    # the time between its first record and its last
    elapsed = recs[-1]["ts"] - recs[0]["ts"]
    summed = sum(r["wall_s"] for r in recs[1:])
    assert summed == pytest.approx(elapsed, rel=0.05, abs=2e-3)
    # and every span of the stretch lies inside it
    covered = sum(sum(r["phases"].values()) for r in recs[1:])
    assert covered <= elapsed + 2e-3


@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_sparse_model_decode_records_keep_expert_counters(attn):
    from cake_tpu.models.moe.config import MoEConfig
    from cake_tpu.models.moe.params import init_params as init_moe

    cfg = MoEConfig.tiny_olmoe()
    moe_params = init_moe(cfg, jax.random.PRNGKey(1), jnp.float32)
    eng = make_engine(cfg, moe_params, paged_attn=attn, **PAGED)
    serve(eng, [([5] * 9, dict(GREEDY, max_new_tokens=8)),
                ([4] * 5, dict(GREEDY, max_new_tokens=8))])
    recs = decode_records(eng)
    assert recs and any(r["chained"] for r in recs)
    k, layers = cfg.num_experts_per_tok, cfg.num_hidden_layers
    for r in recs:
        # every live row routes k experts in every layer
        assert r["moe_rows"] == r["rows"] * k * layers, r
        assert r["moe_rows_padded"] >= r["moe_rows"]
        assert 0 < r["moe_experts_touched"] <= r["moe_rows"]


# -- which rows keep the synchronous step --------------------------------------


@pytest.mark.parametrize("reason", ["none", "multihost", "no-program",
                                    "spec-leftover", "window-end"])
def test_rows_that_keep_the_synchronous_step(tiny_config, params, reason):
    eng = make_engine(tiny_config, params, **PAGED)
    calls = []
    eng._do_decode = lambda plan: calls.append("sync")
    eng._decode_burst = lambda plan, n, chain: calls.append(
        ("in-flight", n))
    plan = [(1, 0), (2, 1)]
    eng._pos[:2] = 20
    if reason == "multihost":
        eng._multihost = True
    elif reason == "no-program":
        eng._decode_scan_impl = None
    elif reason == "spec-leftover":
        eng._specp = object()
    elif reason == "window-end":
        eng._pos[1] = T - 1
    eng._decode_rows(plan)
    assert calls == ([("in-flight", 1)] if reason == "none" else ["sync"])


def test_one_step_program_is_found_by_the_benchmarks_prefix():
    """benchmarks/harness/trace_spans.py finds a decode step's device
    time by its XLA module's name."""
    from cake_tpu.models.llama.config import MODEL_TYPES
    from test_family import tiny_config
    for progs in (engine_mod._decode_scan, engine_mod._decode_scan_ring,
                  *(tiny_config(model_type).family.decode_programs
                    for model_type in MODEL_TYPES)):
        assert ("jit_" + progs.step.__name__).startswith("jit_decode_step")
        assert not ("jit_" + progs.scan.__name__).startswith(
            "jit_decode_step")


# -- the streamed text -----------------------------------------------------------


def collect(got):
    """A stream callback as api/server.py's: (delta, final, n_done)."""
    def stream(delta, final, n_done=0):
        got.append((delta, final, n_done))
    stream.wants_count = True
    return stream


STREAMED = {"greedy": GREEDY,
            "sampled": dict(temperature=0.8, top_p=0.9, repeat_penalty=1.2)}
PROMPTS = [[5, 9, 3 + i] for i in range(3)]


def serve_streams(eng, sampling, n=40, prompts=PROMPTS):
    got = [[] for _ in prompts]
    hs, _ = serve(eng, [(p, dict(max_new_tokens=n, stream=collect(g),
                                 **STREAMED[sampling]))
                        for p, g in zip(prompts, got)])
    return hs, got


def whole_output_chunks(tok, ids):
    """The reference: `incremental_decode` as it was before PR 47, the
    whole output decoded again at every token. [(delta, n_done)] of the
    chunks that carry text, and the text left for the flush."""
    sent, chunks = "", []
    for n in range(1, len(ids) + 1):
        full = tok.decode(ids[:n])
        new = full[len(sent):]
        if new and not new.endswith("\ufffd"):
            chunks.append((new, n))
            sent = full
    return chunks, tok.decode(ids)[len(sent):]


@pytest.mark.parametrize("sampling", list(STREAMED))
@pytest.mark.parametrize("flavour", ["paged-fold", "dense", "ring"])
def test_streamed_chunks_are_the_buffered_text(tiny_config, params, flavour,
                                               sampling):
    """The same prompts and seed, streamed and buffered: the chunks of a
    stream concatenate to the text the buffered response returns."""
    hs, got = serve_streams(
        make_engine(tiny_config, params, **FLAVOURS[flavour]), sampling)
    buffered, _ = serve(
        make_engine(tiny_config, params, **FLAVOURS[flavour]),
        [(p, dict(max_new_tokens=40, **STREAMED[sampling]))
         for p in PROMPTS])
    for h, chunks, b in zip(hs, got, buffered):
        assert h.token_ids == b.token_ids
        assert "".join(c[0] for c in chunks) == b.text() == h.text()
        assert [c[1] for c in chunks] == [False] * (len(chunks) - 1) + [True]
        assert b._req._detok is None          # nobody streams: no decode


@pytest.mark.parametrize("sampling", list(STREAMED))
def test_a_held_tokens_entry_ships_with_its_text(tiny_config, params,
                                                 sampling):
    """Random bytes: most tokens are half a character. A token whose
    text is incomplete sends no chunk, and the chunk that carries its
    text counts its logprob entry (`n_done`); a token that has text is a
    chunk of its own in the step that produced it. Chunk for chunk what
    the whole-output form gave."""
    hs, got = serve_streams(
        make_engine(tiny_config, params, **PAGED), sampling)
    tok = ByteTokenizer(tiny_config.vocab_size)
    held = 0
    for h, chunks in zip(hs, got):
        ids = h.token_ids
        assert len(ids) == 40
        want, tail = whole_output_chunks(tok, ids)
        *body, last = chunks
        assert [(d, n) for d, _, n in body] == [
            c for c in want if c[1] < len(ids)]
        # the last token's chunk is the final one, tail flushed
        assert last == ("".join(d for d, n in want if n == len(ids)) + tail,
                        True, len(ids))
        seen = [n for _, _, n in chunks]
        assert seen == sorted(set(seen))
        held += sum(b - a > 1 for a, b in zip([0] + seen, seen))
        # what a chunk's entries spell is what has been sent
        sent = ""
        for delta, _, n in body:
            sent += delta
            assert tok.decode(ids[:n]) == sent
    assert held > 5


def test_eos_never_reaches_the_tokenizer(tiny_config, params):
    """The stream's end is decided on the id; the tokenizer sees the
    ids before it only, by whatever way the text is asked for."""
    first, _ = serve_streams(make_engine(tiny_config, params, **PAGED),
                             "greedy", n=12, prompts=PROMPTS[:1])
    ids = first[0].token_ids
    eos = ids[6]
    cut = ids[:ids.index(eos)]
    assert cut, "the stream would end on its first token"

    class Spy(ByteTokenizer):
        seen = []

        def decode(self, ids):
            self.seen.append(list(ids))
            return super().decode(ids)

    cfg = dataclasses.replace(tiny_config, eos_token_ids=(eos,))
    eng = make_engine(cfg, params, **PAGED)
    eng.tokenizer = Spy(cfg.vocab_size)
    hs, got = serve_streams(eng, "greedy", n=12, prompts=PROMPTS[:1])
    assert hs[0]._req.out_tokens == cut + [eos]
    assert Spy.seen and not any(eos in ids for ids in Spy.seen)
    assert "".join(c[0] for c in got[0]) == eng.tokenizer.decode(cut)
    # the final chunk counts the EOS entry, and carries no text of it
    assert got[0][-1][1:] == (True, len(cut) + 1)
    assert hs[0].text() == eng.tokenizer.decode(cut)
    assert not any(eos in ids for ids in Spy.seen)


def test_records_count_the_ids_the_detokeniser_decodes(tiny_config, params):
    """`detok_ids`: the ids handed to `decode` inside the emit span
    since the record before. A few a token however long the output; a
    buffered request decodes nothing while it runs."""
    eng = make_engine(tiny_config, params, **PAGED)
    hs, _ = serve_streams(eng, "greedy", n=60)
    recs = list(reversed(eng.flight.dump()))
    counted = [r for r in recs if "detok_ids" in r]
    assert counted and all(r["detok_ids"] > 0 for r in counted)
    assert all("emit.detok" in r["parts"] for r in counted)
    total = sum(h._req._detok.decoded_ids for h in hs)
    # the last step's emit follows the last record
    assert 0 < total - sum(r["detok_ids"] for r in counted) <= 3 * 40
    # random bytes: a held token widens the window, and still a
    # quarter of what the whole-output form decoded (1 + ... + 60 a row)
    assert total < 8 * 3 * 60
    assert 4 * total < 3 * sum(range(1, 61))
    quiet = make_engine(tiny_config, params, **PAGED)
    serve(quiet, [(p, dict(max_new_tokens=20, **GREEDY)) for p in PROMPTS])
    assert not any("detok_ids" in r for r in quiet.flight.dump())
