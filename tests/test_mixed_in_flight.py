"""One mixed step in flight (PR 32).

While a prompt is mid-prefill a paged engine runs mixed steps, and keeps
one in flight as it does a decode step (tests/test_decode_in_flight.py):
the program samples (`make_mixed_sampled`), step k+1 is dispatched before
k is fetched, its decode rows fed from k's carry on the device and its
prompt windows from the host, and k is fetched, recorded and emitted
while k+1 runs (`InferenceEngine._mixed_burst` through `_drive_burst`).
What is pinned here:

  * the chained engine gives the streams of the same engine with
    chaining held off, token for token and logprob for logprob, greedy
    and sampled, under staggered arrivals over several windows, and
    leaves the same keys and rings;
  * a row that ends its prompt in step k decodes from the carry in k+1,
    and the stretch goes on into the sampled decode program;
  * a row that ends (EOS, budget, the window) inside an unfetched step
    emits nothing further, writes nothing further and frees its pages
    once;
  * the host is seen within one step: nothing is dispatched ahead after
    an emit in which a row finished, a submit, a cancel or a command;
  * stretches cross kinds (mixed -> decode on one carry; an admission
    starts the next with a mixed step), and between two chained steps
    the engine launches no other program;
  * a step of several dispatches, a sparse model's counters, the latent
    model's one-window dispatches;
  * the records, the counter, the program's name and its one `n_top`
    form; the paged speculative engine's mixed steps are not chained;
  * why each chain ended (PR 35): every event names ITS cause on the
    next record that is not chained and in `cake_chain_breaks_total`,
    and the gates decide as they did before they said why.
"""

import dataclasses
import threading
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
from cake_tpu.serve.engine import STRETCH_STEPS, InferenceEngine

T = 96
WIDTH = 8
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)
SAMPLED = dict(temperature=0.8, top_p=0.9, repeat_penalty=1.2,
               want_top_logprobs=True)
FLAVOURS = {
    "fold": dict(paged_attn="fold"),
    "fold-int8": dict(paged_attn="fold", kv_dtype="int8"),
    "pallas": dict(paged_attn="pallas"),
}


@pytest.fixture(scope="module")
def params(tiny_config):
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


def make_engine(cfg, params, *, held_off=False, **kw):
    opts = dict(max_slots=4, max_seq_len=T, cache_dtype=jnp.float32,
                sampling=SamplingConfig(temperature=0.0,
                                        repeat_penalty=1.0),
                kv_pages=60, kv_page_size=8, paged_attn="fold",
                prefill_chunk=WIDTH)
    opts.update(kw)
    eng = InferenceEngine(cfg, params, ByteTokenizer(cfg.vocab_size), **opts)
    if held_off:
        # the gate every stretch asks before it dispatches ahead: with
        # the host always wanting the loop back, each step is the same
        # program dispatched, fetched and emitted before the next
        eng._host_attention = lambda: "queue"
    return eng


def serve(eng, requests, later=(), wait=300):
    """Queue `requests` before the loop starts and run to the end.
    later: (n, event) pairs, run on the engine thread inside the emit of
    the first request's n-th token (a step is then in flight behind
    it): an event is a request to submit (prompt, options), or a
    callable given the handles so far. Returns the handles, in order of
    submission, with the engine's final keys and rings."""
    hs = [eng.submit(p, **kw) for p, kw in requests]
    first, emit, todo = hs[0]._req, eng._emit, sorted(later,
                                                      key=lambda e: e[0])

    def hooked(req, *a, **kw):
        emit(req, *a, **kw)
        while todo and req is first and len(first.out_tokens) >= todo[0][0]:
            _n, event = todo.pop(0)
            if callable(event):
                event(hs)
            else:
                hs.append(eng.submit(event[0], **event[1]))

    eng._emit = hooked
    with eng:
        assert hs[0].wait(wait)
        assert not todo
        for h in hs:
            assert h.wait(wait)
        state = (np.asarray(eng._keys), np.asarray(eng._ring))
    return hs, state


def breaks(cause):
    return obs_metrics.REGISTRY.get("cake_chain_breaks_total").labels(
        cause=cause).value


def records(eng, kind=None):
    return [r for r in reversed(eng.flight.dump())
            if kind is None or r["kind"] == kind]


def assert_same_streams(got, want, tops=False):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.token_ids == w.token_ids
        np.testing.assert_allclose(g._req.out_logprobs,
                                   w._req.out_logprobs, atol=1e-5)
        assert [len(t) for t in g._req.out_top] \
            == [len(t) for t in w._req.out_top]
        if tops:
            for tg, tw in zip(g._req.out_top, w._req.out_top):
                assert [i for i, _ in tg] == [i for i, _ in tw]
                np.testing.assert_allclose([l for _, l in tg],
                                           [l for _, l in tw], atol=1e-5)


# -- the chained engine is the engine with chaining held off -------------------


def _staggered(opts):
    """One request decoding, then prompts of 1 to 5 windows arriving
    while it and each other run; lengths differ so that rows end while
    the others' next step is in flight."""
    first = [([5] * 9, dict(opts, max_new_tokens=40))]
    later = [(3, ([11, 3, 7] * 9, dict(opts, max_new_tokens=6))),
             (5, ([2] * 37, dict(opts, max_new_tokens=19))),
             (20, ([9, 4] * 3, dict(opts, max_new_tokens=12))),
             (22, ([6] * 20, dict(opts, max_new_tokens=9)))]
    return first, later


@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
@pytest.mark.parametrize("flavour", list(FLAVOURS))
def test_in_flight_equals_held_off(tiny_config, params, flavour, sampling):
    opts = GREEDY if sampling == "greedy" else SAMPLED
    eng = make_engine(tiny_config, params, **FLAVOURS[flavour])
    got, got_state = serve(eng, *_staggered(opts))
    ref = make_engine(tiny_config, params, held_off=True,
                      **FLAVOURS[flavour])
    want, want_state = serve(ref, *_staggered(opts))
    assert not any(r["chained"] for r in records(ref))
    mixed = records(eng, "mixed")
    assert sum(r["chained"] for r in mixed) >= len(mixed) // 2
    assert_same_streams(got, want, tops=sampling == "sampled")
    for h in got:
        assert len(h.token_ids) == h._req.max_new_tokens
    for g, w in zip(got_state, want_state):
        np.testing.assert_array_equal(g, w)
    assert eng._pager.free_pages == 60 and eng._mixed_pending == {}


def test_row_that_ends_its_prompt_decodes_from_the_carry(tiny_config,
                                                         params):
    """Two prompts of 2 and 4 windows: the shorter one's first token is
    sampled in step 2 and is its input in step 3, dispatched before
    step 2 is fetched, so the host's mirror of it is still stale; when
    the longer one ends, the stretch goes on into the sampled decode
    program from the same carry."""
    eng = make_engine(tiny_config, params)
    seen = []
    run = eng._run_mixed_step

    def spy(step, carry, size):
        flags = step[:, -1]
        if flags.any():         # not start()'s idle runs
            seen.append(dict(
                qlen=step[:, -3].copy(),
                sample=(flags & engine_mod.ROW_SAMPLE) != 0,
                from_carry=(flags & engine_mod.ROW_FROM_CARRY) != 0,
                carried=carry is not None, last_tok=eng._last_tok.copy()))
        return run(step, carry, size)

    eng._run_mixed_step = spy
    # both end in the same step: nothing but the prompts' ends happens
    requests = [([5, 6, 7] * 5, dict(GREEDY, max_new_tokens=14)),
                ([9] * 29, dict(GREEDY, max_new_tokens=12))]
    got, _ = serve(eng, requests)
    want, _ = serve(make_engine(tiny_config, params, held_off=True),
                    requests)
    assert_same_streams(got, want)
    assert [s["qlen"][:2].tolist() for s in seen] \
        == [[8, 8], [7, 8], [1, 8], [1, 5]]
    assert [s["sample"][:2].tolist() for s in seen] \
        == [[False, False], [True, False], [True, False], [True, True]]
    assert [s["from_carry"][:2].tolist() for s in seen] \
        == [[False, False], [False, False], [True, False], [True, False]]
    assert [s["carried"] for s in seen] == [False, True, True, True]
    # step 3 was built before step 2's token reached the host
    assert seen[2]["last_tok"][0] != got[0].token_ids[0]
    recs = records(eng)
    assert [r["kind"] for r in recs[:5]] == ["mixed"] * 4 + ["decode"]
    # the whole run is one stretch: the decode steps after the last
    # window are chained onto it
    assert [r["chained"] for r in recs] == [False] + [True] * (len(recs) - 1)
    assert len(recs) == 4 + 11


# -- a row that ends inside an unfetched step ----------------------------------


@pytest.mark.parametrize("end", ["budget", "eos"])
@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_row_that_ends_in_flight_writes_and_emits_no_more(
        tiny_config, params, attn, end):
    """A decoding row ends while a long prompt keeps the steps mixed:
    the step after its last is dispatched before the host knows (a row
    out of budget is left out of it; after an EOS the program froze the
    row in the carry), so the pool holds what the held-off engine's
    holds and the row's pages go back once."""
    short = 9
    requests = [([3, 7, 9, 4], dict(GREEDY, max_new_tokens=short))]
    later = [(2, ([5] * 70, dict(GREEDY, max_new_tokens=4)))]
    cfg = tiny_config
    if end == "eos":
        # a token the short request emits mid-way, and not before,
        # becomes the EOS
        probe, _ = serve(make_engine(cfg, params, held_off=True,
                                     paged_attn=attn), requests)
        toks = probe[0].token_ids
        short = next(k for k in range(5, short) if toks[k] not in toks[:k])
        cfg = dataclasses.replace(cfg, eos_token_ids=(toks[short],))
        short += 1
    outs, pools = [], []
    for held_off in (False, True):
        eng = make_engine(cfg, params, held_off=held_off, paged_attn=attn)
        released = []
        release = eng._pager.release
        eng._pager.release = lambda pages: (released.append(list(pages)),
                                            release(pages))[1]
        hs, _ = serve(eng, requests, later)
        outs.append([list(h._req.out_tokens) for h in hs])
        pools.append((np.asarray(eng.cache.k), np.asarray(eng.cache.v)))
        assert len(released) == 2 and eng._pager.free_pages == 60
        assert not set(released[0]) & set(released[1])
        if not held_off:
            mixed = records(eng, "mixed")
            ended = next(i for i, r in enumerate(mixed)
                         if hs[0]._req.rid not in r["rids"]) - 1
            # its last step and the one behind it were in flight
            # together; the next waited for the emit that ended the row
            assert mixed[ended]["chained"] and mixed[ended - 1]["chained"]
            assert not mixed[ended + 1]["chained"]
            # the host left a row out of budget out of the step behind
            # its last; it could not know of the EOS (the program did)
            assert mixed[ended]["rows_decode"] == (end == "eos")
    assert outs[0] == outs[1]
    assert len(outs[0][0]) == short
    if end == "eos":
        assert short < 9 and outs[0][0][-1] in cfg.eos_token_ids
    for got, want in zip(*pools):
        np.testing.assert_array_equal(got != 0, want != 0)
        np.testing.assert_allclose(got, want, atol=1e-5)


# -- the host is seen within one step ------------------------------------------


@pytest.mark.parametrize("event", ["finish", "submit", "cancel", "command"])
def test_nothing_is_dispatched_ahead_after(tiny_config, params, event):
    """The event lands inside the emit of step k, with k+1 in flight:
    k+1 is completed, nothing is chained onto it, and the run loop
    plans the step after it (a new stretch's first: not chained)."""
    cause = {"finish": "row_finished", "submit": "queue",
             "cancel": "cancel", "command": "command"}[event]
    counted = breaks(cause)
    eng = make_engine(tiny_config, params)
    n = 6 if event == "finish" else 4
    requests = [([5] * 9, dict(GREEDY, max_new_tokens=(
                    n if event == "finish" else 60))),
                ([7] * 75, dict(GREEDY, max_new_tokens=3))]
    at = {}
    # the newest record is the step being emitted
    later = [(n, lambda hs: at.update(step=eng.flight.dump(1)[0]["step"]))]
    if event == "submit":
        later.append((n, ([8, 9, 3], dict(GREEDY, max_new_tokens=2))))
    elif event == "cancel":
        later.append((n, lambda hs: eng.cancel(hs[0])))
    elif event == "command":
        # as _run_on_engine_thread queues one (which then waits for it:
        # not from the engine thread)
        def post(hs):
            with eng._rid_lock:
                eng._cmd_q.append(
                    (lambda: at.update(ran=eng.flight.dump(1)[0]["step"]),
                     {}, threading.Event()))
        later.append((n, post))
    hs, _ = serve(eng, requests, later)
    first = hs[0]._req
    by_step = {r["step"]: r for r in records(eng)}
    k = at["step"]
    assert by_step[k]["kind"] == by_step[k + 1]["kind"] == "mixed"
    assert by_step[k]["chained"] and by_step[k + 1]["chained"]
    assert not by_step[k + 2]["chained"]
    assert by_step[k + 3]["chained"]
    # the event names itself on the step the run loop planned after it,
    # and on no chained record
    assert by_step[k + 2]["chain_break"] == cause
    assert breaks(cause) - counted >= 1
    assert not any("chain_break" in r for r in by_step.values()
                   if r["chained"])
    assert by_step[k + 2]["rows_admitted"] == (event == "submit")
    rid = first.rid
    if event == "submit":
        assert hs[2]._req.rid in by_step[k + 2]["rids"]
        assert hs[2]._req.rid not in by_step[k + 1]["rids"]
        assert len(hs[2].token_ids) == 2
    elif event == "cancel":
        assert rid in by_step[k + 1]["rids"]
        assert rid not in by_step[k + 2]["rids"]
        # the step in flight at the cancel still gave its token
        assert len(first.out_tokens) == 5
    elif event == "command":
        # it ran between the step in flight at its arrival and the next
        assert at["ran"] == k + 1
    else:
        assert by_step[k + 1]["rows_decode"] == 0
        assert len(first.out_tokens) == 6


def test_stretch_length_returns_to_the_loop(tiny_config, params):
    """Nobody arrives, nothing ends: a stretch of mixed steps still
    hands the thread back to _run_loop every STRETCH_STEPS dispatches
    (a prompt of 40 two-token windows)."""
    eng = make_engine(tiny_config, params, prefill_chunk=2, kv_pages=30)
    serve(eng, [([5] * 80, dict(GREEDY, max_new_tokens=3))])
    flags = [r["chained"] for r in records(eng)]
    assert [r["kind"] for r in records(eng)] == ["mixed"] * 40 + ["decode"] * 2
    assert [i for i, c in enumerate(flags) if not c] == [0, STRETCH_STEPS]


@pytest.mark.parametrize("cause", ["stop", "stretch_cap", "window_end",
                                   "sync", "budget", "idle"])
def test_a_chain_names_why_it_ended(tiny_config, params, cause):
    """What no request does to the loop from outside: a stop, the
    stretch's length, the window's end, an engine that may not chain,
    a plan out of budget, an idle loop. Each reaches the recorder as
    itself (chain_broke, once a break) and lands on the next record
    that is not chained."""
    counted = breaks(cause)
    kw, later = {}, []
    requests = [([5] * 9, dict(GREEDY, max_new_tokens=12))]
    if cause == "stretch_cap":
        kw = dict(prefill_chunk=2, kv_pages=30)
        requests = [([5] * 80, dict(GREEDY, max_new_tokens=3))]
    elif cause == "window_end":
        # a row three tokens from max_seq_len beside one that decodes
        # on; submit() clamps a budget to the window, so the budget
        # gate would speak first: lift the clamp once the row runs
        requests = [([4, 9] * ((T - 4) // 2), dict(GREEDY, max_new_tokens=9)),
                    ([8] * 9, dict(GREEDY, max_new_tokens=40))]
        later = [(1, lambda hs: setattr(hs[0]._req, "max_new_tokens", 1000))]
    elif cause == "sync":
        kw = dict(spec_draft_params=params, spec_draft_config=tiny_config,
                  spec_gamma=3)
        later = [(3, ([7] * 29, dict(GREEDY, max_new_tokens=6)))]
    eng = make_engine(tiny_config, params, **kw)
    told = []
    chain_broke = eng.flight.chain_broke
    eng.flight.chain_broke = lambda c: (told.append(c), chain_broke(c))[1]
    if cause == "stop":
        h = eng.submit([5] * 9, max_new_tokens=60, **GREEDY)
        emit = eng._emit

        def hooked(req, *a, **k):
            emit(req, *a, **k)
            if len(req.out_tokens) == 4:
                eng._stop.set()     # as stop() does, seen by the gate

        eng._emit = hooked
        eng.start()
        eng._thread.join(120)
        assert not eng._thread.is_alive()
        recs = records(eng)
        # the step in flight at the stop was fetched and emitted
        assert len(h._req.out_tokens) == 5 and recs[-1]["chained"]
        assert told == ["stop"]
        # nothing ran after it: the cause waits for whoever would
        landed = eng.flight.record("decode", wall_s=0.0, chained=False)
        assert landed.chain_break == "stop"
        eng.stop()
    elif cause == "idle":
        with eng:
            for _ in range(2):
                h = eng.submit([5] * 9, max_new_tokens=4, **GREEDY)
                assert h.wait(300)
                time.sleep(0.12)    # the loop finds nothing to run
        heads = [r for r in records(eng) if not r["chained"]]
        assert [r.get("chain_break") for r in heads] == [None, "idle"]
        assert "gap_s" not in heads[1]
        # the stretch before the wait ended as it would have anyway
        assert told == ["budget", "budget"]
    else:
        serve(eng, requests, later)
        heads = [r for r in records(eng)[1:] if not r.get("chained")]
        if cause == "budget":
            # the last token of the only row is in the step in flight:
            # nothing is chained onto it, and nothing follows it
            assert told == ["budget"] and not heads
            return
        assert {r.get("chain_break") for r in heads} >= {cause}, heads
        assert cause in told
        if cause == "stretch_cap":
            assert [r["chain_break"] for r in heads] == ["stretch_cap"]
        elif cause == "sync":
            # every mixed step of the paged speculative engine is a
            # chain of one; its rounds (kind `spec`) are no chain at all
            recs = records(eng)
            for before, r in zip(recs, recs[1:]):
                assert (r.get("chain_break") == "sync") \
                    == (before["kind"] == "mixed"), (before, r)
    if cause != "budget":
        assert breaks(cause) - counted >= 1


# the fixed run of test_the_gates_decide_as_before, as the parent
# (bbc166c, before the gates said why) recorded it: (kind, chained) of
# every step in order, and every request's tokens
M, D = "mixed", "decode"
PARENT_STEPS = (
    [(M, False)] + [(M, True)] * 3 + [(M, False), (M, True)]
    + [(D, True)] * 6 + [(M, False)] + [(M, True)] * 2 + [(D, True)] * 8
    + [(D, False)] + [(D, True)] * 9 + [(M, False), (M, True)]
    + [(D, True)] * 2 + [(D, False)] + [(D, True)] * 4)
PARENT_TOKENS = [
    [52, 30, 178, 30, 178, 30, 3, 30, 3, 30, 3, 30] + [3] * 18
    + [77, 21, 30] * 3 + [3],
    [82, 199, 199, 199, 199, 199, 186], [104, 21, 235],
    [9, 155, 43, 43, 43, 43, 43, 30], [162, 141]]


def test_the_gates_decide_as_before(tiny_config, params):
    """Same order, same terms: a fixed run (everything queued before
    the loop starts, events inside fixed emits) gives the sequence of
    record kinds and chained flags and the token streams the parent
    gave (greedy, float32; the same under one, three and all CPU
    threads), which are the streams of the engine with chaining held
    off."""
    requests = [([5, 6] * 10, dict(GREEDY, max_new_tokens=40)),
                ([4] * 45, dict(GREEDY, max_new_tokens=7)),
                ([9, 2, 7], dict(GREEDY, max_new_tokens=3))]
    later = [(9, ([7] * 21, dict(GREEDY, max_new_tokens=8))),
             (20, lambda hs: None),
             (30, ([3] * 11, dict(GREEDY, max_new_tokens=2)))]
    eng = make_engine(tiny_config, params, max_seq_len=64)
    got, _ = serve(eng, requests, later)
    want, _ = serve(make_engine(tiny_config, params, max_seq_len=64,
                                held_off=True), requests, later)
    assert_same_streams(got, want)
    assert [h.token_ids for h in got] == PARENT_TOKENS
    steps = [(r["kind"], bool(r["chained"])) for r in records(eng)]
    assert steps == PARENT_STEPS
    # and what the gates now say of each boundary: the third request's
    # last token, the submit at token 9, a row's end, the submit at
    # token 30, a row's end
    assert [r.get("chain_break") for r in records(eng)
            if not r["chained"]] == [None, "row_finished", "queue",
                                     "row_finished", "queue",
                                     "row_finished"]


def test_stretches_cross_kinds(tiny_config, params):
    """mixed -> decode -> mixed -> decode: the decode steps behind a
    prompt's last window ride its stretch on the same carry; a prompt
    that arrives amid them ends that stretch within a step, its first
    window is the first step of the next (an admission drains the
    chain), and the decode steps behind its last window are chained
    again."""
    requests = [([5, 6] * 10, dict(SAMPLED, max_new_tokens=30))]
    later = [(12, ([7] * 21, dict(SAMPLED, max_new_tokens=8)))]
    eng = make_engine(tiny_config, params)
    got, got_state = serve(eng, requests, later)
    want, want_state = serve(make_engine(tiny_config, params, held_off=True),
                             requests, later)
    assert_same_streams(got, want, tops=True)
    for g, w in zip(got_state, want_state):
        np.testing.assert_array_equal(g, w)
    recs = records(eng)
    kinds = [r["kind"] for r in recs]
    runs = [k for i, k in enumerate(kinds) if i == 0 or kinds[i - 1] != k]
    assert runs == ["mixed", "decode", "mixed", "decode"]
    second = kinds.index("mixed", kinds.index("decode"))
    for i, r in enumerate(recs[1:], start=1):
        if r["kind"] != recs[i - 1]["kind"]:
            # mixed -> decode crosses on the carry; decode -> mixed is
            # an admission
            assert r["chained"] == (r["kind"] == "decode"), (i, r)
    assert got[1]._req.rid in recs[second]["rids"]
    assert not recs[second]["chained"] and recs[second + 1]["chained"]


@pytest.mark.parametrize("room", [1, 2, 4])
def test_row_near_the_window_end_is_not_run_past_it(tiny_config, params,
                                                    room):
    """A decoding row `room` tokens from max_seq_len while a long prompt
    keeps the steps mixed: no step is dispatched ahead onto a position
    the window does not hold (the carry has no window freeze), the cap
    ends the row where the held-off engine's ends, and the pool holds
    the same."""
    requests = [([4, 9] * ((T - room - 1) // 2) + [3] * ((T - room - 1) % 2),
                 dict(GREEDY, max_new_tokens=1000)),
                ([8] * 70, dict(GREEDY, max_new_tokens=3))]
    outs, pools = [], []
    for held_off in (False, True):
        eng = make_engine(tiny_config, params, held_off=held_off)
        hs, _ = serve(eng, requests)
        outs.append([h.token_ids for h in hs])
        pools.append((np.asarray(eng.cache.k), np.asarray(eng.cache.v)))
        assert int(np.max(eng._pos)) <= T and eng._pager.free_pages == 60
        if not held_off:
            assert any(r["chained"] for r in records(eng, "mixed"))
    assert outs[0] == outs[1]
    assert len(outs[0][0]) == room + 1 and len(outs[0][1]) == 3
    for got, want in zip(*pools):
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_no_other_program_between_two_chained_steps(tiny_config, params,
                                                    tmp_path):
    """A profiler capture of the engine thread: every launch of a
    program is a `PjitFunction(<name>)` event, an eager `x.at[i].set(v)`
    half a dozen of them. Between a step and a step chained onto it
    there is none: the packed step goes over as one host array, the
    sampling options are held on the device, the carry never leaves
    it."""
    from jax.profiler import ProfileData
    eng = make_engine(tiny_config, params)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    requests = [([5] * 30, dict(SAMPLED, max_new_tokens=12)),
                ([6, 7] * 9, dict(GREEDY, max_new_tokens=9))]
    with eng:
        # every program compiled and loaded before the capture
        for h in [eng.submit(p, **kw) for p, kw in requests]:
            assert h.wait(300)
        warm = len(records(eng))
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for h in [eng.submit(p, **kw) for p, kw in requests]:
                assert h.wait(300)
        finally:
            jax.profiler.stop_trace()
    recs = records(eng)[warm:]
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    launches = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            events = list(line.events)
            if any(ev.name == "cake/dispatch" for ev in events):
                launches = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     ev.name[len("PjitFunction("):-1])
                    for ev in events if ev.name.startswith("PjitFunction("))
    # (the runtime reports a launch twice, one inside the other)
    names, end = [], -1.0
    for t0, t1, name in launches:
        if t0 >= end:
            names.append(name)
            end = t1
    steps = [i for i, n in enumerate(names)
             if n in ("mixed_step_sampled", "decode_step_sampled")]
    assert [names[i] for i in steps] == [
        r["kind"] + "_step_sampled" for r in recs]
    between = [names[a + 1:b] for a, b in zip(steps, steps[1:])]
    chained = [r["chained"] for r in recs[1:]]
    assert sum(chained) >= len(recs) - 4
    assert [b for b, c in zip(between, chained) if c] == [[]] * sum(chained)
    # the capture is not blind: an admission's programs (the ring's
    # reset, the page table's row) lie before a stretch's first step
    assert any(b for b, c in zip(between, chained) if not c) or names[:steps[0]]


# -- several dispatches a step, the window's end -------------------------------


def test_three_prefilling_rows_and_a_row_at_the_window_end(tiny_config,
                                                           params):
    """Three prompts prefill at once beside a decode row: 49 tokens a
    step at sizes (32, 48), so two dispatches, each sampling its own
    rows and handing keys, ring and carry to the next. The decode row's
    budget lies far past the window: the cap ends it where the held-off
    engine's ends, and no step is chained onto max_seq_len."""
    kw = dict(prefill_chunk=16, max_seq_len=64, kv_pages=40)
    requests = [([5] * 30, dict(SAMPLED, max_new_tokens=1000))]
    later = [(29, ([7 + i] * (52 + 4 * i), dict(SAMPLED, max_new_tokens=3)))
             for i in range(3)]
    eng = make_engine(tiny_config, params, **kw)
    assert eng._mixed_buckets == (32, 48)
    got, got_state = serve(eng, requests, later)
    want, want_state = serve(
        make_engine(tiny_config, params, held_off=True, **kw),
        requests, later)
    assert_same_streams(got, want, tops=True)
    for g, w in zip(got_state, want_state):
        np.testing.assert_array_equal(g, w)
    assert len(got[0].token_ids) == 64 - 30
    assert int(np.max(eng._pos)) <= 64
    split = [r for r in records(eng, "mixed") if r["tokens_computed"] > 48]
    assert len(split) >= 2 and all(r["rows_prefill"] == 3 for r in split)
    assert any(r["chained"] and r["rows_decode"] == 1 for r in split)
    # the first request's last steps ran beside the prompts' windows
    last = [r for r in records(eng) if got[0]._req.rid in r["rids"]][-1]
    assert last["kind"] == "mixed"


# -- a sparse model, the latent model ------------------------------------------


@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_sparse_models_counters_sum_to_the_held_off_runs(attn):
    from cake_tpu.models.moe.config import MoEConfig
    from cake_tpu.models.moe.params import init_params as init_moe

    cfg = MoEConfig.tiny_olmoe()
    moe_params = init_moe(cfg, jax.random.PRNGKey(1), jnp.float32)
    # queued together, so that the steps of both runs hold the same rows
    requests = [([5] * 9, dict(GREEDY, max_new_tokens=14)),
                ([4] * 29, dict(GREEDY, max_new_tokens=8))]
    sums = []
    for held_off in (False, True):
        eng = make_engine(cfg, moe_params, held_off=held_off,
                          paged_attn=attn)
        hs, _ = serve(eng, requests)
        recs = records(eng)
        if not held_off:
            assert any(r["chained"] for r in recs if r["kind"] == "mixed")
        k, layers = cfg.num_experts_per_tok, cfg.num_hidden_layers
        for r in recs:
            tokens = r.get("tokens_real", r["rows"])
            assert r["moe_rows"] == tokens * k * layers, r
            assert r["moe_rows_padded"] >= r["moe_rows"]
            assert 0 < r["moe_experts_touched"] <= r["moe_rows"]
        sums.append({key: sum(r[key] for r in recs)
                     for key in ("moe_rows", "moe_rows_padded",
                                 "moe_experts_touched")})
        # every token of every request went through the layers once
        assert sums[-1]["moe_rows"] == (9 + 13 + 29 + 7) * k * layers
    assert sums[0] == sums[1]


@pytest.fixture(scope="module")
def latent():
    from cake_tpu.models.moe.config import GlmMoeDsaConfig
    from cake_tpu.models.moe.params import init_params
    cfg = GlmMoeDsaConfig.tiny_glm(vocab_size=300, eos_token_ids=(300,))
    return cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def test_latent_models_mixed_steps_chain(latent):
    """One window a dispatch: two prompts prefilling at once are two
    dispatches a step, each sampling its own rows; the steps chain, the
    eleven counters ride each fetch, and the streams are the held-off
    engine's. Served alone, the first request's first token has the
    same bits (a row's bits do not depend on its company:
    tests/test_glm_moe_dsa.py holds the step function to that, this the
    sampled program and the engine around it; its later tokens come
    from the decode program when nobody else prefills)."""
    cfg, glm_params = latent
    kw = dict(max_seq_len=128, kv_pages=64, prefill_chunk=16)
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(3, 250, n))) for n in (40, 70, 21)]
    requests = [(prompts[0], dict(GREEDY, max_new_tokens=10))]
    later = [(2, (p, dict(GREEDY, max_new_tokens=6))) for p in prompts[1:]]
    eng = make_engine(cfg, glm_params, **kw)
    assert eng._mixed_buckets == (32,)
    got, _ = serve(eng, requests, later)
    want, _ = serve(make_engine(cfg, glm_params, held_off=True, **kw),
                    requests, later)
    assert_same_streams(got, want)
    alone, _ = serve(make_engine(cfg, glm_params, **kw), requests)
    assert_same_streams(alone, got[:1])
    assert alone[0]._req.out_logprobs[0] == got[0]._req.out_logprobs[0]
    mixed = records(eng, "mixed")
    assert sum(r["chained"] for r in mixed) >= len(mixed) // 2
    assert all(r["impl"] == "paged-dsa-fold" for r in mixed)
    both = [r for r in mixed if r["rows_prefill"] == 2]
    assert both and all(r["tokens_computed"] == 64 for r in both)
    for r in records(eng):
        assert r["dsa_keys_selected"] > 0 and r["moe_rows_routed"] > 0, r


# -- the records ---------------------------------------------------------------


def test_records_of_a_stretch(tiny_config, params):
    chained_total = obs_metrics.REGISTRY.get(
        "cake_mixed_steps_chained_total")
    before = chained_total.value
    eng = make_engine(tiny_config, params, paged_attn="pallas")
    order = []
    record, emit = eng.flight.record, eng._emit
    eng.flight.record = lambda *a, **kw: (order.append("record"),
                                          record(*a, **kw))[1]
    eng._emit = lambda req, *a, **kw: (order.append(req.rid),
                                       emit(req, *a, **kw))[1]
    hs, _ = serve(eng, [([5] * 9, dict(GREEDY, max_new_tokens=20)),
                        ([4] * 45, dict(GREEDY, max_new_tokens=20))])
    mixed = records(eng, "mixed")
    assert len(mixed) == 6 and all(r["impl"] == "paged-pallas"
                                   for r in mixed)
    assert [r["chained"] for r in mixed] == [False] + [True] * 5
    assert chained_total.value - before == 5
    rids = sorted(h._req.rid for h in hs)
    for r in mixed:
        assert sorted(set(r["rids"])) == rids and r["ts"] > 0
        assert "sample" not in r["phases"]
        assert r["tokens_real"] <= r["tokens_computed"] == 16
    assert [(r["rows_decode"], r["rows_prefill"], r["tokens"])
            for r in mixed] == [(0, 2, 0), (0, 2, 1)] + [(1, 1, 1)] * 3 \
        + [(1, 1, 2)]
    assert all(r["gap_s"] == 0.0 for r in mixed[1:])
    # a chained step's wall_s is the time it added to the loop: from
    # the fetch before it to its own. Held on the recorder's one clock,
    # each bound true however the machine is loaded (six places a
    # reading: ROUND covers every sum here). The spans of a chained
    # step lie between the record before it, which follows that
    # step's fetch, and its own fetch: they never cover more than it
    # added.
    ROUND = 1e-4
    assert [r["step"] for r in mixed] == list(
        range(mixed[0]["step"], mixed[0]["step"] + 6))
    for r in mixed[1:]:
        assert sum(r["phases"].values()) <= r["wall_s"] + ROUND, r
    # And the five together run from the first step's fetch to the
    # last's, inside the loop from the first record to the last but
    # for what the first step spent between its fetch and its record:
    # no more than its loop holds outside its spans. (A wall_s read
    # from a step's own dispatch would count the overlap twice.)
    summed = sum(r["wall_s"] for r in mixed[1:])
    first = mixed[0]
    assert summed <= (sum(r["loop_s"] for r in mixed[1:]) + first["loop_s"]
                      - sum(first["phases"].values()) + ROUND)
    assert mixed[-1]["ts"] >= first["ts"]
    # a record is written before its step's tokens are emitted: a
    # request is a prefill row of the step that samples its first token
    assert order[:4] == ["record", "record", hs[0]._req.rid, "record"]
    assert order.count("record") == len(records(eng))
    # the decode step behind the last window belongs to the stretch
    assert records(eng, "decode")[0]["chained"]


def test_one_program_form_whoever_asks_for_top_logprobs(tiny_config,
                                                        params):
    """The program always returns the cap's top ids; the host drops
    them for a row that did not ask. So start-up readies every program
    a mixed step will run: no record is `compiled`, with or without
    top_logprobs in the step."""
    eng = make_engine(tiny_config, params, max_slots=2)
    with eng:
        assert not eng.flight.dump()
        for n_tokens in eng._mixed_buckets:
            js = eng._obs_jit("mixed_step", (WIDTH, n_tokens), None, ())
            assert not js.new
        asks = eng.submit([5] * 20, max_new_tokens=4, **SAMPLED)
        plain = eng.submit([6] * 20, max_new_tokens=4, **GREEDY)
        assert asks.wait(300) and plain.wait(300)
    assert not any(r["compiled"] for r in records(eng, "mixed"))
    # the first token of each came from a mixed step
    assert len(asks._req.out_top[0]) == eng.n_top == 20
    assert plain._req.out_top[0] == []


def test_the_paged_speculative_engines_mixed_steps_are_not_chained(
        tiny_config, params):
    """Its decode rows go back to the speculative partition after every
    step (_do_spec_paged), so a mixed step is fetched before the next
    is planned; the same sampled program, the same tokens."""
    requests = [([5] * 9, dict(GREEDY, max_new_tokens=16))]
    later = [(3, ([7] * 29, dict(GREEDY, max_new_tokens=6)))]
    eng = make_engine(tiny_config, params, spec_draft_params=params,
                      spec_draft_config=tiny_config, spec_gamma=3)
    got, _ = serve(eng, requests, later)
    plain = make_engine(tiny_config, params)
    want, _ = serve(plain, requests, later)
    assert [h.token_ids for h in got] == [h.token_ids for h in want]
    mixed = records(eng, "mixed")
    assert mixed and not any(r["chained"] for r in mixed)
    assert "sample" not in mixed[0]["phases"]
    assert eng.stats.spec_proposed > 0
    assert any(r["chained"] for r in records(plain, "mixed"))


def test_the_program_is_found_by_the_benchmarks_prefix():
    """benchmarks/harness/trace_spans.py finds a mixed step's device
    time by its XLA module's name."""
    from cake_tpu.models.llama.config import MODEL_TYPES
    from test_family import tiny_config
    for model_type in MODEL_TYPES:
        program = tiny_config(model_type).family.mixed_sampled
        assert ("jit_" + program.__name__).startswith("jit_mixed_step")
