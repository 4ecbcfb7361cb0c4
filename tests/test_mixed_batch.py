"""Token-level continuous batching: ONE mixed ragged step for prefill
chunks and decode rows on the paged engine.

Bars:
  * greedy token equality at f32 KV (the repo convention for
    token-equality tests): mixed == the dense oracle, for both
    paged-attention impls, multi-window prompts included;
  * no decode pause: a request admitted mid-decode gets its first
    chunk in the very next step — a `mixed` flight record carrying
    BOTH row kinds — including under preemption;
  * decode_scan interaction (the K-step-burst admission-delay fix):
    with scan bursts enabled, a waiting admission falls back to single
    mixed steps instead of stalling K steps per burst — regression
    measured as decode tokens the resident stream emits between the
    admission and the arrival's first token.
"""

import time

import pytest

import jax
import jax.numpy as jnp

T = 64
PAGE = 16


@pytest.fixture(scope="module")
def params(tiny_config):
    from cake_tpu.models.llama.params import init_params
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


def _engine(tiny_config, params, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    kw.setdefault("max_slots", 3)
    return InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        max_seq_len=T,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        # f32 KV: the token-equality bar must exercise the mixed step,
        # not bf16 greedy tie-breaks (repo convention, PR 2 lesson)
        cache_dtype=jnp.float32,
        **kw)


def _run_tokens(eng, prompts, max_new=8):
    with eng:
        hs = [eng.submit(p, max_new_tokens=max_new, temperature=0.0,
                         repeat_penalty=1.0) for p in prompts]
        assert all(h.wait(timeout=300) for h in hs)
        return [list(h._req.out_tokens) for h in hs]


def _wait_tokens(handle, n, timeout=120.0):
    t0 = time.perf_counter()
    while (len(handle._req.out_tokens) < n
           and time.perf_counter() - t0 < timeout):
        time.sleep(0.002)
    assert len(handle._req.out_tokens) >= n, "stream never got going"


def _both_kind_steps(eng):
    return [r for r in eng.flight.dump()
            if r["kind"] == "mixed" and r.get("rows_decode", 0) > 0
            and r.get("rows_prefill", 0) > 0]


PROMPTS = [[5] * 9, [11] * 14, [3, 7, 9]]


def test_mixed_token_equality_vs_dense(tiny_config, params):
    """Mixed-step serving == the dense oracle (the dense engine's
    prefill/decode phases), greedy at f32 KV, for both attention impls
    — with prefill_chunk=8 so the 14-token prompt walks MULTIPLE mixed
    windows."""
    want = _run_tokens(_engine(tiny_config, params), PROMPTS)
    for impl in ("fold", "pallas"):
        eng = _engine(tiny_config, params, kv_pages=24,
                      kv_page_size=PAGE, paged_attn=impl,
                      prefill_chunk=8)
        got = _run_tokens(eng, PROMPTS)
        assert got == want, f"paged_attn={impl}"
        assert eng._pager.free_pages == 24
        assert eng._mixed_pending == {}


def test_mixed_admission_joins_next_step_no_decode_pause(tiny_config,
                                                         params):
    """The acceptance bar: a request admitted mid-decode rides the very
    next step as a chunk row alongside the resident decode row — at
    least one mixed flight record carries BOTH row kinds, and the
    arrival's first token lands while the resident stream is still
    decoding."""
    eng = _engine(tiny_config, params, kv_pages=24, kv_page_size=PAGE,
                  prefill_chunk=8)
    with eng:
        a = eng.submit([5] * 9, max_new_tokens=40, temperature=0.0,
                       repeat_penalty=1.0)
        _wait_tokens(a, 3)
        b = eng.submit([7] * 20, max_new_tokens=4, temperature=0.0,
                       repeat_penalty=1.0)        # 3 chunk windows
        assert b.wait(timeout=300)
        assert a.wait(timeout=300)
    assert _both_kind_steps(eng), \
        "no mixed step carried decode AND prefill rows"
    # b's first token arrived while a was still decoding: no pause
    assert b._req.first_token_t < a._req.finish_t


def _metric(name):
    """A label-less counter's value off the exposition /metrics serves."""
    from cake_tpu.obs.metrics import REGISTRY
    return sum(float(ln.split()[-1]) for ln in REGISTRY.render().splitlines()
               if ln.startswith(name + " "))


TOKEN_SERIES = ("cake_mixed_tokens_total",
                "cake_mixed_tokens_computed_total")


@pytest.fixture(scope="module")
def sixteen_slots(tiny_config, params):
    """A started engine of 16 slots x 16-token windows, and what the
    token counters read before it started."""
    eng = _engine(tiny_config, params, max_slots=16, kv_pages=96,
                  kv_page_size=PAGE, prefill_chunk=16)
    before = {name: _metric(name) for name in TOKEN_SERIES}
    with eng:
        yield eng, before


def _run_one(eng, prompt, max_new=4):
    h = eng.submit(prompt, max_new_tokens=max_new, temperature=0.0,
                   repeat_penalty=1.0)
    assert h.wait(timeout=300)
    return list(h._req.out_tokens)


def test_start_readies_every_packed_size(sixteen_slots):
    """start() runs the mixed step once at every packed size with all
    rows idle: it leaves no step record, counts no token, touches no
    page, and the compile accountant has seen every size."""
    from cake_tpu.models.llama.paged import mixed_token_buckets

    eng, before = sixteen_slots
    assert eng._mixed_buckets == mixed_token_buckets(16, 16) == (32, 48)
    assert not eng.flight.dump()
    assert before == {name: _metric(name) for name in TOKEN_SERIES}
    assert eng._pager.free_pages == 96
    for n_tokens in (32, 48):
        js = eng._obs_jit("mixed_step", (16, n_tokens), None, ())
        assert not js.new, n_tokens


def test_a_step_over_the_largest_size_is_split_by_rows(sixteen_slots):
    """Rows in slot order, as many a dispatch as the largest packed
    size (48 here) holds: sixteen prefilling rows of 2, 4, .. 16 tokens
    and eight full windows go in five, decode rows ride along."""
    import numpy as np

    eng, _ = sixteen_slots
    qlen = np.asarray([2, 4, 6, 8, 10, 12, 14, 16] + [16] * 8)
    groups = eng._mixed_groups(qlen)
    assert [int(qlen[g].sum()) for g in groups] == [42, 46, 48, 48, 16]
    assert np.sum(groups, axis=0).tolist() == [1] * 16      # each row once
    assert [np.flatnonzero(g).tolist() for g in groups[:2]] \
        == [[0, 1, 2, 3, 4, 5], [6, 7, 8]]
    # one prefilling row, fourteen decode rows, an idle one: one dispatch
    (only,) = eng._mixed_groups(np.asarray([1] * 7 + [16, 0] + [1] * 7))
    assert only.tolist() == [True] * 8 + [False] + [True] * 7


@pytest.mark.parametrize("arrivals", ["one_at_a_time", "sixteen_at_once"])
def test_no_mixed_step_compiles_after_start(sixteen_slots, arrivals):
    """Whatever token counts arrive — a lone short prompt, a lone
    multi-window prompt, sixteen prompts in one step — no mixed step of
    the served run is flagged `compiled`; each record's tokens_real fit
    its tokens_computed, which is the smallest packed size that holds
    them or, past the largest, the sizes of the step's several
    dispatches; and the two /metrics counters advance by the records'
    sums."""
    eng, _ = sixteen_slots
    seen = {r["step"] for r in eng.flight.dump()}
    before = {name: _metric(name) for name in TOKEN_SERIES}
    if arrivals == "one_at_a_time":
        for n in (3, 16, 17, 40):
            _run_one(eng, [5 + n] * n)
    else:
        prompts = [[3 + i] * (2 + 2 * i) for i in range(16)]
        want = [_run_one(eng, p) for p in prompts[::5]]
        seen = {r["step"] for r in eng.flight.dump()}
        before = {name: _metric(name) for name in TOKEN_SERIES}
        hs = [eng.submit(p, max_new_tokens=4, temperature=0.0,
                         repeat_penalty=1.0) for p in prompts]
        assert all(h.wait(timeout=300) for h in hs)
        # a row's tokens do not depend on which rows shared its
        # dispatch, nor on how many dispatches its step took
        assert [list(h._req.out_tokens) for h in hs[::5]] == want
    mixed = [r for r in eng.flight.dump()
             if r["kind"] == "mixed" and r["step"] not in seen]
    assert mixed
    assert not [r for r in mixed if r["compiled"]]
    sizes = eng._mixed_buckets
    for r in mixed:
        assert 0 < r["tokens_real"] <= r["tokens_computed"], r
        if r["tokens_real"] <= sizes[-1]:
            assert r["tokens_computed"] == next(
                t for t in sizes if r["tokens_real"] <= t), r
        else:
            # several dispatches, none of them wasted
            assert r["tokens_computed"] < r["tokens_real"] + 2 * sizes[-1]
            assert r["tokens_computed"] % 16 == 0
    if arrivals == "one_at_a_time":
        assert {r["tokens_computed"] for r in mixed} == {32}
    else:
        # the sixteen arrive within a step or two of each other (2, 4,
        # .. 16 tokens and eight full windows, 200 in all): the
        # fullest step holds far more than one dispatch computes
        first = max(mixed, key=lambda r: r["tokens_real"])
        assert first["rows_prefill"] >= 8, first
        assert first["tokens_real"] > 2 * sizes[-1]
    for name, key in zip(TOKEN_SERIES, ("tokens_real", "tokens_computed")):
        assert _metric(name) - before[name] == sum(r[key] for r in mixed)


TILE_SERIES = ("cake_mixed_attn_q_tiles_total",
               "cake_mixed_attn_q_tiles_window_total")


def test_mixed_records_count_the_query_tiles_attention_folds(sixteen_slots):
    """attn_q_tiles / attn_q_tiles_window: the host's count of what the
    mixed attention kernel folds for a step's active rows (one tile for
    a decode row, the window's tiles for a row with more real queries
    than a tile holds) beside the tiles of their whole windows; on
    every mixed record, on no decode record, and summed in two /metrics
    series."""
    import numpy as np
    from cake_tpu.ops.ragged_paged_attention import (
        MIXED_Q_TILE, mixed_q_tiles,
    )

    eng, _ = sixteen_slots
    full = mixed_q_tiles(16, 16)
    assert MIXED_Q_TILE < 8 and full == 16 // MIXED_Q_TILE
    # a hand-built step: 14 decode rows, a full window and one of 5
    qlen = np.asarray([1] * 7 + [16, 5] + [1] * 7)
    assert eng._attn_q_tiles(qlen, list(range(16))) == {
        "attn_q_tiles": 14 + 2 * full, "attn_q_tiles_window": 16 * full}
    # rows that are not in the step are not counted
    assert eng._attn_q_tiles(qlen, [0, 7]) == {
        "attn_q_tiles": 1 + full, "attn_q_tiles_window": 2 * full}
    seen = {r["step"] for r in eng.flight.dump()}
    before = {name: _metric(name) for name in TILE_SERIES}
    a = eng.submit([5] * 9, max_new_tokens=24, temperature=0.0,
                   repeat_penalty=1.0)
    _wait_tokens(a, 3)
    # windows of 16, 16 and 8 tokens beside a's decode row
    b = eng.submit([7] * 40, max_new_tokens=4, temperature=0.0,
                   repeat_penalty=1.0)
    assert b.wait(timeout=300) and a.wait(timeout=300)
    new = [r for r in eng.flight.dump() if r["step"] not in seen]
    mixed = [r for r in new if r["kind"] == "mixed"]
    assert any(r["rows_decode"] and r["rows_prefill"] for r in mixed)
    for r in mixed:
        assert r["attn_q_tiles"] == (r["rows_decode"]
                                     + full * r["rows_prefill"]), r
        assert r["attn_q_tiles_window"] == full * (
            r["rows_decode"] + r["rows_prefill"]), r
    decode = [r for r in new if r["kind"] == "decode"]
    assert decode and not [r for r in decode if "attn_q_tiles" in r
                           or "attn_q_tiles_window" in r]
    for name, key in zip(TILE_SERIES, ("attn_q_tiles",
                                       "attn_q_tiles_window")):
        assert _metric(name) - before[name] == sum(r[key] for r in mixed)


WALK_SERIES = ("cake_mixed_attn_pages_total",
               "cake_mixed_attn_pages_table_total",
               "cake_mixed_attn_folds_total")


def test_mixed_records_count_the_pages_attention_walks(sixteen_slots):
    """mixed_attn_pages / mixed_attn_pages_table / mixed_attn_folds: the
    host's count of what the mixed attention kernel walks a layer for
    a step's rows (from the page of a row's first key to that of its
    last real query, none for a row that is not in the step), the
    entries of the call's page table, and the softmax updates at
    `mixed_block` pages each; on every mixed record, on no decode
    record, and summed in three /metrics series."""
    import numpy as np
    from cake_tpu.ops.ragged_paged_attention import mixed_block, mixed_walk

    eng, _ = sixteen_slots
    c, pages = eng.config, eng.cache.max_pages
    F = mixed_block(PAGE, c.num_attention_heads, c.num_key_value_heads,
                    c.head_dim, 16, pages, 4, 4)
    # a hand-built step of one dispatch: two decode rows and a window of
    # 16 that ends on a page's last slot, thirteen rows not in the step
    pos = np.zeros(16, np.int64)
    qlen = np.zeros(16, np.int64)
    pos[:3], qlen[:3] = [0, 2 * PAGE, PAGE], [1, 1, 16]
    want = [mixed_walk(p, n, PAGE, pages, F) for p, n in zip(pos, qlen)]
    assert [w[0] for w in want[:4]] == [1, 3, 2, 0]
    assert eng._mixed_attn_pages(pos, qlen, [qlen > 0]) == {
        "mixed_attn_pages": 6, "mixed_attn_pages_table": 16 * pages,
        "mixed_attn_folds": sum(w[1] for w in want)}
    # two dispatches: each steps through the whole table
    assert eng._mixed_attn_pages(pos, qlen, [qlen == 1, qlen > 1])[
        "mixed_attn_pages_table"] == 2 * 16 * pages
    seen = {r["step"] for r in eng.flight.dump()}
    before = {name: _metric(name) for name in WALK_SERIES}
    a = eng.submit([5] * 9, max_new_tokens=24, temperature=0.0,
                   repeat_penalty=1.0)
    _wait_tokens(a, 3)
    b = eng.submit([7] * 40, max_new_tokens=4, temperature=0.0,
                   repeat_penalty=1.0)
    assert b.wait(timeout=300) and a.wait(timeout=300)
    new = [r for r in eng.flight.dump() if r["step"] not in seen]
    mixed = [r for r in new if r["kind"] == "mixed"]
    assert any(r["rows_decode"] and r["rows_prefill"] for r in mixed)
    for r in mixed:
        rows = r["rows_decode"] + r["rows_prefill"]
        assert rows <= r["mixed_attn_pages"] <= r["mixed_attn_pages_table"]
        assert r["mixed_attn_pages_table"] == 16 * pages, r
        assert (r["mixed_attn_pages"] / F <= r["mixed_attn_folds"]
                <= r["mixed_attn_pages"]), r
    decode = [r for r in new if r["kind"] == "decode"]
    assert decode and not [r for r in decode if "mixed_attn_pages" in r]
    for name, key in zip(WALK_SERIES, ("mixed_attn_pages",
                                       "mixed_attn_pages_table",
                                       "mixed_attn_folds")):
        assert _metric(name) - before[name] == sum(r[key] for r in mixed)


PAGE_SERIES = ("cake_decode_attn_pages_total",
               "cake_decode_attn_pages_table_total")


def test_decode_records_count_the_pages_attention_streams(sixteen_slots):
    """attn_pages / attn_pages_table: the host's count of the KV pages
    the decode attention kernel streams a layer for a step's active
    rows (position // page + 1, from the positions it dispatches: the
    mirrors plus what the steps in flight ship) beside the entries of
    the page table; on every decode record, on no mixed record, and
    summed in two /metrics series."""
    eng, _ = sixteen_slots
    table = 16 * eng.cache.max_pages
    assert eng._attn_pages([(0, 1), (PAGE - 1, 1), (PAGE, 1),
                            (3 * PAGE + 2, 1)]) == {
        "attn_pages": 1 + 1 + 2 + 4, "attn_pages_table": table}
    # a scan's record sums its steps: positions PAGE - 2, PAGE - 1, PAGE
    assert eng._attn_pages([(PAGE - 2, 3), (0, 1)]) == {
        "attn_pages": 1 + 1 + 2 + 1, "attn_pages_table": 3 * table}
    assert eng._attn_pages([]) == {}
    seen = {r["step"] for r in eng.flight.dump()}
    before = {name: _metric(name) for name in PAGE_SERIES}
    # a prompt of 9 tokens: the decode steps' current tokens sit at
    # positions 9, 10, ... and cross two page edges
    h = eng.submit([5] * 9, max_new_tokens=2 * PAGE + 4, temperature=0.0,
                   repeat_penalty=1.0)
    assert h.wait(timeout=300)
    new = [r for r in eng.flight.dump() if r["step"] not in seen]
    decode = sorted((r for r in new if r["kind"] == "decode"),
                    key=lambda r: r["step"])
    assert len(decode) == 2 * PAGE + 3 and any(r["chained"] for r in decode)
    for k, r in enumerate(decode):
        assert r["rows"] == 1 and r["attn_pages"] == (9 + k) // PAGE + 1, r
        assert r["attn_pages_table"] == table, r
    assert {r["attn_pages"] for r in decode} == {1, 2, 3}
    mixed = [r for r in new if r["kind"] == "mixed"]
    assert mixed and not [r for r in mixed if "attn_pages" in r
                          or "attn_pages_table" in r]
    for name, key in zip(PAGE_SERIES, ("attn_pages", "attn_pages_table")):
        assert _metric(name) - before[name] == sum(r[key] for r in decode)


@pytest.mark.slow  # two engines under staggered load -> slow lane
def test_mixed_admission_with_preemption_interleaved(tiny_config,
                                                     params):
    """Preemption composes with the mixed step: victims release at a
    mixed-step boundary (the engine preempts between iterations), the
    interactive arrival's chunks ride alongside the surviving batch
    slot's decode rows, and the preempted stream's recompute-resume
    chunks do too — pool conserved throughout."""
    from cake_tpu.sched import SchedConfig

    eng = _engine(tiny_config, params, max_slots=2, kv_pages=8,
                  kv_page_size=PAGE, prefill_chunk=8,
                  priority_classes=True, preemption=True,
                  sched_config=SchedConfig(preempt_budget=8))
    with eng:
        hb = [eng.submit([5 + i] * 9, max_new_tokens=24,
                         temperature=0.0, repeat_penalty=1.0,
                         priority="batch") for i in range(2)]
        for h in hb:
            _wait_tokens(h, 3)
        hi = eng.submit([2, 9, 4, 7, 3], max_new_tokens=3,
                        temperature=0.0, repeat_penalty=1.0,
                        priority="interactive")
        assert hi.wait(timeout=300)
        assert all(h.wait(timeout=600) for h in hb)
        assert eng.stats.preemptions >= 1
        assert len(hi._req.out_tokens) >= 1
    assert _both_kind_steps(eng), \
        "no mixed step carried decode AND prefill rows"
    assert eng._pager.free_pages == eng.cache.n_pages
    assert eng._mixed_pending == {}


@pytest.mark.slow  # scan-burst engine under live load -> slow lane
def test_mixed_decode_scan_admission_latency(tiny_config, params):
    """The decode_scan bugfix: with K-step scan bursts amortizing
    dispatch while slots decode alone, an arriving request must flip
    the loop to single mixed steps — its chunks join every iteration —
    instead of being delayed K steps per burst. Admission latency is
    measured in STEPS: the decode tokens the resident stream emits
    between the submit and the arrival's first token are bounded by
    the already-in-flight bursts (<= 2K) plus the arrival's own chunk
    windows, never by extra scan bursts dispatched past the waiting
    admission."""
    K = 4
    eng = _engine(tiny_config, params, kv_pages=24, kv_page_size=PAGE,
                  prefill_chunk=8, decode_scan_steps=K)
    with eng:
        a = eng.submit([5] * 9, max_new_tokens=45, temperature=0.0,
                       repeat_penalty=1.0)
        _wait_tokens(a, 2 * K)        # scan bursts are running
        a_at_submit = len(a._req.out_tokens)
        a_at_first = []

        def on_b(delta, final):
            # engine-thread snapshot at b's FIRST emitted token
            if not a_at_first:
                a_at_first.append(len(a._req.out_tokens))

        b = eng.submit([7] * 20, max_new_tokens=4, temperature=0.0,
                       repeat_penalty=1.0, stream=on_b)   # 3 windows
        assert b.wait(timeout=300)
        assert a.wait(timeout=300)
    assert a_at_first, "stream callback never fired"
    # in-flight chained bursts at submit time can still deliver up to
    # 2K tokens; after that, b's 3 chunk windows each ride ONE mixed
    # step (one decode token apiece) — generous slack on top, but far
    # below the unfixed behavior of whole K-token bursts per window
    steps_to_first = a_at_first[0] - a_at_submit
    assert steps_to_first <= 2 * K + 3 + 2, steps_to_first
    # and b's chunks genuinely rode mixed steps with a decoding
    assert _both_kind_steps(eng)
