"""Paged KV cache: pool/table machinery vs the dense oracle.

The round-4 bench finding this exists for: 32 dense slots x max_seq_len
slabs thrash HBM (151 tok/s aggregate vs 408 at 16 slots). Pages bound
resident KV by USED context; the equivalence bar is exact logits vs the
dense ragged decode.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.models.llama.cache import KVCache
from cake_tpu.models.llama.model import RopeTables, decode_step_ragged, prefill
from cake_tpu.models.llama.paged import (
    PageAllocator, PagedKVCache, decode_step_ragged_paged, paged_attention,
    prefill_slot_paged, table_set_slot,
)
from cake_tpu.models.llama.params import init_params

PAGE = 16
T = 64            # max_seq_len
SLOTS = 3


@pytest.fixture(scope="module")
def params(tiny_config):
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


def test_paged_attention_matches_dense():
    """Online-softmax over pages == full attention over the gathered
    sequence (random KV laid out through a shuffled page table)."""
    from cake_tpu.ops.attention import gqa_attention

    B, H, KV, hd = 2, 4, 2, 16
    n_pages, max_pages = 12, 4
    rng = np.random.default_rng(0)
    # the stacked pool as the engine holds it, [L, N, page, KV*hd];
    # layer 1 of 2 is attended, layer 0 holds other data
    layer = 1
    stack_k = jnp.asarray(rng.normal(size=(2, n_pages, PAGE, KV * hd)),
                          jnp.float32)
    stack_v = jnp.asarray(rng.normal(size=(2, n_pages, PAGE, KV * hd)),
                          jnp.float32)
    pool_k = stack_k[layer].reshape(n_pages, PAGE, KV, hd)
    pool_v = stack_v[layer].reshape(n_pages, PAGE, KV, hd)
    # row 0 uses 3 mapped pages (pos mid-page), row 1 uses 2
    table = jnp.asarray([[7, 2, 9, -1], [4, 11, -1, -1]], jnp.int32)
    pos = jnp.asarray([2 * PAGE + 5, PAGE + 3], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.float32)

    got = paged_attention(q, stack_k, stack_v, layer, table, pos)

    for b in range(B):
        pages = [int(p) for p in table[b] if int(p) >= 0]
        k_full = jnp.concatenate([pool_k[p] for p in pages], axis=0)[None]
        v_full = jnp.concatenate([pool_v[p] for p in pages], axis=0)[None]
        n = int(pos[b]) + 1
        mask = jnp.broadcast_to(
            (jnp.arange(k_full.shape[1]) < n)[None, None, None, :],
            (1, H, 1, k_full.shape[1]))
        want = gqa_attention(q[b:b + 1], k_full, v_full, mask=mask)
        np.testing.assert_allclose(np.asarray(got[b:b + 1]),
                                   np.asarray(want), atol=1e-5, rtol=1e-5)


def test_paged_prefill_decode_matches_dense(tiny_config, params):
    """Per-slot prefill + ragged decode over pages == the dense slot
    cache path, token positions ragged across slots."""
    cfg = tiny_config
    rope = RopeTables.create(cfg, T)
    alloc = PageAllocator(n_pages=SLOTS * T // PAGE, page_size=PAGE)
    paged = PagedKVCache.create(cfg, SLOTS, alloc.free_pages, PAGE, T,
                                dtype=jnp.float32)
    dense = KVCache.create(cfg, SLOTS, T, dtype=jnp.float32)

    prompts = [[5] * 9, [11] * 14, [3, 7, 9]]
    from cake_tpu.models.llama.generator import bucket_length
    from cake_tpu.models.llama.model import prefill_slot

    # dense oracle prefills through the engine's builtin slot path
    dense_logits = []
    for slot, ids in enumerate(prompts):
        bucket = bucket_length(len(ids), T)
        toks = jnp.asarray([ids + [0] * (bucket - len(ids))], jnp.int32)
        plen = jnp.asarray([len(ids)], jnp.int32)
        lg, dense = prefill_slot(params, toks, plen, jnp.int32(slot),
                                 dense, rope, cfg)
        dense_logits.append(np.asarray(lg))

    paged_logits = []
    for slot, ids in enumerate(prompts):
        pages = alloc.alloc(len(ids) + 16)
        assert pages is not None
        paged = paged._replace(
            table=table_set_slot(paged.table, slot, pages))
        bucket = bucket_length(len(ids), T)
        toks = jnp.asarray([ids + [0] * (bucket - len(ids))], jnp.int32)
        plen = jnp.asarray([len(ids)], jnp.int32)
        lg, paged = prefill_slot_paged(params, toks, plen,
                                       jnp.int32(slot), paged, rope, cfg)
        paged_logits.append(np.asarray(lg))

    for a, b in zip(dense_logits, paged_logits):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)

    # ragged greedy decode, all slots active at different positions
    pos = np.asarray([len(p) for p in prompts], np.int64)
    toks_d = jnp.asarray([[int(np.argmax(l))] for l in dense_logits],
                         jnp.int32)
    toks_p = jnp.asarray([[int(np.argmax(l))] for l in paged_logits],
                         jnp.int32)
    active = jnp.asarray([True] * SLOTS)
    for step in range(5):
        p = jnp.asarray(pos, jnp.int32)
        lg_d, dense = decode_step_ragged(params, toks_d, p, active,
                                         dense, rope, cfg)
        lg_p, paged = decode_step_ragged_paged(params, toks_p, p, active,
                                               paged, rope, cfg)
        np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_d),
                                   atol=2e-4, rtol=2e-4)
        toks_d = jnp.argmax(lg_d, -1).astype(jnp.int32)[:, None]
        toks_p = jnp.argmax(lg_p, -1).astype(jnp.int32)[:, None]
        np.testing.assert_array_equal(np.asarray(toks_d),
                                      np.asarray(toks_p))
        pos += 1


def test_allocator_admission_and_free():
    alloc = PageAllocator(n_pages=4, page_size=PAGE)
    a = alloc.alloc(PAGE * 2 + 1)     # 3 pages
    assert a is not None and len(a) == 3
    assert alloc.alloc(PAGE + 1) is None   # 2 needed, 1 free
    b = alloc.alloc(PAGE)             # exactly 1 page
    assert b is not None and len(b) == 1
    alloc.free(a)
    assert alloc.free_pages == 3
    c = alloc.alloc(PAGE * 3)
    assert c is not None and sorted(c) == sorted(a)


def test_paged_memory_bound(tiny_config):
    """The capacity claim: a pool budgeted at 1/4 the dense worst case
    allocates 1/4 the KV bytes for the same slot count."""
    slots, T_ = 32, 512
    dense = KVCache.create(tiny_config, slots, T_, dtype=jnp.bfloat16)
    pool = PagedKVCache.create(
        tiny_config, slots, n_pages=(slots * T_ // PAGE) // 4,
        page_size=PAGE, max_seq_len=T_, dtype=jnp.bfloat16)
    dense_bytes = dense.k.nbytes + dense.v.nbytes
    assert pool.memory_bytes() * 3.9 < dense_bytes


def _engine(tiny_config, params, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    return InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        max_slots=4, max_seq_len=T,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        # f32 KV to match this module's f32 params fixture: with bf16
        # storage the dense-vs-paged token equality flips on greedy
        # near-ties (reduction-order ULPs), which tests the tie-break,
        # not the paging machinery
        cache_dtype=jnp.float32,
        **kw)


def test_engine_paged_matches_dense(tiny_config, params):
    """--kv-pages serving: same greedy tokens as the dense engine."""
    prompts = [[5] * 9, [11] * 14, [3, 7, 9], [2] * 6]

    def run(**kw):
        eng = _engine(tiny_config, params, **kw)
        with eng:
            hs = [eng.submit(p, max_new_tokens=8, temperature=0.0,
                             repeat_penalty=1.0) for p in prompts]
            assert all(h.wait(timeout=300) for h in hs)
            return [list(h._req.out_tokens) for h in hs]

    want = run()
    got = run(kv_pages=SLOTS * T // PAGE + 4, kv_page_size=PAGE)
    assert got == want


def test_engine_paged_oversubscription(tiny_config, params):
    """A pool too small for every request AT ONCE still serves them all:
    the allocator gates admission and requeues until pages free — the
    capacity story (slot count scales with used context, not worst
    case)."""
    # each request needs ceil((9 + 8)/16) = 2 pages; pool of 3 pages
    # admits ONE request at a time despite 4 slots
    eng = _engine(tiny_config, params, kv_pages=3, kv_page_size=PAGE)
    with eng:
        hs = [eng.submit([5 + i] * 9, max_new_tokens=8, temperature=0.0,
                         repeat_penalty=1.0) for i in range(5)]
        assert all(h.wait(timeout=600) for h in hs)
        for h in hs:
            assert len(h._req.out_tokens) >= 1
    # every page returned to the pool
    assert eng._pager.free_pages == 3
    assert eng._slot_pages == {}


def test_engine_paged_large_pages_small_prompts(tiny_config, params):
    """Page size LARGER than the prefill bucket (the default-config
    shape: 128-token pages, short prompts bucket to 32): prompt KV must
    land in the partial first page, not be silently dropped — a dropped
    prompt yields a correct first token but garbage continuations."""
    prompts = [[5] * 9, [3, 7, 9, 11, 2]]

    def run(**kw):
        eng = _engine(tiny_config, params, **kw)
        with eng:
            hs = [eng.submit(p, max_new_tokens=8, temperature=0.0,
                             repeat_penalty=1.0) for p in prompts]
            assert all(h.wait(timeout=300) for h in hs)
            return [list(h._req.out_tokens) for h in hs]

    want = run()
    got = run(kv_pages=4, kv_page_size=T)   # one whole-window page each
    assert got == want


def test_engine_paged_impossible_request_fails_fast(tiny_config, params):
    eng = _engine(tiny_config, params, kv_pages=2, kv_page_size=PAGE)
    with eng:
        with pytest.raises(ValueError, match="kv pages"):
            eng.submit([5] * 40, max_new_tokens=20)


def test_engine_paged_fifo_fairness(tiny_config, params):
    """A page-starved request blocks younger admissions (head-of-line
    FIFO) instead of being starved by a stream of smaller requests."""
    # pool of 3 pages; A takes 2 and decodes a while; B needs 3 (starves
    # until A fully retires); C/D need 1 each and arrive after B
    eng = _engine(tiny_config, params, kv_pages=3, kv_page_size=PAGE)
    with eng:
        a = eng.submit([5] * 9, max_new_tokens=20, temperature=0.0,
                       repeat_penalty=1.0)                  # 2 pages
        b = eng.submit([7] * 20, max_new_tokens=25, temperature=0.0,
                       repeat_penalty=1.0)                  # 3 pages
        c = eng.submit([9] * 5, max_new_tokens=4, temperature=0.0,
                       repeat_penalty=1.0)                  # 1 page
        d = eng.submit([11] * 5, max_new_tokens=4, temperature=0.0,
                       repeat_penalty=1.0)                  # 1 page
        for h in (a, b, c, d):
            assert h.wait(timeout=600)
        # b admitted before the younger c/d (first tokens ordered)
        assert b._req.first_token_t < c._req.first_token_t
        assert b._req.first_token_t < d._req.first_token_t
    assert eng._pager.free_pages == 3


def test_engine_paged_page_accounting_invariant(tiny_config, params):
    """After a paged engine drains — a retired request, a CANCELLED
    mid-decode request, and an ERRORED request (device failure ->
    _fail_all + reset) — PageAllocator.free_pages returns to its
    initial value and no slot holds a page mapping. Any leak on the
    cancel/error release paths shows up here as a shrunken pool.
    recovery=False pins the LEGACY fail-all error path (with crash
    recovery — the default — a one-shot failure resubmits the request
    and it completes; that path's accounting is pinned by
    test_faults.py's paged recovery test)."""
    import time as _time

    eng = _engine(tiny_config, params, kv_pages=6, kv_page_size=PAGE,
                  recovery=False)
    with eng:
        # retire path
        done = eng.submit([5] * 9, max_new_tokens=4, temperature=0.0,
                          repeat_penalty=1.0)
        assert done.wait(timeout=300)

        # cancel path: abandon a long request once it is decoding
        long = eng.submit([7] * 9, max_new_tokens=40, temperature=0.0,
                          repeat_penalty=1.0)
        deadline = _time.monotonic() + 120
        while not long._req.out_tokens and _time.monotonic() < deadline:
            _time.sleep(0.01)
        assert long._req.out_tokens, "request never started decoding"
        eng.cancel(long)
        assert long.wait(timeout=120)

        # error path: the next decode step blows up; the engine fails
        # the request, releases its pages and resets
        # (the sampled one-step program: what a pure-decode iteration
        # of a single-host engine dispatches)
        real_step = eng._decode_scan_impl

        def boom(*a, **kw):
            eng._decode_scan_impl = real_step
            raise RuntimeError("injected device failure")

        eng._decode_scan_impl = boom
        errored = eng.submit([9] * 9, max_new_tokens=4, temperature=0.0,
                             repeat_penalty=1.0)
        assert errored.wait(timeout=300)
        assert errored._req.error is not None

        # pool coherent after the reset: serving continues
        again = eng.submit([11] * 9, max_new_tokens=3, temperature=0.0,
                           repeat_penalty=1.0)
        assert again.wait(timeout=300)
        assert again._req.error is None
    assert eng._pager.free_pages == 6
    assert eng._slot_pages == {}


def test_engine_paged_decode_scan_matches_dense(tiny_config, params):
    """K-step scanned decode over the paged cache (one dispatch per K
    tokens) == the dense engine's streams — the dispatch-amortized
    configuration the on-chip throughput claim depends on."""
    prompts = [[5] * 9, [11] * 14, [3, 7, 9], [2] * 6]

    def run(**kw):
        eng = _engine(tiny_config, params, decode_scan_steps=4, **kw)
        with eng:
            hs = [eng.submit(p, max_new_tokens=10, temperature=0.0,
                             repeat_penalty=1.0) for p in prompts]
            assert all(h.wait(timeout=300) for h in hs)
            return [list(h._req.out_tokens) for h in hs]

    want = run()
    got = run(kv_pages=SLOTS * T // PAGE + 4, kv_page_size=PAGE)
    assert got == want
