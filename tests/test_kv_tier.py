"""KV-cache tiering (cake_tpu/kv): quantized pages + host-RAM spill.

Contract bars:
  * quantized writers keep untouched pages BIT-identical and bound the
    write error by the per-page scale step;
  * a spill -> restore host round trip is BIT-identical for int8
    pages + scales (the tier moves raw buffers, never re-quantizes);
  * a preempted-then-resumed stream restored from the host tier is
    token-identical to an unpreempted run at f32 KV (the spill analog
    of PR 5's recompute-resume equality);
  * int8 KV greedy output is an acceptance/tolerance comparison vs the
    f32 reference — token equality stays pinned at f32 KV (repo
    convention since PR 2).
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.kv.host_tier import HostTier, SpilledPages
from cake_tpu.kv.quantized_pool import (
    Int4PagedKVCache, QuantPool, QuantizedPagedKVCache,
    dequantize_pages, page_bytes, qupdate_pool_per_row,
    qwrite_prompt_pages, qwrite_windows_pages, reset_page_scales,
)


def _qwrite_window(pool, layer, vals, row, pos0, n_real=None):
    """One row's window ([1, C, KV, hd] at absolute position pos0,
    n_real of its C positions real) through the batched writer."""
    n = vals.shape[1] if n_real is None else n_real
    return qwrite_windows_pages(
        pool, layer, vals, jnp.asarray([pos0], jnp.int32),
        jnp.asarray([n], jnp.int32), jnp.asarray([True]), row[None])

T = 64
PAGE = 16
GEN = 24
BATCH_PROMPT = [5] * 9
INTER_PROMPT = [2, 9, 4, 7, 3]


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
    kw.setdefault("kv_pages", 8)
    kw.setdefault("kv_page_size", PAGE)
    return InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        cache_dtype=jnp.float32,
        **kw)


# -- host tier units ----------------------------------------------------------


def _entry(n_pages, seed=0, kind="pages"):
    rng = np.random.default_rng(seed)
    return SpilledPages(
        n_pages=n_pages,
        arrays=(rng.integers(-127, 127,
                             size=(2, n_pages, 4), dtype=np.int8),),
        kind=kind)


def test_host_tier_capacity_and_lru():
    tier = HostTier(4, page_bytes=128)
    assert tier.can_hold(4) and not tier.can_hold(5)
    assert tier.put("a", _entry(2))
    assert tier.put("b", _entry(2))
    assert tier.free_pages == 0
    # over-capacity put evicts the LEAST recently used entry
    tier.peek("a")                       # refresh a's recency
    assert tier.put("c", _entry(2, seed=1))
    assert tier.peek("b") is None and tier.peek("a") is not None
    assert tier.evictions == 1
    # an entry that can never fit is refused without mutation
    assert not tier.put("huge", _entry(5))
    assert tier.used_pages == 4
    got = tier.pop("a")
    assert got is not None and tier.used_pages == 2
    assert tier.restores == 2            # counted in pages
    tier.clear()
    assert tier.used_pages == 0 and tier.peek("c") is None


def test_host_tier_roundtrip_bit_identical_int8(tiny_config):
    """fetch_pages -> install_pages into DIFFERENT page ids of a fresh
    pool generation: int8 values and f32 scales bit-identical."""
    rng = np.random.default_rng(3)
    cache = QuantizedPagedKVCache.create(tiny_config, 2, 8, PAGE, T)

    def filled(pool):
        return QuantPool(
            q=jnp.asarray(rng.integers(-127, 128, size=pool.q.shape),
                          jnp.int8),
            scale=jnp.asarray(rng.random(pool.scale.shape),
                              jnp.float32))

    cache = cache._replace(k=filled(cache.k), v=filled(cache.v))
    src = [5, 1, 6]
    arrays = HostTier.fetch_pages(cache, src)
    fresh = QuantizedPagedKVCache.create(tiny_config, 2, 8, PAGE, T)
    dst = [2, 7, 0]
    fresh = HostTier.install_pages(fresh, dst, arrays)
    for s, d in zip(src, dst):
        np.testing.assert_array_equal(
            np.asarray(cache.k.q[:, s]), np.asarray(fresh.k.q[:, d]))
        np.testing.assert_array_equal(
            np.asarray(cache.k.scale[:, s]),
            np.asarray(fresh.k.scale[:, d]))
        np.testing.assert_array_equal(
            np.asarray(cache.v.q[:, s]), np.asarray(fresh.v.q[:, d]))
        np.testing.assert_array_equal(
            np.asarray(cache.v.scale[:, s]),
            np.asarray(fresh.v.scale[:, d]))


def test_host_tier_roundtrip_bit_identical_f32(tiny_config):
    """The tier is dtype-blind: an f32 pool round-trips bit-exact too
    (what makes spill-resume token-identical at f32 KV)."""
    from cake_tpu.models.llama.paged import PagedKVCache
    rng = np.random.default_rng(4)
    cache = PagedKVCache.create(tiny_config, 2, 8, PAGE, T,
                                dtype=jnp.float32)
    cache = cache._replace(
        k=jnp.asarray(rng.normal(size=cache.k.shape), jnp.float32),
        v=jnp.asarray(rng.normal(size=cache.v.shape), jnp.float32))
    arrays = HostTier.fetch_pages(cache, [3, 0])
    fresh = PagedKVCache.create(tiny_config, 2, 8, PAGE, T,
                                dtype=jnp.float32)
    fresh = HostTier.install_pages(fresh, [6, 1], arrays)
    np.testing.assert_array_equal(np.asarray(cache.k[:, 3]),
                                  np.asarray(fresh.k[:, 6]))
    np.testing.assert_array_equal(np.asarray(cache.v[:, 0]),
                                  np.asarray(fresh.v[:, 1]))


# -- quantized pool units -----------------------------------------------------


def test_quantized_write_error_bound_and_isolation():
    """A written window dequantizes within one scale step of the f32
    values, and pages NOT touched by a later write stay bit-identical
    (the RMW writers must not drift neighbors)."""
    rng = np.random.default_rng(5)
    KV, hd = 2, 16
    # the stacked pool, written at layer 1 of 2: the writers index
    # [layer, pages] and must leave the other layer alone
    layer = 1
    pool = QuantPool(q=jnp.zeros((2, 12, PAGE, KV * hd), jnp.int8),
                     scale=jnp.zeros((2, 12, KV), jnp.float32))
    vals = jnp.asarray(rng.normal(size=(1, 2 * PAGE + 3, KV, hd)),
                       jnp.float32)
    row = jnp.asarray([7, 2, 9, -1], jnp.int32)
    pool = qwrite_prompt_pages(pool, layer, vals, row)
    deq = dequantize_pages(pool, layer, jnp.asarray([7, 2, 9])).reshape(
        3 * PAGE, KV, hd)[: 2 * PAGE + 3]
    # symmetric int8: error <= scale/2 = amax/254 per (page, head)
    assert float(jnp.max(jnp.abs(deq - vals[0]))) < 0.05
    before = np.asarray(pool.q[layer, 7]), np.asarray(pool.scale[layer, 7])
    # decode token into page 9 (single-page RMW)
    tok = jnp.asarray(rng.normal(size=(1, 1, KV, hd)), jnp.float32)
    pool2 = qupdate_pool_per_row(
        pool, layer, tok, jnp.asarray([2 * PAGE + 3], jnp.int32),
        jnp.asarray([True]), jnp.asarray([[7, 2, 9, -1]], jnp.int32))
    np.testing.assert_array_equal(before[0], np.asarray(pool2.q[layer, 7]))
    np.testing.assert_array_equal(before[1],
                                  np.asarray(pool2.scale[layer, 7]))
    got = dequantize_pages(pool2, layer, jnp.asarray([9]))[0][3]
    assert float(jnp.max(jnp.abs(got - tok[0, 0]))) < 0.05
    # window write at an arbitrary offset into fresh scale-reset pages
    pool3 = _qwrite_window(
        pool2, layer, tok, jnp.asarray([7, 2, 9, -1], jnp.int32),
        2 * PAGE + 4)
    got3 = dequantize_pages(pool3, layer, jnp.asarray([9]))[0][4]
    assert float(jnp.max(jnp.abs(got3 - tok[0, 0]))) < 0.05
    for p in (pool, pool2, pool3):
        assert not np.asarray(p.q[0]).any()
        assert not np.asarray(p.scale[0]).any()


def test_bucket_padding_cannot_inflate_scales():
    """Bucket-padding garbage past n_real must not enter the page
    scales: scales only grow, so one garbage-inflated amax would
    coarsen the page's REAL tokens for the page's whole life. Writing
    a garbage-padded bucket with n_real must be bit-identical to
    writing the real tokens alone."""
    rng = np.random.default_rng(6)
    KV, hd = 2, 16
    layer = 1
    pool0 = QuantPool(q=jnp.zeros((2, 12, PAGE, KV * hd), jnp.int8),
                      scale=jnp.zeros((2, 12, KV), jnp.float32))
    row = jnp.asarray([7, 2, 9, -1], jnp.int32)

    # prompt writer: bucket 2 pages, real tokens PAGE+3, tail garbage
    n_real = PAGE + 3
    vals = jnp.asarray(rng.normal(size=(1, 2 * PAGE, KV, hd)),
                       jnp.float32)
    garbage = vals.at[:, n_real:].mul(100.0)
    live = jnp.arange(2 * PAGE)[None, :, None, None] < n_real
    clean = jnp.where(live, vals, 0.0)
    got = qwrite_prompt_pages(pool0, layer, garbage, row,
                              jnp.int32(n_real))
    want = qwrite_prompt_pages(pool0, layer, clean, row)
    np.testing.assert_array_equal(np.asarray(got.q), np.asarray(want.q))
    np.testing.assert_array_equal(np.asarray(got.scale),
                                  np.asarray(want.scale))
    # sanity: without n_real the garbage DOES inflate the tail scale
    bad = qwrite_prompt_pages(pool0, layer, garbage, row)
    assert float(jnp.max(jnp.abs(bad.scale - want.scale))) > 0

    # window writer: C-token window, 4 real, huge padding
    win = jnp.asarray(rng.normal(size=(1, PAGE + 5, KV, hd)),
                      jnp.float32)
    win = win.at[:, 4:].mul(100.0)
    got = _qwrite_window(pool0, layer, win, row, 3, 4)
    want = _qwrite_window(pool0, layer, win[:, :4], row, 3)
    np.testing.assert_array_equal(np.asarray(got.q), np.asarray(want.q))
    np.testing.assert_array_equal(np.asarray(got.scale),
                                  np.asarray(want.scale))
    bad = _qwrite_window(pool0, layer, win, row, 3)
    assert float(jnp.max(jnp.abs(bad.scale - want.scale))) > 0


@pytest.mark.slow  # 300 random pool ops, per-op invariants -> slow lane
def test_property_random_int4_pool_interleavings(tiny_config):
    """300 random admit/decode/spill/restore/cancel/retire steps on an
    int4 cache + refcounted allocator + host tier, asserting after
    EVERY op: free + live page conservation; per-page group scales
    monotone between recycles (the RMW writers may only coarsen a
    page, never silently re-quantize it finer); a spill -> restore
    host round trip bit-identical for packed nibbles + scales; and
    every garbage-padded bucket write bit-identical to the real-only
    write (the PR 7 bucket-padding regression, int4 edition)."""
    from cake_tpu.models.llama.paged import PageAllocator

    rng = np.random.default_rng(17)
    N = 8
    cache = Int4PagedKVCache.create(tiny_config, 4, N, PAGE, 4 * PAGE)
    pager = PageAllocator(N, PAGE)
    tier = HostTier(2 * N, page_bytes=page_bytes(tiny_config, PAGE,
                                                 "int4"))
    L = cache.k.q.shape[0]
    KV, hd = tiny_config.num_key_value_heads, tiny_config.head_dim
    MAXP = cache.max_pages
    live: dict = {}      # sid -> (pages, n_tokens)
    parked: dict = {}    # sid -> (n_pages, fetched arrays)
    next_sid = 0

    def row_of(pages):
        return jnp.asarray(pages + [-1] * (MAXP - len(pages)),
                           jnp.int32)

    def over_layers(pool, fn):
        """The device writers take the stacked pool and a layer index
        (they run inside the layer loop): apply fn at every layer."""
        for layer in range(L):
            pool = fn(pool, layer)
        return pool

    def check_conserved():
        assert pager.free_pages + pager.live_pages == N

    def check_monotone(scale_before, reset_pages=()):
        """Scales on non-recycled pages never shrink across a write."""
        for half in ("k", "v"):
            after = np.asarray(getattr(cache, half).scale)
            before = scale_before[half].copy()
            before[:, list(reset_pages)] = 0.0
            assert (after >= before - 1e-7).all()

    for step in range(300):
        scale_before = {"k": np.asarray(cache.k.scale),
                        "v": np.asarray(cache.v.scale)}
        op = rng.choice(["admit", "decode", "spill", "restore",
                         "cancel", "retire"])
        if op == "admit":
            n_tok = int(rng.integers(1, 3 * PAGE))
            pages = pager.alloc(n_tok)
            if pages is None:
                check_conserved()
                continue
            cache = reset_page_scales(cache, pages)
            row = row_of(pages)
            bucket = len(pages) * PAGE
            vals = {h: jnp.asarray(rng.normal(size=(1, bucket, KV, hd)),
                                   jnp.float32) for h in ("k", "v")}
            livemask = (jnp.arange(bucket)[None, :, None, None]
                        < n_tok)
            new = {}
            for h in ("k", "v"):
                garbage = vals[h].at[:, n_tok:].mul(100.0)
                clean = jnp.where(livemask, vals[h], 0.0)
                got = over_layers(
                    getattr(cache, h),
                    lambda p, l: qwrite_prompt_pages(
                        p, l, garbage, row, jnp.int32(n_tok)))
                want = over_layers(
                    getattr(cache, h),
                    lambda p, l: qwrite_prompt_pages(p, l, clean, row))
                np.testing.assert_array_equal(np.asarray(got.q),
                                              np.asarray(want.q))
                np.testing.assert_array_equal(np.asarray(got.scale),
                                              np.asarray(want.scale))
                new[h] = got
            cache = cache._replace(k=new["k"], v=new["v"])
            live[next_sid] = (pages, n_tok)
            check_monotone(scale_before, reset_pages=pages)
            next_sid += 1
        elif op == "decode" and live:
            sid = int(rng.choice(list(live)))
            pages, n_tok = live[sid]
            if n_tok >= len(pages) * PAGE:
                check_conserved()
                continue
            row = row_of(pages)
            new = {}
            for h in ("k", "v"):
                tok = jnp.asarray(rng.normal(size=(1, 1, KV, hd)),
                                  jnp.float32)
                new[h] = over_layers(
                    getattr(cache, h),
                    lambda p, l: qupdate_pool_per_row(
                        p, l, tok, jnp.asarray([n_tok], jnp.int32),
                        jnp.asarray([True]), row[None, :]))
            cache = cache._replace(k=new["k"], v=new["v"])
            live[sid] = (pages, n_tok + 1)
            check_monotone(scale_before)
        elif op == "spill" and live:
            sid = int(rng.choice(list(live)))
            pages, n_tok = live[sid]
            arrays = HostTier.fetch_pages(cache, pages)
            assert tier.put(("victim", sid),
                            SpilledPages(len(pages), arrays, "victim"))
            for p in pages:
                pager.release([p])
            parked[sid] = (len(pages), arrays)
            del live[sid]
        elif op == "restore" and parked:
            sid = int(rng.choice(list(parked)))
            n_pages, want = parked[sid]
            pages = pager.alloc(n_pages * PAGE)
            if pages is None:
                check_conserved()
                continue
            entry = tier.pop(("victim", sid))
            assert entry is not None and entry.n_pages == n_pages
            cache = HostTier.install_pages(cache, pages, entry.arrays)
            back = HostTier.fetch_pages(cache, pages)
            for a, b in zip(back, want):
                np.testing.assert_array_equal(a, b)
            live[sid] = (pages, n_pages * PAGE)
            del parked[sid]
        elif op in ("cancel", "retire") and live:
            sid = int(rng.choice(list(live)))
            pages, _ = live.pop(sid)
            for p in pages:
                pager.release([p])
        check_conserved()
    # drain: every page accounted for at the end
    for pages, _ in live.values():
        for p in pages:
            pager.release([p])
    assert pager.free_pages == N and pager.live_pages == 0


def test_reset_page_scales_zeroes_only_targets(tiny_config):
    cache = QuantizedPagedKVCache.create(tiny_config, 2, 8, PAGE, T)
    ones = jnp.ones_like(cache.k.scale)
    cache = cache._replace(k=cache.k._replace(scale=ones),
                           v=cache.v._replace(scale=ones))
    cache = reset_page_scales(cache, [2, 5])
    sk = np.asarray(cache.k.scale)
    assert (sk[:, [2, 5]] == 0).all()
    assert (sk[:, [0, 1, 3, 4, 6, 7]] == 1).all()


def test_memory_bytes_counts_scales(tiny_config):
    """The satellite fix: storage bytes sum per dtype + scale arrays
    instead of assuming one dtype for the pool."""
    from cake_tpu.models.llama.paged import PagedKVCache
    q8 = QuantizedPagedKVCache.create(tiny_config, 2, 8, PAGE, T)
    want = (q8.k.q.nbytes + q8.k.scale.nbytes
            + q8.v.q.nbytes + q8.v.scale.nbytes)
    assert q8.memory_bytes() == want
    assert q8.memory_bytes() == 8 * page_bytes(tiny_config, PAGE,
                                               jnp.int8)
    f32 = PagedKVCache.create(tiny_config, 2, 8, PAGE, T,
                              dtype=jnp.float32)
    assert f32.memory_bytes() == f32.k.nbytes + f32.v.nbytes
    assert f32.memory_bytes() == 8 * page_bytes(tiny_config, PAGE,
                                                jnp.float32)
    # the capacity story in one assert: int8+scales under ~30% of f32
    assert q8.memory_bytes() < 0.3 * f32.memory_bytes()


# -- config plumbing ----------------------------------------------------------


def test_kv_dtype_int8_requires_pages(tiny_config, params):
    with pytest.raises(ValueError, match="requires --kv-pages"):
        _engine(tiny_config, params, kv_pages=None, kv_dtype="int8")


def test_args_validate_int8_rules():
    from cake_tpu.args import Args
    with pytest.raises(ValueError, match="requires --kv-pages"):
        Args(kv_dtype="int8").validate()
    with pytest.raises(ValueError, match="kv-host-pages"):
        Args(kv_host_pages=0).validate()
    Args(kv_dtype="int8", kv_pages=64, kv_host_pages=4).validate()


def test_args_validate_int4_rules():
    """int4 rides the int8 rules plus the nibble-packing constraint:
    pages hold token PAIRS, so the page size must be even."""
    from cake_tpu.args import Args
    with pytest.raises(ValueError, match="requires --kv-pages"):
        Args(kv_dtype="int4").validate()
    with pytest.raises(ValueError, match="even --kv-page-size"):
        Args(kv_dtype="int4", kv_pages=64, kv_page_size=31).validate()
    Args(kv_dtype="int4", kv_pages=64, kv_host_pages=4).validate()


# -- engine: int8 serving -----------------------------------------------------


def test_engine_int8_serves_and_conserves_pages(tiny_config, params):
    """An int8-KV paged engine serves concurrent greedy streams and
    returns every page at retire (free + live == n_pages)."""
    eng = _engine(tiny_config, params, kv_dtype="int8")
    with eng:
        hs = [eng.submit([5] * 9, max_new_tokens=6),
              eng.submit([3, 7, 9], max_new_tokens=6)]
        assert all(h.wait(timeout=300) for h in hs)
        assert all(len(h.token_ids) > 0 for h in hs)
        assert eng._pager.free_pages == eng.cache.n_pages
        assert eng.kv_quant
    # the pool really is the quantized layout
    assert eng.cache.k.q.dtype == jnp.int8
    assert eng.cache.k.scale.dtype == jnp.float32


def test_engine_int4_serves_and_conserves_pages(tiny_config, params):
    """An int4-KV paged engine serves concurrent greedy streams through
    the nibble-packed pool and returns every page at retire."""
    eng = _engine(tiny_config, params, kv_dtype="int4")
    with eng:
        hs = [eng.submit([5] * 9, max_new_tokens=6),
              eng.submit([3, 7, 9], max_new_tokens=6)]
        assert all(h.wait(timeout=300) for h in hs)
        assert all(len(h.token_ids) > 0 for h in hs)
        assert eng._pager.free_pages == eng.cache.n_pages
        assert eng.kv_quant
    # the pool really is the packed layout: uint8 bytes, half the
    # token axis, f32 scale sidecars
    assert eng.cache.k.q.dtype == jnp.uint8
    assert eng.cache.k.q.shape[2] == PAGE // 2
    assert eng.cache.k.scale.dtype == jnp.float32


@pytest.mark.slow  # two engine phases -> slow lane
def test_engine_int8_greedy_acceptance_vs_f32(tiny_config, params):
    """Tolerance/acceptance vs the f32 reference: same prompts, same
    config, KV storage flipped f32 -> int8. Token EQUALITY is not the
    bar (per-page rounding can flip greedy near-ties on a random tiny
    model); a high agreement fraction and a same-length stream are."""
    def run(kv_dtype):
        eng = _engine(tiny_config, params, kv_dtype=kv_dtype)
        with eng:
            hs = [eng.submit([11] * 14, max_new_tokens=10),
                  eng.submit([2, 9, 4, 7, 3], max_new_tokens=10)]
            assert all(h.wait(timeout=300) for h in hs)
            return [list(h._req.out_tokens) for h in hs]

    ref, got = run("f32"), run("int8")
    total = agree = 0
    for a, b in zip(ref, got):
        assert len(a) == len(b)
        total += len(a)
        agree += sum(x == y for x, y in zip(a, b))
    assert agree / total >= 0.6, (ref, got)


@pytest.mark.slow  # two engine phases -> slow lane
def test_engine_int4_greedy_acceptance_vs_f32(tiny_config, params):
    """The int4 edition of the acceptance bar one tier down: >= 60%
    greedy agreement with the f32 reference at equal stream lengths.
    Nibble precision is ~8x coarser than int8, and the random tiny
    model's logit gaps are near-ties on arbitrary prompts — so the
    probe prompts are strongly repetitive, where the model's argmax is
    decisive and disagreement would indicate a BROKEN int4 path (wrong
    scales, nibble-order bugs), not quantization noise."""
    def run(kv_dtype):
        eng = _engine(tiny_config, params, kv_dtype=kv_dtype)
        with eng:
            hs = [eng.submit([5] * 20, max_new_tokens=8),
                  eng.submit([9] * 20, max_new_tokens=8)]
            assert all(h.wait(timeout=300) for h in hs)
            return [list(h._req.out_tokens) for h in hs]

    ref, got = run("f32"), run("int4")
    total = agree = 0
    for a, b in zip(ref, got):
        assert len(a) == len(b)
        total += len(a)
        agree += sum(x == y for x, y in zip(a, b))
    assert agree / total >= 0.6, (ref, got)


@pytest.mark.slow  # three engine phases under preemption -> slow lane
def test_preempt_spill_restore_token_identity_f32(tiny_config, params):
    """THE spill-resume acceptance bar: a batch stream preempted by an
    interactive arrival, its pages SPILLED to the host tier and
    RESTORED at resume, emits tokens identical to an unpreempted run
    (f32 KV; the PR 5 recompute-equality test, spill edition). The
    host-tier counters prove the spill path actually ran."""
    from cake_tpu.sched import SchedConfig

    kw = dict(max_slots=1, priority_classes=True,
              sched_config=SchedConfig(preempt_budget=8),
              kv_dtype="f32")

    base = _engine(tiny_config, params, **kw)
    with base:
        h = base.submit(BATCH_PROMPT, max_new_tokens=GEN,
                        priority="batch")
        assert h.wait(timeout=300)
        assert base.stats.preemptions == 0
        want = list(h._req.out_tokens)

    eng = _engine(tiny_config, params, preemption=True,
                  kv_host_pages=8, **kw)
    with eng:
        hb = eng.submit(BATCH_PROMPT, max_new_tokens=GEN,
                        priority="batch")
        t0 = time.perf_counter()
        while (len(hb._req.out_tokens) < 4
               and time.perf_counter() - t0 < 120):
            time.sleep(0.002)
        assert len(hb._req.out_tokens) >= 4, "victim never got going"
        hi = eng.submit(INTER_PROMPT, max_new_tokens=4,
                        priority="interactive")
        assert hi.wait(timeout=300) and hb.wait(timeout=300)
        assert eng.stats.preemptions >= 1, "no preemption happened"
        assert eng.stats.kv_spills >= 1, "victim was not spilled"
        assert eng.stats.kv_restores >= 1, "victim was not restored"
        got = list(hb._req.out_tokens)
        assert eng._pager.free_pages == eng.cache.n_pages
        assert eng._host_tier.used_pages == 0
    assert got == want


@pytest.mark.slow  # pool-pressure engine run -> slow lane
def test_cold_prefix_spills_and_restores(tiny_config, params):
    """Admission pressure spills a COLD registered prefix to the host
    tier instead of refusing admission; a later prefix-matching
    request streams it back and still takes the prefix hit."""
    eng = _engine(tiny_config, params, max_seq_len=128, kv_pages=6,
                  kv_dtype="f32", kv_host_pages=4)
    with eng:
        pid = eng.register_prefix(list(range(3, 35)))     # 2 pages
        assert eng._pager.free_pages == 4
        # two 4-page requests oversubscribe the remaining pool: the
        # second admission must spill the cold prefix, not wait
        h1 = eng.submit([9] * 24, max_new_tokens=40)
        h2 = eng.submit([8] * 24, max_new_tokens=40)
        assert h1.wait(timeout=300) and h2.wait(timeout=300)
        assert eng.stats.kv_spills >= 1
        with eng._rid_lock:
            assert eng._prefixes[pid][1] is None          # spilled
        base_hits = eng.stats.prefix_hits
        h3 = eng.submit(list(range(3, 35)) + [7] * 5,
                        max_new_tokens=4)
        assert h3.wait(timeout=300)
        assert eng.stats.kv_restores >= 1
        with eng._rid_lock:
            assert eng._prefixes[pid][1] is not None      # restored
        assert eng.stats.prefix_hits > base_hits
        assert eng._pager.free_pages == eng.cache.n_pages - 2


@pytest.mark.slow  # two engine phases -> slow lane
def test_engine_int8_fold_matches_pallas(tiny_config, params):
    """Engine-level fold==pallas at int8 KV: chunked prefill + mixed
    steps + decode through the quantized pool emit identical token ids
    under both attention impls (both read the SAME stored int8 values,
    so this is kernel parity, not quantization tolerance)."""
    def run(impl):
        eng = _engine(tiny_config, params, kv_dtype="int8",
                      paged_attn=impl, prefill_chunk=8)
        with eng:
            hs = [eng.submit([5] * 9, max_new_tokens=6),
                  eng.submit([3, 7, 9, 11, 2, 8, 6, 1, 9, 4, 3, 2, 7],
                             max_new_tokens=6)]
            assert all(h.wait(timeout=300) for h in hs)
            return [list(h._req.out_tokens) for h in hs]

    assert run("fold") == run("pallas")


@pytest.mark.slow  # two engine phases -> slow lane
def test_engine_int4_fold_matches_pallas(tiny_config, params):
    """Engine-level fold==pallas at int4 KV: chunked prefill + mixed
    steps + decode through the nibble-packed pool emit identical token
    ids under both attention impls (both read the SAME stored nibbles,
    so this is kernel parity, not quantization tolerance)."""
    def run(impl):
        eng = _engine(tiny_config, params, kv_dtype="int4",
                      paged_attn=impl, prefill_chunk=8)
        with eng:
            hs = [eng.submit([5] * 9, max_new_tokens=6),
                  eng.submit([3, 7, 9, 11, 2, 8, 6, 1, 9, 4, 3, 2, 7],
                             max_new_tokens=6)]
            assert all(h.wait(timeout=300) for h in hs)
            return [list(h._req.out_tokens) for h in hs]

    assert run("fold") == run("pallas")


# -- engine: decode-resident spill (pool oversubscription) --------------------


@pytest.mark.slow  # four engine phases under oversubscription -> slow lane
@pytest.mark.parametrize("kw", [
    dict(),
    dict(priority_classes=True),
], ids=["fifo", "slo"])
def test_resident_spill_restore_token_identity_f32(tiny_config, params,
                                                   kw):
    """THE decode-resident spill acceptance bar: a 2-page pool serving
    two 2-page streams oversubscribes like virtual memory — the LRU
    decode-RESIDENT stream's pages park in the host tier so the other
    admits, the streams time-slice in resident_quantum turns, and both
    emit tokens identical to a non-oversubscribed run (f32 KV). Pool
    conserved and the host tier drained once everyone retired.
    Parametrized over the FIFO requeue path and the SLO scheduler's
    requeue path."""
    prompts = [[5] * 9, [3, 7, 9, 11, 2]]

    def run(**extra):
        eng = _engine(tiny_config, params, kv_dtype="f32", **kw,
                      **extra)
        with eng:
            hs = [eng.submit(p, max_new_tokens=20) for p in prompts]
            assert all(h.wait(timeout=300) for h in hs)
            toks = [list(h._req.out_tokens) for h in hs]
            assert all(h._req.error is None for h in hs)
            assert eng._pager.free_pages == eng.cache.n_pages
            if eng._host_tier is not None:
                assert eng._host_tier.used_pages == 0
            stats = eng.stats
        return toks, stats

    want, base = run()                      # 8-page pool: both resident
    assert base.kv_resident_spills == 0
    got, stats = run(kv_pages=2, kv_host_pages=8)
    assert stats.kv_resident_spills >= 1, "no stream was ever parked"
    assert stats.kv_restores >= 1, "parked pages never streamed back"
    assert [len(t) for t in got] == [20, 20]
    assert got == want


@pytest.mark.slow  # oversubscribed engine run -> slow lane
def test_resident_spill_disabled_by_sched_config(tiny_config, params):
    """spill_resident=False pins the pre-PR behavior: admission waits
    for pages instead of parking a resident stream (the pool still
    serves both streams, serially)."""
    from cake_tpu.sched import SchedConfig

    eng = _engine(tiny_config, params, kv_dtype="f32", kv_pages=2,
                  kv_host_pages=8,
                  sched_config=SchedConfig(spill_resident=False))
    with eng:
        hs = [eng.submit([5] * 9, max_new_tokens=20),
              eng.submit([3, 7, 9, 11, 2], max_new_tokens=20)]
        assert all(h.wait(timeout=300) for h in hs)
        assert eng.stats.kv_resident_spills == 0
        assert eng._pager.free_pages == eng.cache.n_pages


@pytest.mark.slow  # pool-pressure engine runs -> slow lane
def test_host_evicted_prefix_degrades_to_full_prefill(
        tiny_config, params):
    """A spilled prefix whose host entry is gone (LRU-evicted) must
    degrade the admission to a whole-prompt prefill: the stale hit is
    dropped BEFORE dispatch (_mixed_admit), so the request never
    attends the never-written prefix region."""
    prompt = list(range(3, 35)) + [7] * 5
    ref = _engine(tiny_config, params, max_seq_len=128, kv_pages=8,
                  kv_dtype="f32")
    with ref:
        h = ref.submit(prompt, max_new_tokens=4)
        assert h.wait(timeout=300)
        want = list(h._req.out_tokens)

    eng = _engine(tiny_config, params, max_seq_len=128, kv_pages=6,
                  kv_dtype="f32", kv_host_pages=4)
    with eng:
        pid = eng.register_prefix(list(range(3, 35)))     # 2 pages
        # oversubscribe the pool so the cold prefix spills to host
        h1 = eng.submit([9] * 24, max_new_tokens=40)
        h2 = eng.submit([8] * 24, max_new_tokens=40)
        assert h1.wait(timeout=300) and h2.wait(timeout=300)
        assert eng.stats.kv_spills >= 1
        with eng._rid_lock:
            assert eng._prefixes[pid][1] is None          # spilled
        eng._host_tier.drop(("prefix", pid))              # "LRU-evicted"
        base_hits = eng.stats.prefix_hits
        h3 = eng.submit(prompt, max_new_tokens=4)
        assert h3.wait(timeout=300)
        assert list(h3._req.out_tokens) == want           # not garbage
        assert eng.stats.prefix_hits == base_hits         # no false hit
        with eng._rid_lock:
            assert pid not in eng._prefixes               # unregistered
        assert eng._pager.free_pages == eng.cache.n_pages
