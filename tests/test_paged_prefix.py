"""Page-granular prefix sharing + chunked prefill on the paged engine.

Three layers of bar:
  * PageAllocator invariants — refcounted retain/release, loud
    double-free / foreign-id rejection, and `free + live == n_pages`
    under random admit/retire/cancel/requeue interleavings (incl.
    shared prefixes);
  * step-program parity — the mixed step (the one program a paged
    engine prefills through) must match the dense / whole-window
    oracle at logit level on a prefix hit and for windows at any page
    offset, for BOTH attn impls and a float and an int8 pool;
  * engine equivalence — a paged engine serving shared prefixes (and
    chunked prefills) emits token-identical streams to unshared serving
    at f32 cache (bf16 storage flips greedy near-ties — the PR 2
    lesson), while allocating strictly fewer pool pages.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.models.llama.paged import (
    PageAllocator, PagedKVCache, mixed_step_paged, mixed_token_buckets,
    prefill_prefix_pages, prefill_slot_paged, table_set_slot,
)

PAGE = 16
T = 64            # max_seq_len


@pytest.fixture(scope="module")
def params(tiny_config):
    from cake_tpu.models.llama.params import init_params
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


# -- allocator invariants ------------------------------------------------------


def _coherent(alloc: PageAllocator) -> bool:
    return alloc.free_pages + alloc.live_pages == alloc.n_pages


def test_allocator_refcount_lifecycle():
    alloc = PageAllocator(n_pages=6, page_size=PAGE)
    prefix = alloc.alloc(2 * PAGE)          # 2 pages at refcount 1
    assert _coherent(alloc) and alloc.free_pages == 4
    # two "slots" map the shared prefix
    alloc.retain(prefix)
    alloc.retain(prefix)
    assert alloc.refcount(prefix[0]) == 3
    assert _coherent(alloc) and alloc.free_pages == 4  # no new pages
    # slot releases decref; pages stay live for the registry
    alloc.release(prefix)
    alloc.release(prefix)
    assert alloc.refcount(prefix[0]) == 1
    assert _coherent(alloc) and alloc.free_pages == 4
    # registry drop frees them
    alloc.release(prefix)
    assert alloc.refcount(prefix[0]) == 0
    assert _coherent(alloc) and alloc.free_pages == 6


def test_allocator_double_free_raises():
    alloc = PageAllocator(n_pages=4, page_size=PAGE)
    pages = alloc.alloc(PAGE)
    alloc.free(pages)
    with pytest.raises(ValueError, match="double-free"):
        alloc.free(pages)
    assert _coherent(alloc)


def test_allocator_foreign_id_raises():
    alloc = PageAllocator(n_pages=4, page_size=PAGE)
    with pytest.raises(ValueError, match="foreign"):
        alloc.free([7])
    with pytest.raises(ValueError, match="foreign"):
        alloc.free([-1])
    assert _coherent(alloc)


def test_allocator_retain_free_page_raises():
    alloc = PageAllocator(n_pages=4, page_size=PAGE)
    pages = alloc.alloc(PAGE)
    alloc.free(pages)
    with pytest.raises(ValueError, match="retain"):
        alloc.retain(pages)


def test_allocator_random_interleavings():
    """Property-style soak: random admit/retire/cancel/requeue cycles
    with a shared prefix mapped into a varying subset of slots. After
    EVERY operation `free + live == n_pages`; at drain the pool is
    whole again. This is the invariant a silently-extending free list
    used to mask."""
    rng = np.random.default_rng(0)
    alloc = PageAllocator(n_pages=24, page_size=PAGE)
    prefix = alloc.alloc(3 * PAGE)              # registry holds 3 pages
    live_slots: dict = {}                       # slot -> page list
    for step in range(300):
        op = rng.integers(0, 3)
        slot = int(rng.integers(0, 8))
        if op == 0 and slot not in live_slots:        # admit
            shared = bool(rng.integers(0, 2))
            need = int(rng.integers(1, 4 * PAGE))
            pages = alloc.alloc(need)
            if pages is None:
                continue                               # requeued
            if shared:
                alloc.retain(prefix)
                pages = list(prefix) + pages
            live_slots[slot] = pages
        elif op == 1 and slot in live_slots:           # retire/cancel
            alloc.release(live_slots.pop(slot))
        elif op == 2 and slot in live_slots:
            # cancel-vs-error race: the second release path finds the
            # mapping already popped (engine dict-pop idempotence) —
            # model it by popping once and releasing once
            alloc.release(live_slots.pop(slot))
        assert _coherent(alloc), f"step {step}: free+live != n_pages"
        assert alloc.refcount(prefix[0]) >= 1, "prefix freed under registry"
    for pages in live_slots.values():
        alloc.release(pages)
    alloc.release(prefix)
    assert alloc.free_pages == 24 and alloc.live_pages == 0


# -- step-program parity: the mixed step against whole-prompt oracles ----------
#
# A paged engine prefills through mixed_step_paged alone (PR 28): a
# prompt walks it in windows from position 0, or from the end of a
# shared prefix whose pages sit at the head of its table row. Logits of
# the prompt's last token, for both attention impls and for a float and
# a quantized pool, through the window program and a packed size.


POOLS = {"float32": 2e-4, "int8": 4e-2}     # logit tolerance by pool
# a 37-token prompt: a 32-token (2-page) head and a 5-token tail
IDS = [5] * 20 + [9] * 12 + [3, 7, 9, 11, 2]
C = 16            # the mixed step's window width
SLOTS = 2


def _pool(cfg, pool: str, n_pages: int = 10):
    if pool == "int8":
        from cake_tpu.kv.quantized_pool import QuantizedPagedKVCache
        return QuantizedPagedKVCache.create(cfg, SLOTS, n_pages, PAGE, T)
    return PagedKVCache.create(cfg, SLOTS, n_pages, PAGE, T,
                               dtype=jnp.float32)


def _dup(c):
    """Fresh buffers so donating step fns can't consume a fixture."""
    return jax.tree_util.tree_map(jnp.array, c)


def _mixed_window(params, cfg, rope, cache, slot, window, pos, attn,
                  n_tokens):
    """One prefill window of row `slot` through the mixed step, every
    other row idle. Returns (the row's logits, cache)."""
    tokens = np.zeros((SLOTS, C), np.int32)
    tokens[slot, :len(window)] = window
    q_len = np.zeros(SLOTS, np.int32)
    q_len[slot] = len(window)
    posv = np.zeros(SLOTS, np.int32)
    posv[slot] = pos
    logits, cache = mixed_step_paged(
        params, jnp.asarray(tokens), jnp.asarray(posv), jnp.asarray(q_len),
        jnp.asarray(q_len > 0), cache, rope, cfg, attn=attn,
        n_tokens=n_tokens)
    return logits[slot], cache


@pytest.mark.parametrize("pool", list(POOLS))
@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_mixed_prefix_hit_matches_dense_oracle(tiny_config, params, attn,
                                               pool):
    """A prefix hit as the engine serves it: the head prefilled once
    into pool pages (prefill_prefix_pages), those pages mapped at the
    head of the slot's row, the suffix one mixed-step window at
    pos = 32 == the dense engine's whole-prompt prefill_slot logits."""
    from cake_tpu.models.llama.cache import KVCache
    from cake_tpu.models.llama.generator import bucket_length
    from cake_tpu.models.llama.model import RopeTables, prefill_slot

    cfg = tiny_config
    rope = RopeTables.create(cfg, T)
    prefix, suffix = IDS[:32], IDS[32:]
    dense = KVCache.create(cfg, SLOTS, T, dtype=jnp.float32)
    bucket = bucket_length(len(IDS), T)
    want, _ = prefill_slot(
        params, jnp.asarray([IDS + [0] * (bucket - len(IDS))], jnp.int32),
        jnp.asarray([len(IDS)], jnp.int32), jnp.int32(0), dense, rope, cfg)

    alloc = PageAllocator(n_pages=10, page_size=PAGE)
    paged = _pool(cfg, pool)
    ppages = alloc.alloc(32)
    row = np.full(paged.table.shape[1], -1, np.int64)
    row[:len(ppages)] = ppages
    paged = prefill_prefix_pages(params, jnp.asarray([prefix], jnp.int32),
                                 jnp.asarray(row, jnp.int32), paged,
                                 rope, cfg, attn=attn)
    spages = alloc.alloc(len(suffix) + 8)
    alloc.retain(ppages)
    paged = paged._replace(
        table=table_set_slot(paged.table, 0, list(ppages) + spages))
    head_pages = jax.tree_util.tree_map(
        lambda x: np.asarray(x[:, np.asarray(ppages)]),
        (paged.k, paged.v))
    for n_tokens in (None, mixed_token_buckets(SLOTS, C)[0]):
        got, after = _mixed_window(params, cfg, rope, _dup(paged), 0,
                                   suffix, 32, attn, n_tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                                   atol=POOLS[pool], rtol=POOLS[pool])
        # the shared head is read-only: the suffix wrote its own pages
        jax.tree_util.tree_map(
            lambda x, h: np.testing.assert_array_equal(
                np.asarray(x[:, np.asarray(ppages)]), h),
            (after.k, after.v), head_pages)


@pytest.mark.parametrize("pool", list(POOLS))
@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_mixed_windows_at_any_page_offset_match_whole_prompt(
        tiny_config, params, attn, pool):
    """The 37-token prompt walked through the mixed step in windows of
    C = 16 == whole-window paged prefill, for page-aligned windows
    (16, 16, 5: starts 0, 16, 32) and for windows that straddle page
    boundaries (5, 16, 16: starts 0, 5, 21 on 16-token pages)."""
    from cake_tpu.models.llama.generator import bucket_length
    from cake_tpu.models.llama.model import RopeTables

    cfg = tiny_config
    rope = RopeTables.create(cfg, T)
    alloc = PageAllocator(n_pages=10, page_size=PAGE)
    pg0 = _pool(cfg, pool)
    pages = alloc.alloc(len(IDS) + 8)
    pg0 = pg0._replace(table=table_set_slot(pg0.table, 1, pages))
    bucket = bucket_length(len(IDS), T)
    want, _ = prefill_slot_paged(
        params, jnp.asarray([IDS + [0] * (bucket - len(IDS))], jnp.int32),
        jnp.asarray([len(IDS)], jnp.int32), jnp.int32(1), _dup(pg0),
        rope, cfg, attn=attn)
    packed = mixed_token_buckets(SLOTS, C)[0]
    for lens, n_tokens in (((16, 16, 5), None), ((16, 16, 5), packed),
                           ((5, 16, 16), None), ((5, 16, 16), packed)):
        pg, start = _dup(pg0), 0
        for n in lens:
            got, pg = _mixed_window(params, cfg, rope, pg, 1,
                                    IDS[start:start + n], start, attn,
                                    n_tokens)
            start += n
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                                   atol=POOLS[pool], rtol=POOLS[pool],
                                   err_msg=f"{lens} n_tokens={n_tokens}")


# -- engine equivalence --------------------------------------------------------


PREFIX = [5] * 20 + [9] * 12          # 32 tokens = 2 pages
SUFFIXES = [[3, 7, 9, 11, 2], [13, 4, 6]]


def _engine(tiny_config, params, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    kw.setdefault("kv_pages", 14)
    kw.setdefault("kv_page_size", PAGE)
    return InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        max_slots=4, max_seq_len=T,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        # f32 KV: bf16 storage flips greedy near-ties against the f32
        # params fixture (reduction-order ULPs) — that tests the tie,
        # not the sharing (PR 2 lesson, pinned in the module docstring)
        cache_dtype=jnp.float32,
        **kw)


def _run_tokens(eng, prompts, max_new=6):
    with eng:
        hs = [eng.submit(p, max_new_tokens=max_new, temperature=0.0,
                         repeat_penalty=1.0) for p in prompts]
        assert all(h.wait(timeout=300) for h in hs)
        return [list(h._req.out_tokens) for h in hs]


def test_engine_prefix_vs_fresh_token_equality(tiny_config, params):
    """The acceptance bar: shared-prefix serving (fold AND pallas) is
    token-identical to unshared whole-prompt serving at f32 cache, and
    every shared page returns to the registry's single reference when
    the requests retire."""
    prompts = [PREFIX + s for s in SUFFIXES]
    want = _run_tokens(_engine(tiny_config, params), prompts)
    for impl in ("fold", "pallas"):
        eng = _engine(tiny_config, params, paged_attn=impl)
        with eng:
            eng.register_prefix(PREFIX)
            hs = [eng.submit(p, max_new_tokens=6, temperature=0.0,
                             repeat_penalty=1.0) for p in prompts]
            assert all(h.wait(timeout=300) for h in hs)
            got = [list(h._req.out_tokens) for h in hs]
            assert eng.stats.prefix_hits == len(prompts)
        assert got == want, f"paged_attn={impl}"
        # retired: only the registry's 2 prefix pages stay live
        assert eng._pager.live_pages == 2
        assert eng._pager.free_pages == 12
        assert eng._prefix_pages_shared == 0


def test_engine_shared_prefix_allocates_strictly_fewer_pages(
        tiny_config, params):
    """Two requests sharing a registered page-aligned prefix hold
    strictly fewer pool pages than two unshared requests — the capacity
    claim, measured while both requests are mid-decode."""
    prompts = [PREFIX + s for s in SUFFIXES]

    def pages_in_use(register):
        eng = _engine(tiny_config, params)
        with eng:
            if register:
                eng.register_prefix(PREFIX)
            hs = [eng.submit(p, max_new_tokens=25, temperature=0.0,
                             repeat_penalty=1.0) for p in prompts]
            deadline = time.monotonic() + 120
            while (any(not h._req.out_tokens for h in hs)
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert all(h._req.out_tokens for h in hs), "not all admitted"
            used = eng.cache.n_pages - eng._pager.free_pages
            shared = eng._prefix_pages_shared
            for h in hs:
                eng.cancel(h)
            assert all(h.wait(timeout=120) for h in hs)
        return used, shared

    used_unshared, shared0 = pages_in_use(False)
    used_shared, shared1 = pages_in_use(True)
    assert shared0 == 0 and shared1 == 2 * 2   # 2 slots x 2 prefix pages
    # unshared: 2 x ceil((37+25)/16) = 8; shared: registry 2 +
    # 2 x ceil((5+25)/16) = 2 + 4 = 6
    assert used_shared < used_unshared


def test_engine_prefix_chunked_suffix_matches(tiny_config, params):
    """--prefill-chunk on the paged engine: a suffix longer than C
    walks C-token windows at pos0 = n_prefix and still matches the
    unshared stream (fold and pallas)."""
    prompts = [PREFIX + [7] * 20]          # suffix 20 > C=16
    want = _run_tokens(_engine(tiny_config, params), prompts)
    for impl in ("fold", "pallas"):
        eng = _engine(tiny_config, params, prefill_chunk=16,
                      paged_attn=impl)
        with eng:
            eng.register_prefix(PREFIX)
            hs = [eng.submit(p, max_new_tokens=6, temperature=0.0,
                             repeat_penalty=1.0) for p in prompts]
            assert all(h.wait(timeout=300) for h in hs)
            got = [list(h._req.out_tokens) for h in hs]
            assert eng.stats.prefix_hits == 1
        assert got == want, f"paged_attn={impl}"


def test_engine_paged_chunked_prefill_matches_whole(tiny_config, params):
    """The lifted restriction: long paged prompts admit in C-token
    windows and match whole-window paged serving (no prefix at all)."""
    prompts = [[5] * 40, [11] * 23, [3, 7, 9]]
    want = _run_tokens(_engine(tiny_config, params), prompts)
    got = _run_tokens(_engine(tiny_config, params, prefill_chunk=16),
                      prompts)
    assert got == want


def test_engine_prefix_unregister_releases_pages(tiny_config, params):
    eng = _engine(tiny_config, params)
    with eng:
        pid = eng.register_prefix(PREFIX)
        assert eng._pager.free_pages == 12
        h = eng.submit(PREFIX + [3, 7], max_new_tokens=3,
                       temperature=0.0, repeat_penalty=1.0)
        assert h.wait(timeout=300)
        eng.unregister_prefix(pid)
        # registry dropped its reference; retired slots dropped theirs
        assert eng._pager.free_pages == 14
        assert eng._pager.live_pages == 0


def test_engine_prefix_metrics_move(tiny_config, params):
    from cake_tpu.obs import metrics as obs_metrics

    hits = obs_metrics.REGISTRY.get("cake_prefix_paged_hits_total")
    saved = obs_metrics.REGISTRY.get("cake_prefix_tokens_saved_total")
    shared = obs_metrics.REGISTRY.get("cake_prefix_pages_shared")
    assert None not in (hits, saved, shared)
    h0, s0 = hits.value, saved.value
    eng = _engine(tiny_config, params)
    with eng:
        eng.register_prefix(PREFIX)
        h = eng.submit(PREFIX + [3, 7], max_new_tokens=3,
                       temperature=0.0, repeat_penalty=1.0)
        assert h.wait(timeout=300)
    assert hits.value == h0 + 1
    assert saved.value == s0 + len(PREFIX)
    assert shared.value == 0       # request retired -> mappings gone


def test_auto_prefix_heals_stale_entry_after_reset(tiny_config, params):
    """A paged reset clears the registry (its pool pages are gone); an
    auto-prefix head->pid entry that lands AFTER the clear (handler
    thread racing _reset_after_error) must not permanently disable
    sharing for that head — the next chat() detects the dangling pid
    and re-registers."""
    from cake_tpu.models.chat import Message
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    # the rendered llama3 head is ~100 byte-tokens: needs a window
    # bigger than this module's T=64 to qualify for auto-registration
    eng = InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        max_slots=4, max_seq_len=256,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        cache_dtype=jnp.float32, kv_pages=32, kv_page_size=PAGE,
        auto_prefix_system=True)
    sysmsg = Message.system("x" * 40)       # head >= one 16-token page
    with eng:
        eng._auto_register_system(sysmsg)
        with eng._rid_lock:
            (head, pid), = eng._auto_pids.items()
        assert pid in eng._prefixes
        # simulate the race losing: registry cleared, stale entry back
        eng._reset_after_error()
        with eng._rid_lock:
            assert not eng._prefixes
            eng._auto_pids[head] = pid      # the late handler write
        eng._auto_register_system(sysmsg)   # next request's path
        with eng._rid_lock:
            new_pid = eng._auto_pids[head]
            assert new_pid is not None and new_pid != pid
            assert new_pid in eng._prefixes


def test_register_refusals_name_their_reason(tiny_config, params):
    """Each remaining refusal names its ACTUAL cause (the old message
    blamed ring/custom step fns for every engine flavor)."""
    # paged: shorter than one page -> nothing to share, says so
    eng = _engine(tiny_config, params)
    with pytest.raises(ValueError, match="page-granular"):
        eng.register_prefix([5] * (PAGE - 1))
    # ...but a page-aligned prefix is accepted (the tentpole): no
    # "unavailable" refusal on the paged engine anymore
    assert eng.register_prefix(PREFIX) >= 1


def test_engine_prefix_oversubscribed_pool_still_serves(tiny_config,
                                                        params):
    """Sharing under pressure: a pool too small for every request AT
    ONCE (after the registry's prefix pages) still serves them all —
    admission requeues on free suffix pages and shared mappings never
    double-free as slots cycle."""
    # pool of 6: registry holds 2, each request needs 2 suffix pages
    # (5 suffix + 20 budget), so at most 2 of the 3 decode together
    eng = _engine(tiny_config, params, kv_pages=6)
    with eng:
        eng.register_prefix(PREFIX)
        hs = [eng.submit(PREFIX + [3 + i] * 5, max_new_tokens=20,
                         temperature=0.0, repeat_penalty=1.0)
              for i in range(3)]
        assert all(h.wait(timeout=600) for h in hs)
        assert all(h._req.error is None for h in hs)
        assert eng.stats.prefix_hits == 3
    assert eng._pager.free_pages == 4      # only the registry's 2 live
    assert eng._pager.live_pages == 2
