"""Paged KV cache: a shared page pool + per-slot page tables.

The capacity fix for many-slot serving (round-4 bench: 32 dense slots ×
max_seq_len slabs thrash HBM — 151 tok/s aggregate vs 408 at 16 slots):
instead of every slot owning a dense [max_seq_len] cache slab, KV lives
in a pool of fixed-size pages and each slot maps position ranges to pages
through a small table. Slot count then scales with USED context — a pool
budgeted at the expected aggregate tokens serves far more concurrent
short requests than the dense worst-case allocation, and the engine's
page allocator (host-side free list) gates admission instead of
over-allocating HBM.

Layout (all static shapes — XLA-friendly):
  pool_k/pool_v: [L, N_pages, page, KV*hd]   (page = tokens per page)
  table:         [slots, max_pages] int32    (page ids; -1 = unmapped)
What a pool row is depends on the attention kind. GQA (every family but
one): `k` and `v` hold a token's keys and values, KV*hd wide, in every
layer. Latent attention (glm_moe_dsa): `k` holds ONE latent row a token
and layer, [L, N_pages, page, kv_lora_rank + qk_rope_head_dim, padded with
zeros to whole 128-lane tiles: 576 -> 640] (the normed c_kv and the
rotated key all heads share; no values: they are up-projected from
it), and `v` the sparse indexer's key,
[L_full, N_pages, page, index_head_dim], in the layers that compute an
index only (none in deepseek_v2, which attends every visible key: its
`v` is empty, a latent pool with no index-key pool). Both pools go
through the one page table and allocator: a page id names the same
token range in each.
A model with recurrent blocks (nemotron_h) keeps TWO kinds of state in
the one cache object (HybridPagedCache): the K/V pool above for its
attention blocks alone ([L_attn, ...]), and per ROW (slot), not per
token, each Mamba block's SSM state `ssm` [L_M, slots, H, P, N] float32
and the last conv_kernel-1 inputs of its causal conv `conv`
[L_M, slots, K-1, conv_dim]. A row's state has no pages: nothing is
allocated or released for it; the step programs zero it where a row's
first token sits at position 0 (a request that takes the slot), carry
it from window to window and from step to step, and leave the rows that
hold no token in a dispatch (idle, frozen, out of budget) as they are.
The state's leaves are the family's: compressed convolutional attention
(zaya) keeps K and V pages in EVERY layer, no `ssm` leaf (None), and in
`conv` [L, slots, 1, cca_tail_width] what a row's next token needs of
the one before it (its latents before and after the first convolution,
and the half of its values that is shifted by a token).
A model with SLIDING-WINDOW latent layers beside its full ones
(dots3_note) keeps a THIRD pool and a second table (WindowedPagedCache):
the full layers' latent rows in `k` ([L_full, ...]: the sliding layers
have none there) and index keys in `v`, through `table` as above; the
sliding layers' rows, of their own width, in `w` [L_sliding, N_w, page,
row_w] through `wtable` [slots, R], a RING: logical page j of a row lies
in wtable[row, j mod R], so a row holds R window pages whatever its
context (R: config.window_ring_pages; ring_holds states why nothing a
query needs is overwritten). `table` is mapped once, at admission;
`wtable` never moves: slot i owns pages i*R .. (i+1)*R - 1 of `w`.
A model whose sliding-window layers are plain GQA beside full GQA layers
(exaone_moe) keeps the same split over ORDINARY K/V pages
(WindowedKVCache): `k` / `v` [L_full, ...] through `table`, and the
sliding layers' keys and values in `wk` / `wv` [L_sliding, slots * R,
page, KV*hd] through the ring `wtable`; both attention kernels take the
band (`window=`) and read a ring through it.
A model whose queries attend the keys a learned indexer SELECTS among
ordinary K/V pages (KeyeVL2) keeps a third pool on the one page table
and allocator: `idx` [L, N_pages, page, index_head_dim], the indexer's
key a token and layer (PagedKVCache's optional leaf; None, no leaf of
the pytree, for every other family).
Page j of a slot covers absolute positions [j*page, (j+1)*page): pages
are position-contiguous, so decode attention is an online-softmax
accumulation over the slot's pages — each page is gathered once, folded
into (m, l, o) running stats (context_parallel's merge machinery), and
never materialised as a dense copy. That is the paged-attention
algorithm expressed in pure XLA; a Pallas kernel with a scalar-prefetched
page table is a drop-in upgrade on the same layout.

Reference contrast: the reference has no paging (dense per-request state,
one request in flight — SURVEY §2.2 Cache); this is serving-scale
machinery the TPU design adds.
"""

from __future__ import annotations

import dataclasses
from functools import partial as _partial
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cake_tpu.kv.quantized_pool import (
    Int4PagedKVCache, Int4Pool, QuantPool, QuantizedPagedKVCache,
    dequantize_pages, qupdate_pool_per_row, qwrite_prompt_pages,
    qwrite_windows_pages,
)
from cake_tpu.models.family import Family
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.step_programs import (
    make_decode_scan, make_mixed_sampled,
)
from cake_tpu.parallel.context_parallel import (
    merge_attention_stats, partial_attention_stats,
)


def _check_pages(page_size: int, max_seq_len: int) -> None:
    if max_seq_len % page_size:
        raise ValueError(
            f"page_size {page_size} must divide max_seq_len "
            f"{max_seq_len}")


class PagedKVCache(NamedTuple):
    """Device state of the paged cache. The page TABLE rides along as a
    device array (updated per admission/retire by the engine); the free
    list stays host-side in the allocator."""
    k: jnp.ndarray        # [L, N_pages, page, KV*hd]
    v: jnp.ndarray        # [L, N_pages, page, KV*hd]
    table: jnp.ndarray    # [slots, max_pages] int32, -1 = unmapped
    # the sparse indexer's keys beside K and V, through the same table
    # [L, N_pages, page, index_head_dim]; None (no leaf) elsewhere
    idx: Optional[jnp.ndarray] = None

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def max_pages(self) -> int:
        return self.table.shape[1]

    @property
    def max_seq_len(self) -> int:
        return self.table.shape[1] * self.k.shape[2]

    @classmethod
    def create(cls, config: LlamaConfig, slots: int, n_pages: int,
               page_size: int, max_seq_len: int, dtype=jnp.bfloat16,
               width: Optional[int] = None):
        """The cache of `config`'s family: the pytree its step programs
        carry (config.family.create_cache; this class for GQA and for
        one kind of latent layer, a class below for the others). width:
        the mixed step's window, which a pool of ring pages is sized
        by."""
        _check_pages(page_size, max_seq_len)
        return config.family.create_cache(config, slots, n_pages, page_size,
                                          max_seq_len, width, dtype)

    @classmethod
    def zeros(cls, shape_k: tuple, shape_v: tuple, slots: int,
              max_pages: int, dtype,
              shape_idx: Optional[tuple] = None) -> "PagedKVCache":
        """Empty pools [L, N_pages, page, row] and an unmapped table."""
        return cls(k=jnp.zeros(shape_k, dtype), v=jnp.zeros(shape_v, dtype),
                   table=jnp.full((slots, max_pages), -1, jnp.int32),
                   idx=(None if shape_idx is None
                        else jnp.zeros(shape_idx, dtype)))

    def memory_bytes(self) -> int:
        """ACTUAL pool storage bytes, summed per leaf — matches the
        quantized cache's accounting (which adds f32 scale sidecars to
        the int8 pools) instead of assuming one dtype for the pool."""
        return sum(leaf.nbytes
                   for leaf in jax.tree_util.tree_leaves((self.k, self.v,
                                                          self.idx)))


class HybridPagedCache(NamedTuple):
    """PagedKVCache plus the rows' state (module docstring): what a
    model with Mamba blocks, with convolutions inside its attention, or
    with a matrix state a layer carries through its step programs,
    donated in and aliased out like the pools. A family NONE of whose
    layers keeps K/V (models/moe/brumby.py) makes the pool with zero
    layers: `k` and `v` hold no byte, `memory_bytes()` is 0, and
    `n_pages`, `page_size` and the table stay what the allocator keeps
    its books of positions in."""
    k: jnp.ndarray        # [L_attn, N_pages, page, KV*hd]; L_attn may be 0
    v: jnp.ndarray
    table: jnp.ndarray    # [slots, max_pages] int32
    # the rows' state, a family's own shapes: [L_M, slots, H, P, N] float32
    # (Mamba, KDA), [L, slots, KV, NB, hd, DB] float32 (retention), or None
    ssm: Optional[jnp.ndarray]
    # what else a row keeps: conv tails [L_M, slots, K-1, conv_dim] in the
    # pool's type; retention's normaliser z [L, slots, KV, D] float32
    conv: jnp.ndarray

    page_size = PagedKVCache.page_size
    n_pages = PagedKVCache.n_pages
    max_pages = PagedKVCache.max_pages
    max_seq_len = PagedKVCache.max_seq_len

    @classmethod
    def zeros(cls, pool: tuple, slots: int, max_pages: int, dtype, *,
              ssm: Optional[tuple], conv: tuple) -> "HybridPagedCache":
        """Empty pools `pool` [L_attn, N_pages, page, KV*hd], an
        unmapped table, and the rows' state at the family's shapes
        (ssm: None where it keeps no recurrent state)."""
        return cls(
            k=jnp.zeros(pool, dtype), v=jnp.zeros(pool, dtype),
            table=jnp.full((slots, max_pages), -1, jnp.int32),
            ssm=None if ssm is None else jnp.zeros(ssm, jnp.float32),
            conv=jnp.zeros(conv, dtype))

    def memory_bytes(self) -> int:
        """Pool bytes (the pages an allocator hands out)."""
        return self.k.nbytes + self.v.nbytes

    def state_bytes(self) -> int:
        """Bytes of the rows' state."""
        return (0 if self.ssm is None else self.ssm.nbytes) + self.conv.nbytes

    beside_bytes = state_bytes


class WindowedPagedCache(NamedTuple):
    """PagedKVCache for latent attention plus the sliding-window
    layers' pool and its ring table (module docstring), donated in and
    aliased out like the other pools."""
    k: jnp.ndarray        # [L_full, N_pages, page, latent_row]
    v: jnp.ndarray        # [L_full, N_pages, page, index_head_dim]
    table: jnp.ndarray    # [slots, max_pages] int32, -1 = unmapped
    w: jnp.ndarray        # [L_sliding, N_window_pages, page, swa_latent_row]
    wtable: jnp.ndarray   # [slots, R] int32: slot i's ring, fixed

    page_size = PagedKVCache.page_size
    n_pages = PagedKVCache.n_pages
    max_pages = PagedKVCache.max_pages
    max_seq_len = PagedKVCache.max_seq_len

    @property
    def n_window_pages(self) -> int:
        return self.w.shape[1]

    @property
    def ring_pages(self) -> int:
        return self.wtable.shape[1]

    @classmethod
    def create(cls, config, slots: int, n_pages: int, page_size: int,
               max_seq_len: int, ring_pages: int,
               dtype=jnp.bfloat16) -> "WindowedPagedCache":
        _check_pages(page_size, max_seq_len)
        c = config
        return cls(
            k=jnp.zeros((len(c.latent_layers), n_pages, page_size,
                         c.latent_row), dtype),
            v=jnp.zeros((len(c.full_layers), n_pages, page_size,
                         c.index_head_dim), dtype),
            table=jnp.full((slots, max_seq_len // page_size), -1,
                           jnp.int32),
            w=jnp.zeros((len(c.sliding_layers), slots * ring_pages,
                         page_size, c.swa_latent_row), dtype),
            wtable=jnp.arange(slots * ring_pages, dtype=jnp.int32)
            .reshape(slots, ring_pages))

    def memory_bytes(self) -> int:
        """Bytes of the pools the page table maps (the full layers')."""
        return self.k.nbytes + self.v.nbytes

    def window_bytes(self) -> int:
        """Bytes of the sliding layers' pool: slots x R pages, whatever
        max_seq_len is."""
        return self.w.nbytes

    beside_bytes = window_bytes


class WindowedKVCache(NamedTuple):
    """K and V pools by kind of layer (module docstring): the full
    layers' on the allocator's pages, the sliding-window layers' in a
    ring a row that slot i owns for good, donated in and aliased out
    like the other pools."""
    k: jnp.ndarray        # [L_full, N_pages, page, KV*hd]
    v: jnp.ndarray
    table: jnp.ndarray    # [slots, max_pages] int32, -1 = unmapped
    wk: jnp.ndarray       # [L_sliding, slots * R, page, KV*hd]
    wv: jnp.ndarray
    wtable: jnp.ndarray   # [slots, R] int32: slot i's ring, fixed

    page_size = PagedKVCache.page_size
    n_pages = PagedKVCache.n_pages
    max_pages = PagedKVCache.max_pages
    max_seq_len = PagedKVCache.max_seq_len
    ring_pages = WindowedPagedCache.ring_pages

    @classmethod
    def create(cls, layers_full: int, layers_sliding: int, row: int,
               slots: int, n_pages: int, page_size: int, max_seq_len: int,
               ring_pages: int, dtype=jnp.bfloat16) -> "WindowedKVCache":
        """row: KV*hd, a token's keys (or values) in one layer."""
        _check_pages(page_size, max_seq_len)
        full = (layers_full, n_pages, page_size, row)
        ring = (layers_sliding, slots * ring_pages, page_size, row)
        return cls(
            k=jnp.zeros(full, dtype), v=jnp.zeros(full, dtype),
            table=jnp.full((slots, max_seq_len // page_size), -1,
                           jnp.int32),
            wk=jnp.zeros(ring, dtype), wv=jnp.zeros(ring, dtype),
            wtable=jnp.arange(slots * ring_pages, dtype=jnp.int32)
            .reshape(slots, ring_pages))

    def memory_bytes(self) -> int:
        """Bytes of the pools the page table maps (the full layers')."""
        return self.k.nbytes + self.v.nbytes

    def window_bytes(self) -> int:
        """Bytes of the sliding layers' pools: slots x R pages, whatever
        max_seq_len is."""
        return self.wk.nbytes + self.wv.nbytes

    beside_bytes = window_bytes


def ring_holds(page_size: int, ring_pages: int, window: int, start: int,
               n_written: int) -> bool:
    """THE RING'S INEQUALITY. A dispatch writes a row's positions
    start .. start + n_written - 1 into the ring and only then attends;
    the write of logical page p takes the place of page p - ring_pages.
    Its first query reaches back to position start - (window - 1), so
    every key any query of the dispatch needs lies in pages
    (start - window + 1) // page .. (start + n_written - 1) // page, and
    the ring holds them all iff the newest page written, less
    ring_pages, lies below the oldest page needed:

        (start + n_written - 1) // page - ring_pages
            < (start - (window - 1)) // page

    (floor division, so that positions before the row's start count as
    pages below 0: nothing lies there). With ring_pages =
    ceil((window - 1 + width) / page) + 1 it holds for every start and
    every n_written <= width (tests/test_dots3.py walks the
    alignments). A stale slot AHEAD of a query (a page of the ring that
    the row has not reached again) is masked by causality, as an
    unwritten one is."""
    newest = (start + n_written - 1) // page_size
    oldest = (start - (window - 1)) // page_size
    return newest - ring_pages < oldest


class PageAllocator:
    """Host-side free list with per-page refcounts. The ENGINE calls
    this at admission/retire — allocation never happens on the device
    path, so the jitted steps see only the (already-updated) table array.

    Refcounts are what make page-granular PREFIX SHARING safe: a shared
    prefix's pages appear in many slots' table rows, each mapping holds
    one reference (`retain`), and `release` returns a page to the free
    list only when its last holder lets go — a retiring request decrefs
    shared pages instead of freeing another slot's live context.

    The invariant `free_pages + live_pages == n_pages` holds after every
    operation; violations (double-free, foreign page ids) raise instead
    of silently corrupting the pool and masking leaks."""

    def __init__(self, n_pages: int, page_size: int):
        self.page_size = page_size
        self.n_pages = n_pages
        self._free: List[int] = list(range(n_pages - 1, -1, -1))
        # page id -> refcount, for every currently-allocated page
        self._refs: dict = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        """Distinct pages currently allocated (each counted once however
        many holders share it): free_pages + live_pages == n_pages."""
        return len(self._refs)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def alloc(self, n_tokens: int) -> Optional[List[int]]:
        """Pages covering n_tokens (each at refcount 1), or None when
        the pool is exhausted (the caller keeps the request queued —
        admission control is the whole point of paging)."""
        need = self.pages_for(n_tokens)
        if need > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(need)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def retain(self, pages: List[int]) -> None:
        """Add one reference to each (already-live) page — a slot
        mapping a shared prefix's pages into its table row. Retaining a
        free or foreign page is a bookkeeping bug: raise before the
        table can alias dead storage."""
        for p in pages:
            if self._refs.get(p, 0) < 1:
                raise ValueError(
                    f"retain of page {p} which is not allocated "
                    f"(refcount 0) — the mapping would alias freed "
                    "storage")
        for p in pages:
            self._refs[p] += 1

    def release(self, pages: List[int]) -> None:
        """Drop one reference per page; a page returns to the free list
        only at refcount 0. Raises on foreign ids and double-frees —
        silently extending the free list would corrupt the pool (one
        page handed to two slots) and mask the leak that caused it."""
        for p in pages:
            if not 0 <= p < self.n_pages:
                raise ValueError(
                    f"release of foreign page id {p!r} (pool has pages "
                    f"0..{self.n_pages - 1})")
        for p in pages:
            n = self._refs.get(p, 0)
            if n <= 0:
                raise ValueError(
                    f"double-free of page {p} (refcount already 0)")
            if n == 1:
                del self._refs[p]
                self._free.append(p)
            else:
                self._refs[p] = n - 1

    def free(self, pages: List[int]) -> None:
        """Alias of release() — kept for call sites that predate
        refcounting; same validation applies."""
        self.release(pages)


def table_set_slot(table: jnp.ndarray, slot: int,
                   pages: List[int]) -> jnp.ndarray:
    """Map `slot` to `pages`: the row is made on the host, so one tiny
    transfer and one scatter (an admission runs with the device idle,
    and every eager program is a launch of its own)."""
    row = np.full(table.shape[1], -1, np.int32)
    row[: len(pages)] = pages
    return table.at[slot].set(jnp.asarray(row))


# -- device ops ---------------------------------------------------------------
#
# Every op below takes the STACKED pool ([L, N_pages, page, KV*hd], or a
# QuantPool/Int4Pool of that layout) and a traced layer index. A writer
# scatters its few token rows into `pool[layer]` in place; a reader
# gathers pages `[layer, ids]`. None slices, reshapes or copies a pool.


def _rows(x):
    """[..., KV, hd] token rows -> the pool's flat [..., KV*hd]."""
    return x.reshape(x.shape[:-2] + (-1,))


def gather_layer_pages(pool, layer, idx, kv_heads: int, dtype):
    """Pages `idx` of `layer` as the per-head view [..., P, KV, hd] in
    `dtype`; out-of-range ids read zeros. A quantized pool dequantizes
    page by page on the gather. What the fold reference folds, and what
    the prefix and chunk prefills attend beside their fresh window."""
    if isinstance(pool, (QuantPool, Int4Pool)):
        return dequantize_pages(pool, layer, idx).astype(dtype)
    pages = pool.at[layer, idx].get(mode="fill", fill_value=0)
    return pages.reshape(pages.shape[:-1] + (kv_heads, -1)).astype(dtype)


@jax.named_scope("kv")
def write_prompt_pages(pool_k, pool_v, layer, k, v, table_row,
                       n_real=None):
    """Scatter a prompt window's KV ([1, S, KV, hd]) into the pool pages
    of one slot, in layer `layer` of the stacked pool (callers run this
    inside the layer loop).

    S need not divide the page size: the final partial window is
    zero-padded to a whole page (a bucket smaller than one page is one
    padded window — with the default 128-token pages most prompts
    bucket below a single page, so S < P is the COMMON case, not an
    edge). Padding positions land in their mapped page as garbage and
    are overwritten by decode before they can be attended, exactly like
    dense padding. UNMAPPED pages (id -1) must not be written — page 0
    would alias another slot — so those windows route to the
    out-of-bounds index and drop.

    A QuantPool (int8 KV tiering, cake_tpu/kv) quantizes on scatter:
    page-aligned windows fully overwrite their pages, so each window
    sets its page's per-head scale fresh. n_real (traced scalar, the
    real token count) matters ONLY there: bucket-padding garbage is
    dead data in an f32 pool but would inflate the fresh page scales,
    so the quantized writer zeroes positions >= n_real first."""
    if isinstance(pool_k, (QuantPool, Int4Pool)):
        return (qwrite_prompt_pages(pool_k, layer, k, table_row, n_real),
                qwrite_prompt_pages(pool_v, layer, v, table_row, n_real))
    N, P = pool_k.shape[1], pool_k.shape[2]
    S = k.shape[1]
    n_win = -(-S // P)
    pad = n_win * P - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # one parallel scatter: unmapped windows route to the out-of-bounds
    # index N and mode="drop" skips them (no dummy-page read-back)
    pages = table_row[:n_win]
    idx = jnp.where(pages >= 0, pages, N)
    kw = _rows(k[0]).reshape(n_win, P, -1)
    vw = _rows(v[0]).reshape(n_win, P, -1)
    pk = pool_k.at[layer, idx].set(kw.astype(pool_k.dtype), mode="drop")
    pv = pool_v.at[layer, idx].set(vw.astype(pool_v.dtype), mode="drop")
    return pk, pv


@jax.named_scope("kv")
def write_windows_pages(pool_k, pool_v, layer, k, v, pos, q_len, active,
                        table):
    """Every row scatters its q_len-token window at absolute position
    pos[b] (NOT necessarily page-aligned: each position resolves its
    own (page, offset) pair through the row's table) into its own
    pages of layer `layer`.

    pool_k/v: [L, N_pages, page, KV*hd]; k/v: [B, C, KV, hd]; pos/q_len:
    [B]; active: [B] bool; table: [slots(=B), max_pages]. One
    vectorized scatter covers the whole mixed batch: decode rows write
    their single token (q_len=1), prefill-chunk rows their window, and
    padding columns (i >= q_len), inactive rows, and positions landing
    on unmapped pages all route to the out-of-bounds index N where
    mode="drop" skips them. Distinct rows own distinct pages and a
    row's positions are distinct, so the targets never collide.

    A QuantPool quantizes on scatter via per-row touched-page
    read-modify-writes (kv/quantized_pool.qwrite_windows_pages)."""
    if isinstance(pool_k, (QuantPool, Int4Pool)):
        return (qwrite_windows_pages(pool_k, layer, k, pos, q_len,
                                     active, table),
                qwrite_windows_pages(pool_v, layer, v, pos, q_len,
                                     active, table))
    N, P = pool_k.shape[1], pool_k.shape[2]
    B, C = k.shape[0], k.shape[1]
    max_pages = table.shape[1]
    positions = pos[:, None] + jnp.arange(C)[None, :]         # [B, C]
    pidx = positions // P
    pages = jnp.take_along_axis(
        table, jnp.minimum(pidx, max_pages - 1), axis=1)
    valid = ((jnp.arange(C)[None, :] < q_len[:, None])
             & active[:, None] & (pidx < max_pages) & (pages >= 0))
    idx = jnp.where(valid, pages, N)
    offs = positions % P
    pk = pool_k.at[layer, idx, offs].set(
        _rows(k).astype(pool_k.dtype), mode="drop")
    pv = pool_v.at[layer, idx, offs].set(
        _rows(v).astype(pool_v.dtype), mode="drop")
    return pk, pv


@jax.named_scope("kv")
def update_pool_per_row(pool_k, pool_v, layer, k, v, pos, active, table):
    """Write one decode token per row into its page of layer `layer`.

    pool_k/v: [L, N_pages, page, KV*hd]; k/v: [B, 1, KV, hd]; pos: [B];
    active: [B] bool; table: [slots(=B), max_pages]. One vectorized
    scatter of B token rows (distinct slots own distinct pages, so the
    B targets are disjoint); inactive rows — and rows whose position
    lands on an unmapped page — route to the out-of-bounds index and
    mode="drop" skips them.

    A QuantPool quantizes on scatter: each row's page is gathered,
    its scale grown to cover the new token, residents re-quantized,
    and the page scattered back (kv/quantized_pool)."""
    if isinstance(pool_k, (QuantPool, Int4Pool)):
        return (qupdate_pool_per_row(pool_k, layer, k, pos, active, table),
                qupdate_pool_per_row(pool_v, layer, v, pos, active, table))
    N, P = pool_k.shape[1], pool_k.shape[2]
    B = k.shape[0]
    rows = jnp.arange(B)
    pages = table[rows, pos // P]
    offs = pos % P
    valid = jnp.logical_and(active, pages >= 0)
    idx = jnp.where(valid, pages, N)
    pk = pool_k.at[layer, idx, offs].set(
        _rows(k[:, 0]).astype(pool_k.dtype), mode="drop")
    pv = pool_v.at[layer, idx, offs].set(
        _rows(v[:, 0]).astype(pool_v.dtype), mode="drop")
    return pk, pv


@jax.named_scope("kv")
def write_token_rows(pool, layer, rows, slot, position, valid, table,
                     ring: bool = False):
    """Scatter one cache row a token into layer `layer` of one pool:
    rows [T, W] at (table[slot[t], position[t] // page], position[t] %
    page). slot/position [T] int32; valid [T] bool. Tokens that are not
    valid, and positions past the table or on unmapped pages, route to
    the out-of-bounds index and drop. What the latent pools' writers
    are: a decode step's tokens (slot = the row) and a mixed step's
    packed axis alike. ring: the table is a row's RING of pages
    (WindowedPagedCache.wtable): logical page j lies in entry j mod R."""
    N, P = pool.shape[1], pool.shape[2]
    max_pages = table.shape[1]
    pidx = position // P
    if ring:
        pidx = pidx % max_pages
    pages = table[slot, jnp.minimum(pidx, max_pages - 1)]
    ok = valid & (pidx < max_pages) & (pages >= 0)
    return pool.at[layer, jnp.where(ok, pages, N), position % P].set(
        rows.astype(pool.dtype), mode="drop")


def _fold_pages(q, pool_k, pool_v, layer, table, causal_bound,
                window: Optional[int] = None,
                scale: Optional[float] = None, selected=None):
    """The XLA reference both paged attentions share: a fori_loop over
    all max_pages, every page gathered once from `pool[layer]` and
    folded into running (m, l, o) stats. causal_bound: [B, C] — the
    last absolute slot query (b, i) attends. window: a band (query
    (b, i) attends its last `window` keys, the bound included); trip j
    of a row is then the logical page first + j, first the page of its
    first query's first key, read through table entry (first + j) mod
    max_pages, so that `table` may be a ring (the kernels' rule:
    ops/ragged_paged_attention._mixed_fold). scale: what the scores are
    multiplied by (None: 1/sqrt(hd)). selected [B, max_pages, C, page]
    float32: a query attends a key of a page only where its entry is
    above 0.5 (None: every key the other rules leave)."""
    B, C, H, hd = q.shape
    _, N, P, width = getattr(pool_k, "q", pool_k).shape
    KV = width // hd
    if isinstance(pool_k, Int4Pool):
        P *= 2      # the packed axis stores two tokens per byte
    max_pages = table.shape[1]
    G = H // KV
    m0 = jnp.full((B, KV, G, C, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KV, G, C, 1), jnp.float32)
    o0 = jnp.zeros((B, KV, G, C, hd), jnp.float32)
    if window is not None:
        first = jnp.maximum(causal_bound[:, 0] - (window - 1), 0) // P

    def fold(j, carry):
        m, l, o = carry
        if window is None:
            pages = table[:, j]                      # [B]
        else:
            j = first + j                            # [B] logical pages
            pages = jnp.take_along_axis(
                table, (j % max_pages)[:, None], axis=1)[:, 0]
        # unmapped slots route to the out-of-bounds index N with a zero
        # fill instead of gathering page 0 (which aliases another
        # slot's live data into the masked lanes). Whether the OOB row
        # read is actually elided is up to the XLA gather lowering —
        # the guarantee that dead pages cost NO bandwidth lives in the
        # pallas kernels (the decode kernel starts no copy for them,
        # the mixed kernel's index map clamps), not here; the fold's masking
        # (below) keeps the fill value out of the output either way.
        idx = jnp.where(pages >= 0, pages, N)
        # a quantized pool dequantizes in the loop: int page * its
        # per-head scale, in f32 — the bit-exact reference the int8 and
        # int4 pallas kernels are pinned against
        kj = gather_layer_pages(pool_k, layer, idx, KV, q.dtype)
        vj = gather_layer_pages(pool_v, layer, idx, KV, q.dtype)
        # validity: absolute slot j*P + t attends for query i iff it is
        # <= the query's causal bound (current token included) AND the
        # page is mapped
        if window is None:
            slots_abs = j * P + jnp.arange(P)        # [P]
            valid = slots_abs[None, None, :] <= causal_bound[:, :, None]
        else:
            slots_abs = (j[:, None] * P + jnp.arange(P))[:, None, :]
            valid = ((slots_abs <= causal_bound[:, :, None])
                     & (slots_abs > causal_bound[:, :, None] - window))
        valid &= (pages >= 0)[:, None, None]
        if selected is not None:
            # (no band beside a selection: j is the loop's scalar)
            valid &= lax.dynamic_index_in_dim(selected, j, axis=1,
                                              keepdims=False) > 0.5
        valid = valid[:, None, None, :, :]           # [B,1,1,C,P]
        mj, lj, oj = partial_attention_stats(q, kj, vj, valid, scale=scale)
        m_new = jnp.maximum(m, mj)
        a_old = jnp.exp(m - m_new)
        a_new = jnp.exp(mj - m_new)
        return (m_new, a_old * l + a_new * lj,
                a_old * o + a_new * oj)

    m, l, o = lax.fori_loop(0, max_pages, fold, (m0, l0, o0))
    out = merge_attention_stats([(m, l, o)])
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(
        B, C, H, hd).astype(q.dtype)


def _kernel_pools(pool_k, pool_v):
    """The kernels' pool operands: (k, v, keyword arguments)."""
    if isinstance(pool_k, (QuantPool, Int4Pool)):
        return pool_k.q, pool_v.q, dict(
            scale_k=pool_k.scale, scale_v=pool_v.scale,
            packed4=isinstance(pool_k, Int4Pool))
    return pool_k, pool_v, {}


def paged_attention(q, pool_k, pool_v, layer, table, pos, *,
                    impl: str = "fold", window: Optional[int] = None,
                    scale: Optional[float] = None, selected=None):
    """Ragged decode attention over layer `layer` of the paged KV.

    impl="fold" (the documented REFERENCE semantics): an XLA fori_loop
    over all max_pages — online-softmax accumulation where every page is
    read once and folded into running (m, l, o) stats; no dense per-slot
    copy ever exists. impl="pallas": the TPU-native single kernel
    (ops/ragged_paged_attention.py) — same math, but one grid step a
    row whose loop runs over the row's LIVE pages alone, pos // page + 1
    of them fetched by the kernel's own copies (an idle row takes no
    trip), instead of folding the whole table. The caller picks the impl the
    shapes allow (serve/engine._setup_paged_exec resolves it once); on
    a chip the kernel raises on a shape its gate refuses — nothing here
    falls back.

    q: [B, 1, H, hd] (rope already applied; the current token's KV must
    already be written to its page); pool_k/v: the stacked pool
    [L, N_pages, page, KV*hd]; layer: traced int32 scalar; table:
    [B, max_pages]; pos: [B] (position of the CURRENT token).
    window (static): a row attends its last `window` keys alone, its
    own included, and `table` may be a ring [B, R] (the kernel walks
    the pages of the band; None: every key). scale (static): what the
    scores are multiplied by, for a model that states its own (None:
    1/sqrt(hd)). selected [B, max_pages, page] float32: the keys a row
    attends among those up to its pos, by page, above 0.5 where it does
    (a sparse indexer's set; None, and no operand of the kernel, for
    every model without one).
    Returns [B, 1, H, hd].
    """
    if impl == "pallas":
        from cake_tpu.ops.ragged_paged_attention import (
            ragged_paged_attention,
        )
        kq, vq, kw = _kernel_pools(pool_k, pool_v)
        if selected is not None:
            kw["selected"] = selected
        return ragged_paged_attention(q, kq, vq, layer, table, pos,
                                      window=window, scale=scale, **kw)
    if impl != "fold":
        raise ValueError(f"unknown paged_attn impl {impl!r}")
    if selected is not None:
        if window is not None:
            raise ValueError("a selection is served without a band only")
        selected = selected[:, :, None, :]
    return _fold_pages(q, pool_k, pool_v, layer, table, pos[:, None],
                       window, scale, selected)


@_partial(jax.jit,
          static_argnames=("packed4", "interpret", "window", "scale"))
def _mixed_kernel(q, pool_k, pool_v, layer, table, pos, q_len, *,
                  interpret: bool, scale_k=None, scale_v=None,
                  packed4: bool = False, window: Optional[int] = None,
                  scale: Optional[float] = None, selected=None):
    """The mixed kernel behind a jit of its own. The mixed step's
    programs of every packed size call it on the same window shapes,
    and a jitted callee is traced once for all of them: the kernel's
    body is a third of what a program costs the host to trace
    (PERF.md §6, PR 27). `interpret` is resolved by the caller, so
    that it is part of the trace's key."""
    from cake_tpu.ops.ragged_paged_attention import (
        ragged_paged_attention_mixed,
    )
    return ragged_paged_attention_mixed(
        q, pool_k, pool_v, layer, table, pos, q_len, scale_k=scale_k,
        scale_v=scale_v, packed4=packed4, window=window, scale=scale,
        selected=selected, interpret=interpret)


def paged_attention_mixed(q, pool_k, pool_v, layer, table, pos, q_len, *,
                          impl: str = "fold",
                          window: Optional[int] = None,
                          scale: Optional[float] = None, selected=None):
    """Mixed ragged attention over layer `layer` of the paged KV: decode
    rows (q_len=1) and prefill-chunk rows (q_len=C at arbitrary page
    offset) in ONE batch.

    impl="fold" (the bit-exact REFERENCE semantics, exactly as the fold
    is for decode): an XLA fori_loop over all max_pages — per-query
    online-softmax accumulation where every page is read once; no dense
    per-slot copy ever exists. impl="pallas": the mixed TPU kernel
    (ops/ragged_paged_attention.ragged_paged_attention_mixed) — same
    math, but each row streams only the pages up to
    ceil((pos + q_len)/page). As for decode, the caller resolves the
    impl from the shapes (chunk widths whose C-scaled scratch overflows
    VMEM take the fold); the kernel raises rather than fall back.

    q: [B, C, H, hd] (rope applied; every real query token's KV already
    written to its page); pos: [B] position of each row's FIRST query;
    q_len: [B] real query tokens (0 = idle row). Columns past q_len are
    padding whose output the caller never reads. window (static):
    query i attends its last `window` keys alone, and `table` may be a
    ring; scale (static): the scores' multiplier (both as
    paged_attention's). selected [B, max_pages, C, page] float32: the
    keys a query attends among those it may see, by page, above 0.5
    where it does (a sparse indexer's sets; None, and no operand of the
    kernel, for every model without one). Returns [B, C, H, hd].
    """
    if impl == "pallas":
        from cake_tpu.ops import ragged_paged_attention as rpa
        kq, vq, kw = _kernel_pools(pool_k, pool_v)
        if selected is not None:
            kw["selected"] = selected
        return _mixed_kernel(q, kq, vq, layer, table, pos, q_len,
                             interpret=not rpa._on_tpu(), window=window,
                             scale=scale, **kw)
    if impl != "fold":
        raise ValueError(f"unknown paged_attn impl {impl!r}")
    if selected is not None and window is not None:
        raise ValueError("a selection is served without a band only")
    # per-query causality: query i of row b sits at pos[b] + i
    C = q.shape[1]
    return _fold_pages(q, pool_k, pool_v, layer, table,
                       pos[:, None] + jnp.arange(C)[None, :], window, scale,
                       selected)


# -- model-level steps (engine step-fn signatures) ----------------------------


def scan_layers_paged_stats(blocks, x, cache: PagedKVCache,
                            config: LlamaConfig, layer_attn,
                            token_mask=None):
    """The layer loop of every paged step program.

    The stacked pool travels as loop CARRY beside the hidden state and
    the layer index; only `blocks` are scanned. A scan's stacked outputs
    cannot alias its inputs, so a pool passed as xs/ys is sliced per
    layer, stacked back and kept twice; a carried pool that each layer
    scatters its token rows into is one buffer from the donated input
    to the output. Whatever pytree the pool is (plain arrays, QuantPool,
    Int4Pool) rides along unchanged. A sparse model's expert weights
    stay out of the scan for the same reason: the grouped matmul is a
    custom call, a scanned operand of which would be sliced into a copy
    every layer, so they reach the block as (stack, layer) and the
    kernel indexes the stack (ops/moe.LayerOf).

    layer_attn(layer, pool_k, pool_v, q, k, v) -> (attn [B,S,H,hd],
    pool_k, pool_v): write this layer's new KV, attend.
    token_mask: [B, S] bool, the positions that hold a real token (the
    expert FFN routes no other); None = all.
    Returns (x, cache, the expert layers' ops/moe.MoEStats with a
    leading layer axis, or None for a dense model)."""
    from cake_tpu.models.llama.model import block_skeleton_stats
    from cake_tpu.ops.moe import EXPERT_LEAVES, LayerOf

    stacked = {k: blocks[k] for k in EXPERT_LEAVES if k in blocks}
    scanned = {k: v for k, v in blocks.items() if k not in stacked}

    def body(carry, lp):
        h, layer, pk, pv = carry

        def attn_fn(q, k, v):
            out, pk2, pv2 = layer_attn(layer, pk, pv, q, k, v)
            return out, (pk2, pv2)

        lp = dict(lp, **{k: LayerOf(v, layer) for k, v in stacked.items()})
        h, (pk, pv), stats = block_skeleton_stats(
            lp, h, config, attn_fn, token_mask=token_mask)
        return (h, layer + 1, pk, pv), stats

    with jax.named_scope("layers"):
        (x, _, pool_k, pool_v), stats = lax.scan(
            body, (x, jnp.int32(0), cache.k, cache.v), scanned)
    return x, cache._replace(k=pool_k, v=pool_v), stats


def scan_layers_paged(blocks, x, cache: PagedKVCache,
                      config: LlamaConfig, layer_attn, n_real=None):
    """scan_layers_paged_stats for the prefill programs, which keep no
    counters. n_real: [B] real tokens of each right-padded window."""
    mask = None
    if n_real is not None:
        mask = jnp.arange(x.shape[1])[None, :] < n_real[:, None]
    x, cache, _ = scan_layers_paged_stats(blocks, x, cache, config,
                                          layer_attn, token_mask=mask)
    return x, cache


def run_blocks_ragged_paged(blocks, x, cache: PagedKVCache, pos, active,
                            rope_c, rope_s, config: LlamaConfig,
                            attn: str = "fold"):
    """run_blocks_ragged over the page pool: write the token, attend the
    pages. x: [B, 1, D]; pos/active: [B]; attn: paged_attention impl
    ({fold,pallas} — static under jit). Returns (x, cache, expert
    counters or None); inactive rows are not routed."""
    from cake_tpu.ops.rope import apply_rope

    def layer_attn(layer, pk, pv, q, k, v):
        q = apply_rope(q, rope_c, rope_s)
        k = apply_rope(k, rope_c, rope_s)
        pk, pv = update_pool_per_row(pk, pv, layer, k, v, pos, active,
                                     cache.table)
        return (paged_attention(q, pk, pv, layer, cache.table, pos,
                                impl=attn), pk, pv)

    return scan_layers_paged_stats(blocks, x, cache, config, layer_attn,
                                   token_mask=active[:, None])


def forward_ragged_paged(params, tokens, cache: PagedKVCache, pos,
                         active, rope, config: LlamaConfig,
                         attn: str = "fold", counters: bool = False):
    """model.forward_ragged's signature over a paged cache — un-jitted,
    so step_programs.make_decode_scan can build the sampled paged decode
    programs from it (one step in flight, or a K-step scan, exactly
    like dense). counters=True returns what a step program returns
    (_step_result: a sparse model's expert counters third)."""
    out = _step_result(*_forward_ragged_paged(
        params, tokens, cache, pos, active, rope, config, attn))
    return out if counters else out[:2]


def _forward_ragged_paged(params, tokens, cache: PagedKVCache, pos,
                          active, rope, config: LlamaConfig, attn: str):
    """(logits, cache, expert counters or None) of one ragged decode."""
    from cake_tpu.models.llama.model import rope_rows_per_row
    from cake_tpu.ops.norms import rms_norm
    from cake_tpu.ops.quant import qmatmul

    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    rope_c, rope_s = rope_rows_per_row(rope.cos, rope.sin, pos)
    x, cache, stats = run_blocks_ragged_paged(
        params["blocks"], x, cache, pos, active, rope_c, rope_s, config,
        attn=attn)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
        logits = qmatmul(x[:, -1], params["lm_head"]).astype(jnp.float32)
    return logits, cache, stats


# the record keys of a sparse model's counter vector, in its order
MOE_COUNTERS = ("moe_rows", "moe_rows_padded", "moe_load_max",
                "moe_load_mean", "moe_experts_touched")


def _step_result(logits, cache, stats):
    """What a step program returns: (logits, cache), and for a sparse
    model a third, its expert counters [5] in the order of MOE_COUNTERS
    (the engine fetches them with the sampled tokens): rows, padded
    rows and experts touched summed over the layers, the busiest and
    the average expert's tokens as the mean over the layers."""
    if stats is None:
        return logits, cache
    return logits, cache, jnp.stack([
        jnp.sum(stats.rows), jnp.sum(stats.rows_padded),
        jnp.mean(stats.load_max), jnp.mean(stats.load_mean),
        jnp.sum(stats.touched)])


@_partial(jax.jit, static_argnames=("config", "attn"),
          donate_argnames=("cache",))
def decode_step_ragged_paged(params, tokens, pos, active,
                             cache: PagedKVCache, rope,
                             config: LlamaConfig, attn: str = "fold"):
    """decode_step_ragged signature over a paged cache — the engine's
    drop-in decode step fn for --kv-pages serving. attn selects the
    paged_attention impl ({fold,pallas}); static, so both variants are
    separately compiled programs with the same traced signature."""
    return _step_result(*_forward_ragged_paged(
        params, tokens, cache, pos, active, rope, config, attn))


@_partial(jax.jit, static_argnames=("config", "attn"),
          donate_argnames=("cache",))
def prefill_slot_paged(params, tokens, prompt_len, slot,
                       cache: PagedKVCache, rope, config: LlamaConfig,
                       attn: str = "fold"):
    """prefill_slot signature over a paged cache: ordinary causal
    prefill math on the fresh window (the window starts at position 0
    and covers the whole prompt, so no cache reads are needed), with
    each layer's KV scattered into the slot's pages. Padding positions
    land in their mapped page as garbage and are overwritten by decode
    before they can be attended — the dense path's exact semantics.
    Windows beyond the slot's mapped pages (bucket padding past the
    allocation) are dropped by the -1 guard in write_prompt_pages.

    attn="pallas" routes the fresh-window attention through the Pallas
    flash kernel (the prompt window starts at position 0, so causal
    flash over the in-window k/v is exact — no page reads are needed at
    prefill); untileable shapes fall back to the einsum path like the
    dense prefill."""
    from cake_tpu.ops.attention import causal_mask, gqa_attention
    from cake_tpu.ops.flash_attention import (
        flash_attention, flash_supported,
    )
    from cake_tpu.ops.norms import rms_norm
    from cake_tpu.ops.quant import qmatmul
    from cake_tpu.ops.rope import apply_rope, rope_rows

    B, S = tokens.shape
    H = config.num_attention_heads
    KV = config.num_key_value_heads
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    rope_c, rope_s = rope_rows(rope.cos, rope.sin, jnp.int32(0), S)
    table_row = jnp.take(cache.table, slot, axis=0)
    use_flash = (attn == "pallas"
                 and flash_supported(S, S, H, KV, hd=config.head_dim))
    mask = None if use_flash else causal_mask(S)

    def layer_attn(layer, pk, pv, q, k, v):
        q = apply_rope(q, rope_c, rope_s)
        k = apply_rope(k, rope_c, rope_s)
        pk, pv = write_prompt_pages(pk, pv, layer, k, v, table_row,
                                    prompt_len[0])
        if use_flash:
            return flash_attention(q, k, v, causal=True), pk, pv
        return gqa_attention(q, k, v, mask=mask), pk, pv

    x, cache = scan_layers_paged(params["blocks"], x, cache, config,
                                 layer_attn, n_real=prompt_len)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
        last = jnp.take_along_axis(
            x, (prompt_len - 1).reshape(B, 1, 1).astype(jnp.int32), axis=1
        )[:, 0]
        logits = qmatmul(last, params["lm_head"]).astype(jnp.float32)
    return logits, cache


# -- prefix sharing (page-granular) --------------------------------------------


@_partial(jax.jit, static_argnames=("config", "attn"),
          donate_argnames=("cache",))
def prefill_prefix_pages(params, tokens, table_row,
                         cache: PagedKVCache, rope, config: LlamaConfig,
                         attn: str = "fold"):
    """Prefill a registered prefix ONCE into dedicated pool pages.

    tokens: [1, S] with S the page-ALIGNED prefix length (the engine
    rounds registrations down to a page boundary; remainder ids join
    each request's suffix); table_row: [max_pages] int32 mapping the
    prefix's dedicated pages (no engine slot involved — the row is a
    standalone mapping, later copied into every matching slot's table
    row head). Ordinary causal prefill at position 0 with each layer's
    KV scattered into the mapped pages; logits are discarded (a
    registered prefix is always a proper head, so the next token comes
    from the suffix prefill). attn="pallas" routes the fresh-window
    attention through the Pallas flash kernel like prefill_slot_paged.
    Returns the updated cache."""
    from cake_tpu.ops.attention import causal_mask, gqa_attention
    from cake_tpu.ops.flash_attention import (
        flash_attention, flash_supported,
    )
    from cake_tpu.ops.rope import apply_rope, rope_rows

    B, S = tokens.shape
    H = config.num_attention_heads
    KV = config.num_key_value_heads
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    rope_c, rope_s = rope_rows(rope.cos, rope.sin, jnp.int32(0), S)
    use_flash = (attn == "pallas"
                 and flash_supported(S, S, H, KV, hd=config.head_dim))
    mask = None if use_flash else causal_mask(S)

    def layer_attn(layer, pk, pv, q, k, v):
        q = apply_rope(q, rope_c, rope_s)
        k = apply_rope(k, rope_c, rope_s)
        pk, pv = write_prompt_pages(pk, pv, layer, k, v, table_row)
        if use_flash:
            return flash_attention(q, k, v, causal=True), pk, pv
        return gqa_attention(q, k, v, mask=mask), pk, pv

    # final norm / lm_head skipped on purpose: only the KV matters here
    _, cache = scan_layers_paged(params["blocks"], x, cache, config,
                                 layer_attn)
    return cache


# -- token-level continuous batching: the mixed ragged step -------------------


class PackPlan(NamedTuple):
    """Where a mixed step's real tokens sit on the packed token axis
    [T]: rows in slot order, a row's q_len tokens contiguous from
    start[b]. Derived inside the program from q_len and active alone.

    start [B]: a row's first packed index (an idle row's is its
    successor's); row/col [T]: the window cell (b, i) a packed position
    holds; real [T]: positions below the step's real count (the
    bucket's padding past it repeats one cell of the last row and is
    never written, routed or read); width: the windows' static C."""

    start: jnp.ndarray
    row: jnp.ndarray
    col: jnp.ndarray
    real: jnp.ndarray
    width: int


def mixed_token_buckets(slots: int, width: int,
                        prefill_rows: tuple = (1, 2)) -> tuple:
    """The static sizes T of the packed mixed step for an engine of
    `slots` rows and `width`-token windows, ascending: what one
    prefilling row and what two need beside decode rows in every other
    slot (p*width + slots - p tokens for p in `prefill_rows`, rounded up
    to 16 positions; latent attention takes one window a dispatch:
    models/moe/glm_dsa.py). The
    last is the most one dispatch computes: a step that holds more is
    run in several (serve/engine._mixed_burst), two prefilling rows
    at a time.

    Why two sizes and not a ladder up to slots*width. Under chat
    traffic two steps in three have one prefilling row and one in four
    has two; more come only when every client starts at once. And each
    further size is a program whose matmuls and reductions XLA:TPU
    tiles by its shape: a row's logits then depend on the company its
    step had, by enough to swap a sparse model's experts, while these
    two sizes gave every row the same bits (PERF.md §6, PR 27). Each
    size also costs the start-up a trace and a load."""
    full = slots * width
    return tuple(sorted({min(full, -(-(p * width + slots - p) // 16) * 16)
                         for p in prefill_rows}))


def mixed_bucket_for(buckets: tuple, n_real: int) -> int:
    """The `n_tokens` one dispatch of n_real <= buckets[-1] tokens runs
    at: the smallest size that holds them."""
    return next(t for t in buckets if n_real <= t)


def pack_plan(q_len, active, n_tokens: int, width: int) -> PackPlan:
    """The PackPlan of a step on an axis of n_tokens positions; q_len,
    active: [B] (an inactive row holds no token whatever its q_len)."""
    B = q_len.shape[0]
    n = jnp.where(active, q_len, 0).astype(jnp.int32)
    end = jnp.cumsum(n)
    start = end - n
    t = jnp.arange(n_tokens, dtype=jnp.int32)
    row = jnp.minimum(
        jnp.sum(t[:, None] >= end[None, :], axis=1, dtype=jnp.int32), B - 1)
    real = t < end[-1]
    return PackPlan(start, row, jnp.where(real, t - start[row], 0), real,
                    width)


def _unpack_windows(x, plan: PackPlan):
    """Packed [T, ...] -> windows [B, C, ...]: one contiguous slice a
    row, x padded by a window so that no slice clamps. The columns past
    a row's q_len hold its neighbours' tokens, which the consumer masks
    as it masks a window's padding."""
    x = jnp.pad(x, ((0, plan.width),) + ((0, 0),) * (x.ndim - 1))
    return jnp.stack([
        lax.dynamic_slice_in_dim(x, plan.start[b], plan.width, axis=0)
        for b in range(plan.start.shape[0])])


@jax.named_scope("kv")
def write_packed_pages(pool_k, pool_v, layer, k, v, plan: PackPlan, pos,
                       q_len, active, table):
    """write_windows_pages from the packed axis. k/v: [T, KV, hd]. T
    row writes a layer: packed position t lands at (table[row, p // P],
    p % P) with p = pos[row] + col; the bucket's padding and positions
    on unmapped pages route to the out-of-bounds index and drop.

    A QuantPool's writer read-modify-writes whole pages a row, so it
    takes the windows: k and v are unpacked as q is."""
    if isinstance(pool_k, (QuantPool, Int4Pool)):
        return write_windows_pages(
            pool_k, pool_v, layer, _unpack_windows(k, plan),
            _unpack_windows(v, plan), pos, q_len, active, table)
    N, P = pool_k.shape[1], pool_k.shape[2]
    max_pages = table.shape[1]
    positions = pos[plan.row] + plan.col
    pidx = positions // P
    pages = table[plan.row, jnp.minimum(pidx, max_pages - 1)]
    valid = plan.real & (pidx < max_pages) & (pages >= 0)
    idx = jnp.where(valid, pages, N)
    offs = positions % P
    pk = pool_k.at[layer, idx, offs].set(
        _rows(k).astype(pool_k.dtype), mode="drop")
    pv = pool_v.at[layer, idx, offs].set(
        _rows(v).astype(pool_v.dtype), mode="drop")
    return pk, pv


def run_blocks_mixed_paged(blocks, x, cache: PagedKVCache, pos, q_len,
                           active, rope_c, rope_s, config: LlamaConfig,
                           attn: str = "fold",
                           plan: Optional[PackPlan] = None):
    """run_blocks over a MIXED batch of per-row windows: write each
    row's window into its pages, attend everything written through the
    table. pos/q_len/active: [B]; attn: paged_attention_mixed impl
    ({fold,pallas} — static under jit).

    plan None: x [B, C, D], rope_c/rope_s [B, C, hd//2] per-row
    per-column tables. With a plan every layer runs over the packed
    axis, x [1, T, D] and rope rows [1, T, hd//2]: K/V are written from
    the packed rows, and q alone is unpacked to the windows the kernel
    takes, [B, C, H, hd], and its result gathered back (T rows)."""
    from cake_tpu.ops.rope import apply_rope

    def layer_attn(layer, pk, pv, q, k, v):
        q = apply_rope(q, rope_c, rope_s)
        k = apply_rope(k, rope_c, rope_s)
        if plan is None:
            pk, pv = write_windows_pages(pk, pv, layer, k, v, pos, q_len,
                                         active, cache.table)
            return (paged_attention_mixed(q, pk, pv, layer, cache.table,
                                          pos, q_len, impl=attn), pk, pv)
        pk, pv = write_packed_pages(pk, pv, layer, k[0], v[0], plan, pos,
                                    q_len, active, cache.table)
        out = paged_attention_mixed(
            _unpack_windows(q[0], plan), pk, pv, layer, cache.table, pos,
            q_len, impl=attn)
        out = jnp.take(out.reshape((-1,) + out.shape[2:]),
                       plan.row * plan.width + plan.col, axis=0)
        return out[None], pk, pv

    # a window's padding, idle rows and a bucket's padding hold no
    # token: not routed
    if plan is None:
        C = x.shape[1]
        real = (jnp.arange(C)[None, :] < q_len[:, None]) & active[:, None]
    else:
        real = plan.real[None, :]
    return scan_layers_paged_stats(blocks, x, cache, config, layer_attn,
                                   token_mask=real)


def _mixed_windows_trunk(params, tokens, pos, q_len, active,
                         cache: PagedKVCache, rope,
                         config: LlamaConfig, attn: str,
                         plan: Optional[PackPlan] = None):
    """Shared body of the mixed ragged step: embed, per-token rope,
    run_blocks_mixed_paged, final norm. mixed_step_paged reads one
    position a row from the normed hidden states, the speculative
    verify (verify_window_paged) reads all of them — the window math
    exists once so the two callers cannot drift. Returns the hidden
    states as the layers ran: [B, C, D], or [1, T, D] under a plan."""
    from cake_tpu.ops.norms import rms_norm

    C = tokens.shape[1]
    # query i of row b sits at absolute position pos[b] + i (clamped
    # into the table for padding — its values are garbage nothing reads)
    if plan is None:
        pos_grid = pos[:, None] + jnp.arange(C)[None, :]
    else:
        tokens = tokens[plan.row, plan.col][None]
        pos_grid = (pos[plan.row] + plan.col)[None]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    pos_grid = jnp.minimum(pos_grid, rope.cos.shape[0] - 1)
    rope_c = jnp.take(rope.cos, pos_grid, axis=0)   # [B, C | 1, T, hd//2]
    rope_s = jnp.take(rope.sin, pos_grid, axis=0)
    x, cache, stats = run_blocks_mixed_paged(
        params["blocks"], x, cache, pos, q_len, active, rope_c, rope_s,
        config, attn=attn, plan=plan)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
    return x, cache, stats


@_partial(jax.jit, static_argnames=("config", "attn", "n_tokens"),
          donate_argnames=("cache",))
def mixed_step_paged(params, tokens, pos, q_len, active,
                     cache: PagedKVCache, rope, config: LlamaConfig,
                     attn: str = "fold", n_tokens: Optional[int] = None):
    """ONE jitted step over a mixed batch of row descriptors — the
    token-level continuous-batching step, prompts and decode rows
    behind a single dispatch seam:

      * a DECODE row carries (pos = current token position, q_len = 1,
        tokens[:, 0] = last sampled token) — exactly the ragged decode
        semantics (write the token, attend the pages);
      * a PREFILL-CHUNK row carries (pos = window start, q_len = real
        window tokens, tokens[:, :q_len] = the window) at any page
        offset, a shared-prefix head included: the window's KV is
        written first, then it attends every position written through
        the table, causally (kj <= pos + qi);
      * an IDLE row carries (q_len = 0, active = False) and touches
        neither its pages nor the output the caller reads.

    tokens: [B, C] int32 right-padded windows; pos/q_len: [B] int32;
    active: [B] bool. Returns ([B, vocab] logits of each row's LAST
    real token, cache) — decode rows sample their next token from it,
    a prefill row whose window ends its prompt samples its FIRST token,
    and mid-prompt rows' logits are simply not consumed. attn selects
    the paged_attention_mixed impl ({fold,pallas}); fold is the
    bit-exact reference for the mixed step exactly as it is for decode.

    n_tokens (static): None runs every layer over all B*C window
    positions. A size T >= the step's real token count (the caller's
    to guarantee: sum of q_len over the active rows) packs those tokens
    out of their windows first and runs embed, norms, projections, FFN
    and KV writes over [1, T, D]; the attention kernel still takes q as
    [B, C, H, hd] windows (run_blocks_mixed_paged). Same tokens, same
    mathematics; how XLA tiles a matmul or a reduction, and so how it
    rounds, may differ with T (mixed_token_buckets says what followed).
    """
    from cake_tpu.ops.quant import qmatmul

    B, C = tokens.shape
    plan = (None if n_tokens is None
            else pack_plan(q_len, active, n_tokens, C))
    x, cache, stats = _mixed_windows_trunk(params, tokens, pos, q_len,
                                           active, cache, rope, config,
                                           attn, plan)
    with jax.named_scope("head"):
        last = (jnp.maximum(q_len, 1) - 1).astype(jnp.int32)
        if plan is None:
            last = jnp.take_along_axis(x, last.reshape(B, 1, 1),
                                       axis=1)[:, 0]
        else:
            last = jnp.take(
                x[0], jnp.minimum(plan.start + last, n_tokens - 1), axis=0)
        logits = qmatmul(last, params["lm_head"]).astype(jnp.float32)
    return _step_result(logits, cache, stats)


def verify_window_paged(params, tokens, pos, q_len, active,
                        cache: PagedKVCache, rope,
                        config: LlamaConfig, attn: str = "fold"):
    """The speculative VERIFY pass over paged KV: the mixed ragged
    step's exact window math (same trunk — write each row's window
    into its pages, attend everything mapped through the table) but
    with logits at EVERY window position [B, C, V], so the target
    scores a row's whole [last_tok, d_0..d_{gamma-1}] burst in one
    launch. A spec row carries (pos = round frontier, q_len = gamma+1);
    an inactive row carries q_len = 0 and touches nothing. Un-jitted:
    the paged spec round (cake_tpu/spec/round.py) calls it inside its
    own jit."""
    from cake_tpu.ops.quant import qmatmul

    x, cache, _ = _mixed_windows_trunk(params, tokens, pos, q_len, active,
                                       cache, rope, config, attn)
    with jax.named_scope("head"):
        logits = qmatmul(x, params["lm_head"]).astype(jnp.float32)
    return logits, cache


# -- what the engine reads of this family (models/family.py) ----------------


def _create_pool(config: LlamaConfig, slots: int, n_pages: int,
                 page_size: int, max_seq_len: int, width, dtype):
    """A token's keys and values, KV*hd wide, in every layer."""
    shape = (config.num_hidden_layers, n_pages, page_size,
             config.num_key_value_heads * config.head_dim)
    return PagedKVCache.zeros(shape, shape, slots,
                              max_seq_len // page_size, dtype)


# GQA rows in the pool: every option the engine has moves them, so the
# table of what they cannot move is empty
FAMILY = Family(
    name="llama", decode_step=decode_step_ragged_paged,
    decode_programs=make_decode_scan(
        _partial(forward_ragged_paged, counters=True)),
    mixed_step=mixed_step_paged,
    mixed_sampled=make_mixed_sampled(mixed_step_paged),
    create_cache=_create_pool)
# ... and with sparse experts where the FFN was: the same programs,
# which then return the expert counters
SPARSE = dataclasses.replace(FAMILY, name="mixtral", counters=MOE_COUNTERS)
