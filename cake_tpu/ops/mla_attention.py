"""Latent attention (MLA) over SELECTED cache rows or over every live
page of a row, and the sparse indexer's score pass: the pieces of
attention a latent layer runs (models/moe/glm_dsa.py; the equations are
in models/reference/glm_moe_dsa.py and deepseek_v2.py).

`attend_selected`. Every head of a token attends the same list of cache
rows (the indexer chose it), and a cache row is one latent: the normed
c_kv [R] and the rotated shared key [dr]. With the key up-projection
absorbed into the query (q_lat = q_nope W_kvb^K, [H, R]) a head's score
against a row is one dot over R + dr numbers, and its value is the
row's first R numbers (up-projected after the weighted sum): multi-query
attention over the latent, H heads sharing each row. The rows arrive
GATHERED, [T, K, R + dr] (the caller's XLA gather out of the page pool:
a per-row DMA from inside a kernel costs more than the row), sorted
best first, so a token's valid rows are its first n_valid.

  * impl "fold": the reference semantics in XLA, float32 softmax.
  * impl "pallas": `cake_mla_attn`, grid (T,): one token's [H, R + dr]
    queries against its [K, R + dr] rows in VMEM, scores, the masked
    softmax and the weighted sum in one pass (K = index_topk rows fit:
    2048 x 576 bf16 is 2.4 MB), so the gathered rows are read once.

`attend_window`. A window's queries share their row's keys, and between
them select most of what is visible, so the window attends its row's
pages WHERE THEY LIE, densely, every key of whole pages up to the
window's last position (`cake_mla_window_attn`): gathering 528 x 2,048
rows cost 25 ms a layer, a dense pass over 12k keys 3 (my chip run,
PR 30). The selection arrives as a mask (`select_window`, below).

  * grid (C // tq,): one step a TILE of tq tokens x H heads of query
    rows (`window_tiles`: 8 x 128 or 16 x 64 = 1,024 rows of a 640-wide
    row), resident with its float32 accumulator while the kernel walks
    the row's live pages 0 .. last_pos // page itself;
  * the walk: the pool lies whole in HBM and the kernel's own copies
    fetch a BLOCK of B pages (4; 2 where blocks of 4 would pad a short
    table: a 9-page ring) into one of two VMEM slots, block k + 1 in
    flight while block k folds, on into the next tile's first block.
    Every tile walks the same pages, so the cursor is arithmetic on
    (tile, block) and the kernel keeps a walk of its own:
    rpa.walk_live_pages (which has folded blocks of pages side by side
    in one slot since PR 62, `block=`) carries a row-to-row cursor in
    SMEM over a [rows, pages] table, where this grid's steps are tiles
    of ONE row's table;
  * a fold: scores [rows, B * page] in one product, ONE max / exp / sum
    and ONE alpha * acc + p . V (contracted over the B * page keys), so
    the accumulator, four times as wide as a page's scores, is read and
    written once a block; m and l are the loop's carries;
  * the mask arrives one of two ways, told apart by what the caller
    passes: a bias array [C, S] float32 (0 on a key the query attends,
    -1e30 elsewhere: a selection, a band), of which a tile holds its tq
    whole rows, or no array and the window's positions (causality
    alone: a [1, keys] compare against iota a token). Either way a
    token's row of the mask reaches its H rows of the scores by a
    sublane broadcast (`_spread`), not through the MXU. A page past the
    last live one or an unmapped entry is masked whatever the caller
    says.

Alone on the chip at the cells' shapes (my chip run, PR 46; PERF.md
section 6): 8.86 -> 3.65 ms at 32 pages of 128 heads, 12.8 -> 5.4 at 96
pages of 64 heads, the 9-page ring of 1,152-wide rows 1.36 -> 1.20.

`attend_pages`. A layer with NO indexer (deepseek_v2, Ling's two MLA
layers) attends every visible key, so a row's single token walks the
row's live pages where they lie (`cake_mla_decode_attn`:
ragged_paged_attention.walk_live_pages, the GQA decode kernel's walk,
over the one latent pool, all heads sharing a page): nothing is
gathered, and a call costs what its live pages cost. A trip of the walk
is a BLOCK of F pages side by side in one ring slot
(`walk_live_pages(block=F)`, as cake_mixed_attn's; F = `decode_block`
from the call's shapes, 4 at both cells') and ONE softmax update:
scores [H, F * page] in one product, one max / exp / sum, one pass over
the [H, r] float32 accumulator. Alone on the chip (my chip run, PR 64;
tools/mla_decode_attn_bench.py, PERF.md section 6): 32 rows at ~4.1k
keys 667 -> 358 us a call at 128 heads (0.64 -> 0.35 us a page), 499 ->
254 at 32 heads.

`index_scores_rows`. I[b, s] = sum_j w[b, j] relu(qI[b, j] . kI[b, s])
for one query a row against that row's whole key range, float32: the
decode rows' pass (XLA). `index_scores_window` is the same for a window
of C queries against ONE row's keys, by ONE kernel (`cake_dsa_index`;
what the step programs call, under the scope `indexer`):

  * grid (C // tq, S // kb) (`index_tiles`: 512 x 512 at the cells'
    shapes): a tile's queries, every head of them, and their weights
    stay in VMEM while the key axis runs; a block of kb keys is
    streamed; a head at a time, [tq, d] x [d, kb] on the matrix unit
    into float32, then relu, the head's weight and the sum over heads
    on the vector unit, and the tile is stored where it belongs in
    [C, S]: no [heads, C, block] intermediate, no stack of blocks and
    no transpose in HBM;
  * bounded by the window's last position: a key block that starts
    past it is not fetched (its index map names the last live block
    again) and not computed, and its tile is written zeros, which
    nobody may select (`index_scored` counts the keys of the blocks
    visited).

The blocked `lax.map` it replaced (a `lax.cond` a block, the blocks
stacked [blocks, C, block] and transposed: four XLA ops, 0.44 s of a
3 s capture in Keye's cell, ledger PR 67) lives in
tests/test_dsa_index_kernel.py and tools/dsa_index_bench.py alone.
Alone on the chip at a window of 512 (my chip run, PR 68; that tool):
Keye's 16 heads of 64 over 33,280 keys at a context of 8k 519 -> 159
us, 32k 920 -> 417; GLM's 32 of 128 over 12,800 at 8k 425 -> 234;
dots3's 64 of 128 over 16,896 at 16k 1,052 -> 775. The matrix unit
passes 8 rows of a 128-wide tile in 8 cycles whatever the contraction's
depth, so a live step of 512 x 512 costs heads x 512 passes (Keye 5.5
us by count, 6.5 read; its 64-deep heads fill half of each pass), and a
step past the bound the 1 MB of zeros it writes, 1.0 us.

`select_window`. The window's top-k as a [C, S] mask, exact, ties at
the k-th value to the lower index, by ONE kernel (`cake_dsa_select`;
what the step programs call, under the scope `index_topk`):

  * grid (C // tq,): a tile of tq queries (`select_tiles`: 128 at the
    cells' tables) whose scores, as `_sortable`'s codes, stay in VMEM
    through the 32 counting passes that find the k-th largest code bit
    by bit, one pass for the keys above it, and the tie pass;
  * bounded by the window's last position: the kernel's own copies
    bring the blocks of keys that start at or before it and no other;
    the rest of the mask is written False. Visibility is a compare of
    the key's index with the query's position: no [C, S] operand;
  * the ties: a count carried over the blocks in index order and, inside
    a chunk of 128 keys, the prefix as a product with a triangle of
    ones on the matrix unit: no running count over the table's width.

`select_mask` is the plain form in XLA (a `fori_loop` of 32 full-width
counting passes and a `cumsum` over [C, S]): the tests' other side and
the kernel's statement; no step program calls it. Alone on the chip at
a window of 512 (my chip run, PR 61; tools/dsa_select_bench.py): Keye's
table of 33,280 at a context of 8k 3.42 -> 0.22 ms, 32k 3.41 -> 0.62;
GLM's 12,800 at 8k 0.57 -> 0.21; dots3's 16,896 at 16k 1.38 -> 0.34
(~0.1 ms of each the full-width mask around the call; inside Keye's
mixed program a call reads 0.14 ms).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops import ragged_paged_attention as rpa

NEG_INF = -1e30


def _attend_fold(q, kv, n_valid, r: int, scale: float):
    K = kv.shape[1]
    s = jnp.einsum("thw,tkw->thk", q, kv,
                   preferred_element_type=jnp.float32) * scale
    ok = jnp.arange(K)[None, :] < n_valid[:, None]
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("thk,tkr->thr", p.astype(kv.dtype), kv[..., :r],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _attend_kernel(n_ref, q_ref, kv_ref, o_ref, *, r: int, scale: float):
    t = pl.program_id(0)
    q = q_ref[...]
    kv = kv_ref[...]
    s = rpa._dot(q, kv, trans_b=True) * scale              # [H, K] f32
    col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < n_ref[t], s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = rpa._dot(p.astype(kv.dtype), kv[:, :r], trans_b=False)
    o_ref[...] = (o / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("r", "scale", "interpret", "scope"))
def _attend_pallas(q, kv, n_valid, *, r: int, scale: float,
                   interpret: bool, scope: str = "mla"):
    T, H, W = q.shape
    K = kv.shape[1]
    return pl.pallas_call(
        functools.partial(_attend_kernel, r=r, scale=scale),
        name="cake_" + scope + "_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T,),
            in_specs=[pl.BlockSpec((None, H, W), lambda t, n: (t, 0, 0)),
                      pl.BlockSpec((None, K, W), lambda t, n: (t, 0, 0))],
            out_specs=pl.BlockSpec((None, H, r), lambda t, n: (t, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((T, H, r), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(n_valid.astype(jnp.int32), q, kv)


def attend_selected(q, kv, n_valid, r: int, scale: float,
                    impl: str = "fold", interpret: Optional[bool] = None,
                    scope: str = "mla"):
    """q [T, H, W] (the absorbed query: q_lat | q_pe), kv [T, K, W] the
    token's selected cache rows (its first n_valid[t] are real), r the
    value's width (the leading r of a row). Returns the weighted sum of
    the rows' first r numbers, [T, H, r]; a token with no valid row
    (padding) gets an unspecified finite result. scope: the kind of
    layer in the kernel's name in a trace (LatentGeometry.scope: a
    sliding-window layer runs the same body at its own sizes as
    `cake_swa_attn`)."""
    if impl == "pallas":
        if interpret is None:
            interpret = not rpa._on_tpu()
        return _attend_pallas(q, kv, n_valid, r=r, scale=scale,
                              interpret=interpret, scope=scope)
    if impl != "fold":
        raise ValueError(f"unknown latent attention impl {impl!r}")
    return _attend_fold(q, kv, n_valid, r, scale)


# -- one query a row over every live page of the row -------------------------


def _pages_fold(q, pool, layer, table, pos, r: int, scale: float):
    """The kernel's recurrence in XLA: page j of every row gathered and
    folded into the rows' float32 (m, l, acc), for j up to the longest
    row's live pages."""
    B, H, W = q.shape
    P, max_pages = pool.shape[2], table.shape[1]
    n = jnp.clip(pos // P + 1, 0, max_pages)

    def page(j, stats):
        m, l, acc = stats
        pid = table[:, j]
        kv = pool.at[layer, jnp.maximum(pid, 0)].get(
            mode="promise_in_bounds").astype(q.dtype)          # [B, P, W]
        s = jnp.einsum("bhw,bpw->bhp", q, kv,
                       preferred_element_type=jnp.float32) * scale
        ok = ((j * P + jnp.arange(P))[None, :] <= pos[:, None]) & (
            (pid >= 0) & (j < n))[:, None]
        s = jnp.where(ok[:, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(ok[:, None, :], jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                alpha * acc + jnp.einsum(
                    "bhp,bpr->bhr", p.astype(q.dtype), kv[..., :r],
                    preferred_element_type=jnp.float32))

    _, l, acc = lax.fori_loop(
        0, jnp.max(n), page,
        (jnp.full((B, H, 1), NEG_INF, jnp.float32),
         jnp.zeros((B, H, 1), jnp.float32),
         jnp.zeros((B, H, r), jnp.float32)))
    return (acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)


# The page kernel's ring: a MiB of pages in flight ahead of the slot
# that folds, as the GQA decode kernel's (rpa._RING_BYTES_AHEAD, read
# on the chip there); a latent row has no V pool, so the ring is one.
# A slot holds a TRIP's pages (decode_block of them), side by side.
def pages_ring_depth(slot_bytes: int) -> int:
    ahead = -(-rpa._RING_BYTES_AHEAD // slot_bytes)
    return 1 + min(max(ahead, 1), rpa._RING_PAGES_AHEAD_MAX)


# What decode_block plans with: half of what a kernel is granted
# unasked (the page kernel states no vmem_limit_bytes); the rest is the
# compiler's own temporaries.
_PAGES_VMEM_PLAN = rpa._VMEM_SCOPED_LIMIT // 2


def pages_vmem_bytes(H: int, W: int, r: int, page: int, itemsize: int,
                     block: int) -> int:
    """Scoped VMEM the page kernel needs at `block` pages a fold: the
    ring (pages_ring_depth slots of `block` pages), the double-buffered
    query and result blocks, the float32 accumulator and the value
    product beside it, and a trip's scores and probabilities ([H,
    block * page] float32 both, the probabilities again in the pool's
    type)."""
    slot = block * page * W * itemsize
    return (pages_ring_depth(slot) * slot + 2 * H * (W + r) * itemsize
            + 2 * H * r * 4 + H * block * page * (8 + itemsize))


def decode_block(H: int, W: int, r: int, page: int, max_pages: int,
                 itemsize: int) -> int:
    """Pages a fold of the page kernel, from the call's shapes alone:
    the most of (4, 2, 1) whose count (pages_vmem_bytes) fits
    _PAGES_VMEM_PLAN and such that whole blocks pad the TABLE by an
    eighth at most (a row's last block is computed whole and masked),
    as window_tiles' B and rpa.mixed_block. 4 at both cells' shapes
    (128 heads over a table of 40 pages of 128 x 640 bfloat16: 3.6 MiB;
    32 heads over one of 76: 2.3). Alone on the chip (PERF.md section
    6, PR 64; tools/mla_decode_attn_bench.py)."""
    def fits(f):
        return (8 * (-max_pages % f) <= max_pages
                and pages_vmem_bytes(H, W, r, page, itemsize, f)
                <= _PAGES_VMEM_PLAN)
    return next(f for f in (4, 2, 1) if f == 1 or fits(f))


def pages_walk(pos: int, page: int, max_pages: int, block: int):
    """(pages, folds) the page kernel walks for a row whose single
    token sits at pos: its live pages 0 .. pos // page, and the softmax
    updates they take at `block` pages each; none for a row with no
    query (pos < 0). The host's count of what `_pages_kernel` does (the
    step records' mla_decode_pages / mla_decode_folds)."""
    pages = min(max(pos // page + 1, 0), max_pages)
    return pages, -(-pages // block)


def _pages_kernel(layer_ref, pos_ref, table_ref, q_ref, pool_hbm, o_ref,
                  buf, sem, cur, acc_ref, *, r: int, scale: float,
                  depth: int, page: int, block: int):
    """One grid step: one ROW, its live pages of the layer's latent
    pool walked by `rpa.walk_live_pages` (the GQA decode kernel's walk;
    one pool, so one copy a page), `block` of them a trip and ONE
    softmax update: scores [H, block * page] in one product, one
    max / exp / sum, one alpha * acc + p . V contracted over the trip's
    keys. A row's last trip is computed whole and masked; a place that
    was not fetched (past the row's last live page, an unmapped hole)
    keeps what lay there, finite (the ring starts as zeros: it is K and
    V at once), under a probability of exactly 0.

    q_ref [H, W], o_ref [H, r]; buf [depth, block * page, W]; sem DMA
    [depth, block]; cur SMEM int32 [4], the walk's; acc_ref [H, r] f32
    (at 128 heads x 512 the accumulator is the whole register file)."""
    H = q_ref.shape[0]

    def copies(layer, pid, slot, _row, _p, f):
        return [pltpu.make_async_copy(
            pool_hbm.at[layer, pid], buf.at[slot, pl.ds(f * page, page)],
            sem.at[slot, f])]

    if block > 1:
        @pl.when(pl.program_id(0) == 0)
        def _():
            buf[...] = jnp.zeros_like(buf)

    pos, pages = rpa.walk_live_pages(layer_ref, pos_ref, table_ref, cur,
                                     copies, depth=depth, page_size=page,
                                     block=block)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    col = lax.broadcasted_iota(jnp.int32, (1, page), 1)

    def fold(j, found, slot, stats):
        m_prev, l_prev = stats
        kv = buf[slot]                                   # [block * page, W]
        s = rpa._dot(q_ref[...], kv, trans_b=True) * scale
        # the causal cut: only the last live page has columns past pos;
        # a place that was not fetched starts past every position
        starts = [(j + f) * page if block == 1
                  else jnp.where(fetched, (j + f) * page, rpa._NEVER)
                  for f, (_pid, fetched) in enumerate(found)]
        visible = jnp.concatenate([at + col <= pos for at in starts], axis=1)
        s = jnp.where(visible, s, NEG_INF)               # [H, block * page]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc_ref[...] = alpha * acc_ref[...] + rpa._dot(
            p.astype(kv.dtype), kv[:, :r], trans_b=False)
        return m_new, alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

    # (the running maximum starts ABOVE a masked score: a trip of holes
    # alone leaves it there, and exp(NEG_INF - NEG_INF / 2) is exactly
    # 0 where exp(NEG_INF - NEG_INF) would be 1)
    _, l = pages(fold, (jnp.full((H, 1), NEG_INF / 2, jnp.float32),
                        jnp.zeros((H, 1), jnp.float32)))
    # a row that folded nothing (idle, or an all-unmapped table) has
    # l == 0 and a zero accumulator: zeros, as the fold gives
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("r", "scale", "interpret"))
def _pages_pallas(q, pool, layer, table, pos, *, r: int, scale: float,
                  interpret: bool):
    B, H, W = q.shape
    page, itemsize = pool.shape[2], pool.dtype.itemsize
    block = decode_block(H, W, r, page, table.shape[1], itemsize)
    depth = pages_ring_depth(block * page * W * itemsize)
    return pl.pallas_call(
        functools.partial(_pages_kernel, r=r, scale=scale, depth=depth,
                          page=page, block=block),
        name="cake_mla_decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, H, W), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, r), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((depth, block * page, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((depth, block)),
                            pltpu.SMEM((4,), jnp.int32),
                            pltpu.VMEM((H, r), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, r), q.dtype),
        # the ring's copies run ahead into the next row
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos.astype(jnp.int32),
      table.astype(jnp.int32), q, pool)


def attend_pages(q, pool, layer, table, pos, r: int, scale: float,
                 impl: str = "fold", interpret: Optional[bool] = None):
    """One query a ROW (q [B, H, W], the absorbed form) over EVERY key
    of the row up to its position: the row's live pages 0 .. pos[b] //
    page of the latent pool [L, N, page, W], read where they lie
    through table [B, max_pages] (-1: a hole, skipped); the query's own
    row must already be written. pos [B] int32; a negative position is
    a row with no query (idle, or served another way in this
    dispatch): it takes no trip and gets zeros. Returns [B, H, r].

    impl "pallas": `cake_mla_decode_attn`, grid (rows,), a dynamic
    count of trips a row, the kernel's own ring of page copies; a trip
    is decode_block pages (F): [H, W] . [W, F * page] scores and [H,
    F * page] . [F * page, r] values, a float32 online softmax, one
    update a trip. impl "fold": the same recurrence in XLA, a page a
    step."""
    if impl == "pallas":
        if interpret is None:
            interpret = not rpa._on_tpu()
        return _pages_pallas(q, pool, layer, table, pos, r=r, scale=scale,
                             interpret=interpret)
    if impl != "fold":
        raise ValueError(f"unknown latent attention impl {impl!r}")
    return _pages_fold(q, pool, jnp.asarray(layer, jnp.int32), table,
                       pos.astype(jnp.int32), r, scale)


# -- a window's queries over their row's pages ---------------------------------


def _window_bias(bias, positions, table_row, last_pos, page: int):
    """The [C, S] float32 bias both window implementations mean: the
    caller's, or causality from the window's positions (a query past
    last_pos, a padded one, sees what the last real one sees), and
    NEG_INF on every key the walk does not visit: a page past
    last_pos's, an unmapped entry."""
    S = table_row.shape[0] * page
    span = jnp.arange(S)
    if bias is None:
        at = jnp.minimum(positions, last_pos)
        bias = jnp.where(span[None, :] <= at[:, None], 0.0, NEG_INF)
    walked = (table_row >= 0) & (jnp.arange(table_row.shape[0])
                                 <= last_pos // page)
    return jnp.where(jnp.repeat(walked, page)[None, :], bias,
                     NEG_INF).astype(jnp.float32)


def _window_fold(q, pool, layer, table_row, bias, last_pos, positions,
                 r: int, scale: float):
    """The reference semantics in XLA: the row's pages gathered whole,
    every (query, key) scored, the bias added."""
    C, H, W = q.shape
    bias = _window_bias(bias, positions, table_row, last_pos, pool.shape[2])
    keys = pool.at[layer, jnp.maximum(table_row, 0)].get(
        mode="promise_in_bounds").reshape(-1, W)               # [S, W]
    s = jnp.einsum("chw,sw->chs", q, keys.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = s + bias[:, None, :]
    ok = s > NEG_INF / 2
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("chs,sr->chr", (p / l).astype(q.dtype),
                     keys[:, :r].astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# What the window call asks of the core's 128 MiB of VMEM (the compiler
# grants a kernel 16 unless told), and what of it window_tiles plans
# with: the rest is the compiler's own temporaries.
_WINDOW_VMEM_LIMIT = 48 * 2**20
_WINDOW_VMEM_PLAN = 32 * 2**20


def window_tiles(C: int, H: int, W: int, r: int, page: int, max_pages: int,
                 itemsize: int, biased: bool):
    """(tq, B) of a window call, from its shapes alone: tq tokens a
    query tile, B pages a softmax update.

    tq: the widest tile of (token, head) rows whose queries (two
    buffers), accumulator and result (two) stay under 8 MiB: 16 tokens
    at 64 heads of a 640-wide bfloat16 row, 8 at 128 heads, 8 at 64
    heads of a 1,152-wide row with a 1,024-wide value.
    B: the most pages of (4, 2, 1) such that the score and probability
    tiles (float32 both, and the probabilities again in the pool's
    type), the value product, two ring slots of B pages and, where a
    bias array comes, its two buffers of tq whole rows fit
    _WINDOW_VMEM_PLAN beside that tile, and such that whole blocks pad
    the TABLE by an eighth at most: a walk's last block is computed
    whole and masked, and a short table (a ring of 9 pages, walked
    whole by every tile) would pay 12 pages at 4 a fold. On the chip
    (PERF.md section 6, PR 46): 4 a fold reads 3-4 % under 2 at 16-96
    pages, 8 what 4 does; the ring 1.20 ms at 2, 1.32 at 4."""
    tile = lambda t: t * H * (2 * W * itemsize + (4 + 2 * itemsize) * r)
    tq = next(t for t in (16, 8, 4, 2, 1)
              if C % t == 0 and (tile(t) <= 8 * 2**20 or t == 1))
    rows = tq * H

    def fits(b):
        need = (tile(tq) + rows * 4 * r + rows * b * page * (8 + itemsize)
                + 2 * b * page * W * itemsize
                + (2 * tq * max_pages * page * 4 if biased else 0))
        return need <= _WINDOW_VMEM_PLAN and 8 * (-max_pages % b) <= max_pages

    return tq, next(b for b in (4, 2, 1) if fits(b) or b == 1)


def window_walk(last_pos: int, page: int, max_pages: int, block: int):
    """(pages, folds) a query tile of the window kernel walks for a
    window that ends at last_pos: the row's live pages, and the softmax
    updates they take at `block` pages each. The host's count of what
    `_window_kernel` does (the step records' window_pages /
    window_folds): what a single-token row at last_pos walks."""
    return pages_walk(last_pos, page, max_pages, block)


def _spread(s, rows, heads: int):
    """s [tq * heads, keys] + a token's [1, keys] row on each of its
    `heads` rows of s (rows(t) -> token t's): sublane broadcasts."""
    return jnp.concatenate(
        [s[t * heads:(t + 1) * heads] + rows(t)
         for t in range(s.shape[0] // heads)], axis=0)


def _window_kernel(layer_ref, table_ref, last_ref, *refs, r: int,
                   scale: float, heads: int, page: int, block: int,
                   biased: bool):
    """One grid step: one TILE of tq tokens x heads query rows over the
    row's live pages 0 .. last // page, `block` of them a softmax
    update.

    The pages come by the kernel's own copies out of the pool in HBM
    into a ring of two slots of `block` pages: block k + 1 (the next
    tile's first, after this tile's last: every tile walks the same
    pages) is in flight while block k folds. A page past the last live
    one or an unmapped entry starts no copy; its columns take NEG_INF
    and its slot keeps what lay there (the ring starts as zeros, so it
    is finite, and a probability of exactly 0 meets it).

    pos_ref (no bias array): SMEM [C], the tokens' positions: a key is
    visible at or before its query's. bias_ref [tq, S] float32
    otherwise: the tile's whole rows. q_ref [tq * heads, W]; o_ref,
    acc_ref [tq * heads, r]; buf [2, block * page, W]; sem DMA
    [2, block]."""
    if biased:
        q_ref, bias_ref, pool_hbm, o_ref, buf, sem, acc_ref = refs
    else:
        pos_ref, q_ref, pool_hbm, o_ref, buf, sem, acc_ref = refs
    i, tiles = pl.program_id(0), pl.num_programs(0)
    rows = q_ref.shape[0]
    tq = rows // heads
    max_pages = table_ref.shape[0]
    layer, last = layer_ref[0], last_ref[0]
    n = jnp.clip(last // page + 1, 0, max_pages)
    nb = pl.cdiv(n, block)

    def page_of(k, b):
        """Logical page b of block k: (its id, whether it is walked)."""
        j = k * block + b
        pid = table_ref[jnp.minimum(j, max_pages - 1)]
        return pid, jnp.logical_and(j < n, pid >= 0)

    def copies(k, slot, act):
        for b in range(block):
            pid, walked = page_of(k, b)

            @pl.when(walked)
            def _():
                act(pltpu.make_async_copy(
                    pool_hbm.at[layer, pid],
                    buf.at[slot, pl.ds(b * page, page)], sem.at[slot, b]))

    @pl.when(i == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)

        @pl.when(nb > 0)
        def _():
            copies(0, 0, lambda c: c.start())

    if not biased:
        at = [jnp.minimum(pos_ref[i * tq + t], last) for t in range(tq)]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def token(t, k, walked):
        """Token t's [1, block * page] float32 of the mask of block k:
        0 on a key it attends, NEG_INF elsewhere."""
        col = lax.broadcasted_iota(jnp.int32, (1, page), 1)
        parts = []
        for b in range(block):
            j = k * block + b
            if biased:
                # (clamped: the last block may pass the table's end)
                start = pl.multiple_of(
                    jnp.minimum(j, max_pages - 1) * page, page)
                parts.append(jnp.where(
                    walked[b], bias_ref[pl.ds(t, 1), pl.ds(start, page)],
                    NEG_INF))
            else:
                seen = jnp.logical_and(j * page + col <= at[t], walked[b])
                parts.append(jnp.where(seen, 0.0, NEG_INF))
        return jnp.concatenate(parts, axis=1)

    def fold(k, stats):
        m_prev, l_prev = stats
        slot = (i * nb + k) % 2
        # the slot this frees held the block folded a step ago
        more = k + 1 < nb

        @pl.when(jnp.logical_or(more, i + 1 < tiles))
        def _():
            copies(jnp.where(more, k + 1, 0), 1 - slot,
                   lambda c: c.start())

        copies(k, slot, lambda c: c.wait())
        kv = buf[slot]                                  # [block * page, W]
        walked = [page_of(k, b)[1] for b in range(block)]
        # a token's mask reaches its heads' rows by sublane broadcasts
        s = _spread(rpa._dot(q_ref[...], kv, trans_b=True) * scale,
                    lambda t: token(t, k, walked), heads)
        # m starts at NEG_INF / 2: a masked score (NEG_INF) lies 5e29
        # below any m, so its exponential is exactly 0, and a row that
        # has met no key yet keeps l == 0 and a zero accumulator
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc_ref[...] = alpha * acc_ref[...] + rpa._dot(
            p.astype(kv.dtype), kv[:, :r], trans_b=False)
        return m_new, alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

    _, l = lax.fori_loop(
        0, nb, fold, (jnp.full((rows, 1), NEG_INF / 2, jnp.float32),
                      jnp.zeros((rows, 1), jnp.float32)))
    o_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("r", "scale", "interpret", "scope"))
def _window_pallas(q, pool, layer, table_row, bias, last_pos, positions, *,
                   r: int, scale: float, interpret: bool,
                   scope: str = "mla"):
    C, H, W = q.shape
    page, max_pages = pool.shape[2], table_row.shape[0]
    biased = bias is not None
    tq, block = window_tiles(C, H, W, r, page, max_pages,
                             pool.dtype.itemsize, biased)
    tile = lambda width: pl.BlockSpec((tq * H, width), lambda i, *_: (i, 0))
    scalars = [jnp.reshape(layer, (1,)), table_row,
               jnp.reshape(last_pos, (1,))]
    if biased:
        operands = [q.reshape(C * H, W), bias]
        in_specs = [tile(W), pl.BlockSpec((tq, max_pages * page),
                                          lambda i, *_: (i, 0))]
    else:
        scalars.append(positions)
        operands, in_specs = [q.reshape(C * H, W)], [tile(W)]
    out = pl.pallas_call(
        functools.partial(_window_kernel, r=r, scale=scale, heads=H,
                          page=page, block=block, biased=biased),
        name="cake_" + scope + "_window_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(C // tq,),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile(r),
            scratch_shapes=[pltpu.VMEM((2, block * page, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((2, block)),
                            pltpu.VMEM((tq * H, r), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((C * H, r), q.dtype),
        # the ring's copies run ahead into the next tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_WINDOW_VMEM_LIMIT),
        interpret=interpret,
    )(*(s.astype(jnp.int32) for s in scalars), *operands, pool)
    return out.reshape(C, H, r)


def attend_window(q, pool, layer, table_row, bias, last_pos, r: int,
                  scale: float, impl: str = "fold",
                  interpret: Optional[bool] = None, scope: str = "mla",
                  positions=None):
    """A window's C queries (q [C, H, W], the absorbed form, in the
    pool's type) over ONE row's pages of the latent pool [L, N, page,
    W], read where they lie through table_row [max_pages]: no gather.
    last_pos: the window's last position; the walk is the row's live
    pages 0 .. last_pos // page, and an unmapped entry (-1) among them
    is never attended. Which of the walked keys a query attends arrives
    one of two ways:

      * bias [C, S] float32: 0 for a key the query attends, NEG_INF for
        every other (a selection, a band);
      * bias None and positions [C] int32: causality alone, a key at or
        before its query's position (a padded query past last_pos sees
        what the last real one does).

    Returns [C, H, r]; a query with no key to attend gets zeros. A
    sliding-window layer passes its row's RING of pages as table_row
    (key index j * page + o is then the o-th token of the ring's j-th
    page, whatever position lies there: the bias says), last_pos = the
    ring's last index, and its own `scope` (the kernel is then named
    `cake_swa_window_attn`).

    impl "pallas": `cake_mla_window_attn` (module docstring). impl
    "fold": the same semantics in XLA, every key of the table scored."""
    if (bias is None) == (positions is None):
        raise ValueError("a window's mask is a bias array or its "
                         "positions: pass one")
    if impl == "pallas":
        if interpret is None:
            interpret = not rpa._on_tpu()
        return _window_pallas(q, pool, layer, table_row, bias, last_pos,
                              positions, r=r, scale=scale,
                              interpret=interpret, scope=scope)
    if impl != "fold":
        raise ValueError(f"unknown latent attention impl {impl!r}")
    return _window_fold(q, pool, layer, table_row, bias, last_pos, positions,
                        r, scale)


# -- the indexer's scores and its selection ------------------------------------


def _sortable(x):
    """float32 -> uint32 codes in the floats' order."""
    u = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def select_mask(scores, visible, k: int):
    """The top-k of each row as a mask: scores [N, S] float32, visible
    [N, S] bool (what a row may select from) -> [N, S] bool with the k
    largest visible scores of a row (all of them where fewer are
    visible), ties at the k-th value to the lower index. Exact, and no
    sort: the k-th largest code is found bit by bit (32 counting
    passes), the ties by a running count."""
    u = jnp.where(visible, _sortable(scores), jnp.uint32(0))

    def bit(i, cand):
        trial = cand | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(u >= trial[:, None], axis=1)
        return jnp.where(n >= k, trial, cand)

    kth = lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[0], jnp.uint32))
    above = visible & (u > kth[:, None])
    tied = visible & (u == kth[:, None])
    room = k - jnp.sum(above, axis=1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, axis=1) <= room))


# What the selection kernel asks of the core's VMEM, and what of it a
# query tile's resident codes may take (the result's two buffers are a
# quarter of that again; the rest is the compiler's temporaries).
_SELECT_VMEM_LIMIT = 48 * 2**20
_SELECT_CODES_BYTES = 18 * 2**20
_INT_MIN = -2**31


def select_tiles(C: int, S: int):
    """(tq, chunk, block) of a selection call, from its shapes alone:
    tq queries a tile, whose codes [tq, S] stay in VMEM (the widest of
    128, 64, .. 8 that divides C under _SELECT_CODES_BYTES: 128 at the
    cells' tables, 17 MB at 33,280; where none divides, the window is
    one tile);
    chunk: the keys one vector op spans (128 lanes; a table that is no
    multiple of 128, a test's, is one chunk); block: the keys one copy
    brings and one step of the bound skips, the most whole chunks up to
    16 that divide the table (13 x 128 at 260 chunks, 12 at 132, 10 at
    100)."""
    chunk = 128 if S % 128 == 0 else S
    per = max(d for d in range(1, 17) if (S // chunk) % d == 0)
    tq = next((t for t in (128, 64, 32, 16, 8)
               if C % t == 0 and t * S * 4 <= _SELECT_CODES_BYTES), C)
    return tq, chunk, per * chunk


def select_walked(last_pos, C: int, S: int):
    """Keys in the blocks a selection call walks for a window that ends
    at last_pos (of the table's S): the step programs' count of what
    `_select_kernel` does (dsa_select_keys_walked)."""
    block = select_tiles(C, S)[2]
    return (jnp.clip(last_pos, 0, S - 1) // block + 1) * block


def _select_kernel(last_ref, pos_ref, scores_hbm, o_ref, codes, sem, *,
                   k: int, chunk: int, block: int):
    """One grid step: one TILE of tq queries against the row's keys
    0 .. last, in blocks of `block`.

    scores_hbm [C, S] float32 in HBM. The tile's live blocks (those
    that start at or before `last`) come by the kernel's own copies
    into `codes` [tq, S] and are turned in place into keys in the
    floats' order, SIGNED int32 (held behind a bitcast: the scratch is
    the copies' float32): `_sortable`'s code with its top bit flipped,
    so that the vector unit's signed compare orders them; a key the
    query does not see (past min(its position, last)) takes the lowest,
    `_sortable`'s 0. They stay there through 32 counting passes (the
    k-th largest code, bit by bit: a compare, a select and an add a
    vector, one lane reduction a pass), one more for the keys above it,
    and the tie pass: in index order, a carried count a query and the
    prefix inside a chunk as a product with a triangle of ones on the
    matrix unit (0/1 in bfloat16, float32 sums: exact). o_ref [tq, S]
    int8: 1 on a selected key; blocks past `last` are written 0 and
    were never read. pos_ref [tq, 1] int32; sem DMA [S // block]."""
    i = pl.program_id(0)
    tq, S = codes.shape
    per = block // chunk
    last = jnp.clip(last_ref[0], 0, S - 1)
    nb = last // block + 1
    at = jnp.minimum(pos_ref[...], last)                      # [tq, 1]
    col = lax.broadcasted_iota(jnp.int32, (tq, chunk), 1)

    def cols(j, p=None):
        if p is None:
            return pl.ds(pl.multiple_of(j * block, block), block)
        return pl.ds(pl.multiple_of(j * block + p * chunk, chunk), chunk)

    def copy(j):
        return pltpu.make_async_copy(
            scores_hbm.at[pl.ds(i * tq, tq), cols(j)],
            codes.at[:, cols(j)], sem.at[j])

    def start(j, carry):
        copy(j).start()
        return carry

    lax.fori_loop(0, nb, start, 0)

    def seen_by(j, p):
        return j * block + p * chunk + col <= at

    def bits(j, p):
        return lax.bitcast_convert_type(codes[:, cols(j, p)], jnp.int32)

    def encode(j, carry):
        copy(j).wait()
        for p in range(per):
            u = bits(j, p)
            # negative floats: the low 31 bits flipped
            key = u ^ ((u >> 31) & jnp.int32(0x7FFFFFFF))
            codes[:, cols(j, p)] = lax.bitcast_convert_type(
                jnp.where(seen_by(j, p), key, _INT_MIN), jnp.float32)
        return carry

    lax.fori_loop(0, nb, encode, 0)

    def count(pred):
        """Keys of each query's live blocks with pred(key): [tq, 1]."""
        def fold(j, acc):
            for p in range(per):
                acc = acc + pred(bits(j, p)).astype(jnp.float32)
            return acc

        # (float32 counts: exact to 2**24 keys, and the lanes' sum is
        # the reduction the chip has)
        acc = lax.fori_loop(0, nb, fold, jnp.zeros((tq, chunk), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def bit(b, cand):
        # cand: the code's bits found so far (`_sortable`'s, unsigned,
        # held in an int32); its signed key flips the top bit
        trial = cand | (jnp.int32(1) << (31 - b))
        key = jnp.broadcast_to(trial ^ _INT_MIN, (tq, chunk))
        return jnp.where(count(lambda x: x >= key) >= k, trial, cand)

    kth = lax.fori_loop(0, 32, bit, jnp.zeros((tq, 1), jnp.int32))
    kth = jnp.broadcast_to(kth ^ _INT_MIN, (tq, chunk))
    room = k - count(lambda x: x > kth)
    triangle = (lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
                <= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
                ).astype(jnp.float32).astype(jnp.bfloat16)

    def emit(j, tied_before):
        for p in range(per):
            x = bits(j, p)
            tied = (x == kth) & seen_by(j, p)
            # tied keys of this chunk at or before each column
            upto = rpa._dot(tied.astype(jnp.float32).astype(jnp.bfloat16),
                            triangle, trans_b=False)
            take = (x > kth) | (tied & (tied_before + upto <= room))
            o_ref[:, cols(j, p)] = take.astype(jnp.int32).astype(o_ref.dtype)
            tied_before = tied_before + upto[:, chunk - 1:]
        return tied_before

    lax.fori_loop(0, nb, emit, jnp.zeros((tq, 1), jnp.float32))

    def blank(j, carry):
        o_ref[:, cols(j)] = jnp.zeros((tq, block), o_ref.dtype)
        return carry

    lax.fori_loop(nb, S // block, blank, 0)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _select_pallas(scores, positions, last_pos, *, k: int, interpret: bool):
    C, S = scores.shape
    tq, chunk, block = select_tiles(C, S)
    picked = pl.pallas_call(
        functools.partial(_select_kernel, k=k, chunk=chunk, block=block),
        name="cake_dsa_select",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(C // tq,),
            in_specs=[pl.BlockSpec((tq, 1), lambda i, *_: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tq, S), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tq, S), jnp.float32),
                            pltpu.SemaphoreType.DMA((S // block,))]),
        out_shape=jax.ShapeDtypeStruct((C, S), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_SELECT_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(last_pos, (1,)).astype(jnp.int32),
      positions.astype(jnp.int32).reshape(C, 1), scores)
    return picked != 0


def select_window(scores, positions, last_pos, k: int,
                  interpret: Optional[bool] = None):
    """A window's top-k as a mask, by one kernel (`cake_dsa_select`):
    scores [C, S] float32 of the window's C queries against ONE row's
    keys, positions [C] int32, last_pos the window's last position ->
    [C, S] bool, bit for bit

        select_mask(scores, span <= min(positions, last_pos)[:, None], k)

    so a query at or before last_pos selects among the keys at or
    before its own position, and a padded query past it what the last
    real one may see, by its own scores (nobody reads those rows).
    Visibility comes from the positions (no [C, S] operand), and the
    keys past last_pos's block are neither read nor counted: a call
    costs what the window's context costs (`select_walked`), not what
    the table is wide. `_select_kernel` says how; `select_tiles` what
    it holds."""
    C, S = scores.shape
    if scores.dtype != jnp.float32 or positions.shape != (C,) or k < 1:
        raise ValueError(
            f"cake_dsa_select takes float32 scores [C, S], positions [C] "
            f"and k >= 1: got {scores.dtype}{list(scores.shape)}, "
            f"positions {list(positions.shape)}, k={k}")
    if interpret is None:
        interpret = not rpa._on_tpu()
    tq = select_tiles(C, S)[0]
    if tq * S * 4 > _SELECT_CODES_BYTES or (not interpret and S % 128):
        raise ValueError(
            f"cake_dsa_select cannot run at a window of {C} queries over "
            f"{S} keys: a tile of {tq} queries' codes is {tq * S * 4} bytes "
            f"of {_SELECT_CODES_BYTES}, and on the chip the table must be "
            f"a multiple of 128 keys (select_tiles)")
    return _select_pallas(scores, positions, last_pos, k=k,
                          interpret=interpret)


def _weighted_relu(q, k, w):
    """q [.., Q, J, d], k [.., S, d], w [.., Q, J] -> [.., Q, S] f32."""
    dots = jnp.einsum("...qjd,...sd->...qjs", q, k,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("...qjs,...qj->...qs", jax.nn.relu(dots),
                      w.astype(jnp.float32))


def index_scores_rows(qI, kI, w):
    """One query a row: qI [B, J, d], kI [B, S, d], w [B, J] ->
    I [B, S] float32."""
    return _weighted_relu(qI[:, None], kI, w[:, None])[:, 0]


# What the score kernel asks of the core's VMEM, what of it a call's
# tiles may take by `index_vmem_bytes`' count, and the most 128-key
# chunks a key block holds.
_INDEX_VMEM_LIMIT = 48 * 2**20
_INDEX_TILE_BYTES = 24 * 2**20
_INDEX_BLOCK_CHUNKS = 4


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def index_vmem_bytes(tq: int, J: int, d: int, kb: int, itemsize: int) -> int:
    """VMEM one step of `_index_kernel` holds: the query tile
    [tq, J * d] and its weights [tq, J] float32, a key block [kb, d]
    and the result tile [tq, kb] float32, two buffers each (the
    pipeline's), and two more [tq, kb] float32 for a head's products
    and the running sum. A minor axis takes whole vectors of 128
    lanes."""
    return (2 * tq * _lanes(J * d) * itemsize + 2 * tq * _lanes(J) * 4
            + 2 * kb * _lanes(d) * itemsize + 4 * tq * _lanes(kb) * 4)


def index_key_block(S: int) -> int:
    """Keys one step of the score kernel streams and one step of its
    bound skips: the most whole chunks up to _INDEX_BLOCK_CHUNKS that
    divide the table (a chunk is 128 keys; 8 in a table that is no
    multiple of 128, a test's): 512 at 260, 100 and 132 chunks."""
    chunk = 128 if S % 128 == 0 else 8 if S % 8 == 0 else S
    return chunk * max(n for n in range(1, _INDEX_BLOCK_CHUNKS + 1)
                       if (S // chunk) % n == 0)


def index_tiles(C: int, J: int, d: int, S: int, itemsize: int = 2):
    """(tq, kb) of a score call, from its shapes alone. kb:
    `index_key_block`. tq: the queries whose heads stay resident over
    the key axis, the widest of 512, 256, .. 8 that divides C under
    _INDEX_TILE_BYTES by `index_vmem_bytes` (where none divides, the
    window is one tile): 512 at the three cells' shapes (16 heads of
    64, 32 of 128, 64 of 128: 6.8, 12.8 and 20.8 MB)."""
    kb = index_key_block(S)
    tq = next((t for t in (512, 256, 128, 64, 32, 16, 8)
               if C % t == 0 and index_vmem_bytes(t, J, d, kb, itemsize)
               <= _INDEX_TILE_BYTES), C)
    return tq, kb


def index_scored(last_pos, S: int):
    """Keys of the blocks a score call visits for a window that ends at
    last_pos (of the table's S): the step programs' count of what
    `_index_kernel` does (dsa_index_keys_scored)."""
    kb = index_key_block(S)
    return (jnp.clip(last_pos, 0, S - 1) // kb + 1) * kb


def _index_kernel(last_ref, q_ref, w_ref, k_ref, o_ref, *, heads: int):
    """One grid step: a TILE of tq queries against ONE block of kb
    keys. q_ref [tq, heads * d] (a query's heads side by side) and
    w_ref [tq, heads] float32 are the tile's and stay while the key
    axis runs; k_ref [kb, d] is the block the index map brought: the
    step's own where it starts at or before last_ref[0], else the last
    live one again, which the pipeline does not fetch a second time.
    A live block: a head at a time, [tq, d] x [d, kb] on the matrix
    unit into float32, the relu, the head's weight and the sum over
    heads in ascending order on the vector unit, all float32; the tile
    is stored where it belongs in [C, S]. A block past the bound is
    written zeros and nothing of it was read."""
    kb = o_ref.shape[1]
    d = q_ref.shape[1] // heads
    live = pl.program_id(1) * kb <= last_ref[0]

    @pl.when(live)
    def _():
        keys = k_ref[...]
        acc = None
        for j in range(heads):
            dots = rpa._dot(q_ref[:, j * d:(j + 1) * d], keys, trans_b=True)
            term = w_ref[:, j:j + 1] * jnp.maximum(dots, 0.0)
            acc = term if acc is None else acc + term
        o_ref[...] = acc

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_pallas(qI, kI, w, last_pos, *, interpret: bool):
    C, J, d = qI.shape
    S = kI.shape[0]
    tq, kb = index_tiles(C, J, d, S, qI.dtype.itemsize)
    last = jnp.clip(last_pos, 0, S - 1).astype(jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(_index_kernel, heads=J),
        name="cake_dsa_index",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(C // tq, S // kb),
            in_specs=[
                pl.BlockSpec((tq, J * d), lambda i, j, last: (i, 0)),
                pl.BlockSpec((tq, J), lambda i, j, last: (i, 0)),
                pl.BlockSpec(
                    (kb, d),
                    lambda i, j, last: (jnp.minimum(j, last[0] // kb), 0))],
            out_specs=pl.BlockSpec((tq, kb), lambda i, j, last: (i, j))),
        out_shape=jax.ShapeDtypeStruct((C, S), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_INDEX_VMEM_LIMIT),
        interpret=interpret,
    )(last, qI.reshape(C, J * d), w.astype(jnp.float32), kI)


def index_scores_window(qI, kI, w, last_pos,
                        interpret: Optional[bool] = None):
    """A window's queries against one row's keys, by one kernel
    (`cake_dsa_index`): qI [C, J, d], kI [S, d] (the pool's dtype, as
    qI's), w [C, J] -> I [C, S] float32,

        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])

    every product, the relu, the weights and the sum in float32. Key
    blocks that start past `last_pos` (the window's last position:
    nothing there is visible to any query) are zeros, and are neither
    read nor computed: a call costs what the window's context costs
    (`index_scored`), not what the table is wide. `_index_kernel` says
    how; `index_tiles` what it holds."""
    C, J, d = qI.shape
    S = kI.shape[0]
    if kI.shape != (S, d) or w.shape != (C, J) or kI.dtype != qI.dtype:
        raise ValueError(
            f"cake_dsa_index takes queries [C, J, d], keys [S, d] of their "
            f"dtype and weights [C, J]: got {qI.dtype}{list(qI.shape)}, "
            f"{kI.dtype}{list(kI.shape)}, {list(w.shape)}")
    if interpret is None:
        interpret = not rpa._on_tpu()
    tq, kb = index_tiles(C, J, d, S, qI.dtype.itemsize)
    need = index_vmem_bytes(tq, J, d, kb, qI.dtype.itemsize)
    if need > _INDEX_TILE_BYTES or (not interpret
                                    and (S % 128 or (J * d) % 128)):
        raise ValueError(
            f"cake_dsa_index cannot run at a window of {C} queries of {J} "
            f"heads of {d} over {S} keys: a tile of {tq} queries against "
            f"{kb} keys is {need} bytes of {_INDEX_TILE_BYTES}, and on the "
            f"chip the table and a query's {J * d} numbers must be "
            f"multiples of 128 (index_tiles)")
    return _index_pallas(qI, kI, w, last_pos, interpret=interpret)
