"""Pallas TPU ragged paged attention for the paged serving path.

The XLA reference (`models/llama/paged.py:paged_attention`, kept as the
fold implementation) is a `lax.fori_loop` over ALL `max_pages` table
columns: every decode step, every layer, every row folds the whole page
axis, so a 3-page request pays the same gather traffic as a 32-page one
and page reads never stay resident in VMEM. This kernel is the
TPU-native formulation of the same online-softmax fold (the "Ragged
Paged Attention" shape, PAPERS.md arxiv 2604.15464):

  * the DECODE kernel (`ragged_paged_attention`) runs one grid step a
    ROW and walks the row's live pages itself: a loop of
    `pos // page + 1` trips (a dynamic count) fetches page
    `table[row, j]` of the layer out of the pool, which stays whole in
    HBM, with the kernel's own copies into a ring of VMEM slots, and
    folds it into the row's f32 (m, l, acc), the flash-attention
    recurrence of `ops/flash_attention.py`, carried in registers. A
    call costs what its live pages cost: a row that holds 3 pages of
    a 16-page table takes 3 trips, an idle row none (the (rows, pages)
    grid this replaced paid ~0.3 us for each of its cells, live or not:
    PERF.md section 6, PR 42);
  * the page table and per-row positions ride as scalar-prefetched SMEM
    operands: the pool is indexed directly by physical page id, no
    host-side gather and no dense per-row copy;
  * the ring: K and V of the pages AHEAD are in flight while page j
    folds, `decode_ring_depth` slots from the page's bytes (a static
    shape), and the pages ahead are the NEXT rows' when this row's run
    out, so only a call's first row starts cold. An unmapped hole
    inside the live range starts no copy and folds nothing;
  * the MIXED kernel keeps the older form: grid (rows, pages), page
    axis innermost and sequential, one page a grid step through a k/v
    BlockSpec whose index map resolves `table[row, j]`; pages past the
    row's live count clamp their index to the last live page, so
    Pallas elides the repeated DMA, and `pl.when` skips the compute;
  * causal + unmapped-page masking inside a live page (absolute slot
    `j*page + t` attends iff `<= pos` and the page id is mapped);
  * GQA without repeat_kv: the KV-head axis is unrolled statically
    inside the kernel (KV is 2-8 in practice), so query group g of kv
    head k reads exactly its own `hd`-wide lane slice of the page block
    — each live page is streamed through VMEM ONCE for all H heads;
  * page-granular PREFIX SHARING is free at decode: the kernel only
    ever reads pages through the table, so the same physical page id
    appearing in many rows' table heads (a shared system prompt's KV,
    serve/engine page-granular prefix sharing) needs zero kernel
    changes — each row streams the shared page like any other, and
    nothing here ever writes the pool.

Layout contract: the kernels take the STACKED pool as it is stored,
[L, N_pages, page, KV*hd] (`models/llama/paged.py`; a packed int4 pool
[L, N_pages, page//2, KV*hd]), and the layer as one more scalar-prefetch
operand. What a copy (decode) or a k/v block (mixed) moves is (layer,
page) -> one (page, KV*hd) tile, lane-aligned when hd is a multiple of
128, DMA'd straight out of the pool: the wrappers neither slice a
layer out nor reshape anything. (A
reshape from a per-head [.., KV, hd] pool to this shape is a relayout
of the whole pool on the chip — four times the kernel's own time,
PERF.md PR 24 — which is why the pool is STORED this way.)

The MIXED variant (`ragged_paged_attention_mixed`) extends the row
metadata with a per-row query length: one grid processes decode rows
(q_len=1) and prefill-chunk rows (q_len=C at arbitrary page offset)
in the same launch — the token-level continuous-batching step the
engine's `mixed_step_paged` path dispatches, with per-row causal
masking and the same per-row early exit. Its work in a (row, page)
cell follows q_len too: a decode row folds its one query, not its
window (`_mixed_fold`, MIXED_Q_TILE).

CPU tests run the same kernel with interpret=True
(tests/test_ragged_paged_attn.py), mirroring flash_attention.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dot(a, b, *, trans_b: bool):
    """MXU dot with f32 accumulation: a @ b.T (scores) or a @ b (the
    value fold). bf16 operands pin DEFAULT precision — their products
    are exact in one pass, and Mosaic refuses a bf16 lhs under an fp32
    contract precision ("Bad lhs type", v5e, PR 21), which is what a
    process-wide jax_default_matmul_precision=highest (the test lane's
    setting) would otherwise hand the kernel."""
    return jax.lax.dot_general(
        a, b, (((1,), (1 if trans_b else 0,)), ((), ())),
        precision=(jax.lax.Precision.DEFAULT
                   if a.dtype == jnp.bfloat16 else None),
        preferred_element_type=jnp.float32)


def _unpack_nibbles(block, hd_slice):
    """In-register nibble unpack of one packed int4 page block column
    slice: block [P//2, hd] uint8 -> [P, hd] f32 in [-8, 7]. The pool's
    pack_page_nibbles layout puts token t in the low nibble of packed
    row t and token t + P//2 in the high nibble, so concatenating the
    two half-planes along the sublane axis restores natural token
    order."""
    p32 = block[:, hd_slice].astype(jnp.int32)
    return jnp.concatenate([(p32 & 0xF) - 8, (p32 >> 4) - 8],
                           axis=0).astype(jnp.float32)


def _decode_fold(q, k_page, v_page, scales, j, pos, stats, *, scale: float,
                 page_size: int, kv_heads: int, group: int, head_dim: int,
                 packed4: bool, window: Optional[int] = None):
    """Fold one live page of a decode row into its online-softmax
    stats: the one body of the float, int8 and int4 pools.

    q:       [H, hd], the row's single query, all heads (f32 over a
             quantized pool)
    k_page/v_page: [page, KV*hd] views of the ring slot that holds
             logical page j of the row (nibble-PACKED int4:
             [page//2, KV*hd], unpacked in registers a kv head)
    scales:  None for a float pool, else `scales(kv) -> (k, v)`, the
             page's per-kv-head dequantization scales out of SMEM. One
             scale covers a page's every column of a kv head, so it
             folds into the dot OUTPUTS: no dequantized page exists.
    stats:   (m [H, 1], l [H, 1], acc [H, hd]) f32, the flash-attention
             recurrence of `ops/flash_attention.py`; returned updated.
    window:  the band (walk_live_pages): slots at or below pos - window
             are masked as the slots past pos are.
    """
    P = page_size
    hd = head_dim
    G = group
    m_prev, l_prev, acc = stats

    def head(page, kv):
        lanes = slice(kv * hd, (kv + 1) * hd)
        if packed4:
            return _unpack_nibbles(page[...], lanes)       # [P, hd] f32
        h = page[:, lanes]
        return h if scales is None else h.astype(jnp.float32)

    # causal mask over the page's absolute slots (current token
    # included); every folded page has >= 1 valid column, so the online
    # max below never sees a fully-masked row
    col = j * P + jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
    col_valid = col <= pos                         # [1, P]
    if window is not None:
        col_valid = jnp.logical_and(col_valid, col > pos - window)
    # scores a kv head: query group g of kv head k against the page's
    # k-lane slice (static unroll: KV is small)
    parts = []
    for kv in range(kv_heads):
        s_kv = _dot(q[kv * G:(kv + 1) * G], head(k_page, kv), trans_b=True)
        parts.append(s_kv if scales is None else s_kv * scales(kv)[0])
    s = jnp.concatenate(parts, axis=0) * scale     # [H, P]
    s = jnp.where(col_valid, s, NEG_INF)

    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                         # [H, P]
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    outs = []
    for kv in range(kv_heads):
        vh = head(v_page, kv)                      # [P, hd]
        o_kv = _dot(p[kv * G:(kv + 1) * G].astype(vh.dtype), vh,
                    trans_b=False)
        outs.append(o_kv if scales is None else o_kv * scales(kv)[1])
    return m_new, l_new, acc * alpha + jnp.concatenate(outs, axis=0)


def walk_live_pages(layer_ref, pos_ref, table_ref, cur, copies, *,
                    depth: int, page_size: int,
                    window: Optional[int] = None):
    """The walk of a decode kernel whose grid step is one ROW: the row's
    live pages 0 .. pos // page, fetched out of pools that lie whole in
    HBM by the kernel's own copies into rings of `depth` VMEM slots,
    the pages ahead in flight while page j folds.

    window (static): a BAND. The row attends its last `window` keys,
    its own included, so the walk starts at the page that holds
    position pos - window + 1 and takes the trips from there to
    pos // page (two at a window of one page, whatever the context),
    and logical page p is read through table entry p mod max_pages: a
    table of every page a row can hold reads as it always did, and a
    RING of R entries (models/llama/paged.WindowedKVCache.wtable)
    serves its logical page p from entry p mod R. None: no band, and
    the program is the one it was.

    copies(layer, pid, slot): the async copies of the layer's page
             `pid` into ring slot `slot`, one a pool (K and V; a latent
             pool is a list of one), the same to start and to wait on.
    cur:     SMEM int32 [4]: the copies' cursor (row, page, count of
             pages started) and the count of pages folded, carried from
             row to row: the pages ahead are the NEXT rows' when this
             row's run out, so the ring is warm at every row but the
             call's first. An unmapped hole inside the live range
             starts no copy and folds nothing; a row with no live page
             (pos < 0) takes no trip.

    Primes the ring at the call's first row, then returns (pos, pages):
    the row's position, and pages(fold, stats) -> stats, the loop over
    the row's live pages with fold(j, pid, slot, stats) called once the
    copies of logical page j (page id pid) have landed in `slot`."""
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = layer_ref[0]
    max_pages = table_ref.shape[1]

    def first_page(row):
        """The logical page a row's walk starts at."""
        if window is None:
            return 0
        return jnp.maximum(pos_ref[row] - (window - 1), 0) // page_size

    def live_pages(row):
        if window is None:
            return jnp.clip(pos_ref[row] // page_size + 1, 0, max_pages)
        pos = pos_ref[row]
        return jnp.where(
            pos >= 0,
            jnp.minimum(pos // page_size - first_page(row) + 1, max_pages),
            0)

    def page_id(row, j):
        """The page of trip j of a row's walk."""
        if window is None:
            return table_ref[row, j]
        return table_ref[row, (first_page(row) + j) % max_pages]

    def next_live_row(row):
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < nb, live_pages(jnp.minimum(r, nb - 1)) == 0),
            lambda r: r + 1, row)

    def start_next():
        row, j, count = cur[0], cur[1], cur[2]

        @pl.when(row < nb)
        def _():
            # an unmapped hole inside the live range starts no copy
            # (and folds nothing, below): its slot stays idle
            pid = page_id(row, j)

            @pl.when(pid >= 0)
            def _():
                for c in copies(layer, pid, count % depth):
                    c.start()

            cur[2] = count + 1
            row_ends = j + 1 == live_pages(row)

            @pl.when(row_ends)
            def _():
                cur[0] = next_live_row(row + 1)
                cur[1] = 0

            @pl.when(jnp.logical_not(row_ends))
            def _():
                cur[1] = j + 1

    @pl.when(b == 0)
    def _():
        cur[0] = next_live_row(0)
        cur[1] = 0
        cur[2] = 0
        cur[3] = 0

        def prime(_, carry):
            start_next()
            return carry

        jax.lax.fori_loop(0, depth - 1, prime, 0)

    n = live_pages(b)
    first = cur[3]
    cur[3] = first + n
    j0 = first_page(b)

    def pages(fold, stats):
        def landed(j, pid, stats):
            slot = (first + j) % depth
            for c in copies(layer, pid, slot):
                c.wait()
            return fold(j if window is None else j0 + j, pid, slot, stats)

        def page(j, stats):
            # the slot this frees held page j - 1, folded a step ago
            start_next()
            pid = page_id(b, j)
            return jax.lax.cond(pid >= 0, lambda s: landed(j, pid, s),
                                lambda s: s, stats)

        return jax.lax.fori_loop(0, n, page, stats)

    return pos_ref[b], pages


def _decode_kernel(layer_ref, pos_ref, table_ref, *refs, quantized: bool,
                   depth: int, page_size: int, kv_heads: int,
                   window: Optional[int] = None, **fold_shape):
    """One grid step: one ROW of the ragged decode fold, its live pages
    of the layer walked by `walk_live_pages`, K and V together.

    sk_ref/sv_ref (a quantized pool only): the layer's flat scales
    q_ref/o_ref:   [1, 1, H, hd], the row's query and result
    k_hbm/v_hbm:   [L, N_pages, page, KV*hd], never read but by a copy
    kbuf/vbuf:     [depth, page, KV*hd] VMEM; sem: DMA [2, depth]
    cur:           SMEM int32 [4], the walk's
    """
    if quantized:
        sk_ref, sv_ref, *refs = refs
    q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, cur = refs
    H, hd = q_ref.shape[2:]

    def copies(layer, pid, slot):
        return [pltpu.make_async_copy(pool.at[layer, pid], buf.at[slot],
                                      sem.at[i, slot])
                for i, (pool, buf) in enumerate(((k_hbm, kbuf),
                                                 (v_hbm, vbuf)))]

    pos, pages = walk_live_pages(layer_ref, pos_ref, table_ref, cur, copies,
                                 depth=depth, page_size=page_size,
                                 window=window)
    q = q_ref[0, 0]                                # [H, hd]
    if quantized:
        q = q.astype(jnp.float32)

    def fold(j, pid, slot, stats):
        scales = None
        if quantized:
            def scales(kv):
                at = pid * kv_heads + kv
                return sk_ref[at], sv_ref[at]
        return _decode_fold(q, kbuf.at[slot], vbuf.at[slot], scales, j, pos,
                            stats, page_size=page_size, kv_heads=kv_heads,
                            window=window, **fold_shape)

    _, l, acc = pages(fold, (jnp.full((H, 1), NEG_INF, jnp.float32),
                             jnp.zeros((H, 1), jnp.float32),
                             jnp.zeros((H, hd), jnp.float32)))
    # a row that folded no page (an idle slot, an all-unmapped table)
    # has l == 0 and started no copy: emit zeros, matching the fold
    # reference's merge_attention_stats guard
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def _layer_scales(scale, layer):
    """[L, N_pages, KV] per-page scales -> the layer's, as the 1-D
    [N_pages*KV] f32 array the quantized kernels prefetch (index
    page*KV + kv). SMEM pads a 2-D array's minor dim to 128 words, so
    the 2-D form cost 16x its bytes at KV=8 (f32[2048, 8] took the
    whole 1.00M of SMEM under libtpu 0.0.34); 1-D costs what it holds.
    A layer's scales are N_pages*KV words — the slice is not a pool."""
    scale = jnp.asarray(scale, jnp.float32)
    return jax.lax.dynamic_index_in_dim(
        scale, layer, axis=0, keepdims=False).reshape(-1)


def _pool_block(Pb: int, width: int, index_map):
    """One page of one layer: the stacked pool's (layer, page) tile,
    with the layer axis squeezed so the kernel bodies see
    [1, page, KV*hd]."""
    return pl.BlockSpec((None, 1, Pb, width), index_map)


def ragged_paged_attention(q, pool_k, pool_v, layer, table, pos, *,
                           scale: float | None = None,
                           scale_k=None, scale_v=None,
                           packed4: bool = False,
                           window: Optional[int] = None,
                           interpret: bool | None = None):
    """Ragged decode attention over a paged KV pool, one Pallas kernel.

    q:            [B, 1, H, hd] — rope applied; the current token's KV
                  must already be written to its page (the
                  update_pool_per_row contract).
    pool_k/pool_v:[L, N_pages, page, KV*hd] — the stacked pool, as
                  stored; only pages of `layer` are read
    layer:        int32 scalar (traced: the layer loop's counter)
    table:        [B, max_pages] int32 page ids, -1 = unmapped
    pos:          [B] int32 — position of the CURRENT token per row
    scale_k/scale_v: optional [L, N_pages, KV] f32 per-page per-kv-head
                  dequantization scales — present iff the pool is the
                  int8/int4 KV tier (cake_tpu/kv); pages then stream
                  quantized and the layer's scales prefetch into SMEM.
    packed4:      the pool is nibble-PACKED int4
                  ([L, N_pages, page//2, KV*hd] uint8, kv/quantized_pool
                  pack_page_nibbles layout); requires scale_k/scale_v.
    window:       static. A row attends its last `window` keys, its own
                  included, and walks the pages that hold them alone;
                  `table` may then be a ring (walk_live_pages). None:
                  every key up to pos.
    Returns [B, 1, H, hd] in q.dtype. Numerically matches
    `models/llama/paged.py:paged_attention` (the fold reference) to f32
    tolerance — tests/test_ragged_paged_attn.py pins the parity.
    """
    B, S, H, hd = q.shape
    if S != 1:
        raise ValueError(f"decode kernel takes one query per row, got S={S}")
    _, N, Pb, width = pool_k.shape
    KV = width // hd
    P = Pb * 2 if packed4 else Pb       # REAL tokens per page
    G = H // KV
    max_pages = table.shape[1]
    quantized = scale_k is not None
    if packed4 and not quantized:
        raise ValueError("packed4 pools require scale_k/scale_v")
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    if interpret is None:
        interpret = not _on_tpu()
    if not interpret and not ragged_paged_supported(
            P, H, KV, hd, quantized=quantized, n_pages=N,
            packed4=packed4, slots=B, max_pages=max_pages,
            kv_itemsize=pool_k.dtype.itemsize):
        raise ValueError(
            f"ragged paged attention cannot run on this chip at page="
            f"{P} H={H} KV={KV} hd={hd} pool={pool_k.dtype} pages={N} "
            f"table={B}x{max_pages} (ragged_paged_supported); use the "
            "fold")

    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    operands = [layer, jnp.asarray(pos, jnp.int32),
                jnp.asarray(table, jnp.int32)]
    if quantized:
        operands += [_layer_scales(scale_k, layer[0]),
                     _layer_scales(scale_v, layer[0])]
    depth = decode_ring_depth(Pb * width * pool_k.dtype.itemsize)
    kernel = functools.partial(
        _decode_kernel, quantized=quantized, depth=depth, scale=scale,
        page_size=P, kv_heads=KV, group=G, head_dim=hd, packed4=packed4,
        window=window)
    row = pl.BlockSpec((1, 1, H, hd), lambda b, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(operands),
        grid=(B,),
        in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((depth, Pb, width), pool_k.dtype),
            pltpu.VMEM((depth, Pb, width), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, depth)),
            pltpu.SMEM((4,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, hd), q.dtype),
        name="cake_decode_attn",
        # the ring's copies run ahead into the next row
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(*operands, q, pool_k, pool_v)


# Queries to a TILE of the mixed kernel's window. A (row, page) grid
# cell folds the row's first tile when that holds its every real query
# (a decode row, q_len 1) and its whole window otherwise, so a decode
# row costs MIXED_Q_TILE*G rows of scores a page and kv head, not C*G.
# Every slice is static, so the tile need not fill a sublane tile; on a
# v5e a call of 14 decode rows and two windows took, at Mistral's /
# OLMoE's shapes, 242 / 282 us at a tile of 1, 244 / 283 at 2, 260 / 283
# at 4, 285 / 306 at 16 (698 / 410 before; PERF.md section 6, PR 34).
MIXED_Q_TILE = 1


def mixed_q_tiles(q_len: int, q_width: int) -> int:
    """Query tiles the mixed kernel folds a live page into for a row of
    q_len real queries in a window of q_width: one, or all of them.
    The host's count of the kernel's work (obs/steps `attn_q_tiles`)
    is this, summed over a step's active rows."""
    return 1 if q_len <= MIXED_Q_TILE else -(-q_width // MIXED_Q_TILE)


def _mixed_first_page(pos, page_size: int, window: Optional[int]):
    """The logical page grid step 0 of a mixed row stands for: the page
    that holds the first key its first query attends under a band of
    `window` keys (0 with no band: python, so that the program without
    one is the one it was)."""
    if window is None:
        return 0
    return jnp.maximum(pos - (window - 1), 0) // page_size


def _mixed_fold(pos_ref, qlen_ref, table_ref, q_ref, o_ref, acc_ref, m_ref,
                l_ref, page_kv, *, scale: float, page_size: int,
                kv_heads: int, group: int, head_dim: int, q_width: int,
                window: Optional[int] = None, sel_ref=None):
    """One (row, page) grid step of the MIXED ragged fold: each row
    carries q_width query slots of which q_len are real — a decode row
    (q_len=1) and a prefill-chunk row (q_len=C at arbitrary page
    offset) fold through the same grid. The one body of the float,
    int8 and int4 kernels: `page_kv(kv, pid)` hands it kv head `kv` of
    the page as (kh, vh [P, hd], k_scale, v_scale), the scales None for
    a float pool.

    q_ref:   [1, C, H, hd] — the row's query window, first token at
             absolute position pos
    scratch: acc [KV*C*G, hd] f32, m/l [KV*C*G, 128] f32, rows ordered
    (kv, query, group) so each kv head's fold is a contiguous slice;
    carried across the page axis exactly like the decode kernel.

    The work follows q_len: a row whose real queries all lie in its
    first tile (MIXED_Q_TILE queries: a decode row, an idle row) folds,
    initialises and finishes that tile alone — scratch rows
    [kv*C*G, +Tq*G) of each kv head — and any other row its whole
    window, in one piece (folding a window tile by tile under a loop
    cost a full window 1.8x the time on a v5e: each tile pays for the
    page's K and V again). A query's online softmax runs page by page
    in the same order either way, so its result does not depend on its
    row's q_len, nor on the other rows'. Output columns: those of a
    tile the row did not fold are ZERO (written at the row's first
    page); padded columns of a folded span are what they always were,
    the fold of a query that is not there — finite, never read.

    window (static): a BAND. Query i attends keys pos + i - window + 1
    .. pos + i. Grid step j then stands for logical page first + j,
    first the page of the first key the row's first query attends, so
    that no page wholly before the band is fetched or folded, and the
    mask cuts the rest; logical page p is read through table entry
    p mod max_pages (a ring of R entries serves page p from entry
    p mod R; a whole table reads as it did).

    sel_ref (None: no such operand, and the program is the one it was):
    [1, 1, C, P] float32, the grid step's page of a per-(query, key)
    selection: query i attends a key of this page only where its entry
    is above 0.5 (a sparse indexer's sets: models/moe/keye_vl2.py). A
    query's row reaches its G rows of the scores by a sublane
    broadcast, as ops/mla_attention._spread's.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    C = q_width
    G = group
    P = page_size
    hd = head_dim
    Tq = min(MIXED_Q_TILE, C)

    pos = pos_ref[b]
    # last REAL query's absolute position bounds the live page count;
    # q_len=0 (idle row) clamps to pos so the row still costs one page
    # of masked compute, never a negative bound
    n_q = jnp.maximum(qlen_ref[b], 1)
    last = pos + n_q - 1
    # lp: the logical page this grid step stands for
    if window is None:
        lp = j
        page = table_ref[b, j]
    else:
        lp = _mixed_first_page(pos, P, window) + j
        page = table_ref[b, lp % nj]
    live = jnp.logical_and(lp * P <= last, page >= 0)

    def span(body):
        """body(nq) once, nq (static) the queries this row folds."""
        if Tq == C:
            body(C)
        else:
            pl.when(n_q <= Tq)(functools.partial(body, Tq))
            pl.when(n_q > Tq)(functools.partial(body, C))

    def rows(kv, nq):
        return slice(kv * C * G, kv * C * G + nq * G)

    def heads(kv):
        return slice(kv * G, (kv + 1) * G)

    @pl.when(j == 0)
    def _init():
        def init(nq):
            if nq < C:
                o_ref[0, nq:] = jnp.zeros((C - nq,) + o_ref.shape[2:],
                                          o_ref.dtype)
            for kv in range(kv_heads):
                r = rows(kv, nq)
                acc_ref[r] = jnp.zeros((nq * G, hd), jnp.float32)
                m_ref[r] = jnp.full((nq * G, m_ref.shape[1]), NEG_INF,
                                    jnp.float32)
                l_ref[r] = jnp.zeros((nq * G, l_ref.shape[1]), jnp.float32)
        span(init)

    @pl.when(live)
    def _fold():
        pid = jnp.maximum(page, 0)

        def fold(nq):
            R = nq * G
            # per-(query, column) causal mask: query i sits at absolute
            # position pos + i and attends page slots <= it (current
            # token included — its KV is written before the kernel runs)
            qidx = jax.lax.broadcasted_iota(jnp.int32, (R, P), 0) // G
            col = lp * P + jax.lax.broadcasted_iota(jnp.int32, (R, P), 1)
            valid = col <= pos + qidx
            if window is not None:
                valid = jnp.logical_and(valid, col > pos + qidx - window)
            if sel_ref is not None:
                picked = jnp.concatenate(
                    [jnp.broadcast_to(sel_ref[0, 0, i:i + 1, :], (G, P))
                     for i in range(nq)], axis=0)            # [R, P]
                valid = jnp.logical_and(valid, picked > 0.5)
            for kv in range(kv_heads):
                kh, vh, k_scale, v_scale = page_kv(kv, pid)  # [P, hd]
                qh = q_ref[0, :nq, heads(kv), :].reshape(R, hd)
                if k_scale is None:
                    s = _dot(qh, kh, trans_b=True) * scale   # [R, P]
                else:
                    # dequantization folds into the dot outputs: one
                    # scale covers a page's every column of a kv head
                    s = _dot(qh.astype(jnp.float32), kh,
                             trans_b=True) * (scale * k_scale)
                s = jnp.where(valid, s, NEG_INF)
                r = rows(kv, nq)
                m_prev = m_ref[r, :1]                        # [R, 1]
                m_cur = jnp.max(s, axis=-1, keepdims=True)
                m_new = jnp.maximum(m_prev, m_cur)
                alpha = jnp.exp(m_prev - m_new)
                # a query whose causal horizon precedes this page (or an
                # all-hole row) has every column masked: m_new stays
                # NEG_INF and exp(s - m_new) would be exp(0)=1 garbage —
                # the explicit mask multiply keeps its l at 0 so _finish
                # emits zeros, matching the fold reference's guard
                p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
                l_new = (alpha * l_ref[r, :1]
                         + jnp.sum(p, axis=-1, keepdims=True))
                out = _dot(p.astype(vh.dtype), vh,
                           trans_b=False)                    # [R, hd]
                if v_scale is not None:
                    out = out * v_scale
                acc_ref[r] = acc_ref[r] * alpha + out
                m_ref[r] = jnp.broadcast_to(m_new, (R, m_ref.shape[1]))
                l_ref[r] = jnp.broadcast_to(l_new, (R, l_ref.shape[1]))
        span(fold)

    @pl.when(j == nj - 1)
    def _finish():
        def finish(nq):
            for kv in range(kv_heads):
                r = rows(kv, nq)
                l = l_ref[r, :1]
                l = jnp.where(l == 0.0, 1.0, l)
                o = (acc_ref[r] / l).reshape(nq, G, hd)
                o_ref[0, :nq, heads(kv), :] = o.astype(o_ref.dtype)
        span(finish)


def _rpa_mixed_kernel(layer_ref, pos_ref, qlen_ref, table_ref, q_ref, k_ref,
                      v_ref, *refs, head_dim: int, selecting: bool = False,
                      **shape):
    """The mixed kernel over a float pool: k_ref/v_ref [1, page, KV*hd],
    one physical page, a kv head a lane slice. selecting: one more
    input follows them, the selection's page (_mixed_fold's sel_ref)."""
    hd = head_dim
    sel_ref = None
    if selecting:
        sel_ref, *refs = refs
    o_ref, acc_ref, m_ref, l_ref = refs

    def page_kv(kv, pid):
        lanes = slice(kv * hd, (kv + 1) * hd)
        return k_ref[0, :, lanes], v_ref[0, :, lanes], None, None

    _mixed_fold(pos_ref, qlen_ref, table_ref, q_ref, o_ref, acc_ref, m_ref,
                l_ref, page_kv, head_dim=hd, sel_ref=sel_ref, **shape)


def _rpa_mixed_kernel_q(layer_ref, pos_ref, qlen_ref, table_ref, sk_ref,
                        sv_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                        l_ref, *, packed4: bool, kv_heads: int,
                        head_dim: int, **shape):
    """The mixed kernel over a quantized pool: pages stream as int8 (a
    quarter of the f32 page bytes) or nibble-PACKED int4 (an eighth;
    the block holds page_size//2 sublanes and unpacks in registers per
    kv head), and the per-(page, kv-head) scales prefetch into SMEM —
    the decode q8/q4 kernels' scheme with the mixed kernel's per-row
    query width."""
    hd = head_dim

    def page_kv(kv, pid):
        lanes = slice(kv * hd, (kv + 1) * hd)
        if packed4:
            kh = _unpack_nibbles(k_ref[0], lanes)
            vh = _unpack_nibbles(v_ref[0], lanes)
        else:
            kh = k_ref[0, :, lanes].astype(jnp.float32)
            vh = v_ref[0, :, lanes].astype(jnp.float32)
        at = pid * kv_heads + kv
        return kh, vh, sk_ref[at], sv_ref[at]

    _mixed_fold(pos_ref, qlen_ref, table_ref, q_ref, o_ref, acc_ref, m_ref,
                l_ref, page_kv, kv_heads=kv_heads, head_dim=hd, **shape)


def ragged_paged_attention_mixed(q, pool_k, pool_v, layer, table, pos,
                                 q_len, *,
                                 scale: float | None = None,
                                 scale_k=None, scale_v=None,
                                 packed4: bool = False,
                                 window: Optional[int] = None,
                                 selected=None,
                                 interpret: bool | None = None):
    """MIXED ragged attention over a paged KV pool, one Pallas kernel.

    The per-row query-length extension of `ragged_paged_attention`: one
    grid handles decode rows (q_len=1) and prefill-chunk rows (q_len=C
    at arbitrary page offset) in the same launch, with per-row causal
    masking and the same per-row early exit (a row streams only the
    pages up to ceil((pos + q_len) / page)).

    q:            [B, C, H, hd] — rope applied; every real query
                  token's KV must already be written to its page (the
                  write_windows_pages contract). Columns past q_len are
                  padding the caller never reads (the step fn samples
                  at column q_len - 1); their output is finite: zero
                  past the first tile of a row that folds no more
                  (_mixed_fold), else the fold of a query that is not
                  there.
    pool_k/pool_v:[L, N_pages, page, KV*hd] — the stacked pool, as
                  stored; only pages of `layer` are read
    layer:        int32 scalar (traced: the layer loop's counter)
    table:        [B, max_pages] int32 page ids, -1 = unmapped
    pos:          [B] int32 — absolute position of each row's FIRST
                  query token (decode rows: the current token's
                  position, exactly the decode kernel's pos)
    q_len:        [B] int32 — real query tokens per row (0 = idle row,
                  output zeros)
    window:       static. Query i of a row attends keys pos + i -
                  window + 1 .. pos + i, and the row's grid steps are
                  the pages from its first query's first key on;
                  `table` may then be a ring (_mixed_fold). None: every
                  key up to the query.
    selected:     [B, max_pages, C, page] float32, or None. Query
                  (b, i) attends key j * page + o only where
                  selected[b, j, i, o] is above 0.5, among the keys
                  causality leaves it (a sparse indexer's sets), BY
                  PAGE: the [C, page] block a grid step needs is one
                  contiguous copy beside its K and V pages (query-major,
                  it was C rows of 512 B a step). A float pool without a
                  band only. None: no such operand, and the program is
                  the one it was.
    Returns [B, C, H, hd] in q.dtype. Numerically matches
    `models/llama/paged.py:paged_attention_mixed` (the fold reference)
    to f32 tolerance — tests/test_ragged_paged_attn.py pins the parity.
    """
    B, C, H, hd = q.shape
    _, N, Pb, width = pool_k.shape
    KV = width // hd
    P = Pb * 2 if packed4 else Pb       # REAL tokens per page
    G = H // KV
    max_pages = table.shape[1]
    quantized = scale_k is not None
    if packed4 and not quantized:
        raise ValueError("packed4 pools require scale_k/scale_v")
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    if interpret is None:
        interpret = not _on_tpu()
    if not interpret and not ragged_paged_mixed_supported(
            P, H, KV, hd, C, quantized=quantized, n_pages=N,
            packed4=packed4, slots=B, max_pages=max_pages,
            q_itemsize=q.dtype.itemsize,
            kv_itemsize=pool_k.dtype.itemsize):
        raise ValueError(
            f"mixed ragged paged attention cannot run on this chip at "
            f"page={P} H={H} KV={KV} hd={hd} C={C} pool={pool_k.dtype} "
            f"pages={N} table={B}x{max_pages} "
            "(ragged_paged_mixed_supported); use the fold or a "
            "narrower window")

    if selected is not None and (quantized or window is not None):
        raise ValueError("a selection is served over a float pool "
                         "without a band only")

    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def sel_index(b, j, layer_ref, pos_ref, qlen_ref, table_ref):
        # (dead pages clamp to the last live one, as kv_index's)
        last = pos_ref[b] + jnp.maximum(qlen_ref[b], 1) - 1
        return (b, jnp.minimum(j, last // P), 0, 0)

    def kv_index(b, j, layer_ref, pos_ref, qlen_ref, table_ref, *_scales):
        # clamp dead pages (past the row's live count) to the LAST live
        # page — the repeated block index elides the DMA, so a row
        # streams only the pages its window actually covers
        last = pos_ref[b] + jnp.maximum(qlen_ref[b], 1) - 1
        if window is None:
            jj = jnp.minimum(j, last // P)
        else:
            jj = jnp.minimum(
                _mixed_first_page(pos_ref[b], P, window) + j,
                last // P) % max_pages
        page = table_ref[b, jj]
        return (layer_ref[0], jnp.maximum(page, 0), 0, 0)

    if quantized:
        kernel = functools.partial(
            _rpa_mixed_kernel_q, packed4=packed4, scale=scale, page_size=P,
            kv_heads=KV, group=G, head_dim=hd, q_width=C, window=window)
        n_prefetch = 6
        operands = (layer, jnp.asarray(pos, jnp.int32),
                    jnp.asarray(q_len, jnp.int32),
                    jnp.asarray(table, jnp.int32),
                    _layer_scales(scale_k, layer[0]),
                    _layer_scales(scale_v, layer[0]),
                    q, pool_k, pool_v)
    else:
        kernel = functools.partial(
            _rpa_mixed_kernel, scale=scale, page_size=P, kv_heads=KV,
            group=G, head_dim=hd, q_width=C, window=window)
        n_prefetch = 4
        operands = (layer, jnp.asarray(pos, jnp.int32),
                    jnp.asarray(q_len, jnp.int32),
                    jnp.asarray(table, jnp.int32), q, pool_k, pool_v)
    selecting = []
    if selected is not None:
        kernel = functools.partial(kernel, selecting=True)
        operands += (selected.astype(jnp.float32),)
        selecting = [pl.BlockSpec((1, 1, C, P), sel_index)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, C, H, hd), lambda b, j, *_: (b, 0, 0, 0)),
            _pool_block(Pb, width, kv_index),
            _pool_block(Pb, width, kv_index),
        ] + selecting,
        out_specs=pl.BlockSpec((1, C, H, hd),
                               lambda b, j, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV * C * G, hd), jnp.float32),
            pltpu.VMEM((KV * C * G, 128), jnp.float32),
            pltpu.VMEM((KV * C * G, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, H, hd), q.dtype),
        name="cake_mixed_attn",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# What the compiler gives one kernel on a v5e TensorCore, read from its
# own refusals: AOT compiles against a v5e topology (jax 0.9.0, libtpu
# 0.0.34, PR 21) said "Scoped allocation with size 16.04M and limit
# 16.00M exceeded scoped vmem limit" for the mixed kernel at H=32,
# hd=128, C=256, and "Used 2.01M of 1.00M smem" for two f32[2048, 8]
# scale operands. The kernels set no vmem_limit_bytes, so the default
# scoped limit is the ceiling.
_VMEM_SCOPED_LIMIT = 16 * 2**20
_SMEM_BYTES = 2**20


# The decode kernel's ring: what it keeps in flight AHEAD of the page
# it folds. A page copy lands in well under a microsecond on a v5e, so
# a MiB ahead (K and V together) covers it at 819 GB/s: 8 pairs of
# 64 KiB pages (2 KV heads), 2 of 256 KiB (8), 1 of 512 KiB (16). Read
# on the chip at the cells' shapes, calls of 16 and 32 rows (PERF.md
# section 6, PR 42): ONE pair ahead cost 125 us where two or more cost
# 100 at 64 KiB pages (the fold bounds that row from there on: sixteen
# ahead read the same), 107 against 98 at 256 KiB, and nothing at
# 512 KiB (144 with one ahead, 145 with two to eight).
_RING_BYTES_AHEAD = 2**20
_RING_PAGES_AHEAD_MAX = 16


def decode_ring_depth(page_bytes: int) -> int:
    """Slots of the decode kernel's ring for K (or V) pages of
    `page_bytes`: the one that folds, and _RING_BYTES_AHEAD of K and V
    in flight behind it (a page at least, _RING_PAGES_AHEAD_MAX at
    most: a page of a few KiB is a test's)."""
    ahead = -(-_RING_BYTES_AHEAD // (2 * page_bytes))
    return 1 + min(max(ahead, 1), _RING_PAGES_AHEAD_MAX)


def decode_vmem_bytes(page_bytes: int, H: int, hd: int) -> int:
    """Scoped VMEM the decode kernel asks for: the K and the V ring and
    the double-buffered q and out blocks ([H, hd], counted at four
    bytes). The row's f32 stats live in registers."""
    return (2 * decode_ring_depth(page_bytes) * page_bytes
            + 2 * 2 * H * hd * 4)


def _smem_need(slots: int, max_pages: int, scale_words: int) -> int:
    """Bytes the scalar-prefetch operands take: the [slots, max_pages]
    int32 table pads its minor dim to 128 words; pos/q_len and the flat
    scale arrays (2 x scale_words f32) are 1-D."""
    table = slots * (-(-max_pages // 128) * 128) * 4
    return table + 2 * slots * 4 + 2 * scale_words * 4


def _pool_supported(page_size: int, H: int, KV: int,
                    hd: int, quantized: bool = False,
                    n_pages: Optional[int] = None,
                    packed4: bool = False,
                    slots: Optional[int] = None,
                    max_pages: Optional[int] = None,
                    kv_itemsize: int = 2) -> bool:
    """What both kernels' gates ask of a pool: the shape classes whose
    numbers were checked against the fold ON A CHIP (v5e, PR 21), plus
    the SMEM bound and the decode kernel's ring in VMEM.

    Float pools: hd a multiple of 16 and pages of 8 tokens or more —
    hd 16/64/128 with 8/16/64/128-token bf16 pages all compiled under
    Mosaic 0.9.0 and matched to bf16 resolution (max abs error 8e-3),
    so the old "lane-filling head dim" rule was a guess the compiler
    does not share. Quantized pools: only the production class was
    run — hd a multiple of 128 and a page that fills the pool dtype's
    sublane tile (32 int8 rows; 64 real tokens for a packed int4 block
    of 32) — so narrower ones stay on the fold there and keep
    exercising the kernel in interpret mode on the CPU.

    The SMEM rule bounds the scalar-prefetch operands (the page table,
    and a quantized pool's whole-pool scales) against the measured
    1 MiB; pass n_pages / slots / max_pages to enforce it. The VMEM
    rule holds the decode kernel's ring of pages (decode_ring_depth
    slots of K and of V; kv_itemsize is a float pool's) under the
    compiler's scoped limit."""
    if H % KV != 0:
        return False
    if packed4 and page_size % 2:
        return False
    if not _on_tpu():
        return True      # interpret mode imposes no tiling constraints
    quantized = quantized or packed4
    if quantized:
        tiles = hd % 128 == 0 and page_size % (64 if packed4 else 32) == 0
    else:
        tiles = hd % 16 == 0 and page_size % 8 == 0
    scale_words = n_pages * KV if quantized and n_pages else 0
    page_bytes = ((page_size // 2 if packed4 else page_size) * KV * hd
                  * (1 if quantized else kv_itemsize))
    return (tiles
            and _smem_need(slots or 0, max_pages or 0,
                           scale_words) <= _SMEM_BYTES
            and decode_vmem_bytes(page_bytes, H, hd) <= _VMEM_SCOPED_LIMIT)


def ragged_paged_supported(page_size: int, H: int, KV: int, hd: int,
                           **pool) -> bool:
    """Static shape gate for the DECODE kernel on the hardware path:
    the pool's rules (_pool_supported, same arguments) and, on a chip,
    a page row that fills whole lane tiles. The kernel's own copies
    slice a (page, KV*hd) tile out of the pool in HBM, and Mosaic
    admits such a slice only where KV*hd is a multiple of 128
    ("Slice shape along dimension 3 must be aligned to tiling (128)":
    compiler, PR 42, at 32, 64 and 192 lanes, float32 and bfloat16,
    pages of 8 to 128 tokens). Every served model's pool is (hd 128;
    hd 64 from 2 KV heads up); a test's hd 16 x 2, MQA at hd 64 and
    2 KV heads of 96 decode through the fold on a chip, where the
    (rows, pages) BlockSpec pipeline this kernel replaced compiled
    them. The mixed kernel still takes them."""
    return (_pool_supported(page_size, H, KV, hd, **pool)
            and (not _on_tpu() or (KV * hd) % 128 == 0))


def mixed_scratch_bytes(H: int, hd: int, q_width: int) -> int:
    """f32 VMEM scratch the mixed kernel allocates per grid cell: the
    [KV*C*G, hd] accumulator plus two [KV*C*G, 128] m/l buffers, and
    KV*G == H. A head narrower than a lane tile pads the accumulator's
    rows to 128 lanes (the compiler at H=32, hd=64, C=256, PR 56:
    "Scoped allocation with size 16.88M", 2.4 MiB over the unpadded
    count)."""
    return 4 * q_width * H * (max(hd, 128) + 256)


def mixed_vmem_bytes(page_size: int, H: int, KV: int, hd: int,
                     q_width: int, q_itemsize: int = 2,
                     kv_itemsize: int = 2) -> int:
    """Scoped VMEM one mixed grid cell needs: the f32 scratch plus the
    double-buffered q and out blocks ([C, H, hd]) and k/v page blocks.
    Checked against the compiler at 20 shapes (H 8-64, KV 1-32, C
    5-512, pages 16-256): every shape it refused needs more than
    _VMEM_SCOPED_LIMIT by this count, and none it accepted was more
    than 0.7 MiB over."""
    q_block = q_width * H * hd * q_itemsize
    kv_block = page_size * KV * hd * kv_itemsize
    return (mixed_scratch_bytes(H, hd, q_width)
            + 2 * 2 * q_block + 2 * 2 * kv_block)


def ragged_paged_mixed_supported(page_size: int, H: int, KV: int,
                                 hd: int, q_width: int,
                                 quantized: bool = False,
                                 n_pages: Optional[int] = None,
                                 packed4: bool = False,
                                 slots: Optional[int] = None,
                                 max_pages: Optional[int] = None,
                                 q_itemsize: int = 2,
                                 kv_itemsize: int = 2) -> bool:
    """Gate for the MIXED hardware kernel: the pool's rules
    (_pool_supported) PLUS a power-of-two GQA group and the VMEM bound. The kernel folds each
    kv head's [C, G, hd] queries to [C*G, hd]; Mosaic does that shape
    cast for G in 1, 2, 4, 8 and refuses it for G=7 ("unsupported shape
    cast", v5e, PR 21). And unlike the C=1 decode kernel, its scratch
    and q/out blocks scale with the query width C: the compiler
    refuses the kernel outright past its scoped limit (at H=32,
    hd=128: C=128 needs 11 MiB and compiles, C=256 needs 21 MiB and
    does not)."""
    if not _pool_supported(page_size, H, KV, hd,
                           quantized=quantized, n_pages=n_pages,
                           packed4=packed4, slots=slots,
                           max_pages=max_pages,
                           kv_itemsize=kv_itemsize):
        return False
    if not _on_tpu():
        return True      # interpret mode allocates host memory
    G = H // KV
    if G & (G - 1):
        return False
    return mixed_vmem_bytes(page_size, H, KV, hd, q_width, q_itemsize,
                            kv_itemsize) <= _VMEM_SCOPED_LIMIT
