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
  * the MIXED kernel walks the same way since PR 62 (it was the last on
    a (rows, pages) grid, one page a grid step through a k/v BlockSpec):
    a grid step a row, the walk bounded by the row's LAST real query,
    `mixed_block` pages side by side in a ring slot and ONE softmax
    update a block, the statistics carried by the loop; an idle row
    takes no trip;
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
operand. What a copy moves is (layer, page) -> one (page, KV*hd) tile,
lane-aligned when hd is a multiple of 128, DMA'd straight out of the
pool: the wrappers neither slice a layer out nor reshape anything. (A
reshape from a per-head [.., KV, hd] pool to this shape is a relayout
of the whole pool on the chip — four times the kernel's own time,
PERF.md PR 24 — which is why the pool is STORED this way.)

The MIXED variant (`ragged_paged_attention_mixed`) extends the row
metadata with a per-row query length: one grid processes decode rows
(q_len=1) and prefill-chunk rows (q_len=C at arbitrary page offset)
in the same launch — the token-level continuous-batching step the
engine's `mixed_step_paged` path dispatches, with per-row causal
masking and the same per-row early exit. Its work a page follows
q_len too: a decode row folds its one query, not its window
(`_mixed_fold`, MIXED_Q_TILE).

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
_NEVER = 2**30      # a position no query reaches


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
                 packed4: bool, window: Optional[int] = None, sel=None):
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
    sel:     None, or [1, page] float32: the page's row of a selection
             (a sparse indexer's set); a slot is attended only where its
             entry is above 0.5, among those the other rules leave.
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
    # included); without a selection every folded page has >= 1 valid
    # column, so the online max below never sees a fully-masked row
    col = j * P + jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
    col_valid = col <= pos                         # [1, P]
    if window is not None:
        col_valid = jnp.logical_and(col_valid, col > pos - window)
    if sel is not None:
        col_valid = jnp.logical_and(col_valid, sel > 0.5)
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
    if sel is not None:
        # a page with nothing selected, before any that has: m_new stays
        # NEG_INF and exp(s - m_new) is exp(0) = 1 (_mixed_fold's guard)
        p = p * col_valid.astype(jnp.float32)
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
                    window: Optional[int] = None, first_ref=None,
                    block: int = 1):
    """The walk of a kernel whose grid step is one ROW: the row's live
    pages 0 .. pos // page, fetched out of pools that lie whole in HBM
    by the kernel's own copies into rings of `depth` VMEM slots, the
    pages ahead in flight while page j folds.

    window (static): a BAND. The row attends its last `window` keys,
    its own included, so the walk starts at the page that holds
    position pos - window + 1 and takes the trips from there to
    pos // page (two at a window of one page, whatever the context),
    and logical page p is read through table entry p mod max_pages: a
    table of every page a row can hold reads as it always did, and a
    RING of R entries (models/llama/paged.WindowedKVCache.wtable)
    serves its logical page p from entry p mod R. None: no band, and
    the program is the one it was.

    first_ref (None: pos_ref): where a row holds SEVERAL queries (the
    mixed kernel), pos_ref gives the position of its last one, which
    bounds the walk, and first_ref that of its first, whose band the
    walk starts at.

    block (static): the pages of a TRIP, side by side in one ring slot,
    so that one fold (one softmax update) takes them all. 1: a trip is
    a page, and the program is the one it was. More: a trip's pages
    past the row's last live one, and its unmapped ones, start no copy
    and are handed to the fold as not fetched (their part of the slot
    keeps what lay there).

    copies(layer, pid, slot, row, p, f): the async copies of the
             layer's page `pid` into place f of ring slot `slot`, one a
             pool (K and V; a latent pool is a list of one), the same
             to start and to wait on. (row, p): whose page it is and
             which of its walk, for a copy that goes by them.
    cur:     SMEM int32 [4]: the copies' cursor (row, trip, count of
             trips started) and the count of trips folded, carried from
             row to row: the pages ahead are the NEXT rows' when this
             row's run out, so the ring is warm at every row but the
             call's first. An unmapped hole inside the live range
             starts no copy and folds nothing; a row with no live page
             (pos < 0) takes no trip.

    Primes the ring at the call's first row, then returns (pos, pages):
    the row's position, and pages(fold, stats) -> stats, the loop over
    the row's trips with fold(j, found, slot, stats) called once the
    copies of the trip that starts at logical page j have landed in
    `slot`; found: [(pid, fetched)], a pair a page of the trip."""
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = layer_ref[0]
    max_pages = table_ref.shape[1]
    if first_ref is None:
        first_ref = pos_ref

    def first_page(row):
        """The logical page a row's walk starts at."""
        if window is None:
            return 0
        return jnp.maximum(first_ref[row] - (window - 1), 0) // page_size

    def live_pages(row):
        if window is None:
            return jnp.clip(pos_ref[row] // page_size + 1, 0, max_pages)
        pos = pos_ref[row]
        return jnp.where(
            pos >= 0,
            jnp.minimum(pos // page_size - first_page(row) + 1, max_pages),
            0)

    def trips(row):
        n = live_pages(row)
        return n if block == 1 else (n + (block - 1)) // block

    def page_id(row, j):
        """The page of place j of a row's walk."""
        if window is None:
            return table_ref[row, j]
        return table_ref[row, (first_page(row) + j) % max_pages]

    def trip_pages(row, j):
        """Trip j of a row's walk: [(place in the walk, page id,
        whether it is fetched)], a triple a page."""
        if block == 1:
            pid = page_id(row, j)
            return [(j, pid, pid >= 0)]
        n = live_pages(row)
        found = []
        for f in range(block):
            p = j * block + f
            pid = page_id(row, jnp.minimum(p, max_pages - 1))
            found.append((p, pid, jnp.logical_and(p < n, pid >= 0)))
        return found

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
            for f, (p, pid, fetched) in enumerate(trip_pages(row, j)):
                @pl.when(fetched)
                def _():
                    for c in copies(layer, pid, count % depth, row, p, f):
                        c.start()

            cur[2] = count + 1
            row_ends = j + 1 == trips(row)

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

    n = trips(b)
    first = cur[3]
    cur[3] = first + n
    j0 = first_page(b)

    def pages(fold, stats):
        def landed(j, found, stats):
            slot = (first + j) % depth
            for f, (p, pid, fetched) in enumerate(found):
                def wait():
                    for c in copies(layer, pid, slot, b, p, f):
                        c.wait()
                if block == 1:
                    wait()
                else:
                    pl.when(fetched)(wait)
            at = j if block == 1 else j * block
            return fold(at if window is None else j0 + at,
                        [(pid, fetched) for _p, pid, fetched in found],
                        slot, stats)

        def trip(j, stats):
            # the slot this frees held trip j - 1, folded a step ago
            start_next()
            found = trip_pages(b, j)
            if block > 1:
                return landed(j, found, stats)
            return jax.lax.cond(found[0][2],
                                lambda s: landed(j, found, s),
                                lambda s: s, stats)

        return jax.lax.fori_loop(0, n, trip, stats)

    return pos_ref[b], pages


def _decode_kernel(layer_ref, pos_ref, table_ref, *refs, quantized: bool,
                   selecting: bool, depth: int, page_size: int,
                   kv_heads: int, window: Optional[int] = None,
                   **fold_shape):
    """One grid step: one ROW of the ragged decode fold, its live pages
    of the layer walked by `walk_live_pages`, K and V together.

    sk_ref/sv_ref (a quantized pool only): the layer's flat scales
    q_ref/o_ref:   [1, 1, H, hd], the row's query and result
    k_hbm/v_hbm:   [L, N_pages, page, KV*hd], never read but by a copy
    sel_ref        (selecting only): [1, max_pages, page] float32, the
                   row's selection by LOGICAL page, whole in VMEM
    kbuf/vbuf:     [depth, page, KV*hd] VMEM; sem: DMA [2, depth]
    cur:           SMEM int32 [4], the walk's
    """
    if quantized:
        sk_ref, sv_ref, *refs = refs
    q_ref, k_hbm, v_hbm, *refs = refs
    sel_ref = None
    if selecting:
        sel_ref, *refs = refs
    o_ref, kbuf, vbuf, sem, cur = refs
    H, hd = q_ref.shape[2:]

    def copies(layer, pid, slot, *_trip):
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

    def fold(j, found, slot, stats):
        (pid, _fetched), = found
        scales = None
        if quantized:
            def scales(kv):
                at = pid * kv_heads + kv
                return sk_ref[at], sv_ref[at]
        return _decode_fold(q, kbuf.at[slot], vbuf.at[slot], scales, j, pos,
                            stats, page_size=page_size, kv_heads=kv_heads,
                            window=window,
                            sel=sel_ref[0, pl.ds(j, 1)] if selecting else None,
                            **fold_shape)

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


def ragged_paged_attention(q, pool_k, pool_v, layer, table, pos, *,
                           scale: float | None = None,
                           scale_k=None, scale_v=None,
                           packed4: bool = False,
                           window: Optional[int] = None,
                           selected=None,
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
    selected:     [B, max_pages, page] float32, or None. Row b attends
                  key j * page + o only where selected[b, j, o] is above
                  0.5, among the keys up to its pos (a sparse indexer's
                  set): the row walks its own live pages under the mask,
                  a row's [max_pages, page] block in VMEM a grid step
                  (float32 as the mixed kernel's: a page's row of it
                  loads at a dynamic sublane like any 32-bit block's,
                  and 133 KB a row at a table of 260 pages is 0.2 us of
                  copy; a packed int8 block was not tried). A float
                  pool without a band only. None: no such operand, and
                  the program is the one it was.
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
    selecting = selected is not None
    if selecting and (quantized or window is not None):
        raise ValueError("a selection is served over a float pool "
                         "without a band only")

    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    operands = [layer, jnp.asarray(pos, jnp.int32),
                jnp.asarray(table, jnp.int32)]
    if quantized:
        operands += [_layer_scales(scale_k, layer[0]),
                     _layer_scales(scale_v, layer[0])]
    depth = decode_ring_depth(Pb * width * pool_k.dtype.itemsize)
    kernel = functools.partial(
        _decode_kernel, quantized=quantized, selecting=selecting,
        depth=depth, scale=scale, page_size=P, kv_heads=KV, group=G,
        head_dim=hd, packed4=packed4, window=window)
    row = pl.BlockSpec((1, 1, H, hd), lambda b, *_: (b, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    inputs, in_specs = [q, pool_k, pool_v], [row, hbm, hbm]
    if selecting:
        inputs.append(selected.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, max_pages, P),
                                     lambda b, *_: (b, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(operands),
        grid=(B,),
        in_specs=in_specs,
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
    )(*operands, *inputs)


# Queries to a TILE of the mixed kernel's window. A row's walk folds
# the row's first tile when that holds its every real query
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


def _mixed_fold(pos_ref, qlen_ref, q_ref, o_ref, acc_ref, pages, page_kv, *,
                scale: float, page_size: int, block: int, kv_heads: int,
                group: int, head_dim: int, q_width: int,
                quantized: bool = False, window: Optional[int] = None,
                sel_page=None):
    """One ROW of the MIXED ragged fold: each row carries q_width query
    slots of which q_len are real — a decode row (q_len=1) and a
    prefill-chunk row (q_len=C at arbitrary page offset) fold through
    the same call. The one body of the float, int8 and int4 pools:
    `page_kv(kv, slot, found)` hands it kv head `kv` of the trip's
    pages in ring slot `slot` as (kh, vh [block * P, hd], k_scales,
    v_scales), the scales a list of a page's each, None for a float
    pool.

    pages:   the row's walk (walk_live_pages): its live pages from the
             page of the first key its first query attends to that of
             its LAST real query, `block` of them a trip and a softmax
             update. An idle row (q_len 0) takes no trip.
    q_ref:   [1, C, H, hd] — the row's query window, first token at
             absolute position pos
    acc_ref: [KV*C*G, hd] f32, rows ordered (kv, query, group) so each
             kv head's fold is a contiguous slice; the softmax's m and
             l ([R, 1] a kv head) are carries of the walk's loop.

    The work follows q_len: a row whose real queries all lie in its
    first tile (MIXED_Q_TILE queries: a decode row, an idle row) folds,
    initialises and finishes that tile alone — accumulator rows
    [kv*C*G, +Tq*G) of each kv head — and any other row its whole
    window, in one piece (folding a window tile by tile under a loop
    cost a full window 1.8x the time on a v5e: each tile pays for the
    page's K and V again). What does not change from page to page is
    made once a row: the queries' [nq*G, hd] form a kv head, and the
    mask's [R, P] of (query's position - column). A query's online
    softmax runs trip by trip in the same order either way (both spans
    take the same `block`), so its result does not depend on its row's
    q_len, nor on the other rows'. Output columns: those of a tile the
    row did not fold are ZERO; padded columns of a folded span are what
    they always were, the fold of a query that is not there — finite,
    never read.

    window (static): a BAND. Query i attends keys pos + i - window + 1
    .. pos + i; the walk starts at the page of the first key the row's
    first query attends, so that no page wholly before the band is
    fetched or folded, and the mask cuts the rest.

    sel_page (None: no selection, and the program is the one it was):
    sel_page(slot, f) -> [C, P] float32, page f of the trip of a
    per-(query, key) selection: query i attends a key of that page only
    where its entry is above 0.5 (a sparse indexer's sets:
    models/moe/keye_vl2.py). A query's row reaches its G rows of the
    scores by a sublane broadcast, as ops/mla_attention._spread's.
    """
    b = pl.program_id(0)
    C = q_width
    G = group
    P = page_size
    hd = head_dim
    Tq = min(MIXED_Q_TILE, C)
    pos = pos_ref[b]
    n_q = qlen_ref[b]

    def rows(kv, nq):
        return slice(kv * C * G, kv * C * G + nq * G)

    def heads(kv):
        return slice(kv * G, (kv + 1) * G)

    def row(nq):
        """The row's walk over its first nq (static) queries."""
        R = nq * G
        if nq < C:
            o_ref[0, nq:] = jnp.zeros((C - nq,) + o_ref.shape[2:],
                                      o_ref.dtype)
        qs = []
        for kv in range(kv_heads):
            acc_ref[rows(kv, nq)] = jnp.zeros((R, hd), jnp.float32)
            qh = q_ref[0, :nq, heads(kv), :].reshape(R, hd)
            qs.append(qh.astype(jnp.float32) if quantized else qh)
        # per-(query, column) causal mask: query i sits at absolute
        # position pos + i and attends page slots <= it (current token
        # included — its KV is written before the kernel runs): column
        # t of logical page p is seen where p * P <= pos + i - t
        ahead = (pos + jax.lax.broadcasted_iota(jnp.int32, (R, P), 0) // G
                 - jax.lax.broadcasted_iota(jnp.int32, (R, P), 1))

        def fold(lp, found, slot, stats):
            parts = []
            for f, (_pid, fetched) in enumerate(found):
                start = (lp + f) * P
                if block > 1:
                    # a page that was not fetched starts past every query
                    start = jnp.where(fetched, start, _NEVER)
                seen = start <= ahead
                if window is not None:
                    seen = jnp.logical_and(seen, start + window > ahead)
                if sel_page is not None:
                    sel = sel_page(slot, f)
                    picked = jnp.concatenate(
                        [jnp.broadcast_to(sel[i:i + 1, :], (G, P))
                         for i in range(nq)], axis=0)        # [R, P]
                    seen = jnp.logical_and(seen, picked > 0.5)
                parts.append(seen)
            valid = parts[0] if block == 1 else jnp.concatenate(parts, axis=1)
            out_stats = []
            for kv in range(kv_heads):
                kh, vh, k_scales, v_scales = page_kv(kv, slot, found)
                if k_scales is None:
                    s = _dot(qs[kv], kh, trans_b=True) * scale  # [R, F*P]
                elif block == 1:
                    # dequantization folds into the dot outputs: one
                    # scale covers a page's every column of a kv head
                    s = _dot(qs[kv], kh, trans_b=True) * (scale * k_scales[0])
                else:
                    s = _dot(qs[kv], kh, trans_b=True) * (
                        scale * jnp.concatenate(
                            [jnp.full((1, P), k, jnp.float32)
                             for k in k_scales], axis=1))
                s = jnp.where(valid, s, NEG_INF)
                m_prev, l_prev = stats[kv]                   # [R, 1]
                m_cur = jnp.max(s, axis=-1, keepdims=True)
                m_new = jnp.maximum(m_prev, m_cur)
                alpha = jnp.exp(m_prev - m_new)
                # a query whose causal horizon precedes this trip (or an
                # all-hole row) has every column masked: m_new stays
                # NEG_INF and exp(s - m_new) would be exp(0)=1 garbage —
                # the explicit mask multiply keeps its l at 0 so the
                # finish emits zeros, matching the fold reference's guard
                p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
                l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
                if v_scales is None:
                    out = _dot(p.astype(vh.dtype), vh,
                               trans_b=False)                # [R, hd]
                else:
                    # a scale a page: each page's product by its own
                    out = sum(_dot(p[:, f * P:(f + 1) * P],
                                   vh[f * P:(f + 1) * P],
                                   trans_b=False) * v
                              for f, v in enumerate(v_scales))
                r = rows(kv, nq)
                acc_ref[r] = acc_ref[r] * alpha + out
                out_stats.append((m_new, l_new))
            return tuple(out_stats)

        stats = pages(fold, tuple(
            (jnp.full((R, 1), NEG_INF, jnp.float32),
             jnp.zeros((R, 1), jnp.float32)) for _ in range(kv_heads)))
        for kv in range(kv_heads):
            # a row that folded no page (idle, all-unmapped) has l == 0
            # and a zero accumulator: zeros, as the fold reference gives
            l = stats[kv][1]
            l = jnp.where(l == 0.0, 1.0, l)
            o = (acc_ref[rows(kv, nq)] / l).reshape(nq, G, hd)
            o_ref[0, :nq, heads(kv), :] = o.astype(o_ref.dtype)

    if Tq == C:
        row(C)
    else:
        pl.when(n_q <= Tq)(functools.partial(row, Tq))
        pl.when(n_q > Tq)(functools.partial(row, C))


def _mixed_kernel(layer_ref, last_ref, pos_ref, qlen_ref, table_ref, *refs,
                  quantized: bool, selecting: bool, packed4: bool,
                  depth: int, block: int, page_size: int, kv_heads: int,
                  head_dim: int, window: Optional[int] = None, **fold_shape):
    """One grid step: one ROW of the mixed fold, its live pages of the
    layer walked by `walk_live_pages`, K and V (and, selecting, the
    selection's pages: indexed by row and LOGICAL page) together.

    last_ref:      [B] the position of each row's last real query, -1
                   for an idle row: the walk's bound (pos_ref: its
                   first query's, where a band starts)
    sk_ref/sv_ref  (a quantized pool only): the layer's flat scales
    q_ref/o_ref:   [1, C, H, hd], the row's window and result
    k_hbm/v_hbm:   [L, N_pages, page, KV*hd], never read but by a copy
                   (sel_hbm: [B, max_pages, C, page] float32)
    kbuf/vbuf:     [depth, block * page, KV*hd] VMEM (selbuf: [depth,
                   block, C, page]); sem: DMA [pools, depth, block]
    cur:           SMEM int32 [4], the walk's
    acc_ref:       [KV*C*G, hd] f32
    """
    if quantized:
        sk_ref, sv_ref, *refs = refs
    q_ref, k_hbm, v_hbm, *refs = refs
    sel_hbm = selbuf = None
    if selecting:
        sel_hbm, o_ref, kbuf, vbuf, selbuf, sem, cur, acc_ref = refs
    else:
        o_ref, kbuf, vbuf, sem, cur, acc_ref = refs
    hd = head_dim
    Pb = kbuf.shape[1] // block     # a page's rows as stored

    def copies(layer, pid, slot, row, p, f):
        at = pl.ds(f * Pb, Pb)
        cs = [pltpu.make_async_copy(pool.at[layer, pid], buf.at[slot, at],
                                    sem.at[i, slot, f])
              for i, (pool, buf) in enumerate(((k_hbm, kbuf),
                                               (v_hbm, vbuf)))]
        if selecting:
            cs.append(pltpu.make_async_copy(
                sel_hbm.at[row, p], selbuf.at[slot, f], sem.at[2, slot, f]))
        return cs

    if block > 1:
        # a page that is not fetched keeps what lay in its place, and a
        # probability of exactly 0 must meet a finite value there
        @pl.when(pl.program_id(0) == 0)
        def _():
            vbuf[...] = jnp.zeros_like(vbuf)

    _, pages = walk_live_pages(layer_ref, last_ref, table_ref, cur, copies,
                               depth=depth, page_size=page_size,
                               window=window, first_ref=pos_ref, block=block)

    def page_kv(kv, slot, found):
        lanes = slice(kv * hd, (kv + 1) * hd)
        if not quantized:
            return kbuf[slot, :, lanes], vbuf[slot, :, lanes], None, None
        if packed4:
            def unpacked(buf):
                return jnp.concatenate(
                    [_unpack_nibbles(buf[slot, f * Pb:(f + 1) * Pb], lanes)
                     for f in range(block)], axis=0)
            kh, vh = unpacked(kbuf), unpacked(vbuf)
        else:
            kh = kbuf[slot, :, lanes].astype(jnp.float32)
            vh = vbuf[slot, :, lanes].astype(jnp.float32)
        def scales(ref):
            if block == 1:
                return [ref[found[0][0] * kv_heads + kv]]
            # (a page that is not fetched has no scale: its place in the
            # slot is finite, and 0 keeps it so)
            return [jnp.where(fetched,
                              ref[jnp.maximum(pid, 0) * kv_heads + kv], 0.0)
                    for pid, fetched in found]
        return kh, vh, scales(sk_ref), scales(sv_ref)

    _mixed_fold(pos_ref, qlen_ref, q_ref, o_ref, acc_ref, pages, page_kv,
                page_size=page_size, block=block, kv_heads=kv_heads,
                head_dim=hd, quantized=quantized, window=window,
                sel_page=((lambda slot, f: selbuf[slot, f]) if selecting
                          else None),
                **fold_shape)


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
    call handles decode rows (q_len=1) and prefill-chunk rows (q_len=C
    at arbitrary page offset), a grid step a ROW, which walks its live
    pages itself (walk_live_pages: the pages up to
    ceil((pos + q_len) / page), `mixed_block` of them a softmax update,
    out of the pool in HBM into rings of VMEM slots; an idle row takes
    no trip), with per-row causal masking.

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
                  window + 1 .. pos + i, and the row's walk starts at
                  the page of its first query's first key; `table` may
                  then be a ring (walk_live_pages). None: every key up
                  to the query.
    selected:     [B, max_pages, C, page] float32, or None. Query
                  (b, i) attends key j * page + o only where
                  selected[b, j, i, o] is above 0.5, among the keys
                  causality leaves it (a sparse indexer's sets), BY
                  PAGE: the [C, page] block of a page is one contiguous
                  copy beside its K and V pages (query-major, it was C
                  rows of 512 B a page). A float pool without a band
                  only. None: no such operand, and the program is the
                  one it was.
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

    selecting = selected is not None
    if selecting and (quantized or window is not None):
        raise ValueError("a selection is served over a float pool "
                         "without a band only")

    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    pos = jnp.asarray(pos, jnp.int32)
    q_len = jnp.asarray(q_len, jnp.int32)
    # the walk's bound: the row's LAST real query; an idle row has none
    last = jnp.where(q_len > 0, pos + q_len - 1, -1)
    operands = [layer, last, pos, q_len, jnp.asarray(table, jnp.int32)]
    if quantized:
        operands += [_layer_scales(scale_k, layer[0]),
                     _layer_scales(scale_v, layer[0])]
    block = mixed_block(P, H, KV, hd, C, max_pages, q.dtype.itemsize,
                        pool_k.dtype.itemsize, selecting=selecting)
    depth = decode_ring_depth(block * Pb * width * pool_k.dtype.itemsize)
    kernel = functools.partial(
        _mixed_kernel, quantized=quantized, selecting=selecting,
        packed4=packed4, depth=depth, block=block, scale=scale, page_size=P,
        kv_heads=KV, group=G, head_dim=hd, q_width=C, window=window)
    row = pl.BlockSpec((1, C, H, hd), lambda b, *_: (b, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    ring = pltpu.VMEM((depth, block * Pb, width), pool_k.dtype)
    inputs, in_specs = [q, pool_k, pool_v], [row, hbm, hbm]
    rings = [ring, ring]
    if selecting:
        inputs.append(selected.astype(jnp.float32))
        in_specs.append(hbm)
        rings.append(pltpu.VMEM((depth, block, C, P), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(operands),
        grid=(B,),
        in_specs=in_specs,
        out_specs=row,
        scratch_shapes=rings + [
            pltpu.SemaphoreType.DMA((len(rings), depth, block)),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((KV * C * G, hd), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, H, hd), q.dtype),
        name="cake_mixed_attn",
        # the ring's copies run ahead into the next row
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_MIXED_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*operands, *inputs)


def mixed_entries_walk(first_pos: int, n: int, width: int, tile: int,
                       page_size: int, max_pages: int, block: int):
    """(pages, table entries, folds) of ONE call that hands a window of
    `width` slots, n of them real tokens from first_pos on, to the mixed
    kernel as width / tile entries of `tile` queries over the one row's
    table (exaone_moe.attend_window's form, keye_vl2's): mixed_walk
    summed over the entries; an entry past the window's last token is
    idle."""
    pages = folds = 0
    for start in range(0, width, tile):
        p, f = mixed_walk(first_pos + start, min(max(n - start, 0), tile),
                          page_size, max_pages, block)
        pages, folds = pages + p, folds + f
    return pages, (width // tile) * max_pages, folds


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# What the compiler gives one kernel on a v5e TensorCore, read from its
# own refusals: AOT compiles against a v5e topology (jax 0.9.0, libtpu
# 0.0.34, PR 21) said "Scoped allocation with size 16.04M and limit
# 16.00M exceeded scoped vmem limit" for the mixed kernel at H=32,
# hd=128, C=256, and "Used 2.01M of 1.00M smem" for two f32[2048, 8]
# scale operands. The decode kernel sets no vmem_limit_bytes, so the
# default scoped limit is its ceiling; the mixed kernel states its own
# (_MIXED_VMEM_LIMIT) and keeps this one for an entry's WIDTH.
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
    them. The mixed kernel's copies are the same since PR 62, and so
    is its rule (ragged_paged_mixed_supported)."""
    return (_pool_supported(page_size, H, KV, hd, **pool)
            and (not _on_tpu() or (KV * hd) % 128 == 0))


def mixed_scratch_bytes(H: int, hd: int, q_width: int) -> int:
    """f32 VMEM the mixed kernel keeps a row: the [KV*C*G, hd]
    accumulator plus the softmax's m and l, [KV*C*G, 1] each, which as
    carries of the walk's loop take a lane tile a row like the
    [KV*C*G, 128] scratch they were, and KV*G == H. A head narrower
    than a lane tile pads the accumulator's rows to 128 lanes (the
    compiler at H=32, hd=64, C=256, PR 56: "Scoped allocation with size
    16.88M", 2.4 MiB over the unpadded count)."""
    return 4 * q_width * H * (max(hd, 128) + 256)


# What the mixed call asks of the core's 128 MiB of VMEM (the compiler
# grants a kernel 16 unless told), and what of it mixed_block plans
# with: the rest is the compiler's own temporaries. An ENTRY's width is
# still chosen against the 16 MiB count at one page a fold
# (ragged_paged_mixed_supported, exaone_moe.query_tile): wider entries
# are a change of their own (ROADMAP.md S5 (c)).
_MIXED_VMEM_LIMIT = 48 * 2**20
_MIXED_VMEM_PLAN = 32 * 2**20


def mixed_vmem_bytes(page_size: int, H: int, KV: int, hd: int,
                     q_width: int, q_itemsize: int = 2,
                     kv_itemsize: int = 2, block: int = 1,
                     selecting: bool = False) -> int:
    """Scoped VMEM the mixed kernel needs at `block` pages a fold: the
    f32 scratch, the double-buffered q and out blocks ([C, H, hd]), the
    K and the V ring (decode_ring_depth slots of `block` pages; the
    selection's ring of [C, page] float32 beside them), and a kv
    head's scores and probabilities ([C*G, block * page] float32 both,
    and the probabilities again in the pool's type)."""
    q_block = q_width * H * hd * q_itemsize
    page_bytes = page_size * KV * hd * kv_itemsize
    slots = decode_ring_depth(block * page_bytes) * block
    ring = slots * (2 * page_bytes
                    + (q_width * page_size * 4 if selecting else 0))
    scores = q_width * (H // KV) * block * page_size * (8 + kv_itemsize)
    return (mixed_scratch_bytes(H, hd, q_width) + 2 * 2 * q_block + ring
            + scores)


def mixed_block(page_size: int, H: int, KV: int, hd: int, q_width: int,
                max_pages: int, q_itemsize: int = 2, kv_itemsize: int = 2,
                selecting: bool = False) -> int:
    """Pages a fold of the mixed kernel, from the call's shapes alone:
    the most of (4, 2, 1) whose count (mixed_vmem_bytes) fits
    _MIXED_VMEM_PLAN and such that whole blocks pad the TABLE by an
    eighth at most (a walk's last block is computed whole and masked:
    a ring of 3 entries would pay 4 pages a row at 4 a fold), as
    ops/mla_attention.window_tiles' B."""
    def fits(f):
        return (8 * (-max_pages % f) <= max_pages
                and mixed_vmem_bytes(page_size, H, KV, hd, q_width,
                                     q_itemsize, kv_itemsize, f,
                                     selecting) <= _MIXED_VMEM_PLAN)
    return next(f for f in (4, 2, 1) if f == 1 or fits(f))


def mixed_walk(pos: int, q_len: int, page_size: int, max_pages: int,
               block: int, window: Optional[int] = None):
    """(pages, folds) the mixed kernel walks for a row whose first
    query sits at pos and that holds q_len real ones: its live pages,
    from the band's first under a window, and the softmax updates they
    take at `block` pages each; none for an idle row. The host's count
    of what the kernel does (the step records' mixed_attn_pages /
    mixed_attn_folds)."""
    if q_len <= 0:
        return 0, 0
    first = 0 if window is None else max(pos - (window - 1), 0) // page_size
    pages = min(max((pos + q_len - 1) // page_size - first + 1, 0),
                max_pages)
    return pages, -(-pages // block)


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
    (_pool_supported) PLUS a page row of whole lane tiles (the kernel's
    own copies, as ragged_paged_supported's), a power-of-two GQA group
    and the VMEM bound. The kernel folds each kv head's [C, G, hd]
    queries to [C*G, hd]; Mosaic does that shape cast for G in 1, 2, 4,
    8 and refuses it for G=7 ("unsupported shape cast", v5e, PR 21).
    And unlike the C=1 decode kernel, its scratch and q/out blocks
    scale with the query width C, which is held to what the count at
    one page a fold (mixed_vmem_bytes) puts under the 16 MiB a kernel
    is granted unasked (at H=32, hd=128: C=128 needs 12 MiB, C=256
    needs 22): the widths the cells were sized at, though the kernel
    states a higher limit now (wider entries: ROADMAP.md S5 (c))."""
    if not _pool_supported(page_size, H, KV, hd,
                           quantized=quantized, n_pages=n_pages,
                           packed4=packed4, slots=slots,
                           max_pages=max_pages,
                           kv_itemsize=kv_itemsize):
        return False
    if not _on_tpu():
        return True      # interpret mode allocates host memory
    G = H // KV
    if G & (G - 1) or (KV * hd) % 128:
        return False
    return mixed_vmem_bytes(page_size, H, KV, hd, q_width, q_itemsize,
                            kv_itemsize) <= _VMEM_SCOPED_LIMIT
