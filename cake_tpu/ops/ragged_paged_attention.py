"""Pallas TPU ragged paged attention for the paged serving path.

The XLA reference (`models/llama/paged.py:paged_attention`, kept as the
fold implementation) is a `lax.fori_loop` over ALL `max_pages` table
columns: every decode step, every layer, every row folds the whole page
axis, so a 3-page request pays the same gather traffic as a 32-page one
and page reads never stay resident in VMEM. This kernel is the
TPU-native formulation of the same online-softmax fold (the "Ragged
Paged Attention" shape, PAPERS.md arxiv 2604.15464):

  * grid (rows, pages) with the page axis innermost and sequential —
    each grid step streams ONE page of the pool through VMEM and folds
    it into f32 (m, l, acc) scratch carried across the page axis, the
    flash-attention recurrence of `ops/flash_attention.py`;
  * the page table and per-row positions ride as scalar-prefetched SMEM
    operands, so the k/v BlockSpec index maps resolve `table[row, j]`
    BEFORE the DMA is issued — the pool is indexed directly by physical
    page id, no host-side gather and no dense per-row copy;
  * per-row early exit: pages past the row's live count
    `ceil((pos+1)/page)` clamp their index map to the last live page, so
    Pallas elides the repeated DMA, and `pl.when` skips the compute —
    a short row costs its own pages, not `max_pages`;
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
operand. The k/v block is (layer, page) -> one (page, KV*hd) tile,
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


def _rpa_kernel(layer_ref, pos_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                acc_ref, m_ref, l_ref, *, scale: float, page_size: int,
                kv_heads: int, group: int, head_dim: int):
    """One (row, page) grid step of the ragged fold.

    layer_ref: [1] — read by the k/v index maps only (every kernel here
             takes it first and its body never touches it)
    q_ref:   [1, 1, H, hd] — the row's single decode query, all heads
    k_ref/v_ref: [1, page, KV*hd] — one physical page of the layer (the
             block's layer axis is squeezed)
    scratch: acc [H, hd] f32, m/l [H, 128] f32, carried across the page
    axis (innermost, sequential) exactly like flash_attention's k axis.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    page = table_ref[b, j]
    # page j is live iff it covers a position <= pos AND is mapped; dead
    # pages cost neither compute (gated here) nor bandwidth (their index
    # map repeats the last live page, so the DMA is elided)
    live = jnp.logical_and(j * page_size <= pos, page >= 0)

    @pl.when(live)
    def _fold():
        q = q_ref[0, 0]                        # [H, hd]
        P = page_size
        hd = head_dim
        # causal mask over the page's absolute slots (current token
        # included); every gated-in page has >= 1 valid column, so the
        # online max below never sees a fully-masked row
        col_valid = (j * P + jax.lax.broadcasted_iota(
            jnp.int32, (1, P), 1)) <= pos      # [1, P]
        # scores per kv head: query group g of kv head k against the
        # page's k-lane slice (static unroll — KV is small)
        parts = []
        for kv in range(kv_heads):
            kh = k_ref[0, :, kv * hd:(kv + 1) * hd]    # [P, hd]
            qh = q[kv * group:(kv + 1) * group]        # [G, hd]
            parts.append(_dot(qh, kh, trans_b=True))
        s = jnp.concatenate(parts, axis=0) * scale     # [H, P]
        s = jnp.where(col_valid, s, NEG_INF)

        m_prev = m_ref[:, :1]                  # [H, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                 # [H, P]
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        outs = []
        for kv in range(kv_heads):
            vh = v_ref[0, :, kv * hd:(kv + 1) * hd]    # [P, hd]
            ph = p[kv * group:(kv + 1) * group]        # [G, P]
            outs.append(_dot(ph.astype(vh.dtype), vh, trans_b=False))
        acc_ref[:] = acc_ref[:] * alpha + jnp.concatenate(outs, axis=0)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_ref[:, :1]
        # a row whose every page was dead (inactive slot / all-unmapped
        # table) has l == 0: emit zeros, matching the fold reference's
        # merge_attention_stats guard
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _rpa_kernel_q8(layer_ref, pos_ref, table_ref, sk_ref, sv_ref, q_ref, k_ref,
                   v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale: float,
                   page_size: int, kv_heads: int, group: int,
                   head_dim: int):
    """int8 variant of _rpa_kernel: the page blocks stream as int8 (a
    quarter of the f32 DMA bytes — the whole point of KV tiering) and
    the per-(page, kv-head) scales ride as scalar-prefetched SMEM
    operands (1-D, index page*KV + kv — _layer_scales). Because one
    scale covers a page's every column for a given kv head,
    dequantization folds into the dot OUTPUTS: the score block scales
    by scale_k[page, kv] and the value fold by scale_v[page, kv] — no
    dequantized page copy ever exists."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    page = table_ref[b, j]
    live = jnp.logical_and(j * page_size <= pos, page >= 0)

    @pl.when(live)
    def _fold():
        q = q_ref[0, 0]                        # [H, hd]
        P = page_size
        hd = head_dim
        pid = jnp.maximum(page, 0)
        col_valid = (j * P + jax.lax.broadcasted_iota(
            jnp.int32, (1, P), 1)) <= pos      # [1, P]
        parts = []
        for kv in range(kv_heads):
            kh = k_ref[0, :, kv * hd:(kv + 1) * hd].astype(
                jnp.float32)                           # [P, hd]
            qh = q[kv * group:(kv + 1) * group].astype(jnp.float32)
            s_kv = _dot(qh, kh, trans_b=True)
            parts.append(s_kv * sk_ref[pid * kv_heads + kv])
        s = jnp.concatenate(parts, axis=0) * scale     # [H, P]
        s = jnp.where(col_valid, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                 # [H, P]
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        outs = []
        for kv in range(kv_heads):
            vh = v_ref[0, :, kv * hd:(kv + 1) * hd].astype(jnp.float32)
            ph = p[kv * group:(kv + 1) * group]        # [G, P]
            o_kv = _dot(ph, vh, trans_b=False)
            outs.append(o_kv * sv_ref[pid * kv_heads + kv])
        acc_ref[:] = acc_ref[:] * alpha + jnp.concatenate(outs, axis=0)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


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


def _rpa_kernel_q4(layer_ref, pos_ref, table_ref, sk_ref, sv_ref, q_ref, k_ref,
                   v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale: float,
                   page_size: int, kv_heads: int, group: int,
                   head_dim: int):
    """int4 variant of _rpa_kernel_q8: the page blocks stream as
    nibble-PACKED uint8 — an EIGHTH of the f32 DMA bytes — and unpack
    in registers per kv head before the dots. Scales prefetch into
    SMEM and fold into the dot outputs exactly like the int8 kernel;
    page_size here is REAL tokens (the packed block holds page_size//2
    sublanes)."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    page = table_ref[b, j]
    live = jnp.logical_and(j * page_size <= pos, page >= 0)

    @pl.when(live)
    def _fold():
        q = q_ref[0, 0]                        # [H, hd]
        P = page_size
        hd = head_dim
        pid = jnp.maximum(page, 0)
        col_valid = (j * P + jax.lax.broadcasted_iota(
            jnp.int32, (1, P), 1)) <= pos      # [1, P]
        parts = []
        for kv in range(kv_heads):
            kh = _unpack_nibbles(k_ref[0],
                                 slice(kv * hd, (kv + 1) * hd))  # [P, hd]
            qh = q[kv * group:(kv + 1) * group].astype(jnp.float32)
            s_kv = _dot(qh, kh, trans_b=True)
            parts.append(s_kv * sk_ref[pid * kv_heads + kv])
        s = jnp.concatenate(parts, axis=0) * scale     # [H, P]
        s = jnp.where(col_valid, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                 # [H, P]
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        outs = []
        for kv in range(kv_heads):
            vh = _unpack_nibbles(v_ref[0],
                                 slice(kv * hd, (kv + 1) * hd))  # [P, hd]
            ph = p[kv * group:(kv + 1) * group]        # [G, P]
            o_kv = _dot(ph, vh, trans_b=False)
            outs.append(o_kv * sv_ref[pid * kv_heads + kv])
        acc_ref[:] = acc_ref[:] * alpha + jnp.concatenate(outs, axis=0)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


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
            packed4=packed4, slots=B, max_pages=max_pages):
        raise ValueError(
            f"ragged paged attention cannot run on this chip at page="
            f"{P} H={H} KV={KV} hd={hd} pool={pool_k.dtype} pages={N} "
            f"table={B}x{max_pages} (ragged_paged_supported); use the "
            "fold")

    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def kv_index(b, j, layer_ref, pos_ref, table_ref, *_scales):
        # clamp dead pages (past the row's live count) to the LAST live
        # page: the repeated block index elides the DMA, so a short row
        # streams only its own pages. Unmapped holes inside the live
        # range clamp to page 0 — one page of wasted bandwidth, masked
        # out in compute.
        jj = jnp.minimum(j, pos_ref[b] // P)
        page = table_ref[b, jj]
        return (layer_ref[0], jnp.maximum(page, 0), 0, 0)

    if quantized:
        kern_fn = _rpa_kernel_q4 if packed4 else _rpa_kernel_q8
        kernel = functools.partial(
            kern_fn, scale=scale, page_size=P, kv_heads=KV,
            group=G, head_dim=hd)
        n_prefetch = 5
        operands = (layer, jnp.asarray(pos, jnp.int32),
                    jnp.asarray(table, jnp.int32),
                    _layer_scales(scale_k, layer[0]),
                    _layer_scales(scale_v, layer[0]),
                    q, pool_k, pool_v)
    else:
        kernel = functools.partial(
            _rpa_kernel, scale=scale, page_size=P, kv_heads=KV, group=G,
            head_dim=hd)
        n_prefetch = 3
        operands = (layer, jnp.asarray(pos, jnp.int32),
                    jnp.asarray(table, jnp.int32), q, pool_k, pool_v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, H, hd), lambda b, j, *_: (b, 0, 0, 0)),
            _pool_block(Pb, width, kv_index),
            _pool_block(Pb, width, kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, H, hd),
                               lambda b, j, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, hd), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, hd), q.dtype),
        name="cake_decode_attn",
        # only the page axis carries scratch state; rows schedule freely
        # across megacore
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)


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


def _mixed_fold(pos_ref, qlen_ref, table_ref, q_ref, o_ref, acc_ref, m_ref,
                l_ref, page_kv, *, scale: float, page_size: int,
                kv_heads: int, group: int, head_dim: int, q_width: int):
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
    page = table_ref[b, j]
    live = jnp.logical_and(j * P <= last, page >= 0)

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
            col = j * P + jax.lax.broadcasted_iota(jnp.int32, (R, P), 1)
            valid = col <= pos + qidx
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
                      v_ref, o_ref, acc_ref, m_ref, l_ref, *, head_dim: int,
                      **shape):
    """The mixed kernel over a float pool: k_ref/v_ref [1, page, KV*hd],
    one physical page, a kv head a lane slice."""
    hd = head_dim

    def page_kv(kv, pid):
        lanes = slice(kv * hd, (kv + 1) * hd)
        return k_ref[0, :, lanes], v_ref[0, :, lanes], None, None

    _mixed_fold(pos_ref, qlen_ref, table_ref, q_ref, o_ref, acc_ref, m_ref,
                l_ref, page_kv, head_dim=hd, **shape)


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

    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def kv_index(b, j, layer_ref, pos_ref, qlen_ref, table_ref, *_scales):
        # clamp dead pages (past the row's live count) to the LAST live
        # page — the repeated block index elides the DMA, so a row
        # streams only the pages its window actually covers
        last = pos_ref[b] + jnp.maximum(qlen_ref[b], 1) - 1
        jj = jnp.minimum(j, last // P)
        page = table_ref[b, jj]
        return (layer_ref[0], jnp.maximum(page, 0), 0, 0)

    if quantized:
        kernel = functools.partial(
            _rpa_mixed_kernel_q, packed4=packed4, scale=scale, page_size=P,
            kv_heads=KV, group=G, head_dim=hd, q_width=C)
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
            group=G, head_dim=hd, q_width=C)
        n_prefetch = 4
        operands = (layer, jnp.asarray(pos, jnp.int32),
                    jnp.asarray(q_len, jnp.int32),
                    jnp.asarray(table, jnp.int32), q, pool_k, pool_v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, C, H, hd), lambda b, j, *_: (b, 0, 0, 0)),
            _pool_block(Pb, width, kv_index),
            _pool_block(Pb, width, kv_index),
        ],
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


def _smem_need(slots: int, max_pages: int, scale_words: int) -> int:
    """Bytes the scalar-prefetch operands take: the [slots, max_pages]
    int32 table pads its minor dim to 128 words; pos/q_len and the flat
    scale arrays (2 x scale_words f32) are 1-D."""
    table = slots * (-(-max_pages // 128) * 128) * 4
    return table + 2 * slots * 4 + 2 * scale_words * 4


def ragged_paged_supported(page_size: int, H: int, KV: int,
                           hd: int, quantized: bool = False,
                           n_pages: Optional[int] = None,
                           packed4: bool = False,
                           slots: Optional[int] = None,
                           max_pages: Optional[int] = None) -> bool:
    """Static shape gate for the hardware path: the shape classes whose
    numbers were checked against the fold ON A CHIP (v5e, PR 21), plus
    the SMEM bound.

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
    1 MiB; pass n_pages / slots / max_pages to enforce it."""
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
    return tiles and _smem_need(slots or 0, max_pages or 0,
                                scale_words) <= _SMEM_BYTES


def mixed_scratch_bytes(H: int, hd: int, q_width: int) -> int:
    """f32 VMEM scratch the mixed kernel allocates per grid cell: the
    [KV*C*G, hd] accumulator plus two [KV*C*G, 128] m/l buffers, and
    KV*G == H."""
    return 4 * q_width * H * (hd + 256)


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
    """Gate for the MIXED hardware kernel: the decode gate's rules PLUS
    a power-of-two GQA group and the VMEM bound. The kernel folds each
    kv head's [C, G, hd] queries to [C*G, hd]; Mosaic does that shape
    cast for G in 1, 2, 4, 8 and refuses it for G=7 ("unsupported shape
    cast", v5e, PR 21). And unlike the C=1 decode kernel, its scratch
    and q/out blocks scale with the query width C: the compiler
    refuses the kernel outright past its scoped limit (at H=32,
    hd=128: C=128 needs 11 MiB and compiles, C=256 needs 21 MiB and
    does not)."""
    if not ragged_paged_supported(page_size, H, KV, hd,
                                  quantized=quantized, n_pages=n_pages,
                                  packed4=packed4, slots=slots,
                                  max_pages=max_pages):
        return False
    if not _on_tpu():
        return True      # interpret mode allocates host memory
    G = H // KV
    if G & (G - 1):
        return False
    return mixed_vmem_bytes(page_size, H, KV, hd, q_width, q_itemsize,
                            kv_itemsize) <= _VMEM_SCOPED_LIMIT
