"""int8/int4 KV page pools with per-page, per-kv-head scales.

Decode is HBM-bandwidth-bound and the KV cache is the growing term
(BENCH_MEASURED: int8 *weights* already run at 1.6x the bf16 roofline;
the 32-slot config collapses to 151 tok/s from cache thrash). Storing
KV pages as int8 with one symmetric scale per (layer, page, kv head)
cuts pool bytes ~4x vs f32 — the same `--kv-pages` byte budget admits
proportionally more resident streams — while attention reads dequantize
in registers exactly like `ops/quant.py` weight-only matmuls.

Layout (the paged pool's, with a scale sidecar):

  pool.q:     [L, N_pages, page, KV*hd] int8
  pool.scale: [L, N_pages, KV]          f32

The two minor axes are stored flattened, the shape the attention kernels
stream (`ops/ragged_paged_attention.py`); the per-head [.., KV, hd] view
exists only on the few pages or token rows a writer has gathered.

The scale is PER PAGE, which is what makes spill/restore trivial (a
page + its scale row is self-contained) but means incremental writes
must keep the already-quantized page consistent:

  * whole-window writes (prompt prefill: pages fully overwritten) set
    the page's scale fresh from the window's amax;
  * incremental writes (decode tokens, chunk windows at arbitrary
    offsets) GATHER the touched pages, grow the scale monotonically
    (new_scale = max(old, amax(new)/127)), RE-quantize the resident
    int8 values by the ratio old/new (one extra rounding, bounded by
    half a step of the new scale), write the new tokens, and scatter
    back. The engine zeroes a page's scales at allocation so a fresh
    page's first write always sets its own scale instead of inheriting
    a previous occupant's.

The INT4 variant (`Int4Pool`) halves the bytes again: a page stores
nibble-packed values (the `ops/int4_matmul.pack_int4` group-halves
layout with one group per page — token t rides the LOW nibble of
packed row t, token t + page/2 the HIGH nibble, bias +8) in a
[L, N_pages, page//2, KV*hd] uint8 pool, same f32 scale sidecar, same
monotone-scale RMW discipline at qmax 7. Every writer here is
polymorphic over the two pool types: int4 pages unpack on gather and
repack on scatter, so the quantization math is shared line-for-line.

`QuantPool`/`Int4Pool` are NamedTuples, so the stacked [L, ...] pool
rides the layer loop's carry like a plain array pool
(`models/llama/paged.scan_layers_paged`): every writer here takes the
STACKED pool and a layer index, gathers `[layer, pages]`, and scatters
the same pages back in place — no per-layer slice of a pool exists.
The writers in `models/llama/paged.py` dispatch on the leaf type.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# symmetric int8 range and the amax floor (ops/quant.py convention)
_QMAX = 127.0
# symmetric int4 range: clip to [-7, 7] so the +8 packing bias keeps
# every value a strict nibble (ops/int4_matmul convention)
_QMAX4 = 7.0
_EPS = 1e-8


class QuantPool(NamedTuple):
    """One int8 page pool half (k or v): values + per-page scales.

    q:     int8, [L, N_pages, page, KV*hd]
    scale: f32,  [L, N_pages, KV]
    """

    q: jnp.ndarray
    scale: jnp.ndarray


class Int4Pool(NamedTuple):
    """One int4 page pool half (k or v): nibble-packed values + scales.

    q:     uint8, [L, N_pages, page//2, KV*hd] — two tokens per
           byte: token t in the low nibble of packed row t, token
           t + page//2 in the high nibble, +8 bias (pack_int4 layout
           with one group per page)
    scale: f32,   [L, N_pages, KV]
    """

    q: jnp.ndarray
    scale: jnp.ndarray


def pack_page_nibbles(q: jnp.ndarray) -> jnp.ndarray:
    """[..., P, KV, hd] ints in [-8, 7] -> [..., P//2, KV, hd] uint8.

    The `ops/int4_matmul.pack_int4` group-halves layout with g = P (one
    group per page): +8 bias, low nibble = token t, high nibble =
    token t + P//2."""
    P = q.shape[-3]
    v = (q.astype(jnp.int32) + 8) & 0xF
    lo = v[..., : P // 2, :, :]
    hi = v[..., P // 2:, :, :]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_page_nibbles(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of pack_page_nibbles: [..., P//2, KV, hd] uint8 ->
    [..., P, KV, hd] int8 in [-8, 7], token order restored."""
    p32 = packed.astype(jnp.int32)
    lo = (p32 & 0xF) - 8
    hi = (p32 >> 4) - 8
    return jnp.concatenate([lo, hi], axis=-3).astype(jnp.int8)


def _pool_qmax(pool) -> float:
    return _QMAX4 if isinstance(pool, Int4Pool) else _QMAX


def _pool_page(pool) -> int:
    """Tokens per page of a stacked pool (the packed int4 axis stores
    two tokens per row)."""
    return pool.q.shape[2] * (2 if isinstance(pool, Int4Pool) else 1)


def _gather_q(pool, layer, idx) -> jnp.ndarray:
    """Gather pages `idx` of `layer` as UNPACKED int values
    [..., P, KV, hd]. Out-of-range ids fill with garbage that every
    caller either masks (amax) or drops on the scatter-back."""
    q = pool.q.at[layer, idx].get(mode="fill", fill_value=0)
    q = q.reshape(q.shape[:-1] + (pool.scale.shape[-1], -1))
    if isinstance(pool, Int4Pool):
        q = unpack_page_nibbles(q)
    return q


def _gather_scale(pool, layer, idx) -> jnp.ndarray:
    """Scales of pages `idx` of `layer`, [..., KV]; out-of-range ids
    read 0."""
    return pool.scale.at[layer, idx].get(mode="fill", fill_value=0.0)


def _scatter_q(pool, layer, idx, qw, new_s):
    """Scatter whole pages of `layer` back in place (packing int4
    values first); OOB ids drop. qw: [..., P, KV, hd] ints; new_s:
    [..., KV] f32."""
    if isinstance(pool, Int4Pool):
        qw = pack_page_nibbles(qw)
    else:
        qw = qw.astype(jnp.int8)
    qw = qw.reshape(qw.shape[:-2] + (-1,))
    return pool._replace(
        q=pool.q.at[layer, idx].set(qw, mode="drop"),
        scale=pool.scale.at[layer, idx].set(new_s, mode="drop"),
    )


class QuantizedPagedKVCache(NamedTuple):
    """PagedKVCache with int8 pools + scale sidecars. Same property
    surface as models/llama/paged.PagedKVCache, so the engine and the
    jitted step fns are layout-blind (NamedTuple pytree; the page
    TABLE rides along identically)."""

    k: QuantPool
    v: QuantPool
    table: jnp.ndarray    # [slots, max_pages] int32, -1 = unmapped

    @property
    def page_size(self) -> int:
        return self.k.q.shape[2]

    @property
    def n_pages(self) -> int:
        return self.k.q.shape[1]

    @property
    def max_pages(self) -> int:
        return self.table.shape[1]

    @property
    def max_seq_len(self) -> int:
        return self.table.shape[1] * self.k.q.shape[2]

    @classmethod
    def create(cls, config, slots: int, n_pages: int, page_size: int,
               max_seq_len: int) -> "QuantizedPagedKVCache":
        if max_seq_len % page_size:
            raise ValueError(
                f"page_size {page_size} must divide max_seq_len "
                f"{max_seq_len}")
        L = config.num_hidden_layers
        KV = config.num_key_value_heads
        hd = config.head_dim
        shape = (L, n_pages, page_size, KV * hd)
        sshape = (L, n_pages, KV)
        return cls(
            k=QuantPool(q=jnp.zeros(shape, jnp.int8),
                        scale=jnp.zeros(sshape, jnp.float32)),
            v=QuantPool(q=jnp.zeros(shape, jnp.int8),
                        scale=jnp.zeros(sshape, jnp.float32)),
            table=jnp.full((slots, max_seq_len // page_size), -1,
                           jnp.int32),
        )

    def memory_bytes(self) -> int:
        """ACTUAL storage bytes: int8 pools summed per dtype PLUS the
        f32 scale sidecars (the one-dtype `k.nbytes + v.nbytes`
        shortcut undercounts a mixed-dtype pool)."""
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            (self.k, self.v)))


class Int4PagedKVCache(NamedTuple):
    """PagedKVCache with nibble-packed int4 pools + scale sidecars.
    Same property surface as PagedKVCache / QuantizedPagedKVCache so
    the engine and the jitted step fns stay layout-blind; page_size is
    REAL tokens per page (2x the packed storage axis)."""

    k: Int4Pool
    v: Int4Pool
    table: jnp.ndarray    # [slots, max_pages] int32, -1 = unmapped

    @property
    def page_size(self) -> int:
        return self.k.q.shape[2] * 2

    @property
    def n_pages(self) -> int:
        return self.k.q.shape[1]

    @property
    def max_pages(self) -> int:
        return self.table.shape[1]

    @property
    def max_seq_len(self) -> int:
        return self.table.shape[1] * self.k.q.shape[2] * 2

    @classmethod
    def create(cls, config, slots: int, n_pages: int, page_size: int,
               max_seq_len: int) -> "Int4PagedKVCache":
        if max_seq_len % page_size:
            raise ValueError(
                f"page_size {page_size} must divide max_seq_len "
                f"{max_seq_len}")
        if page_size % 2:
            raise ValueError(
                f"int4 KV pages nibble-pack two tokens per byte: "
                f"page_size {page_size} must be even")
        L = config.num_hidden_layers
        KV = config.num_key_value_heads
        hd = config.head_dim
        shape = (L, n_pages, page_size // 2, KV * hd)
        sshape = (L, n_pages, KV)
        return cls(
            k=Int4Pool(q=jnp.zeros(shape, jnp.uint8),
                       scale=jnp.zeros(sshape, jnp.float32)),
            v=Int4Pool(q=jnp.zeros(shape, jnp.uint8),
                       scale=jnp.zeros(sshape, jnp.float32)),
            table=jnp.full((slots, max_seq_len // page_size), -1,
                           jnp.int32),
        )

    def memory_bytes(self) -> int:
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            (self.k, self.v)))


def page_bytes(config, page_size: int, dtype=jnp.float32) -> int:
    """Storage bytes ONE pool page costs (k + v, all layers, scale
    sidecars included for int8/int4) — the ONE source the bench
    `--kv-tier` byte budget, `memory_bytes`, and the host tier's
    accounting all price pages in."""
    L = config.num_hidden_layers
    KV = config.num_key_value_heads
    hd = config.head_dim
    name = dtype if isinstance(dtype, str) else jnp.dtype(dtype).name
    if name == "int8":
        per = L * page_size * KV * hd * 1 + L * KV * 4
    elif name == "int4":
        per = L * (page_size // 2) * KV * hd * 1 + L * KV * 4
    else:
        per = L * page_size * KV * hd * jnp.dtype(dtype).itemsize
    return 2 * per          # k and v


def _quantize_windows(vals: jnp.ndarray, qmax: float = _QMAX):
    """Quantize whole page windows: vals [..., P, KV, hd] f32-ish ->
    (q int8 same shape in [-qmax, qmax], scale f32 [..., KV]) with
    amax over (P, hd)."""
    v32 = vals.astype(jnp.float32)
    amax = jnp.max(jnp.abs(v32), axis=(-3, -1))            # [..., KV]
    scale = jnp.maximum(amax, _EPS) / qmax
    q = jnp.clip(jnp.round(v32 / scale[..., None, :, None]),
                 -qmax, qmax).astype(jnp.int8)
    return q, scale


def _requant(q_old: jnp.ndarray, ratio: jnp.ndarray,
             qmax: float = _QMAX) -> jnp.ndarray:
    """Re-quantize resident int values after a monotone scale growth:
    q' = round(q * old/new). ratio broadcasts [..., KV] over
    [..., P, KV, hd]."""
    return jnp.clip(
        jnp.round(q_old.astype(jnp.float32) * ratio[..., None, :, None]),
        -qmax, qmax).astype(jnp.int8)


def dequantize_pages(pool, layer, idx: jnp.ndarray) -> jnp.ndarray:
    """Gather pages `idx` of `layer` from the stacked pool and
    dequantize to f32: [*idx.shape, P, KV, hd]. Out-of-range ids read a
    zero page (the fold's unmapped-page semantics; an int4 fill page
    unpacks to -8s but its zero scale zeroes the product)."""
    q = _gather_q(pool, layer, idx)
    s = _gather_scale(pool, layer, idx)
    return q.astype(jnp.float32) * s[..., None, :, None]


def reset_page_scales(cache, pages):
    """Zero the scales of freshly-allocated pages (host-computed page
    list; one tiny eager scatter per admission, the table_set_slot
    precedent). A fresh page's first incremental write then sets its
    own scale instead of inheriting a previous occupant's amax —
    without this, a page recycled from a large-activation request
    would quantize a new request's small values to ~0."""
    idx = jnp.asarray(list(pages), jnp.int32)
    zeros = jnp.zeros((cache.k.scale.shape[0], idx.shape[0],
                       cache.k.scale.shape[2]), jnp.float32)
    return cache._replace(
        k=cache.k._replace(scale=cache.k.scale.at[:, idx].set(zeros)),
        v=cache.v._replace(scale=cache.v.scale.at[:, idx].set(zeros)),
    )


# -- writers (stacked pool + layer index, models/llama/paged.py contracts) ----


def qwrite_prompt_pages(pool, layer, vals: jnp.ndarray,
                        table_row: jnp.ndarray, n_real=None):
    """write_prompt_pages over a quantized pool (int8 or int4):
    page-ALIGNED windows
    fully overwrite their pages, so each window quantizes fresh (scale
    from the window's own amax; zero padding cannot raise it) and both
    q and scale scatter in one parallel write. Unmapped windows route
    to the out-of-bounds index and drop.

    n_real (traced scalar) marks the real prompt length: BUCKET padding
    positions carry token-id-0 garbage k/v that is dead data for the
    f32 pool (overwritten by decode before it can be attended) but
    would POISON a fresh page scale here — the scale only grows after
    this write, so a garbage-inflated amax coarsens the page's real
    tokens for the page's whole life. Padding values are zeroed before
    quantization instead."""
    N, P = pool.q.shape[1], _pool_page(pool)
    S = vals.shape[1]
    KV, hd = vals.shape[2], vals.shape[3]
    if n_real is not None:
        live = jnp.arange(S)[None, :, None, None] < n_real
        vals = jnp.where(live, vals, 0)
    n_win = -(-S // P)
    pad = n_win * P - S
    if pad:
        vals = jnp.pad(vals, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pages = table_row[:n_win]
    idx = jnp.where(pages >= 0, pages, N)
    w = vals[0].reshape(n_win, P, KV, hd)
    q, scale = _quantize_windows(w, _pool_qmax(pool))
    return _scatter_q(pool, layer, idx, q, scale)


def qupdate_pool_per_row(pool, layer, vals: jnp.ndarray, pos,
                         active, table):
    """update_pool_per_row over a quantized pool: each active row's
    decode token lands in ONE page — gather that page + scale, grow
    the scale to cover the token, re-quantize residents by old/new,
    write the token, scatter back. Distinct rows own distinct pages so
    the B round-trips are disjoint; inactive/unmapped rows route to
    the out-of-bounds index on both the gather (zero/one fill) and the
    scatter (drop)."""
    N, P = pool.q.shape[1], _pool_page(pool)
    qmax = _pool_qmax(pool)
    B = vals.shape[0]
    rows = jnp.arange(B)
    pages = table[rows, pos // P]
    offs = pos % P
    valid = jnp.logical_and(active, pages >= 0)
    idx = jnp.where(valid, pages, N)
    qs = _gather_q(pool, layer, idx)                    # [B,P,KV,hd]
    ss = _gather_scale(pool, layer, idx)                # [B,KV]
    tok = vals[:, 0].astype(jnp.float32)                # [B,KV,hd]
    need = jnp.maximum(jnp.max(jnp.abs(tok), axis=-1), _EPS) / qmax
    new_s = jnp.maximum(ss, need)
    qr = _requant(qs, ss / new_s, qmax)
    qt = jnp.clip(jnp.round(tok / new_s[..., None]),
                  -qmax, qmax).astype(jnp.int8)         # [B,KV,hd]
    mask = (jnp.arange(P)[None, :] == offs[:, None])    # [B,P]
    qw = jnp.where(mask[..., None, None], qt[:, None], qr)
    return _scatter_q(pool, layer, idx, qw, new_s)


def _window_pages_rmw(pool, layer, vals, j_idx, off_idx, wmask_src,
                      idx, touched):
    """The gather -> rescale -> overwrite -> scatter core of the
    window writer. vals: [B, C, KV, hd]; j_idx/off_idx: window page /
    in-page offset per position; wmask_src: per-position write
    validity (padding positions neither write nor enter the MONOTONE
    page scale's amax); idx: [B, W] gather/scatter page ids (OOB =
    dropped); touched: [B, W] pages that receive >= 1 position."""
    W = idx.shape[-1]
    P = _pool_page(pool)
    qmax = _pool_qmax(pool)
    B, KV, hd = vals.shape[0], vals.shape[-2], vals.shape[-1]
    qs = _gather_q(pool, layer, idx)               # [B, W, P, KV, hd]
    ss = _gather_scale(pool, layer, idx)           # [B, W, KV]
    # place the window's values + mask into page coordinates: every
    # (page, offset) target is distinct within a row, so one scatter
    buf = jnp.zeros((B, W + 1, P, KV, hd), jnp.float32)
    msk = jnp.zeros((B, W + 1, P), bool)
    jj = jnp.where(wmask_src, j_idx, W)            # invalid -> dropped row
    b = jnp.arange(B)[:, None]
    buf = buf.at[b, jj, off_idx].set(vals.astype(jnp.float32))
    msk = msk.at[b, jj, off_idx].set(wmask_src)
    buf, msk = buf[:, :W], msk[:, :W]
    amax = jnp.max(jnp.where(msk[..., None, None], jnp.abs(buf), 0.0),
                   axis=(-3, -1))                  # [B, W, KV]
    need = jnp.maximum(amax, _EPS) / qmax
    new_s = jnp.where(touched[..., None], jnp.maximum(ss, need), ss)
    qr = _requant(qs, jnp.where(new_s > 0, ss / jnp.maximum(new_s, _EPS),
                                0.0), qmax)
    qt = jnp.clip(jnp.round(buf / jnp.maximum(new_s, _EPS)[..., None, :,
                                              None]),
                  -qmax, qmax).astype(jnp.int8)
    qw = jnp.where(msk[..., None, None], qt, qr)
    return _scatter_q(pool, layer, idx, qw, new_s)


def qwrite_windows_pages(pool, layer, vals: jnp.ndarray, pos,
                         q_len, active, table):
    """write_windows_pages over a quantized pool: the batched mixed
    writer — every row's q_len-token window at its own offset (any
    in-page offset), decode rows (q_len=1) included. Per row the
    window spans at most ceil(C/P)+1 consecutive pages — those are
    gathered, rescaled, overwritten at the window's positions, and
    scattered back; rows own disjoint (non-shared) pages, so the
    batched page round-trips never collide."""
    N, P = pool.q.shape[1], _pool_page(pool)
    B, C = vals.shape[0], vals.shape[1]
    max_pages = table.shape[1]
    W = -(-C // P) + 1
    positions = pos[:, None] + jnp.arange(C)[None, :]     # [B, C]
    pidx = positions // P
    first = pos // P                                      # [B]
    win_pidx = first[:, None] + jnp.arange(W)[None, :]    # [B, W]
    pages = jnp.take_along_axis(
        table, jnp.minimum(win_pidx, max_pages - 1), axis=1)
    last_q = jnp.maximum(q_len, 1) - 1
    touched = (active[:, None] & (q_len[:, None] > 0)
               & (win_pidx <= (pos + last_q)[:, None] // P)
               & (win_pidx < max_pages) & (pages >= 0))
    idx = jnp.where(touched, pages, N)
    p_pages = jnp.take_along_axis(
        table, jnp.minimum(pidx, max_pages - 1), axis=1)
    wvalid = ((jnp.arange(C)[None, :] < q_len[:, None])
              & active[:, None] & (pidx < max_pages) & (p_pages >= 0))
    return _window_pages_rmw(pool, layer, vals, pidx - first[:, None],
                             positions % P, wvalid, idx, touched)
