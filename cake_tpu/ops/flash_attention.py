"""Pallas TPU flash attention (causal, GQA-aware) for the prefill path.

The reference computes attention as naive matmul→softmax→matmul in f32
(llama3/attention.rs:96-118), materialising the full [S, T] score matrix in
memory. On TPU that matrix is pure HBM traffic; the flash formulation keeps
one [block_q, block_k] tile in VMEM and carries online-softmax statistics
(m, l) across key blocks, so the kernel is MXU-bound instead of
bandwidth-bound for long sequences.

Layout: grid (batch, q_head, q_block, k_block); the k_block axis is the
innermost (sequential on TPU), carrying f32 accumulators in VMEM scratch.
GQA is handled in the k/v index maps (query head h reads kv head h // G) —
no repeat_kv materialisation. Causal blocks above the diagonal are skipped
with `pl.when` (upper-triangular tiles cost ~0).

CPU tests run the same kernel with interpret=True (tests/test_flash.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_softmax_step(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
                         scale: float, mask, v_valid=None):
    """One (q_block, k_block) tile of the online-softmax recurrence.

    mask: boolean [block_q, block_k] (True = attend) or None. Shared by
    the fresh-window and cache-aware kernels.
    v_valid: boolean [block_k, 1] or None — zero out v rows beyond the
    cache frontier before the p @ v matmul: a masked score contributes
    p = 0, but 0 * non-finite garbage is NaN, so garbage must never reach
    the dot.
    """
    q = q_ref[0, 0]                      # [block_q, hd]
    k = k_ref[0, 0]                      # [block_k, hd]
    v = v_ref[0, 0]
    if v_valid is not None:
        v = jnp.where(v_valid, v, 0.0)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                            # [block_q, block_k]
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]                # [block_q, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)      # rescale of old accumulator
    p = jnp.exp(s - m_new)               # [block_q, block_k]
    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _finish_block(o_ref, acc_ref, l_ref):
    l = l_ref[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)      # fully-masked row guard
    o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  window=None):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    def compute():
        mask = None
        if causal:
            qi = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kj = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = kj <= qi
            if window is not None:
                # sliding-window attention: at most `window` most-recent
                # positions per query (own position included)
                mask &= kj > qi - window
        _online_softmax_step(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                             scale=scale, mask=mask)

    if causal:
        # k_start/q_start are traced (grid ids), so gate at runtime;
        # with a window, key blocks entirely BELOW every query's window
        # are skipped too (the flash win windows exist for: out-of-window
        # tiles cost ~0)
        gate = k_start <= q_start + block_q - 1
        if window is not None:
            gate &= k_start + block_k - 1 > q_start - window

        @pl.when(gate)
        def _():
            compute()
    else:
        compute()

    @pl.when(ik == nk - 1)
    def _finish():
        _finish_block(o_ref, acc_ref, l_ref)


def _flash_kernel_cached(pos_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *,
                         scale: float, block_q: int, block_k: int,
                         seq_len: int, window=None):
    """Cache-aware variant: queries sit at absolute positions
    pos..pos+seq_len-1 and attend the whole KV cache [T], masked to
    kj <= pos + qi (chunked/continued prefill; pos is a prefetched
    scalar, so one compiled kernel serves every chunk position)."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    pos = pos_ref[0]

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # skip key blocks entirely above this query block's last position
    # (their DMAs are also elided — the k/v index maps clamp to the same
    # limit, so Pallas re-reads the resident block instead of fetching);
    # with a window, blocks entirely below every query's window skip too
    gate = k_start <= pos + q_start + block_q - 1
    if window is not None:
        gate &= k_start + block_k - 1 > pos + q_start - window

    @pl.when(gate)
    def _():
        qi = pos + q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kj = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kj <= qi
        if window is not None:
            mask &= kj > qi - window
        # cache slots at/after the write frontier pos+seq_len may hold
        # stale or non-finite garbage in the boundary block
        col_valid = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < pos + seq_len
        _online_softmax_step(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                             scale=scale, mask=mask,
                             v_valid=col_valid)

    @pl.when(ik == nk - 1)
    def _finish():
        _finish_block(o_ref, acc_ref, l_ref)


def _flash_bhsd(q, k, v, *, scale, causal, block_q, block_k, interpret,
                window=None):
    """q [B,H,S,hd], k/v [B,KV,T,hd] -> [B,H,S,hd]."""
    B, H, S, hd = q.shape
    _, KV, T, _ = k.shape
    G = H // KV
    nq = S // block_q
    nk = T // block_k

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window,
    )
    return pl.pallas_call(
        kernel,
        name="cake_flash_prefill",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        # only the innermost (k) axis carries scratch state; the rest can be
        # scheduled across megacore
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)


def flash_attention(q, k, v, *, scale: float | None = None,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None,
                    window: int | None = None):
    """Flash attention over [B, S, H, hd] q and [B, T, KV, hd] k/v.

    Falls back to None-signalling (caller uses the einsum path) is NOT done
    here — callers should check `flash_supported(...)` first. Shapes must
    tile: S % block_q == 0, T % block_k == 0.
    """
    B, S, H, hd = q.shape
    _, T, KV, _ = k.shape
    if window is not None and not causal:
        raise ValueError(
            "window requires causal=True: the non-causal kernel applies "
            "no window mask, so the window would be silently ignored")
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    qt = jnp.swapaxes(q, 1, 2)        # [B, H, S, hd]
    kt = jnp.swapaxes(k, 1, 2)        # [B, KV, T, hd]
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash_bhsd(qt, kt, vt, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k, interpret=interpret,
                      window=window)
    return jnp.swapaxes(out, 1, 2)


def _flash_bhsd_cached(pos, q, k, v, *, scale, block_q, block_k,
                       interpret, window=None):
    """q [B,H,S,hd] at absolute offset pos; k/v [B,KV,T,hd] full cache."""
    B, H, S, hd = q.shape
    _, KV, T, _ = k.shape
    G = H // KV
    grid = (B, H, S // block_q, T // block_k)
    kernel = functools.partial(
        _flash_kernel_cached, scale=scale, block_q=block_q, block_k=block_k,
        seq_len=S, window=window,
    )

    def kv_index(b, h, i, j, pos_ref):
        # clamp skipped k-blocks (beyond this q-block's causal limit) to
        # the limit block: Pallas elides the DMA when the index repeats,
        # so a pos=0 whole-cache call reads only the live prefix, not all
        # T slots. With a window, blocks entirely BELOW every query's
        # window clamp to the lowest in-window block — at long context
        # this is most of the cache, and flash there is bandwidth-bound,
        # so eliding these DMAs is the point of the window.
        limit = jax.lax.div(pos_ref[0] + i * block_q + block_q - 1,
                            jnp.int32(block_k))
        j = jnp.minimum(j, limit)
        if window is not None:
            lo = jax.lax.div(
                jnp.maximum(pos_ref[0] + i * block_q - window + 1,
                            jnp.int32(0)),
                jnp.int32(block_k))
            j = jnp.maximum(j, lo)
        return (b, h // G, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_index),
            pl.BlockSpec((1, 1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, i, j, *_: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="cake_flash_prefill_cached",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), q, k, v)


def flash_attention_cached(q, k_cache, v_cache, pos, *,
                           scale: float | None = None, block_q: int = 128,
                           block_k: int = 128,
                           interpret: bool | None = None,
                           window: int | None = None):
    """Flash attention for a query window at absolute position `pos`
    against the full KV cache (chunked/continued prefill, pos > 0).

    q:              [B, S, H, hd] — the chunk's queries (RoPE applied)
    k_cache/v_cache:[B, T, KV, hd] — entries < pos+S written (the chunk's
                    own k/v included); later slots may be garbage, they
                    are causally masked.
    pos:            traced scalar — one compiled kernel serves every chunk.
    Equivalent to gqa_attention(q, kc, vc, mask=decode_mask(pos, S, T)).
    """
    B, S, H, hd = q.shape
    _, T, KV, _ = k_cache.shape
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k_cache, 1, 2)
    vt = jnp.swapaxes(v_cache, 1, 2)
    out = _flash_bhsd_cached(pos, qt, kt, vt, scale=scale,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret, window=window)
    return jnp.swapaxes(out, 1, 2)


def flash_supported(S: int, T: int, H: int, KV: int, hd: int,
                    block_q: int = 128, block_k: int = 128) -> bool:
    """Static shape check for the flash path (S = query window, T = KV
    length — equal for fresh-prompt prefill, T > S for the cache-aware
    chunked-prefill kernel).

    Beyond divisibility, the clamped blocks must be Mosaic-tileable: the
    second-minor dim of a bf16 tile is 16, so unaligned blocks (e.g. S=100
    -> block_q=100) compile only in interpret mode and must fall back to
    the einsum path on hardware. The minor (lane) dim is the head dim:
    on real TPU it must fill 128-wide lanes, or Mosaic rejects the
    kernel (found running the tiny-shape suite on silicon: hd=16
    compiles in interpret mode, HTTP-500s out of the hardware compiler).
    Callers that know the head dim pass it; production configs (hd=128)
    pass the gate, tiny test configs fall back to the einsum path on
    hardware and keep exercising the kernel in interpret mode on CPU.
    """
    bq = min(block_q, S)
    bk = min(block_k, T)
    if hd % 128 != 0 and jax.default_backend() == "tpu":
        return False
    return (S > 1 and S % bq == 0 and T % bk == 0 and H % KV == 0
            and bq % 16 == 0 and bk % 16 == 0)
