"""Rotary position embeddings.

Reference semantics: cos/sin tables precomputed for every position up to the
max sequence length (llama3/cache.rs:23-61: inv_freq = theta^(-2i/d), outer
product with positions) and applied per attention call via candle's
`rotary_emb::rope` (attention.rs:25-35), which is the non-interleaved
("rotate-half" / NeoX / HF-Llama) variant.

On TPU the tables live in HBM once per process; `apply_rope` gathers the
rows for the current positions with a dynamic slice (static shapes, no
recompute per step).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def precompute_rope(head_dim: int, max_seq_len: int, theta: float = 10000.0,
                    dtype=jnp.float32):
    """(cos, sin) tables of shape [max_seq_len, head_dim//2]."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [S, hd/2]
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def rope_rows(cos, sin, pos, seq_len: int):
    """Slice [pos : pos+seq_len] rows out of the tables (pos may be traced)."""
    c = lax.dynamic_slice_in_dim(cos, pos, seq_len, axis=0)
    s = lax.dynamic_slice_in_dim(sin, pos, seq_len, axis=0)
    return c, s


def rope_rows_per_row(cos, sin, pos):
    """Gather one table row per batch element (ragged decode).

    pos: [B] absolute positions -> (cos, sin) of shape [B, 1, head_dim//2],
    ready for `apply_rope` in per-row mode.
    """
    c = jnp.take(cos, pos, axis=0)[:, None, :]
    s = jnp.take(sin, pos, axis=0)[:, None, :]
    return c, s


def apply_rope(x, cos, sin):
    """Rotate-half RoPE.

    x:        [batch, seq, heads, head_dim]
    cos/sin:  [seq, R//2] shared across the batch, or
              [batch, seq, R//2] per-row (ragged decode).
    R is the rotated part of a head: the whole head (tables of
    head_dim//2), or under a partial rotary factor its first R dims,
    paired (i, i + R//2); dims [R, head_dim) pass through.
    """
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
    half = x.shape[-1] // 2
    x1 = x[..., :half]
    x2 = x[..., half:]
    if cos.ndim == 2:
        c = cos[None, :, None, :].astype(jnp.float32)
        s = sin[None, :, None, :].astype(jnp.float32)
    else:
        c = cos[:, :, None, :].astype(jnp.float32)
        s = sin[:, :, None, :].astype(jnp.float32)
    x1f = x1.astype(jnp.float32)
    x2f = x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1)
    return out.astype(x.dtype)
