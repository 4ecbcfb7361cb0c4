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

import math
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np
from jax import lax


def precompute_rope(head_dim: int, max_seq_len: int, theta: float = 10000.0,
                    dtype=jnp.float32, yarn: "Yarn | None" = None):
    """(cos, sin) tables of shape [max_seq_len, head_dim//2]. yarn: the
    config's `rope_scaling` of type yarn (the frequencies blended, the
    tables times its attention factor); None: plain RoPE."""
    if yarn is None:
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                      / head_dim)
        )
        factor = None
    else:
        inv_freq = jnp.asarray(yarn_inv_freq(head_dim, theta, yarn),
                               jnp.float32)
        factor = yarn.table_factor
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [S, hd/2]
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if factor is not None and factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return cos.astype(dtype), sin.astype(dtype)


class Yarn(NamedTuple):
    """config.json `rope_scaling` of type "yarn" (DeepSeek-V2's keys):
    positions past `original` (the trained length) are reached by
    dividing the SLOW frequencies by `factor` and keeping the fast ones,
    with a linear ramp between the pairs that turn beta_fast and
    beta_slow times over the trained length."""

    factor: float
    original: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def magnitude(scale: float, a: float) -> float:
        """m(s, a) = 0.1 a ln s + 1 (1 at or under the trained length)."""
        return 1.0 if scale <= 1 else 0.1 * a * math.log(scale) + 1.0

    @property
    def table_factor(self) -> float:
        """What cos and sin are multiplied by."""
        return (self.magnitude(self.factor, self.mscale)
                / self.magnitude(self.factor, self.mscale_all_dim))

    @property
    def softmax_factor(self) -> float:
        """What the softmax scale head_dim^-0.5 is multiplied by:
        m(factor, mscale_all_dim)^2 (1 where the key is absent or 0)."""
        if not self.mscale_all_dim:
            return 1.0
        return self.magnitude(self.factor, self.mscale_all_dim) ** 2


def yarn_inv_freq(dim: int, theta: float, yarn: Yarn) -> np.ndarray:
    """The dim/2 blended frequencies, float64: with f_i = theta^(-2i/dim)
    and c(n) = dim ln(original / (2 pi n)) / (2 ln theta), the pair at
    which n turns fit the trained length, low = max(floor(c(beta_fast)),
    0), high = min(ceil(c(beta_slow)), dim - 1), ramp r_i = clip((i -
    low) / (high - low), 0, 1): inv_freq_i = (f_i / factor) r_i + f_i
    (1 - r_i)."""
    f = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))

    def c(n: float) -> float:
        return (dim * math.log(yarn.original / (n * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(c(yarn.beta_fast)), 0)
    high = min(math.ceil(c(yarn.beta_slow)), dim - 1)
    span = (high - low) or 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / span,
                   0.0, 1.0)
    return (f / yarn.factor) * ramp + f * (1.0 - ramp)


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
