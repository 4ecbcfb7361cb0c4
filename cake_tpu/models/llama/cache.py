"""Fixed-shape KV cache for TPU decode.

The reference grows the cache by concatenation each step and trims past
MAX_SEQ_LEN (llama3/cache.rs:93-122 — with a latent axis bug SURVEY.md §2.2
tells us not to replicate). Growing shapes force recompilation under XLA, so
the TPU design preallocates `[num_layers, batch, max_seq, kv_heads, head_dim]`
buffers and writes each step's k/v with `dynamic_update_slice`; the absolute
write position is a traced scalar, so prefill and every decode step reuse one
compiled program.

Per-session isolation (reference `Cache::as_new`, cache.rs:125-129) is
`KVCache.fresh()` — a zeroed cache of the same spec; `clear()` semantics
(cache.rs:132-135) are the same operation since the buffers are dense arrays.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from cake_tpu.models.llama.config import LlamaConfig


class KVCache(NamedTuple):
    """Stacked per-layer KV buffers. k/v: [L, B, S_max, KV, hd]."""

    k: jnp.ndarray
    v: jnp.ndarray

    @classmethod
    def create(cls, config: LlamaConfig, batch_size: int, max_seq_len: int,
               dtype=jnp.bfloat16, num_layers: int | None = None) -> "KVCache":
        L = num_layers if num_layers is not None else config.num_hidden_layers
        shape = (
            L, batch_size, max_seq_len,
            config.num_key_value_heads, config.head_dim,
        )
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))

    def fresh(self) -> "KVCache":
        """Zeroed cache with identical spec (reference cache.rs:125-135)."""
        return KVCache(k=jnp.zeros_like(self.k), v=jnp.zeros_like(self.v))

    @property
    def max_seq_len(self) -> int:
        return self.k.shape[2]

    @property
    def batch_size(self) -> int:
        return self.k.shape[1]


@jax.named_scope("kv")
def update_layer_cache_per_row(k_cache, v_cache, new_k, new_v, pos, active):
    """Write one new k/v per row at that row's own position (ragged decode).

    k_cache/v_cache: [B, S_max, KV, hd]
    new_k/new_v:     [B, 1, KV, hd] (single decode token per row)
    pos:             [B] absolute positions (one per row)
    active:          [B] bool; inactive rows keep their existing cache line
                     (their pos may be stale — a retired slot must not
                     corrupt state a future prefill won't overwrite).
    """
    b = jnp.arange(k_cache.shape[0])
    sel = active[:, None, None]
    old_k = k_cache[b, pos]
    old_v = v_cache[b, pos]
    k_cache = k_cache.at[b, pos].set(
        jnp.where(sel, new_k[:, 0].astype(k_cache.dtype), old_k))
    v_cache = v_cache.at[b, pos].set(
        jnp.where(sel, new_v[:, 0].astype(v_cache.dtype), old_v))
    return k_cache, v_cache


@jax.named_scope("kv")
def update_layer_cache(k_cache, v_cache, new_k, new_v, pos):
    """Write one layer's new k/v at absolute position `pos`.

    k_cache/v_cache: [B, S_max, KV, hd]
    new_k/new_v:     [B, S, KV, hd]
    pos:             traced scalar start index
    Returns the updated buffers (same shapes — jit-donatable).
    """
    zeros = (0, pos, 0, 0)
    k_cache = lax.dynamic_update_slice(k_cache, new_k.astype(k_cache.dtype), zeros)
    v_cache = lax.dynamic_update_slice(v_cache, new_v.astype(v_cache.dtype), zeros)
    return k_cache, v_cache


# -- ring-buffer (sliding-window) writes --------------------------------------

@jax.named_scope("kv")
def update_layer_cache_ring(k_cache, v_cache, new_k, new_v, pos, n_real=None):
    """Write S <= W new k/v at ring slots (pos+i) % W.

    k_cache/v_cache: [B, W, KV, hd] ring buffers (W = window capacity)
    new_k/new_v:     [B, S, KV, hd]
    pos:             traced scalar absolute start position
    n_real:          traced count of REAL tokens in the window; entries
                     i >= n_real keep the slot's previous content — a
                     padded chunk's junk would otherwise alias ring slots
                     of positions still inside upcoming queries' windows
                     (the dense cache never had this hazard: junk landed
                     at untouched higher positions).
    """
    B, W = k_cache.shape[0], k_cache.shape[1]
    S = new_k.shape[1]
    assert S <= W, f"ring write of {S} tokens exceeds ring capacity {W}"
    slots = jnp.mod(pos + jnp.arange(S), W)                  # [S] unique
    keep = (jnp.arange(S) >= (S if n_real is None else n_real))
    old_k = k_cache[:, slots]
    old_v = v_cache[:, slots]
    sel = keep[None, :, None, None]
    k_cache = k_cache.at[:, slots].set(
        jnp.where(sel, old_k, new_k.astype(k_cache.dtype)))
    v_cache = v_cache.at[:, slots].set(
        jnp.where(sel, old_v, new_v.astype(v_cache.dtype)))
    return k_cache, v_cache


def update_layer_cache_per_row_ring(k_cache, v_cache, new_k, new_v, pos,
                                    active):
    """Ragged single-token ring write: row b writes at slot pos[b] % W."""
    W = k_cache.shape[1]
    return update_layer_cache_per_row(k_cache, v_cache, new_k, new_v,
                                      jnp.mod(pos, W), active)


@jax.named_scope("kv")
def update_layer_cache_window_per_row(k_cache, v_cache, new_k, new_v,
                                      pos0, active):
    """Write a W-token window per row at that row's own start position
    (the batched speculative verify: row b's tokens j land at absolute
    positions pos0[b]+j).

    k_cache/v_cache: [B, S_max, KV, hd]
    new_k/new_v:     [B, W, KV, hd]
    pos0:            [B] absolute start positions
    active:          [B] bool; inactive rows keep their cache lines.
    Indices clamp at S_max-1 (callers bound pos0+W <= S_max; the clamp
    only protects inactive rows' stale pos0)."""
    B, W = new_k.shape[:2]
    b = jnp.arange(B)[:, None]
    idx = jnp.clip(pos0[:, None] + jnp.arange(W)[None],
                   0, k_cache.shape[1] - 1)
    sel = active[:, None, None, None]
    old_k = k_cache[b, idx]
    old_v = v_cache[b, idx]
    k_cache = k_cache.at[b, idx].set(
        jnp.where(sel, new_k.astype(k_cache.dtype), old_k))
    v_cache = v_cache.at[b, idx].set(
        jnp.where(sel, new_v.astype(v_cache.dtype), old_v))
    return k_cache, v_cache
