"""Fixed-shape KV cache for TPU decode.

The reference grows the cache by concatenation each step and trims past
MAX_SEQ_LEN (llama3/cache.rs:93-122 — with a latent axis bug SURVEY.md §2.2
tells us not to replicate). Growing shapes force recompilation under XLA, so
the TPU design preallocates `[num_layers, batch, max_seq, kv_heads, head_dim]`
buffers and writes each step's k/v with `dynamic_update_slice`; the absolute
write position is a traced scalar, so prefill and every decode step reuse one
compiled program. The writers below take that STACKED buffer and a layer index
and write in place (the layout contract above them); the loops around them
carry it (model.scan_layers, parallel/pipeline._gpipe_stage_loop).

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


def layer_rows(cache, layer, row0, n):
    """Layer `layer`'s lines of rows row0..row0+n of a stacked buffer
    [L, B, T, KV, hd] -> [n, T, KV, hd]: what attention reads."""
    return lax.dynamic_slice(
        cache, (layer, row0, 0, 0, 0), (1, n) + cache.shape[2:])[0]


# -- writers --------------------------------------------------------------------
#
# Layout contract (PERF.md section 3): the stacked cache [L, B, T, KV, hd] is
# the CARRY of every loop around it (the layer loop of model.run_blocks /
# run_blocks_ragged, the GPipe tick of parallel/pipeline.py), donated in and
# aliased out. A writer takes the whole buffer plus the layer index and does
# ONE scatter / dynamic_update_slice at [layer, row, pos]; it returns the same
# buffer. What must not be written (an inactive row, a pipeline bubble's
# microbatch, a padded ring entry) is gated at the ROWS written (a scatter
# routes them out of bounds and drops them, a window write puts back the
# window that was there): never by a select over the cache. row0 is the
# first batch row of the rows at hand (a microbatch of the tick; 0 = all).


def _scatter(k_cache, v_cache, at, new_k, new_v):
    """One scatter each of new_k / new_v at index `at`; out-of-bounds
    targets drop."""
    return (k_cache.at[at].set(new_k.astype(k_cache.dtype), mode="drop"),
            v_cache.at[at].set(new_v.astype(v_cache.dtype), mode="drop"))


@jax.named_scope("kv")
def update_layer_cache_per_row(k_cache, v_cache, layer, new_k, new_v, pos,
                               active, row0=0):
    """Write one new k/v per row at that row's own position (ragged decode).

    k_cache/v_cache: [L, B, S_max, KV, hd]
    new_k/new_v:     [n, 1, KV, hd] (single decode token per row)
    pos:             [n] absolute positions (one per row)
    active:          [n] bool; inactive rows keep their existing cache line
                     (their pos may be stale: a retired slot must not
                     corrupt state a future prefill won't overwrite):
                     they route to the out-of-bounds row and drop.
    """
    B = k_cache.shape[1]
    b = jnp.where(active, row0 + jnp.arange(new_k.shape[0]), B)
    return _scatter(k_cache, v_cache, (layer, b, pos), new_k[:, 0],
                    new_v[:, 0])


@jax.named_scope("kv")
def update_layer_cache(k_cache, v_cache, layer, new_k, new_v, pos, row0=0,
                       live=None):
    """Write a layer's new k/v window at absolute position `pos`.

    k_cache/v_cache: [L, B, S_max, KV, hd]
    new_k/new_v:     [n, S, KV, hd]
    pos:             traced scalar start index
    live:            traced bool or None; False (a pipeline bubble) puts
                     back the [n, S, KV, hd] window that was there.
    Returns the updated buffers (same shapes, jit-donatable).
    """
    start = (layer, row0, pos, 0, 0)

    def write(cache, new):
        new = new.astype(cache.dtype)[None]
        if live is not None:
            new = jnp.where(live, new,
                            lax.dynamic_slice(cache, start, new.shape))
        return lax.dynamic_update_slice(cache, new, start)

    return write(k_cache, new_k), write(v_cache, new_v)


# -- ring-buffer (sliding-window) writes --------------------------------------

@jax.named_scope("kv")
def update_layer_cache_ring(k_cache, v_cache, layer, new_k, new_v, pos,
                            n_real=None, row0=0):
    """Write S <= W new k/v at ring slots (pos+i) % W.

    k_cache/v_cache: [L, B, W, KV, hd] ring buffers (W = window capacity)
    new_k/new_v:     [n, S, KV, hd]
    pos:             traced scalar absolute start position
    n_real:          traced count of REAL tokens in the window; entries
                     i >= n_real keep the slot's previous content: a
                     padded chunk's junk would otherwise alias ring slots
                     of positions still inside upcoming queries' windows
                     (the dense cache never had this hazard: junk landed
                     at untouched higher positions). 0 writes nothing
                     (a pipeline bubble).
    """
    W = k_cache.shape[2]
    n, S = new_k.shape[:2]
    assert S <= W, f"ring write of {S} tokens exceeds ring capacity {W}"
    b = (row0 + jnp.arange(n))[:, None]
    i = jnp.arange(S)
    slots = jnp.mod(pos + i, W)                              # [S] unique
    if n_real is not None:
        slots = jnp.where(i < n_real, slots, W)              # dropped
    return _scatter(k_cache, v_cache, (layer, b, slots[None]), new_k, new_v)


def update_layer_cache_per_row_ring(k_cache, v_cache, layer, new_k, new_v,
                                    pos, active, row0=0):
    """Ragged single-token ring write: row b writes at slot pos[b] % W."""
    W = k_cache.shape[2]
    return update_layer_cache_per_row(k_cache, v_cache, layer, new_k, new_v,
                                      jnp.mod(pos, W), active, row0)


@jax.named_scope("kv")
def update_layer_cache_window_per_row(k_cache, v_cache, layer, new_k, new_v,
                                      pos0, active, row0=0):
    """Write a W-token window per row at that row's own start position
    (the batched speculative verify: row b's tokens j land at absolute
    positions pos0[b]+j).

    k_cache/v_cache: [L, B, S_max, KV, hd]
    new_k/new_v:     [n, W, KV, hd]
    pos0:            [n] absolute start positions
    active:          [n] bool; inactive rows keep their cache lines.
    Positions clamp at S_max-1 (callers bound pos0+W <= S_max)."""
    n, W = new_k.shape[:2]
    B, T = k_cache.shape[1:3]
    b = jnp.where(active, row0 + jnp.arange(n), B)[:, None]
    idx = jnp.clip(pos0[:, None] + jnp.arange(W)[None], 0, T - 1)
    return _scatter(k_cache, v_cache, (layer, b, idx), new_k, new_v)
