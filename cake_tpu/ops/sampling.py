"""Token sampling: argmax / temperature / top-k / top-p + repeat penalty.

Reference: candle's `LogitsProcessor` configured from Args
(llama3/llama.rs:35-48: temperature<=0 -> ArgMax, else TopKThenTopP /
TopK / TopP / All) and `apply_repeat_penalty` over the last
`repeat_last_n` generated tokens (llama.rs:311-320, candle semantics:
positive logits are divided by the penalty, negative multiplied).

Everything here is jit-compatible and batched: token history is a fixed
shape [B, repeat_last_n] ring buffer (pad slots = -1), so the whole
sample step fuses into the decode program with no host round-trip.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SamplingConfig:
    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    repeat_penalty: float = 1.1
    repeat_last_n: int = 128

    @property
    def greedy(self) -> bool:
        return self.temperature is None or self.temperature <= 0.0


def apply_repeat_penalty(logits, recent_tokens, penalty: float):
    """Penalise recently-generated tokens.

    logits:        [B, V] f32
    recent_tokens: [B, N] int32, -1 marks empty ring-buffer slots
    """
    if penalty == 1.0:
        return logits
    B, V = logits.shape
    valid = recent_tokens >= 0
    ids = jnp.clip(recent_tokens, 0, V - 1)
    hit = jnp.zeros((B, V), dtype=bool)
    batch_idx = jnp.arange(B)[:, None].repeat(recent_tokens.shape[1], axis=1)
    hit = hit.at[batch_idx, ids].max(valid)
    penalised = jnp.where(logits >= 0.0, logits / penalty, logits * penalty)
    return jnp.where(hit, penalised, logits)


def _mask_top_k(logits, k: int):
    """Keep only the k largest logits per row."""
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _ordered_keys(x):
    """int32 keys that order as the f32 values of x do: a float's bits,
    the low 31 flipped where the sign is set. -0.0 is filed with +0.0 (as
    `<` files it) and -inf is the smallest key a top-k mask can leave."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = jnp.where(x == 0.0, 0, bits)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _signed(prefix):
    """A uint32 search prefix as the int32 key of the same rank."""
    return jax.lax.bitcast_convert_type(
        prefix ^ jnp.uint32(0x80000000), jnp.int32)


def nucleus_floor(scaled, top_p):
    """Nucleus (top-p) filtering without ordering the row: keep token i iff
    the probability mass of the tokens STRICTLY above it is < p, so ties of
    the last kept value survive together; the rest become -inf.

    scaled: [..., V] f32 logits (-inf entries, a top-k mask's, stay out)
    top_p:  a float or [...] f32, one p a row; p <= 0 keeps the maximum and
            its ties, p >= 1 keeps every token

    With g(K) = sum(probs[key > K]), non-increasing in K, the kept set is
    {key >= K*} for K* = min{K : g(K) < p}. K* has 32 bits, fixed from the
    top, each by ONE masked sum over the row: the candidate is the prefix
    with this bit 0 and the lower bits 1, the largest K the 0 allows.
    """
    p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32),
                         scaled.shape[:-1])[..., None]
    probs = jax.nn.softmax(scaled, axis=-1)
    keys = _ordered_keys(scaled)

    def fix_bit(i, prefix):
        bit = jnp.uint32(1) << (31 - i).astype(jnp.uint32)
        above = jnp.sum(
            jnp.where(keys > _signed(prefix | (bit - 1)), probs, 0.0),
            axis=-1, keepdims=True)
        return jnp.where(above < p, prefix, prefix | bit)

    floor = jax.lax.fori_loop(0, 32, fix_bit, jnp.zeros(p.shape, jnp.uint32))
    # the top token always survives (p <= 0 finds no K: every bit is set)
    top = _ordered_keys(jnp.max(scaled, axis=-1, keepdims=True))
    floor = jnp.minimum(_signed(floor), top)
    floor = jnp.where(p >= 1.0, jnp.iinfo(jnp.int32).min, floor)
    return jnp.where(keys < floor, -jnp.inf, scaled)


def _mask_top_p(logits, p: float):
    """Nucleus filtering with one p for every row (`nucleus_floor`)."""
    return nucleus_floor(logits, p)


@partial(jax.jit, static_argnames=("config",))
def sample_tokens(rng, logits, recent_tokens, config: SamplingConfig):
    """Sample next token ids. logits [B, V] -> [B] int32."""
    logits = logits.astype(jnp.float32)
    logits = apply_repeat_penalty(logits, recent_tokens, config.repeat_penalty)
    if config.greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / config.temperature
    if config.top_k is not None:
        logits = _mask_top_k(logits, config.top_k)
    if config.top_p is not None:
        logits = _mask_top_p(logits, config.top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def update_ring(recent_tokens, new_tokens, step):
    """Push new tokens into the [B, N] ring buffer at slot step % N."""
    N = recent_tokens.shape[1]
    slot = jnp.mod(step, N)
    return recent_tokens.at[:, slot].set(new_tokens)


def update_ring_per_row(recent_tokens, new_tokens, steps):
    """Per-row ring push: row b writes at slot steps[b] % N (ragged decode)."""
    N = recent_tokens.shape[1]
    b = jnp.arange(recent_tokens.shape[0])
    return recent_tokens.at[b, jnp.mod(steps, N)].set(new_tokens)


def _apply_repeat_penalty_per_row(logits, recent_tokens, penalty):
    """Like `apply_repeat_penalty` but penalty is a [B] traced vector."""
    B, V = logits.shape
    valid = recent_tokens >= 0
    ids = jnp.clip(recent_tokens, 0, V - 1)
    hit = jnp.zeros((B, V), dtype=bool)
    batch_idx = jnp.arange(B)[:, None].repeat(recent_tokens.shape[1], axis=1)
    hit = hit.at[batch_idx, ids].max(valid)
    pen = penalty[:, None]
    penalised = jnp.where(logits >= 0.0, logits / pen, logits * pen)
    return jnp.where(hit, penalised, logits)


@partial(jax.jit, static_argnames=("top_k", "n_top"))
@jax.named_scope("sample")   # the caller's scope stops at a jit's edge
def sample_tokens_ragged(keys, logits, recent_tokens, temperature, top_p,
                         repeat_penalty, top_k: Optional[int] = None,
                         n_top: int = 0):
    """Batched sampling with PER-ROW options (continuous batching: each slot
    carries its own request's temperature/top_p/repeat_penalty).

    keys:            [B] PRNG keys (one per slot — a row's stream is
                     independent of which other requests share the batch)
    logits:          [B, V]
    recent_tokens:   [B, N] ring buffers (-1 = empty)
    temperature:     [B] f32; <= 0 means greedy for that row
    top_p:           [B] f32; >= 1 keeps every token of that row, <= 0 its
                     maximum alone (`nucleus_floor`)
    repeat_penalty:  [B] f32; 1.0 disables
    top_k:           static engine-wide k (the REST API exposes only
                     temperature/top_p per request, matching the reference's
                     global Args.top_k)
    n_top:           static: also return the n most probable alternative
                     tokens per row (the OpenAI `top_logprobs` quantity);
                     0 skips the extra top_k entirely
    Returns ([B] int32 ids, [B] f32 logprobs, [B, n_top] int32 top ids,
    [B, n_top] f32 top logprobs) — the chosen token's log-probability
    under the post-penalty model distribution (the OpenAI `logprobs`
    quantity; temperature/top-p are sampling transforms and do not change
    the reported probability, the HF/vLLM convention). Computed here so
    the penalized logits are reused — one penalty pass, one softmax.
    """
    logits = logits.astype(jnp.float32)
    logits = _apply_repeat_penalty_per_row(logits, recent_tokens,
                                           repeat_penalty)
    greedy = temperature <= 0.0
    argmax_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    safe_t = jnp.where(greedy, 1.0, temperature)[:, None]
    scaled = logits / safe_t
    if top_k is not None:
        scaled = _mask_top_k(scaled, top_k)
    filtered = nucleus_floor(scaled, top_p)
    sampled = jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg)
    )(keys, filtered).astype(jnp.int32)
    ids = jnp.where(greedy, argmax_ids, sampled)
    lp = jax.nn.log_softmax(logits, axis=-1)
    chosen_lp = jnp.take_along_axis(lp, ids[:, None], axis=-1)[:, 0]
    B = logits.shape[0]
    if n_top > 0:
        top_lps, top_ids = jax.lax.top_k(lp, n_top)
        top_ids = top_ids.astype(jnp.int32)
    else:
        top_ids = jnp.zeros((B, 0), jnp.int32)
        top_lps = jnp.zeros((B, 0), jnp.float32)
    return ids, chosen_lp, top_ids, top_lps
