"""The accept/resample arithmetic of a speculative round.

The subtle part of speculation (Leviathan et al., 2023: rejection
sampling with the leftover-residual correction) apart from the round
that moves KV (cake_tpu/spec/round.py), so that it is tested on arrays
built by hand (tests/test_spec_accept.py).

Everything here is branch-free jnp arithmetic on stacked logits —
trace-safe inside any caller's jit, cache-layout agnostic (nothing
touches KV), and shape-polymorphic over the batch dimension.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "advance_row_keys", "greedy_accept", "rejection_accept",
    "assemble_sampled",
]


def advance_row_keys(keys, advance_mask):
    """Per-row PRNG split: returns (keys', subs [B, 2]) where keys'
    advanced only for rows in advance_mask (idle slots and greedy rows
    keep their stream untouched — concurrency must not change a
    request's sampled tokens)."""
    new_keys, subs = jax.vmap(jax.random.split, out_axes=1)(keys)
    return jnp.where(advance_mask[:, None], new_keys, keys), subs


def greedy_accept(drafts, targets):
    """Accepted-draft count per row under exact-match (greedy)
    acceptance: the longest prefix where draft == target argmax."""
    match = drafts == targets[:, : drafts.shape[1]]
    return jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)


def rejection_accept(drafts, d_probs, t_probs, u, gamma: int):
    """Leviathan accept/reject over a [B, gamma] draft burst, plus the
    leftover-residual distribution at the first rejected position r —
    norm(max(0, p_t - p_d)); at r == gamma (all accepted) the bonus
    token samples from the target's own distribution.
    Returns (n_acc [B], resid [B, V])."""
    B = drafts.shape[0]
    idx = drafts[..., None]                            # [B, gamma, 1]
    p_t = jnp.take_along_axis(t_probs[:, :gamma], idx, axis=-1)[..., 0]
    p_d = jnp.take_along_axis(d_probs, idx, axis=-1)[..., 0]
    accept = u < jnp.minimum(1.0, p_t / jnp.maximum(p_d, 1e-20))
    n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                    axis=1)
    r = jnp.minimum(n_acc, gamma)
    row = jnp.arange(B)
    p_t_r = t_probs[row, r]                            # [B, V]
    p_d_r = jnp.where((r < gamma)[:, None],
                      d_probs[row, jnp.minimum(r, gamma - 1)], 0.0)
    resid = jnp.maximum(p_t_r - p_d_r, 0.0)
    resid = resid / jnp.maximum(jnp.sum(resid, -1, keepdims=True),
                                1e-20)
    return n_acc, resid


def assemble_sampled(drafts, correction, n_acc, gamma: int):
    """Per-row output burst for the sampled path: accepted drafts, then
    the correction/bonus token at position n_acc, tail padded with the
    last draft (masked off by the caller's n_emit mask)."""
    return jnp.where(jnp.arange(gamma + 1)[None] ==
                     jnp.minimum(n_acc, gamma)[:, None],
                     correction[:, None],
                     jnp.concatenate([drafts, drafts[:, -1:]], axis=1))
