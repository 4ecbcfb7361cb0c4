"""One batched propose-verify-accept round over PAGED KV.

The draft loop is a lax.scan of gamma+1 ragged paged decode steps
(models/llama/paged.forward_ragged_paged — each step writes the draft
token's KV into the DRAFT pool through the draft table row and attends
it), the verify is ONE mixed-window pass with logits at every position
(paged.verify_window_paged — target KV for positions pos..pos+gamma
scatters into the target row's pages, suffix-extension pages included),
and acceptance is the arithmetic in cake_tpu/spec/accept.py.

Cache contract: last_tok sits at absolute `pos` with its KV not yet
written in EITHER pool; the round writes positions pos..pos+gamma in
both; positions past the accepted frontier hold masked garbage that the
next round overwrites before attending (nothing rolls back). The CALLER
(serve/engine._do_spec_paged) must have extended both table rows to
cover pos+gamma inclusive — writes past the mapped pages are silently
dropped by the -1 guard, which would zero an accepted position's KV.

Both pools share one PageAllocator id space (the draft pool is created
with the target pool's page geometry), so this round needs no allocator
knowledge at all: alloc/extend/truncate stay host-side in the engine.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.paged import (
    PagedKVCache, forward_ragged_paged, verify_window_paged,
)
from cake_tpu.spec.accept import (
    advance_row_keys, assemble_sampled, greedy_accept, rejection_accept,
)

__all__ = ["spec_round_paged"]


@partial(jax.jit,
         static_argnames=("t_cfg", "d_cfg", "gamma", "attn"),
         donate_argnames=("t_cache", "d_cache"))
def spec_round_paged(t_params, d_params, t_cache: PagedKVCache,
                     d_cache: PagedKVCache, last_tok, pos, active,
                     keys, temp, t_rope, d_rope,
                     t_cfg: LlamaConfig, d_cfg: LlamaConfig,
                     gamma: int, attn: str = "fold"):
    """One round for EVERY planned spec row in one compiled program.

    last_tok [B, 1] at per-row absolute `pos` (KV unwritten in both
    pools); active [B] marks the spec rows (inactive rows' pages are
    untouched: draft steps carry `active`, the verify window carries
    q_len = 0); keys [B, 2] per-slot PRNG keys (advanced only for
    active sampled rows — the same streams a plain-decode engine would
    consume, so a spec-degraded stream's sampling is unperturbed);
    temp [B] (<= 0 -> greedy row: argmax drafts + exact-match
    acceptance; > 0 -> leftover-residual rejection sampling).
    Returns (out [B, gamma+1] — first n_emit[b] valid, rest -1;
    n_emit [B] (0 for inactive rows); t_cache; d_cache; keys)."""
    greedy = temp <= 0.0
    temp_eff = jnp.where(greedy, 1.0, temp)[:, None]

    def draft_body(carry, _):
        cache, tok, p, keys = carry
        logits, cache = forward_ragged_paged(d_params, tok, cache, p,
                                             active, d_rope, d_cfg,
                                             attn=attn)
        probs = jax.nn.softmax(logits / temp_eff, axis=-1)
        nxt_g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        keys, subs = advance_row_keys(keys, active & ~greedy)
        nxt_s = jax.vmap(jax.random.categorical)(
            subs, logits / temp_eff).astype(jnp.int32)
        nxt = jnp.where(greedy, nxt_g, nxt_s)
        return ((cache, nxt[:, None], p + active, keys),
                (nxt, probs))

    # gamma+1 draft steps: step gamma writes the last draft's KV (an
    # all-accept round needs no patch-up pass); its proposal is unused
    (d_cache, _, _, keys), (drafts_all, d_probs_all) = jax.lax.scan(
        draft_body, (d_cache, last_tok, pos, keys), None,
        length=gamma + 1)
    drafts = drafts_all[:gamma].T                      # [B, gamma]
    d_probs = jnp.swapaxes(d_probs_all[:gamma], 0, 1)  # [B, gamma, V]

    # verify: ONE mixed-window pass scores [last_tok, d_0..d_{g-1}]
    # per row and writes target KV for positions pos..pos+gamma
    tokens_v = jnp.concatenate([last_tok, drafts], axis=1)
    q_len = jnp.where(active, gamma + 1, 0).astype(jnp.int32)
    t_logits, t_cache = verify_window_paged(
        t_params, tokens_v, pos, q_len, active, t_cache, t_rope,
        t_cfg, attn=attn)                              # [B, g+1, V]

    # greedy rows: exact-match acceptance against the target argmax
    targets = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
    n_acc_g = greedy_accept(drafts, targets)

    # sampled rows: leftover-residual rejection sampling per row;
    # greedy rows' residual/correction are computed but unused and
    # their keys never advance
    t_probs = jax.nn.softmax(t_logits / temp_eff[..., None], axis=-1)
    keys, subs = advance_row_keys(keys, active & ~greedy)
    u = jax.vmap(lambda k: jax.random.uniform(k, (gamma,)))(subs)
    n_acc_s, resid = rejection_accept(drafts, d_probs, t_probs, u,
                                      gamma)
    keys, subs = advance_row_keys(keys, active & ~greedy)
    correction = jax.vmap(jax.random.categorical)(
        subs, jnp.log(jnp.maximum(resid, 1e-20))).astype(jnp.int32)
    out_s = assemble_sampled(drafts, correction, n_acc_s, gamma)

    n_acc = jnp.where(greedy, n_acc_g, n_acc_s)
    out = jnp.where(greedy[:, None], targets, out_s)
    n_emit = jnp.where(active, n_acc + 1, 0)
    mask = jnp.arange(gamma + 1)[None] < n_emit[:, None]
    out = jnp.where(mask, out, -1)
    return out, n_emit, t_cache, d_cache, keys
