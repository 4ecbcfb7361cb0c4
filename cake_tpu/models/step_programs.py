"""The sampled step programs: a ragged forward or a mixed step, the
sample and the EOS freeze in ONE jitted program, so that the next step
can be dispatched from this one's tokens while they are still on the
device. A paged family builds its own once, at module level beside its
trunk (models/family.py), so the jit cache is shared across engine
instances; the dense, ring and mesh engines build theirs from the same
`make_decode_scan`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from cake_tpu.ops.sampling import sample_tokens_ragged, update_ring_per_row


@jax.jit
@jax.named_scope("sample")
def _split_keys(keys):
    """Split a [B]-vector of PRNG keys into (next_keys, subkeys)."""
    split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return split[:, 0], split[:, 1]


@jax.named_scope("sample")
def _masked_sample(active_mask, keys, logits, ring, steps, temp, top_p,
                   penalty, *, top_k, n_top=0):
    """ONE per-row sample with masked state advance — the single source of
    the engine's sampling semantics: rows outside active_mask keep their
    PRNG key and ring untouched. Used eagerly by serve/engine._sample_rows
    and traced inside the programs below, so the two decode paths cannot
    drift.
    Returns (next_tokens [B], keys, ring, logprobs [B],
    top ids [B, n_top], top logprobs [B, n_top])."""
    new_keys, sub = _split_keys(keys)
    nxt, lp, top_ids, top_lps = sample_tokens_ragged(
        sub, logits, ring, temp, top_p, penalty, top_k=top_k, n_top=n_top)
    keys = jnp.where(active_mask[:, None], new_keys, keys)
    ring = jnp.where(active_mask[:, None],
                     update_ring_per_row(ring, nxt, steps), ring)
    return nxt, keys, ring, lp, top_ids, top_lps


class DecodePrograms:
    """The sampled decode programs over one ragged forward, called as
    the scan always was (`num_steps=` picks the program): `step`, one
    decode step + sample with no lax.scan around it, the program the
    engine keeps in flight; `scan`, num_steps of the same body in one
    lax.scan (--decode-scan N>1). `lower` is obs/steps.lower_cost's
    seam. `out_sharding` is where the programs leave their small
    outputs (make_decode_scan); the engine puts the small inputs it
    rebuilds from host mirrors there too, so that a stretch's first
    dispatch and its chained ones are ONE executable: a mesh program
    otherwise compiles, or loads from the cache, once per combination
    of host-made and program-made arguments (five times a start-up on
    the four-chip engine, 5 s of its warm-up; my chip run, PR 29)."""

    def __init__(self, step, scan, out_sharding=None):
        self.step, self.scan = step, scan
        self.out_sharding = out_sharding

    def __call__(self, *args, num_steps: int, **kw):
        if num_steps == 1:
            return self.step(*args, **kw)
        return self.scan(*args, num_steps=num_steps, **kw)

    def lower(self, *args, num_steps: int, **kw):
        if num_steps == 1:
            return self.step.lower(*args, **kw)
        return self.scan.lower(*args, num_steps=num_steps, **kw)


def make_decode_scan(forward_fn, out_sharding=None) -> DecodePrograms:
    """Build the jitted sampled decode programs (DecodePrograms) over
    any ragged forward (single-device model.forward_ragged, or the
    shard_mapped pipelined forward from parallel.pipeline
    .make_engine_step_fns): one decode step + sample, once
    (`decode_step_sampled`, num_steps=1) or num_steps times in a
    lax.scan (`decode_scan`), so a pipelined engine keeps a step in
    flight, or amortizes host dispatch across K tokens per round trip,
    exactly like the single-device engine.

    forward_fn(params, tokens, cache, pos, active, rope, config)
    -> (logits, cache), with model.forward_ragged's signature; a
    sparse model's forward returns its expert counters third
    (paged._step_result), which the one-step program returns last and
    the scan drops. A paged family's forward takes `attn` (fold or
    pallas) besides; it is a static argument of both programs, as it
    is of the mixed program, and a forward that has no such choice is
    never handed one (attn=None).
    out_sharding: optional sharding constraint for the non-cache
    outputs (multi-host serving localizes them per process, so they
    must leave the program fully replicated).

    Same per-row semantics as the synchronous step (_do_decode +
    _sample_rows — both go through _masked_sample): inactive rows touch
    neither their cache lines nor their PRNG/ring state, and a row that
    emits EOS freezes from then on — in the synchronous step the
    scheduler frees the slot immediately, so without freezing the
    slot's PRNG/ring stream would diverge between the two modes.
    A row also freezes once it has emitted `budget[row]` tokens within
    this call, so a program may be dispatched past a request's
    max_new_tokens (or chained speculatively, _decode_burst) without
    writing a single token beyond the budget.
    Returns ([B, num_steps] tokens, [B, num_steps] logprobs,
    [B, num_steps, n_top] x2, cache, keys, ring, state) where state =
    (tok, pos, steps, live) is the final carry — feeding it back as
    (last_tok, pos, steps, active) chains the next dispatch entirely on
    device (no host round-trip between them). The host mirrors
    (_pos/_steps/_last_tok) are advanced by the caller.
    """

    def body(carry, params, rope, config, temp, top_p, penalty, steps_in,
             budget, top_k, n_top, attn):
        tok, pos, cache, keys, ring, steps, live = carry
        # per-row budget freeze: emitted-so-far = steps - steps_in
        # (both advance only while live), so a row stops producing
        # the moment its allowance for this call is used up
        live = live & ((steps - steps_in) < budget)
        logits, cache, *moe = forward_fn(
            params, tok[:, None], cache, pos, live, rope, config,
            **({} if attn is None else {"attn": attn}))
        nxt, keys, ring, lp, t_i, t_l = _masked_sample(
            live, keys, logits, ring, steps, temp, top_p, penalty,
            top_k=top_k, n_top=n_top)
        tok = jnp.where(live, nxt, tok)
        pos = pos + live
        steps = steps + live
        live = live & ~jnp.isin(
            nxt, jnp.asarray(config.eos_token_ids, jnp.int32))
        return ((tok, pos, cache, keys, ring, steps, live),
                (nxt, lp, t_i, t_l), moe)

    def result(carry, outs, moe=()):
        """outs: [B, num_steps(, n_top)] each."""
        tok, pos, cache, keys, ring, steps, live = carry
        outs = (*outs, keys, ring, tok, pos, steps, live, *moe)
        if out_sharding is not None:
            outs = tuple(jax.lax.with_sharding_constraint(o, out_sharding)
                         for o in outs)
        (toks_o, lps_o, ti_o, tl_o, keys_o, ring_o, tok, pos, steps, live,
         *moe) = outs
        return (toks_o, lps_o, ti_o, tl_o, cache, keys_o, ring_o,
                (tok, pos, steps, live), *moe)

    jit = partial(jax.jit, donate_argnames=("cache", "keys", "ring"))

    # the name is the XLA module's (jit_decode_step_...): the benchmark
    # finds a decode step's device time by that prefix
    @partial(jit, static_argnames=("config", "top_k", "n_top", "attn"))
    def decode_step_sampled(params, last_tok, pos, active, cache, rope,
                            config, keys, ring, steps, temp, top_p,
                            penalty, budget, top_k, n_top: int = 0,
                            attn=None):
        carry, outs, moe = body(
            (last_tok, pos, cache, keys, ring, steps, active), params,
            rope, config, temp, top_p, penalty, steps, budget, top_k,
            n_top, attn)
        return result(carry, tuple(o[:, None] for o in outs), moe)

    @partial(jit, static_argnames=("config", "num_steps", "top_k",
                                   "n_top", "attn"))
    def decode_scan(params, last_tok, pos, active, cache, rope,
                    config, keys, ring, steps, temp, top_p, penalty,
                    budget, num_steps: int, top_k, n_top: int = 0,
                    attn=None):
        def scanned(carry, _):
            return body(carry, params, rope, config, temp, top_p,
                        penalty, steps, budget, top_k, n_top, attn)[:2]

        carry, (toks, lps, tops_i, tops_l) = jax.lax.scan(
            scanned, (last_tok, pos, cache, keys, ring, steps, active),
            None, length=num_steps)
        return result(carry, (toks.T, lps.T, jnp.swapaxes(tops_i, 0, 1),
                              jnp.swapaxes(tops_l, 0, 1)))

    return DecodePrograms(decode_step_sampled, decode_scan, out_sharding)


# what a row does in a mixed step (the last column of the packed step)
ROW_ACTIVE, ROW_SAMPLE, ROW_FROM_CARRY = 1, 2, 4


def make_mixed_sampled(mixed_fn):
    """Build the jitted sampled mixed step over a mixed step function
    (a family's `mixed_step`, models/family.Family: one signature):
    the forward on the packed axis, then _masked_sample over the rows
    that sample this step, then the EOS freeze of make_decode_scan's
    body, so that the next step can be dispatched from this one's
    tokens while they are still on the device.

    step [B, C + 4] int32, the step as the host knows it, a row a slot:
    its window of C tokens, then its position, its q_len, its step
    count and its flags. ROW_ACTIVE: the row is in this dispatch;
    ROW_SAMPLE: it samples (a decode row, a row whose window ends its
    prompt; every other row keeps its key and ring); ROW_FROM_CARRY: a
    step the host has not fetched yet sampled it, so its input token,
    its position, its step count and whether it still lives (no EOS
    yet) come from that step's carry. ONE array because each host array
    is a transfer of its own, which a stretch's first step pays with
    the device idle. carry = (tok, pos, steps, live), each [B], in the
    decode programs' form. A row that is not active passes its carry
    through, so a step of several dispatches threads one carry through
    them.
    Returns (tokens [B], logprobs [B], top ids and top logprobs
    [B, n_top], cache, keys, ring, carry, and a sparse model's
    counters): the carry feeds the next mixed step, or the sampled
    decode programs as (last_tok, pos, steps, active)."""

    # the name is the XLA module's (jit_mixed_step_...): the benchmark
    # finds a mixed step's device time by that prefix
    @partial(jax.jit, static_argnames=("config", "attn", "n_tokens",
                                       "top_k", "n_top"),
             donate_argnames=("cache", "keys", "ring"))
    def mixed_step_sampled(params, step, cache, rope, config, keys, ring,
                           temp, top_p, penalty, carry, attn, n_tokens,
                           top_k, n_top: int = 0):
        tokens = step[:, :-4]
        pos, q_len, steps, flags = (step[:, i] for i in range(-4, 0))
        active, sample, from_carry = ((flags & bit) != 0 for bit in (
            ROW_ACTIVE, ROW_SAMPLE, ROW_FROM_CARRY))
        c_tok, c_pos, c_steps, c_live = carry
        tok = jnp.where(from_carry, c_tok, tokens[:, 0])
        pos = jnp.where(from_carry, c_pos, pos)
        steps = jnp.where(from_carry, c_steps, steps)
        live = active & jnp.where(from_carry, c_live, True)
        logits, cache, *counters = mixed_fn(
            params, tokens.at[:, 0].set(tok), pos, q_len, live, cache,
            rope, config, attn=attn, n_tokens=n_tokens)
        sampled = sample & live
        nxt, keys, ring, lp, t_i, t_l = _masked_sample(
            sampled, keys, logits, ring, steps, temp, top_p, penalty,
            top_k=top_k, n_top=n_top)
        eos = jnp.isin(nxt, jnp.asarray(config.eos_token_ids, jnp.int32))
        carry = (jnp.where(sampled, nxt, jnp.where(active, tok, c_tok)),
                 jnp.where(active, pos + jnp.where(live, q_len, 0), c_pos),
                 jnp.where(active, steps + sampled, c_steps),
                 jnp.where(active, sampled & ~eos, c_live))
        return (nxt, lp, t_i, t_l, cache, keys, ring, carry, *counters)

    return mixed_step_sampled


