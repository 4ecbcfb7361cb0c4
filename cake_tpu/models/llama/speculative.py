"""Speculative decoding: draft-model propose, one target verify pass.

A capability past the reference's surface (it decodes strictly one token
per model pass, llama.rs:285-298): a small draft model proposes gamma
tokens autoregressively, the target scores all of them in ONE forward
(logits at every position, model.forward_logits_all), and the standard
accept/resample rule keeps the leading agreeing prefix plus one
correction token — so each target pass yields 1..gamma+1 tokens. With
greedy sampling the output is the target's own greedy stream
(tests/test_speculative.py asserts token-for-token equality against
LlamaGenerator), up to one caveat shared by every speculative
implementation: the verify pass scores gamma+1 positions in one batched
forward, whose bf16 accumulation order differs from stepwise decode by
~1e-2 logits — when the target's top-2 logits tie within that noise,
the two evaluation shapes may break the tie differently. Both streams
are valid greedy outputs of the same model. With temperature sampling
the accept/resample rule preserves the target distribution (Leviathan
et al., 2023 — public algorithm).

TPU shape: one jitted program per spec step — the draft loop is a
lax.scan of gamma+1 decode steps (the +1 writes the last draft's KV so an
all-accept step needs no patch-up pass), the verify is one forward over
the gamma+1-token window (masked-einsum attention against the cache —
the window is a handful of tokens, so the flash kernel would gain
nothing), and accept/resample is branch-free arithmetic on the stacked
logits. Nothing rolls back: both
caches index KV by absolute position, and positions past the accepted
frontier are masked (decode_mask) until overwritten, exactly like padded
prefill garbage.

Scope (v1): batch 1 (speculation is a latency feature), single device,
repeat_penalty == 1.0 (the verify pass scores gamma+1 positions in
parallel, so a within-burst penalty ring cannot be replayed exactly).
"""

from __future__ import annotations

import logging
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models import Token
from cake_tpu.models.chat import History, Message
from cake_tpu.models.llama.cache import KVCache
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import (
    bucket_length, encode_text, StreamDetokenizer,
)
from cake_tpu.models.llama.model import (
    RopeTables, forward, forward_logits_all, prefill,
)
from cake_tpu.ops.sampling import SamplingConfig

log = logging.getLogger(__name__)


@partial(jax.jit,
         static_argnames=("t_cfg", "d_cfg", "gamma", "greedy"),
         donate_argnames=("t_cache", "d_cache"))
def spec_step(t_params, d_params, t_cache: KVCache, d_cache: KVCache,
              last_tok, pos, t_rope: RopeTables, d_rope: RopeTables,
              rng, temperature,
              t_cfg: LlamaConfig, d_cfg: LlamaConfig,
              gamma: int, greedy: bool):
    """One propose-verify-accept round.

    last_tok [1, 1] at absolute `pos` (its KV not yet written).
    Returns (tokens [1, gamma+1] — first n_emit valid, rest -1,
    n_emit scalar, t_cache, d_cache, rng).
    """
    return _spec_round(t_params, d_params, t_cache, d_cache, last_tok,
                       pos, t_rope, d_rope, rng, temperature,
                       t_cfg, d_cfg, gamma, greedy)


# The accept/resample arithmetic moved to cake_tpu/spec/accept.py so
# the PAGED round (cake_tpu/spec/round.py) shares it verbatim with the
# dense rounds below; the historical underscore names stay importable
# here for the dense path's callers and tests.
from cake_tpu.spec.accept import (  # noqa: E402
    advance_row_keys as _advance_row_keys,
    assemble_sampled as _assemble_sampled,
    greedy_accept as _greedy_accept,
    rejection_accept as _rejection_accept,
)


@partial(jax.jit,
         static_argnames=("t_cfg", "d_cfg", "gamma"),
         donate_argnames=("t_cache", "d_cache"))
def spec_round_batched(t_params, d_params, t_cache: KVCache,
                       d_cache: KVCache, last_tok, pos, active, keys,
                       temp, t_rope: RopeTables, d_rope: RopeTables,
                       t_cfg: LlamaConfig, d_cfg: LlamaConfig,
                       gamma: int):
    """One propose-verify-accept round for EVERY active slot in one
    compiled program: gamma+1 batched ragged draft steps + one batched
    windowed verify. The per-slot engine path (spec_step_slot) ran B
    separate batch-1 rounds, streaming the weights B times per round —
    this streams them once, which is the whole cost model of batched
    decode.

    last_tok [B, 1] at per-row absolute `pos` (KV not yet written);
    active [B]; keys [B, 2] per-slot PRNG keys (advanced only for
    active sampled rows); temp [B] (<= 0 -> greedy row: argmax drafts,
    exact-match acceptance; > 0 -> leftover-residual rejection
    sampling, per row).
    Returns (out [B, gamma+1] — first n_emit[b] valid, rest -1;
    n_emit [B] (0 for inactive rows); t_cache; d_cache; keys;
    state = (last_tok [B, 1], pos [B]) — each active row's final
    emitted token at its advanced frontier, fed straight back as the
    next round's (last_tok, pos) by the engine's double-buffered spec
    burst without a host round-trip)."""
    from cake_tpu.models.llama.model import (
        forward_ragged, forward_window_ragged,
    )

    B = last_tok.shape[0]
    greedy = temp <= 0.0
    temp_eff = jnp.where(greedy, 1.0, temp)[:, None]

    def draft_body(carry, _):
        cache, tok, p, keys = carry
        logits, cache = forward_ragged(d_params, tok, cache, p, active,
                                       d_rope, d_cfg)
        probs = jax.nn.softmax(logits / temp_eff, axis=-1)
        nxt_g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        keys, subs = _advance_row_keys(keys, active & ~greedy)
        nxt_s = jax.vmap(jax.random.categorical)(
            subs, logits / temp_eff).astype(jnp.int32)
        nxt = jnp.where(greedy, nxt_g, nxt_s)
        return ((cache, nxt[:, None], p + active, keys),
                (nxt, probs))

    (d_cache, _, _, keys), (drafts_all, d_probs_all) = jax.lax.scan(
        draft_body, (d_cache, last_tok, pos, keys), None,
        length=gamma + 1)
    drafts = drafts_all[:gamma].T                      # [B, gamma]
    d_probs = jnp.swapaxes(d_probs_all[:gamma], 0, 1)  # [B, gamma, V]

    tokens_v = jnp.concatenate([last_tok, drafts], axis=1)
    t_logits, t_cache = forward_window_ragged(
        t_params, tokens_v, t_cache, pos, active, t_rope, t_cfg)

    # greedy rows: exact-match acceptance against the target argmax
    targets = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
    n_acc_g = _greedy_accept(drafts, targets)

    # sampled rows: leftover-residual rejection sampling (per row),
    # the same _rejection_accept/_assemble_sampled math as _spec_round.
    # Greedy rows' residual/correction are computed but unused (their
    # out comes from `targets`) and their keys never advance.
    t_probs = jax.nn.softmax(t_logits / temp_eff[..., None], axis=-1)
    keys, subs = _advance_row_keys(keys, active & ~greedy)
    u = jax.vmap(lambda k: jax.random.uniform(k, (gamma,)))(subs)
    n_acc_s, resid = _rejection_accept(drafts, d_probs, t_probs, u,
                                       gamma)
    keys, subs = _advance_row_keys(keys, active & ~greedy)
    correction = jax.vmap(jax.random.categorical)(
        subs, jnp.log(jnp.maximum(resid, 1e-20))).astype(jnp.int32)
    out_s = _assemble_sampled(drafts, correction, n_acc_s, gamma)

    n_acc = jnp.where(greedy, n_acc_g, n_acc_s)
    out = jnp.where(greedy[:, None], targets, out_s)
    n_emit = jnp.where(active, n_acc + 1, 0)
    mask = jnp.arange(gamma + 1)[None] < n_emit[:, None]
    out = jnp.where(mask, out, -1)
    # chained-round state (the engine's double-buffered spec burst
    # feeds this straight back as (last_tok, pos) without a host
    # round-trip): each active row continues from its final emitted
    # token at its advanced frontier
    last = jnp.take_along_axis(
        out, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
    last_out = jnp.where(active, last, last_tok[:, 0])[:, None]
    pos_out = pos + n_emit
    state = (last_out, pos_out)
    return out, n_emit, t_cache, d_cache, keys, state


def _spec_round(t_params, d_params, t_cache: KVCache, d_cache: KVCache,
                last_tok, pos, t_rope: RopeTables, d_rope: RopeTables,
                rng, temperature,
                t_cfg: LlamaConfig, d_cfg: LlamaConfig,
                gamma: int, greedy: bool):
    B = last_tok.shape[0]

    def draft_body(carry, i):
        cache, tok, p, rng = carry
        logits, cache = forward(d_params, tok, cache, p, d_rope, d_cfg)
        if greedy:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            probs = jax.nn.softmax(logits, axis=-1)
        else:
            rng, sub = jax.random.split(rng)
            probs = jax.nn.softmax(logits / temperature, axis=-1)
            nxt = jax.random.categorical(sub, logits / temperature
                                         ).astype(jnp.int32)
        return (cache, nxt[:, None], p + 1, rng), (nxt, probs)

    # gamma+1 iterations: iteration gamma writes the gamma-th draft's KV
    # (needed when every draft is accepted) and its proposal is discarded
    (d_cache, _, _, rng), (drafts_all, d_probs_all) = jax.lax.scan(
        draft_body, (d_cache, last_tok, pos, rng),
        jnp.arange(gamma + 1))
    drafts = drafts_all[:gamma].T                      # [B, gamma]
    d_probs = jnp.swapaxes(d_probs_all[:gamma], 0, 1)  # [B, gamma, V]

    # verify: target scores [last_tok, d_0..d_{gamma-1}] in one pass,
    # writing target KV for positions pos..pos+gamma
    tokens_v = jnp.concatenate([last_tok, drafts], axis=1)  # [B, gamma+1]
    t_logits, t_cache = forward_logits_all(
        t_params, tokens_v, t_cache, pos, t_rope, t_cfg)   # [B, g+1, V]

    if greedy:
        targets = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
        # emitted = targets[:, :n_acc+1] (accepted drafts equal targets;
        # position n_acc is the correction / bonus token)
        n_acc = _greedy_accept(drafts, targets)
        out = targets
        n_emit = n_acc + 1
    else:
        t_probs = jax.nn.softmax(t_logits / temperature, axis=-1)
        rng, sub = jax.random.split(rng)
        u = jax.random.uniform(sub, drafts.shape)
        n_acc, resid = _rejection_accept(drafts, d_probs, t_probs, u,
                                         gamma)
        rng, sub = jax.random.split(rng)
        correction = jax.random.categorical(
            sub, jnp.log(jnp.maximum(resid, 1e-20))).astype(jnp.int32)
        out = _assemble_sampled(drafts, correction, n_acc, gamma)
        n_emit = n_acc + 1

    mask = jnp.arange(gamma + 1)[None] < n_emit[:, None]
    out = jnp.where(mask, out, -1)
    return out, n_emit, t_cache, d_cache, rng


@partial(jax.jit,
         static_argnames=("t_cfg", "d_cfg", "gamma", "greedy",
                          "num_rounds"),
         donate_argnames=("t_cache", "d_cache"))
def spec_scan(t_params, d_params, t_cache: KVCache, d_cache: KVCache,
              last_tok, pos, t_rope: RopeTables, d_rope: RopeTables,
              rng, temperature,
              t_cfg: LlamaConfig, d_cfg: LlamaConfig,
              gamma: int, greedy: bool, num_rounds: int):
    """num_rounds propose-verify-accept rounds chained on device
    (lax.scan over _spec_round), so the host pays ONE dispatch + fetch
    per num_rounds rounds instead of per round — the host-stepped loop
    pays a device-to-host fetch every round, which bounds batch-1
    speculation by the fetch time regardless of acceptance.

    Caller must guarantee pos + num_rounds*(gamma+1) <= max_seq_len
    (every round writes up to gamma+1 cache positions at its dynamic
    offset). Returns (outs [num_rounds, gamma+1] — per round the first
    n valid, rest -1; ns [num_rounds]; t_cache; d_cache; rng). Tokens
    after an EOS inside the window are overshoot for the caller to
    discard (same contract as the engine's budget-frozen scans)."""

    def body(carry, _):
        t_cache, d_cache, tok, p, rng = carry
        out, n, t_cache, d_cache, rng = _spec_round(
            t_params, d_params, t_cache, d_cache, tok, p,
            t_rope, d_rope, rng, temperature, t_cfg, d_cfg, gamma,
            greedy)
        last = out[:, n[0] - 1][:, None]    # [1, 1] for the next round
        return (t_cache, d_cache, last, p + n[0], rng), (out[0], n[0])

    (t_cache, d_cache, _tok, _pos, rng), (outs, ns) = jax.lax.scan(
        body, (t_cache, d_cache, last_tok, pos, rng), None,
        length=num_rounds)
    return outs, ns, t_cache, d_cache, rng


class SpeculativeGenerator:
    """TextGenerator with draft-model speculation (batch 1).

    Exposes the same protocol as LlamaGenerator plus acceptance stats;
    next_token streams from an internal burst buffer so the CLI/API token
    loop is unchanged.
    """

    MODEL_NAME = "llama3-spec"

    def __init__(self, config: LlamaConfig, params,
                 draft_config: LlamaConfig, draft_params,
                 tokenizer, *, gamma: int = 4, max_seq_len: int = 4096,
                 sampling: Optional[SamplingConfig] = None,
                 seed: int = 299792458, cache_dtype=jnp.bfloat16,
                 spec_rounds: int = 4):
        if gamma < 1:
            raise ValueError("gamma must be >= 1")
        if spec_rounds < 1:
            raise ValueError("spec_rounds must be >= 1")
        sampling = sampling or SamplingConfig()
        if sampling.repeat_penalty != 1.0:
            raise ValueError(
                "speculative decoding supports repeat_penalty=1.0 only "
                "(the verify pass scores the burst in parallel)")
        if sampling.top_k is not None or (sampling.top_p or 1.0) < 1.0:
            raise ValueError(
                "speculative decoding samples from the full temperature "
                "softmax; top_k/top_p are not supported (the accept/"
                "resample identity assumes the unfiltered distributions)")
        self.config = config
        self.params = params
        self.draft_config = draft_config
        self.draft_params = draft_params
        self.tokenizer = tokenizer
        self.gamma = gamma
        self.max_seq_len = max_seq_len
        self.sampling = sampling
        self.rope = RopeTables.create(config, max_seq_len)
        self.d_rope = RopeTables.create(draft_config, max_seq_len)
        self.cache = KVCache.create(config, 1, max_seq_len,
                                    dtype=cache_dtype)
        self.d_cache = KVCache.create(draft_config, 1, max_seq_len,
                                      dtype=cache_dtype)
        self.history = History(config.chat_template)
        self.rng = jax.random.PRNGKey(seed)
        self.spec_rounds = spec_rounds
        self.proposed = 0        # drafts offered to the verifier
        self.accepted = 0        # drafts kept
        self._reset_session()

    # -- TextGenerator protocol ----------------------------------------------

    def add_message(self, message: Message) -> None:
        self.history.add_message(message)

    def reset(self) -> None:
        self.history.clear()
        self.cache = self.cache.fresh()
        self.d_cache = self.d_cache.fresh()
        self._reset_session()

    def _reset_session(self) -> None:
        self.tokens: List[int] = []
        self.index_pos = 0
        self._buffer: List[int] = []
        self._detok = StreamDetokenizer(self.tokenizer)

    def generated_tokens(self) -> int:
        return len(self.tokens)

    def set_sampling(self, temperature=None, top_p=None, **overrides):
        """Per-request sampling overrides (the locked API path's
        contract). Speculation supports temperature only — the verify
        pass scores raw model probabilities, so top-p/top-k filtering
        would break the accept/resample correctness proof; a request
        asking for them gets a clean error instead of silently different
        sampling."""
        from dataclasses import replace
        if top_p is not None and top_p < 1.0:
            raise ValueError(
                "--draft-model serving supports temperature only "
                "(top_p/top_k would break speculative accept/resample)")
        if overrides.get("top_k") is not None:
            raise ValueError(
                "--draft-model serving supports temperature only")
        if temperature is not None:
            self.sampling = replace(self.sampling,
                                    temperature=temperature)

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.proposed if self.proposed else 0.0

    def next_token(self, index: int) -> Token:
        if index == 0:
            self._prefill_prompt()
        if not self._buffer:
            self._fill_buffer()
        tid = self._buffer.pop(0)
        self.tokens.append(tid)
        if tid in self.config.eos_token_ids:
            return Token(id=tid, text=self._detok.add(final=True),
                         is_end_of_stream=True)
        return Token(id=tid, text=self._detok.add((tid,)),
                     is_end_of_stream=False)

    # -- internals ------------------------------------------------------------

    def _prefill_prompt(self) -> None:
        ids = encode_text(self.tokenizer, self.history.render())
        if len(ids) > self.max_seq_len - self.gamma - 2:
            raise ValueError(
                f"prompt length {len(ids)} leaves no speculation window "
                f"(max_seq_len {self.max_seq_len}, gamma {self.gamma})")
        bucket = bucket_length(len(ids), self.max_seq_len)
        padded = ids + [0] * (bucket - len(ids))
        toks = jnp.asarray([padded], jnp.int32)
        plen = jnp.asarray([len(ids)], jnp.int32)
        logits, self.cache = prefill(
            self.params, toks, plen, self.cache, self.rope, self.config)
        _, self.d_cache = prefill(
            self.draft_params, toks, plen, self.d_cache, self.d_rope,
            self.draft_config)
        first = self._sample_first(logits)
        self._buffer = [int(first)]
        self.index_pos = len(ids)

    def _sample_first(self, logits):
        if self._greedy:
            return jnp.argmax(logits, axis=-1)[0]
        self.rng, sub = jax.random.split(self.rng)
        return jax.random.categorical(
            sub, logits / self.sampling.temperature)[0]

    @property
    def _greedy(self) -> bool:
        t = self.sampling.temperature
        return t is None or t <= 0.0

    def _fill_buffer(self) -> None:
        if self.index_pos + self.gamma + 1 >= self.max_seq_len:
            raise ValueError(
                f"speculation window exceeds max_seq_len {self.max_seq_len}"
                f" at position {self.index_pos}")
        if not self.tokens:
            raise RuntimeError(
                "next_token(index>0) called before the index==0 prefill")
        last = jnp.asarray([[self.tokens[-1]]], jnp.int32)
        R = self.spec_rounds
        if (R > 1 and self.index_pos + R * (self.gamma + 1)
                <= self.max_seq_len):
            # R rounds per dispatch+fetch (spec_scan): the host-stepped
            # loop pays one fetch per round, so chaining rounds on
            # device divides the fetches per token by R. Near the
            # window end fall back to single rounds
            # (two compiled programs total: R-round and 1-round).
            outs, ns, self.cache, self.d_cache, self.rng = spec_scan(
                self.params, self.draft_params, self.cache, self.d_cache,
                last, jnp.int32(self.index_pos), self.rope, self.d_rope,
                self.rng,
                jnp.float32(self.sampling.temperature or 1.0),
                self.config, self.draft_config, self.gamma,
                self._greedy, R)
            ns_h, outs_h = jax.device_get((ns, outs))
            eos = set(self.config.eos_token_ids)
            for k in range(R):
                n = int(ns_h[k])
                toks = [int(t) for t in outs_h[k, :n]]
                self._buffer.extend(toks)
                self.proposed += self.gamma
                self.accepted += n - 1
                self.index_pos += n
                if any(t in eos for t in toks):
                    # rounds past EOS ran on device (overshoot by
                    # design) but condition on post-EOS garbage — they
                    # must pollute neither the stream nor the
                    # acceptance stats
                    break
            return
        out, n_emit, self.cache, self.d_cache, self.rng = spec_step(
            self.params, self.draft_params, self.cache, self.d_cache,
            last, jnp.int32(self.index_pos), self.rope, self.d_rope,
            self.rng,
            jnp.float32(self.sampling.temperature or 1.0),
            self.config, self.draft_config, self.gamma, self._greedy)
        # one batched fetch (int(n_emit) then asarray(out) would each
        # wait for the device and copy to the host)
        n_emit_h, out_h = jax.device_get((n_emit, out))
        n = int(n_emit_h[0])
        self._buffer.extend(int(t) for t in out_h[0, :n])
        self.proposed += self.gamma
        self.accepted += n - 1
        self.index_pos += n

    # -- batch generation (bench/tests parity with LlamaGenerator) -----------

    def generate_on_device(self, prompt_ids: np.ndarray,
                           prompt_len: np.ndarray,
                           num_tokens: int) -> np.ndarray:
        """Greedy/spec generation for a [1, S] prompt; returns
        [1, num_tokens]. Host-stepped (one device call per burst)."""
        if prompt_ids.shape[0] != 1:
            raise ValueError("speculative decoding is batch-1")
        toks = jnp.asarray(prompt_ids, jnp.int32)
        plen = jnp.asarray(prompt_len, jnp.int32)
        cache = self.cache.fresh()
        d_cache = self.d_cache.fresh()
        logits, cache = prefill(self.params, toks, plen, cache, self.rope,
                                self.config)
        _, d_cache = prefill(self.draft_params, toks, plen, d_cache,
                             self.d_rope, self.draft_config)
        rng = self.rng
        if self._greedy:
            first = int(jnp.argmax(logits, axis=-1)[0])
        else:
            rng, sub = jax.random.split(rng)
            first = int(jax.random.categorical(
                sub, logits / self.sampling.temperature)[0])
        out = [first]
        pos = int(np.asarray(plen)[0])
        R = self.spec_rounds
        while len(out) < num_tokens:
            if pos + self.gamma + 1 >= self.max_seq_len:
                raise ValueError("speculation window exceeds max_seq_len")
            last = jnp.asarray([[out[-1]]], jnp.int32)
            if R > 1 and pos + R * (self.gamma + 1) <= self.max_seq_len:
                outs_d, ns_d, cache, d_cache, rng = spec_scan(
                    self.params, self.draft_params, cache, d_cache, last,
                    jnp.int32(pos), self.rope, self.d_rope, rng,
                    jnp.float32(self.sampling.temperature or 1.0),
                    self.config, self.draft_config, self.gamma,
                    self._greedy, R)
                ns_h, outs_h = jax.device_get((ns_d, outs_d))
                for k in range(R):
                    n = int(ns_h[k])
                    self.proposed += self.gamma
                    self.accepted += n - 1
                    out.extend(int(t) for t in outs_h[k, :n])
                    pos += n
                continue
            burst, n_emit, cache, d_cache, rng = spec_step(
                self.params, self.draft_params, cache, d_cache, last,
                jnp.int32(pos), self.rope, self.d_rope, rng,
                jnp.float32(self.sampling.temperature or 1.0),
                self.config, self.draft_config, self.gamma, self._greedy)
            n_emit_h, burst_h = jax.device_get((n_emit, burst))
            n = int(n_emit_h[0])
            self.proposed += self.gamma
            self.accepted += n - 1
            out.extend(int(t) for t in burst_h[0, :n])
            pos += n
        # persist the advanced PRNG stream: repeated sampled calls must
        # differ, matching LlamaGenerator.generate_on_device
        self.rng = rng
        return np.asarray([out[:num_tokens]], np.int32)
