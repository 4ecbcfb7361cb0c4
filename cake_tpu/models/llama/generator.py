"""LlamaGenerator: stateful text generation over the functional model.

Capability parity with the reference's `LLama` driver (llama3/llama.rs):
  * first `next_token` call renders the chat history through the Llama-3
    template and tokenizes it (llama.rs:140-166, 281-283),
  * KV-cached decode feeds only the last token with its absolute position
    (llama.rs:285-298),
  * repeat-penalty over the last `repeat_last_n` tokens + sampling
    (llama.rs:311-326),
  * EOS detection (llama.rs:26-30, 339 — the reference checks a single id;
    we honor the config's full eos set, e.g. <|eot_id|> AND <|end_of_text|>),
  * `reset()` clears history/tokens/position (llama.rs:267-274). Unlike the
    reference — whose workers keep stale KV across REST requests
    (SURVEY.md §3.3) — reset here zeroes the entire cache explicitly.

TPU specifics: prompts are right-padded to bucket lengths so prefill
compiles once per bucket, not once per prompt length; decode is one cached
XLA program.  `generate_scan` runs the whole decode loop on-device via
`lax.scan` (zero host round-trips) for batch/throughput serving.
"""

from __future__ import annotations

import logging
import time
from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models import Token
from cake_tpu.models.chat import History, Message
from cake_tpu.models.llama.cache import KVCache
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.model import (
    RopeTables, decode_step, forward, prefill,
)
from cake_tpu.ops.sampling import (
    SamplingConfig, sample_tokens, update_ring,
)

log = logging.getLogger(__name__)

PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)


def bucket_length(n: int, max_seq_len: int) -> int:
    """Smallest bucket >= n (bounds the number of compiled prefill shapes)."""
    for b in PREFILL_BUCKETS:
        if b >= n and b <= max_seq_len:
            return b
    return max_seq_len


def chunk_windows(ids: List[int], C: int):
    """Yield (padded_window, n_real, start) fixed-C windows over a prompt —
    the ONE definition of the chunked-prefill windowing contract
    (right-padded final window, last real token at n_real - 1), shared by
    the sequential generator and the engine."""
    for start in range(0, len(ids), C):
        w = ids[start:start + C]
        n = len(w)
        yield w + [0] * (C - n), n, start


class ByteTokenizer:
    """Fallback tokenizer (tests / no tokenizer.json): UTF-8 bytes + offset."""

    OFFSET = 3  # leave room for pad/bos/eos

    def __init__(self, vocab_size: int = 259):
        self.vocab_size = vocab_size

    def encode(self, text: str) -> List[int]:
        return [b + self.OFFSET for b in text.encode("utf-8")]

    def decode(self, ids: List[int]) -> str:
        data = bytes(max(0, i - self.OFFSET) for i in ids
                     if i >= self.OFFSET and i - self.OFFSET < 256)
        return data.decode("utf-8", errors="replace")


def load_tokenizer(model_dir: str):
    """HF tokenizer.json loader (same file the reference consumes)."""
    import os
    from tokenizers import Tokenizer
    path = os.path.join(model_dir, "tokenizer.json")
    return Tokenizer.from_file(path)


def encode_text(tokenizer, text: str) -> List[int]:
    """Tokenize, normalising HF `Encoding.ids` vs plain-list tokenizers."""
    enc = tokenizer.encode(text)
    return list(enc.ids if hasattr(enc, "ids") else enc)


class StreamDetokenizer:
    """Streaming detokenization of one output: `add` takes the NEW ids
    and returns the text they finalize. It keeps its place instead of
    decoding the output from token 0 (the offset form of
    text-generation-inference and vLLM): of the ids so far it holds
    only those from `prefix_offset`, the start of what the last delta
    ended with, and decodes that window twice, up to `read_offset` and
    whole. Both decodes start at the same id, so a decoder that treats
    a sequence's first token specially (a stripped leading space)
    treats both alike and the tail of the second past the first is what
    the whole decode would have appended.

    Text is held back (empty delta, the window grows) while the tail
    decodes to an incomplete UTF-8 sequence (the replacement char) or
    to nothing, so multi-token characters stream whole. final=True
    flushes a permanently-incomplete tail at end of stream — the
    streamed total must equal the buffered decode of the same ids."""

    __slots__ = ("_tokenizer", "_ids", "_read", "_prefix_text",
                 "decoded_ids")

    def __init__(self, tokenizer):
        self._tokenizer = tokenizer
        self._ids: List[int] = []        # ids[prefix_offset:]
        self._read = 0                   # read_offset - prefix_offset
        # decode(ids[prefix_offset:read_offset]); None until needed
        self._prefix_text: Optional[str] = ""
        # ids handed to tokenizer.decode so far (obs: `detok_ids`)
        self.decoded_ids = 0

    def _decode(self, ids: List[int]) -> str:
        self.decoded_ids += len(ids)
        return self._tokenizer.decode(ids)

    def add(self, new_ids: Sequence[int] = (), final: bool = False) -> str:
        ids = self._ids
        ids.extend(new_ids)
        if len(ids) == self._read:
            return ""   # nothing unread (a flush with no held tail)
        if self._prefix_text is None:
            self._prefix_text = self._decode(ids[:self._read])
        text = self._decode(ids)
        if len(text) <= len(self._prefix_text) or (
                text.endswith("\ufffd") and not final):
            return ""
        new = text[len(self._prefix_text):]
        self._ids = ids[self._read:]
        self._read = len(self._ids)
        self._prefix_text = None
        return new


class LlamaGenerator:
    """TextGenerator implementation (reference models/mod.rs:52-64)."""

    MODEL_NAME = "llama3"

    def __init__(
        self,
        config: LlamaConfig,
        params,
        tokenizer,
        *,
        max_seq_len: int = 4096,
        batch_size: int = 1,
        sampling: Optional[SamplingConfig] = None,
        seed: int = 299792458,
        cache_dtype=jnp.bfloat16,
        forward_fn=None,
        cache: Optional[KVCache] = None,
        parallel=None,
        prefill_chunk: Optional[int] = None,
    ):
        self.config = config
        self.params = params
        self.tokenizer = tokenizer
        self.max_seq_len = max_seq_len
        self.batch_size = batch_size
        self.sampling = sampling or SamplingConfig()
        self.rope = RopeTables.create(config, max_seq_len)
        # forward_fn: optional replacement for the single-device jitted
        # steps — e.g. parallel.pipeline.make_pipeline_forward's output when
        # a topology shards the model. Signature:
        #   forward_fn(params, tokens, cache, pos, rope,
        #              last_idx=None, is_prefill=False) -> (logits, cache)
        self._forward_fn = forward_fn
        # parallel: opaque (plan, mesh) context carried for consumers that
        # need to build matching-sharded state (Master.make_engine).
        self.parallel = parallel
        # prefill_chunk: process prompts in fixed windows of this many
        # tokens (one compiled program for ALL prompt lengths and chunk
        # positions, bounded activation memory); None = whole-prompt
        # prefill with bucketed shapes.
        if prefill_chunk is not None and (
                prefill_chunk < 1 or max_seq_len % prefill_chunk != 0):
            # a padded final window [start, start+C) must stay inside the
            # cache: dynamic_update_slice CLAMPS an out-of-range start and
            # would silently overwrite earlier live entries
            raise ValueError(
                f"prefill_chunk {prefill_chunk} must be >= 1 and divide "
                f"max_seq_len {max_seq_len}")
        self.prefill_chunk = prefill_chunk
        # the dense [L, batch, max_seq, KV, hd] cache is built on first
        # use: an API server decodes from its engine's own cache and
        # never touches this one (0.25-0.5 GiB at 8B widths)
        self.cache_dtype = cache_dtype if cache is None else cache.k.dtype
        self._cache = cache
        self.history = History(config.chat_template)
        self.rng = jax.random.PRNGKey(seed)
        self._reset_session()

    @property
    def cache(self) -> KVCache:
        if self._cache is None:
            self._cache = KVCache.create(
                self.config, self.batch_size, self.max_seq_len,
                dtype=self.cache_dtype)
        return self._cache

    @cache.setter
    def cache(self, value) -> None:
        self._cache = value

    # -- TextGenerator protocol ---------------------------------------------

    def add_message(self, message: Message) -> None:
        self.history.add_message(message)

    def reset(self) -> None:
        """Clear chat + decode state (reference llama.rs:267-274), including
        the full KV cache (explicit pipeline-wide reset; see SURVEY.md §3.3
        for the reference wart this avoids)."""
        self.history.clear()
        if self._cache is not None:
            self._cache = self._cache.fresh()
        self._reset_session()

    def _reset_session(self) -> None:
        self.tokens: List[int] = []      # all generated token ids
        self.index_pos = 0               # absolute position in the cache
        self._ring = jnp.full((self.batch_size, self.sampling.repeat_last_n),
                              -1, dtype=jnp.int32)
        self._detok = StreamDetokenizer(self.tokenizer)
        self._prompt_len = 0

    def generated_tokens(self) -> int:
        return len(self.tokens)

    def set_sampling(self, **overrides) -> None:
        """Apply per-request sampling overrides (None values ignored).

        SamplingConfig is a static jit arg, so a changed config costs one
        (cached thereafter) recompile of the tiny sample step only.
        """
        from dataclasses import replace
        kw = {k: v for k, v in overrides.items() if v is not None}
        if kw:
            self.sampling = replace(self.sampling, **kw)

    def next_token(self, index: int) -> Token:
        """Generate one token; index==0 triggers prompt prefill."""
        limit = getattr(self._forward_fn, "max_decode_tokens", None)
        if limit is not None and index >= limit:
            # e.g. the SP adapter's replicated decode tail is full; writing
            # past it would clamp over live cache entries
            raise ValueError(
                f"decode budget exhausted: this serving mode holds at most "
                f"{limit} generated tokens per session")
        if index == 0:
            logits = self._prefill_prompt()
        else:
            tok = jnp.full((self.batch_size, 1), self.tokens[-1], jnp.int32)
            if self._forward_fn is None:
                logits, self.cache = decode_step(
                    self.params, tok, jnp.int32(self.index_pos), self.cache,
                    self.rope, self.config,
                )
            else:
                logits, self.cache = self._forward_fn(
                    self.params, tok, self.cache, jnp.int32(self.index_pos),
                    self.rope,
                )
            self.index_pos += 1

        self.rng, sub = jax.random.split(self.rng)
        next_id = sample_tokens(sub, logits, self._ring, self.sampling)
        self._ring = update_ring(self._ring, next_id, len(self.tokens))
        tid = int(next_id[0])
        self.tokens.append(tid)

        if tid in self.config.eos_token_ids:
            # flush any held-back UTF-8 tail so the streamed total equals
            # the buffered decode of the same ids (engine parity)
            return Token(id=tid, text=self._detok.add(final=True),
                         is_end_of_stream=True)
        return Token(id=tid, text=self._detok.add((tid,)),
                     is_end_of_stream=False)

    # -- internals -----------------------------------------------------------

    def _encode_prompt(self) -> List[int]:
        ids = encode_text(self.tokenizer, self.history.render())
        # a custom forward may impose its own (inclusive) prompt bound —
        # e.g. the SP adapter's context window; dense decode needs one
        # free slot past the prompt
        limit = getattr(self._forward_fn, "max_prompt_len", None)
        if limit is None:
            limit = self.max_seq_len - 1
        if len(ids) > limit:
            raise ValueError(
                f"prompt length {len(ids)} exceeds limit {limit} "
                f"(max_seq_len {self.max_seq_len})"
            )
        return ids

    def _prefill_prompt(self):
        ids = self._encode_prompt()
        self._prompt_len = len(ids)
        C = self.prefill_chunk
        if C and len(ids) > C and self._forward_fn is None:
            logits = self._prefill_chunked(ids, C)
            self.index_pos = len(ids)
            return logits
        bucket = bucket_length(len(ids), self.max_seq_len)
        padded = ids + [0] * (bucket - len(ids))
        toks = jnp.asarray([padded] * self.batch_size, dtype=jnp.int32)
        plen = jnp.full((self.batch_size,), len(ids), dtype=jnp.int32)
        if self._forward_fn is None:
            logits, self.cache = prefill(
                self.params, toks, plen, self.cache, self.rope, self.config
            )
        else:
            logits, self.cache = self._forward_fn(
                self.params, toks, self.cache, jnp.int32(0), self.rope,
                last_idx=(plen - 1).astype(jnp.int32), is_prefill=True,
            )
        self.index_pos = len(ids)
        return logits

    def _prefill_chunked(self, ids: List[int], C: int):
        """Walk the prompt in fixed windows of C tokens: every chunk (and
        every future prompt) hits ONE compiled program, and attention per
        chunk runs against the growing cache (cache-aware flash kernel on
        TPU) instead of over a monolithic [S, S] window."""
        from cake_tpu.models.llama.model import prefill_chunk
        B = self.batch_size
        logits = None
        for window, n_real, start in chunk_windows(ids, C):
            toks = jnp.asarray([window] * B, dtype=jnp.int32)
            last_idx = jnp.full((B,), n_real - 1, dtype=jnp.int32)
            logits, self.cache = prefill_chunk(
                self.params, toks, jnp.int32(start), last_idx, self.cache,
                self.rope, self.config,
            )
        return logits

    # -- fully on-device generation (throughput path) ------------------------

    def generate_on_device(self, prompt_ids: np.ndarray, prompt_len: np.ndarray,
                           num_tokens: int) -> np.ndarray:
        """Generate num_tokens for a [B, S] batch with zero host round-trips.

        Returns [B, num_tokens] int32. EOS is not early-exited (static trip
        count keeps the program fixed-shape); callers trim at the first eos.
        Runs on a scratch cache — the interactive session cache/state is
        untouched. prompt_len must be uniform: decode positions are shared
        across the batch, and a shorter row would both attend pad-garbage KV
        and cache its tokens at the wrong RoPE positions. (Per-row positions
        arrive with the continuous-batching scheduler.)
        """
        plen_arr = np.asarray(prompt_len, dtype=np.int32)
        if not (plen_arr == plen_arr[0]).all():
            raise ValueError(
                "generate_on_device requires uniform prompt_len; "
                f"got {plen_arr.tolist()}"
            )
        plimit = getattr(self._forward_fn, "max_prompt_len", None)
        if plimit is not None and int(plen_arr[0]) > plimit:
            # e.g. the SP adapter's context window: a longer prompt would
            # silently truncate and zero the last-position hidden state
            raise ValueError(
                f"prompt length {int(plen_arr[0])} exceeds this serving "
                f"mode's prompt limit {plimit}")
        toks = jnp.asarray(prompt_ids, dtype=jnp.int32)
        plen = jnp.asarray(plen_arr)
        self.rng, sub = jax.random.split(self.rng)
        if self._forward_fn is not None:
            # a forward that allocates its own cache at prefill (SP) never
            # reads the one we pass — skip the full-size fresh() copy
            cache = (self.cache
                     if getattr(self._forward_fn, "allocates_cache", False)
                     else self.cache.fresh())
            return self._generate_hostloop(toks, plen, cache, sub,
                                           num_tokens)
        cache = self.cache.fresh()
        out, _ = _generate_scan(
            self.params, toks, plen, cache, self.rope, self.config,
            self.sampling, sub, num_tokens,
        )
        return np.asarray(out)

    def _generate_hostloop(self, toks, plen, cache, rng,
                           num_tokens: int) -> np.ndarray:
        """Host-stepped generation over a custom forward (pipeline path).

        The pipelined forward is already one compiled program per step with
        a donated cache; stepping it from the host matches the reference's
        master decode loop (master.rs:96-108) while every step stays a
        single XLA computation over the whole mesh.
        """
        B = toks.shape[0]
        fwd = self._forward_fn
        limit = getattr(fwd, "max_decode_tokens", None)
        if limit is not None and num_tokens > limit:
            raise ValueError(
                f"num_tokens {num_tokens} exceeds this serving mode's "
                f"decode budget of {limit} tokens per session")
        logits, cache = fwd(self.params, toks, cache, jnp.int32(0),
                            self.rope, last_idx=(plen - 1).astype(jnp.int32),
                            is_prefill=True)
        ring = jnp.full((B, self.sampling.repeat_last_n), -1, jnp.int32)
        rng, sub = jax.random.split(rng)
        first = sample_tokens(sub, logits, ring, self.sampling)
        ring = update_ring(ring, first, 0)
        if num_tokens > 1 and hasattr(fwd, "decode_scan"):
            # adapter provides an on-device multi-step decode (SP): the
            # remaining tokens cost ONE dispatch instead of one per token
            rest, cache, ring, rng = fwd.decode_scan(
                self.params, first[:, None], 0, cache, self.rope, rng,
                ring, num_steps=num_tokens - 1, sampling=self.sampling)
            out = jnp.concatenate([first[:, None], rest], axis=1)
            return np.asarray(out).astype(np.int32)
        outs = [np.asarray(first)]
        tok = first
        pos = int(np.max(np.asarray(plen)))
        for step in range(1, num_tokens):
            logits, cache = fwd(self.params, tok[:, None], cache,
                                jnp.int32(pos), self.rope)
            pos += 1
            rng, sub = jax.random.split(rng)
            tok = sample_tokens(sub, logits, ring, self.sampling)
            ring = update_ring(ring, tok, step)
            outs.append(np.asarray(tok))
        return np.stack(outs, axis=1).astype(np.int32)


@partial(jax.jit,
         static_argnames=("config", "sampling", "num_tokens"),
         donate_argnames=("cache",))
def _generate_scan(params, tokens, prompt_len, cache: KVCache,
                   rope: RopeTables, config: LlamaConfig,
                   sampling: SamplingConfig, rng, num_tokens: int):
    """prefill + num_tokens decode steps as one compiled program."""
    B = tokens.shape[0]
    last_idx = (prompt_len - 1).astype(jnp.int32)
    logits, cache = forward(params, tokens, cache, jnp.int32(0), rope,
                            config, last_idx=last_idx)
    ring0 = jnp.full((B, sampling.repeat_last_n), -1, dtype=jnp.int32)
    rng, sub = jax.random.split(rng)
    first = sample_tokens(sub, logits, ring0, sampling)
    ring0 = update_ring(ring0, first, 0)
    # decode positions are uniform only for uniform prompt_len; use max
    pos0 = jnp.max(prompt_len).astype(jnp.int32)

    def body(carry, step):
        cache, tok, ring, rng, pos = carry
        rng, sub = jax.random.split(rng)
        logits, cache = forward(params, tok[:, None], cache, pos, rope, config)
        nxt = sample_tokens(sub, logits, ring, sampling)
        ring = update_ring(ring, nxt, step)
        return (cache, nxt, ring, rng, pos + 1), nxt

    (cache, _, _, _, _), rest = jax.lax.scan(
        body, (cache, first, ring0, rng, pos0), jnp.arange(1, num_tokens)
    )
    out = jnp.concatenate([first[:, None], rest.T], axis=1)  # [B, num_tokens]
    return out, cache


def trim_at_eos(ids: np.ndarray, eos_ids: Tuple[int, ...]) -> List[List[int]]:
    """Cut each row at its first EOS token."""
    out = []
    for row in ids:
        cut = len(row)
        for j, t in enumerate(row):
            if int(t) in eos_ids:
                cut = j
                break
        out.append([int(t) for t in row[:cut]])
    return out
