"""Llama-3 forward functions: pure, jit-friendly, static shapes.

Block semantics match the reference decoder block (transformer.rs:51-73):
  x = x + attn(rms_norm(x))        # input_layernorm -> GQA+RoPE -> o_proj
  x = x + mlp(rms_norm(x))         # post_attention_layernorm -> SwiGLU
with attention accumulated in f32 (attention.rs:96-118) and RoPE from
precomputed tables (cache.rs:23-61).

The whole-model forward (reference llama.rs:72-137: embedding -> block walk
-> final norm -> last-position slice -> lm_head -> f32 logits) is expressed
as one `lax.scan` over the stacked block params; a contiguous sub-range of
the stack gives a pipeline stage's forward (parallel/pipeline.py).
"""

from __future__ import annotations

import logging
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from cake_tpu.models.llama.cache import (
    KVCache, layer_rows, update_layer_cache, update_layer_cache_per_row,
    update_layer_cache_per_row_ring, update_layer_cache_ring,
    update_layer_cache_window_per_row,
)
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.ops.attention import (
    decode_mask, decode_mask_per_row, gqa_attention,
)
from cake_tpu.ops.flash_attention import (
    flash_attention, flash_attention_cached, flash_supported,
)
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import qmatmul
from cake_tpu.ops.rope import (
    apply_rope, precompute_rope, rope_rows, rope_rows_per_row,
)


log = logging.getLogger(__name__)


class RopeTables(NamedTuple):
    cos: jnp.ndarray
    sin: jnp.ndarray

    @classmethod
    def create(cls, config: LlamaConfig, max_seq_len: int) -> "RopeTables":
        cos, sin = precompute_rope(
            config.rope_dim, max_seq_len, config.rope_theta,
            yarn=getattr(config, "rope_scaling", None),
        )
        if getattr(config, "swa_rope_theta", None):
            # a second kind of attention layer with a rotation of its own
            return WindowRopeTables(cos, sin, *precompute_rope(
                config.swa_qk_rope_head_dim, max_seq_len,
                config.swa_rope_theta))
        if getattr(config, "index_rope_dim", None):
            # a sparse indexer whose heads are rotated whole, by the
            # frequencies of their own width
            return IndexRopeTables(cos, sin, *precompute_rope(
                config.index_rope_dim, max_seq_len, config.rope_theta))
        return cls(cos, sin)


class WindowRopeTables(NamedTuple):
    """RopeTables of a model whose sliding-window layers rotate by a
    theta (and over a width) of their own (dots3_note)."""
    cos: jnp.ndarray
    sin: jnp.ndarray
    swa_cos: jnp.ndarray
    swa_sin: jnp.ndarray


class IndexRopeTables(NamedTuple):
    """RopeTables of a model whose sparse indexer rotates its heads
    whole, over a width of their own at the model's theta (KeyeVL2)."""
    cos: jnp.ndarray
    sin: jnp.ndarray
    index_cos: jnp.ndarray
    index_sin: jnp.ndarray


def _qk_norm(x, weight, eps: float, tp_axis: Optional[str]):
    """RMSNorm over the WHOLE projection [.., heads*hd] (OLMoE: before
    the split into heads, before RoPE). Under manual tensor parallelism
    the projection is split by heads over `tp_axis`, so the mean square
    is summed over the axis."""
    xf = x.astype(jnp.float32)
    ss = jnp.sum(jnp.square(xf), axis=-1, keepdims=True)
    n = x.shape[-1]
    if tp_axis is not None:
        ss = lax.psum(ss, tp_axis)
        n = n * lax.psum(1, tp_axis)
    y = xf * lax.rsqrt(ss / n + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def block_skeleton_stats(lp, x, config: LlamaConfig, attn_fn,
                         tp_axis: Optional[str] = None,
                         ep_axis: Optional[str] = None,
                         token_mask=None):
    """Decoder-block math with a pluggable attention:
    rms → qkv proj → attn_fn(q, k, v) → o_proj → residual → rms → FFN →
    residual (reference transformer.rs:51-73). attn_fn returns
    (attn [B,S,H,hd], extras) — extras carry e.g. updated caches.
    Returns (x, extras, the MoE layer's counters or None).

    What a family adds is keyed on the layer's leaves, so every caller
    (scan, pipeline, ragged decode, the paged steps) works for all of
    them and a family runs only its own code: `bq` a QKV bias (Qwen2),
    `q_norm` an RMSNorm over the whole query and key projections
    (OLMoE), `router` a sparse mixture-of-experts FFN (models/moe) in
    place of the dense SwiGLU (mlp.rs:15-18).

    tp_axis: when running *manually* tensor-parallel under shard_map, the
    mesh axis name to psum partial row-parallel outputs over (Megatron: o_proj
    and down_proj each produce partial sums). Head counts are derived from
    the weight shapes, so the same code runs on full or head-sharded weights.
    ep_axis: shard_map expert-parallel axis for the MoE path (ops/moe.py).
    token_mask: [B, S] bool, the positions that hold a real token (a
    mixed step pads every row to its window); the MoE FFN routes no
    other. The dense FFN ignores it.
    """
    B, S, D = x.shape
    hd = config.head_dim
    H = lp["wq"].shape[-1] // hd      # local head count under TP
    KV = lp["wk"].shape[-1] // hd

    # the named scopes are the device trace's vocabulary (PERF.md §3):
    # every family and engine inherits them from here, and
    # benchmarks/harness/trace_spans.py sums device time by them
    with jax.named_scope("attn_norm"):
        h = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
    with jax.named_scope("qkv"):
        q = qmatmul(h, lp["wq"])
        k = qmatmul(h, lp["wk"])
        v = qmatmul(h, lp["wv"])
        if "bq" in lp:  # Qwen2-family QKV bias (config.attention_bias)
            q = q + lp["bq"]
            k = k + lp["bk"]
            v = v + lp["bv"]
        if "q_norm" in lp:  # OLMoE query/key norm (config.qk_norm)
            q = _qk_norm(q, lp["q_norm"], config.rms_norm_eps, tp_axis)
            k = _qk_norm(k, lp["k_norm"], config.rms_norm_eps, tp_axis)
        # without the barrier XLA folds the head split below into wq and
        # wk and, every layer, slices and transposes them for it (copy
        # s8[1,4096,4096] / [1,4096,1024] and their constant_dynamic-slice_
        # fusions: 2.7 ms of a 13.5 ms decode step, PERF.md §6 PR 43)
        q, k, v = lax.optimization_barrier((q, k, v))
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, KV, hd)
        v = v.reshape(B, S, KV, hd)
    with jax.named_scope("attn"):
        attn, extras = attn_fn(q, k, v)
    with jax.named_scope("o_proj"):
        attn_out = qmatmul(attn.reshape(B, S, H * hd), lp["wo"])
        if tp_axis is not None:
            attn_out = lax.psum(attn_out, tp_axis)
        x = x + attn_out

    stats = None
    with jax.named_scope("ffn"):
        h = rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
        if "router" in lp:
            from cake_tpu.ops.moe import moe_mlp
            # AttributeError here means MoE params were paired with a
            # dense LlamaConfig — a real mismatch that must not default
            # silently.
            mlp_out, stats = moe_mlp(
                lp, h, config.num_experts_per_tok, config.norm_topk_prob,
                ep_axis=ep_axis, token_mask=token_mask)
        else:
            gate = jax.nn.silu(qmatmul(h, lp["w_gate"]))
            mlp_out = qmatmul(gate * qmatmul(h, lp["w_up"]), lp["w_down"])
        if tp_axis is not None:
            mlp_out = lax.psum(mlp_out, tp_axis)
        x = x + mlp_out
    return x, extras, stats


def block_skeleton(lp, x, config: LlamaConfig, attn_fn,
                   tp_axis: Optional[str] = None,
                   ep_axis: Optional[str] = None):
    """block_skeleton_stats for the callers that keep no counters."""
    x, extras, _ = block_skeleton_stats(lp, x, config, attn_fn,
                                        tp_axis=tp_axis, ep_axis=ep_axis)
    return x, extras


def block_forward(lp, x, k_cache, v_cache, layer, pos, rope_c, rope_s, mask,
                  config: LlamaConfig, tp_axis: Optional[str] = None,
                  ep_axis: Optional[str] = None,
                  is_prefill: bool = False, chunked: bool = False,
                  ring: bool = False, write_len=None, row0=0, live=None):
    """One decoder block with KV-cache update.

    lp: single-layer param dict (leaves without the L axis)
    x:  [n, S, D]; k_cache/v_cache: the STACKED cache [L, B, T, KV, hd],
    of which this block writes and attends [layer, row0:row0+n] (cache.py's
    layout contract); pos: traced scalar
    rope_c/rope_s: [S, hd/2] rows for positions pos..pos+S
    mask: [S, T] boolean
    chunked: static — this prefill window continues an existing cache
    (pos may be > 0), so flash must use the cache-aware kernel; fresh
    whole-prompt prefill (pos == 0 by contract) uses the cheaper
    S-window kernel that never touches the cache tail.
    live: traced bool or None; False (a pipeline bubble) writes nothing.
    Returns (x, k_cache, v_cache).
    """
    n, S = x.shape[:2]
    T = k_cache.shape[2]

    def attn_fn(q, k, v):
        H, KV = q.shape[2], k.shape[2]
        q = apply_rope(q, rope_c, rope_s)
        k = apply_rope(k, rope_c, rope_s)
        if ring:
            # Ring (sliding-window) uniform forward: attend the PRE-write
            # ring + the fresh window (a full-W window's write would
            # destroy in-window history its own early queries need), then
            # write. Ring slots permute key positions, which the flash
            # kernels' sequential-position masks cannot express -> einsum.
            k_full = jnp.concatenate(
                [layer_rows(k_cache, layer, row0, n),
                 k.astype(k_cache.dtype)], axis=1)
            v_full = jnp.concatenate(
                [layer_rows(v_cache, layer, row0, n),
                 v.astype(v_cache.dtype)], axis=1)
            attn = gqa_attention(q, k_full, v_full, mask=mask)
            n_real = write_len
            if live is not None:
                n_real = jnp.where(live, S if n_real is None else n_real, 0)
            return attn, update_layer_cache_ring(
                k_cache, v_cache, layer, k, v, pos, n_real=n_real,
                row0=row0)
        kc, vc = update_layer_cache(k_cache, v_cache, layer, k, v, pos,
                                    row0=row0, live=live)
        use_flash = is_prefill and config.use_flash_attention
        if use_flash and not chunked and flash_supported(S, S, H, KV, hd=config.head_dim):
            # Fresh prompt at pos=0 with an empty cache: causal attention
            # over the in-window k/v IS the cached-decode mask, so the
            # kernel reads only the S fresh keys — no cache traffic.
            # Sliding-window models pass the window to the kernel (out-of-
            # window key blocks are skipped entirely).
            attn = flash_attention(q, k, v, causal=True,
                                   window=config.sliding_window)
        elif (use_flash and chunked and flash_supported(S, T, H, KV, hd=config.head_dim)
                and kc.dtype == q.dtype):
            # (dtype guard: the Pallas kernel reads the cache directly, so
            # fp8-stored KV takes the einsum path, which upcasts on read)
            # Continued prefill at pos>0: the cache-aware kernel attends
            # the cache under kj <= pos+qi; key blocks past the frontier
            # neither compute nor DMA (index-map clamp).
            attn = flash_attention_cached(
                q, layer_rows(kc, layer, row0, n),
                layer_rows(vc, layer, row0, n), pos,
                window=config.sliding_window)
        else:
            if use_flash:
                if (chunked and flash_supported(S, T, H, KV, hd=config.head_dim)
                        and kc.dtype != q.dtype):
                    # intended fallback, not a shape problem
                    log.debug(
                        "chunked prefill with %s-stored KV takes the "
                        "einsum path (upcast on read)", kc.dtype)
                else:
                    log.warning(
                        "flash attention requested but unsupported for "
                        "S=%d T=%d H=%d KV=%d (non-tileable shapes) — "
                        "falling back to the einsum path", S, T, H, KV)
            attn = gqa_attention(q, layer_rows(kc, layer, row0, n),
                                 layer_rows(vc, layer, row0, n), mask=mask)
        return attn, (kc, vc)

    x, (k_cache, v_cache) = block_skeleton(lp, x, config, attn_fn,
                                           tp_axis=tp_axis, ep_axis=ep_axis)
    return x, k_cache, v_cache


def scan_layers(blocks, x, cache: KVCache, layer_fn):
    """The layer loop of every dense step program: scan `blocks` alone,
    with the stacked cache as CARRY beside the hidden state and the layer
    index (as paged.scan_layers_paged carries the pool). A scan's stacked
    outputs cannot alias its inputs, so a cache passed as xs/ys is sliced
    per layer, stacked back and kept twice; a carried cache that each
    layer writes its rows into is one buffer from the donated input to
    the output.

    layer_fn(lp, h, layer, k, v) -> (h, k, v)."""
    def body(carry, lp):
        h, layer, k, v = carry
        h, k, v = layer_fn(lp, h, layer, k, v)
        return (h, layer + 1, k, v), None

    with jax.named_scope("layers"):
        (x, _, k, v), _ = lax.scan(
            body, (x, jnp.int32(0), cache.k, cache.v), blocks)
    return x, KVCache(k=k, v=v)


def run_blocks(blocks, x, cache: KVCache, pos, rope_c, rope_s, mask,
               config: LlamaConfig,
               tp_axis: Optional[str] = None,
               ep_axis: Optional[str] = None,
               is_prefill: bool = False,
               chunked: bool = False,
               ring: bool = False,
               write_len=None, row0=0, live=None
               ) -> Tuple[jnp.ndarray, KVCache]:
    """Scan the stacked blocks [L, ...] over the hidden state.

    This is the TPU equivalent of the reference's sequential block walk with
    contiguous-run batching (llama.rs:81-117): the scan compiles the whole
    contiguous range into one XLA program, so "batch blocks per hop" holds
    by construction. x: [n, S, D] is rows row0..row0+n of the cache's
    batch (a microbatch of the pipeline's tick; all of it by default).
    """
    def layer_fn(lp, h, layer, k, v):
        return block_forward(lp, h, k, v, layer, pos, rope_c, rope_s, mask,
                             config, tp_axis=tp_axis, ep_axis=ep_axis,
                             is_prefill=is_prefill, chunked=chunked,
                             ring=ring, write_len=write_len, row0=row0,
                             live=live)

    return scan_layers(blocks, x, cache, layer_fn)


def forward(params, tokens, cache: KVCache, pos, rope: RopeTables,
            config: LlamaConfig, last_idx: Optional[jnp.ndarray] = None,
            return_hidden: bool = False, is_prefill: bool = False,
            chunked: bool = False, ring: bool = False, write_len=None):
    """Full forward: tokens [B, S] + cache @ pos -> (logits [B, V] f32, cache).

    last_idx: per-batch index of the final *real* token within the window
    (for right-padded prefill); defaults to S-1.
    """
    B, S = tokens.shape
    T = cache.max_seq_len
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    rope_c, rope_s = rope_rows(rope.cos, rope.sin, pos, S)
    from cake_tpu.ops.attention import uniform_forward_mask
    mask = uniform_forward_mask(pos, S, T, config.sliding_window, ring,
                                n_real=write_len)
    x, cache = run_blocks(params["blocks"], x, cache, pos, rope_c, rope_s,
                          mask, config, is_prefill=is_prefill,
                          chunked=chunked, ring=ring, write_len=write_len)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
        if return_hidden:
            return x, cache
        if last_idx is None:
            last = x[:, -1]
        else:
            last = jnp.take_along_axis(
                x, last_idx.reshape(B, 1, 1).astype(jnp.int32), axis=1
            )[:, 0]
        logits = qmatmul(last, params["lm_head"]).astype(jnp.float32)
    return logits, cache


def forward_logits_all(params, tokens, cache: KVCache, pos,
                       rope: RopeTables, config: LlamaConfig):
    """Logits at every position [B, S, V] (training / scoring path)."""
    x, cache = forward(params, tokens, cache, pos, rope, config,
                       return_hidden=True)
    return qmatmul(x, params["lm_head"]).astype(jnp.float32), cache


# -- jitted entry points -----------------------------------------------------

@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def prefill(params, tokens, prompt_len, cache: KVCache, rope: RopeTables,
            config: LlamaConfig):
    """Process a (right-padded) prompt window starting at position 0.

    tokens:     [B, S_padded]
    prompt_len: [B] true lengths; logits taken at prompt_len-1.
    Padded slots write garbage KV beyond prompt_len, but decode masks by
    absolute position and overwrites slot `pos` before attending it, so the
    garbage is never observed.
    """
    last_idx = (prompt_len - 1).astype(jnp.int32)
    return forward(params, tokens, cache, jnp.int32(0), rope, config,
                   last_idx=last_idx, is_prefill=True)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def decode_step(params, token, pos, cache: KVCache, rope: RopeTables,
                config: LlamaConfig):
    """One KV-cached decode step: token [B, 1] at absolute pos -> logits."""
    return forward(params, token, cache, pos, rope, config)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def prefill_chunk(params, tokens, pos, last_idx, cache: KVCache,
                  rope: RopeTables, config: LlamaConfig):
    """Prefill ONE fixed-size window at absolute position `pos` (chunked
    prefill for long prompts). pos is traced, so every chunk of a prompt —
    and every prompt — reuses one compiled program per chunk shape. With
    flash enabled, attention runs the cache-aware Pallas kernel
    (ops/flash_attention.flash_attention_cached)."""
    return forward(params, tokens, cache, pos, rope, config,
                   last_idx=last_idx, is_prefill=True, chunked=True)


# -- ragged (per-row position) entry points for continuous batching ----------


def run_blocks_ragged(blocks, x, cache: KVCache, pos, active,
                      rope_c, rope_s, mask, config: LlamaConfig,
                      tp_axis: Optional[str] = None,
                      ep_axis: Optional[str] = None,
                      ring: bool = False,
                      cache_update=None, row0=0
                      ) -> Tuple[jnp.ndarray, KVCache]:
    """Scan the stacked blocks for per-row-position ragged decode.

    x: [n, S, D], rows row0..row0+n of the cache's batch; pos/active: [n];
    rope_c/rope_s: [n, S, hd/2] per-row rows; mask: [n, S, T]. S = 1 for
    single-token decode; the batched speculative verify passes
    S = gamma+1 windows with its own cache_update. Inactive rows compute
    garbage but leave their cache lines untouched. Shared by the
    single-device ragged decode, the pipelined engine step
    (parallel/pipeline.py: stage-local blocks/cache views, a microbatch's
    rows, `active` false all through a bubble tick), and
    forward_window_ragged, so the block-scan attention wiring exists
    exactly once.

    cache_update(k_cache, v_cache, layer, k, v, pos, active, row0)
    -> (k_cache, v_cache): the writer of the step's KV into the stacked
    cache (cache.py's layout contract); default = single-token per-row
    write (ring-modular when ring=True)."""
    if cache_update is None:
        cache_update = (update_layer_cache_per_row_ring if ring
                        else update_layer_cache_per_row)
    n = x.shape[0]

    def layer_fn(lp, h, layer, kc, vc):
        def attn_fn(q, k, v):
            q = apply_rope(q, rope_c, rope_s)
            k = apply_rope(k, rope_c, rope_s)
            kc2, vc2 = cache_update(kc, vc, layer, k, v, pos, active, row0)
            attn = gqa_attention(q, layer_rows(kc2, layer, row0, n),
                                 layer_rows(vc2, layer, row0, n), mask=mask)
            return attn, (kc2, vc2)

        h, (kc, vc) = block_skeleton(lp, h, config, attn_fn,
                                     tp_axis=tp_axis, ep_axis=ep_axis)
        return h, kc, vc

    return scan_layers(blocks, x, cache, layer_fn)


def ragged_decode(params, tokens, pos, active, cache: KVCache,
                  rope: RopeTables, config: LlamaConfig, blocks_runner,
                  ring: bool = False):
    """Shared frame for per-row-position single-token decode: embedding →
    per-row rope rows/masks → blocks_runner → final norm → logits.

    blocks_runner(blocks, x, cache, pos, active, rope_c, rope_s, mask)
    -> (y, cache) walks the decoder blocks — single-device scan here,
    shard_mapped pipeline in parallel/pipeline.make_engine_step_fns — so
    the ragged-decode frame exists exactly once.
    """
    T = cache.max_seq_len
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    rope_c, rope_s = rope_rows_per_row(rope.cos, rope.sin, pos)
    if ring:
        from cake_tpu.ops.attention import ring_decode_mask_per_row
        mask = ring_decode_mask_per_row(pos, T)
    else:
        mask = decode_mask_per_row(pos, T,
                                   window=config.sliding_window)
    x, cache = blocks_runner(params["blocks"], x, cache, pos, active,
                             rope_c, rope_s, mask)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
        logits = qmatmul(x[:, -1], params["lm_head"]).astype(jnp.float32)
    return logits, cache


def forward_ragged(params, tokens, cache: KVCache, pos, active,
                   rope: RopeTables, config: LlamaConfig):
    """Single-token decode where every batch row sits at its own position.

    tokens: [B, 1]; pos: [B] absolute positions; active: [B] bool —
    inactive rows (free slots between requests) compute garbage but leave
    their cache lines untouched. Returns (logits [B, V] f32, cache).
    """
    def runner(blocks, x, cache, pos, active, rope_c, rope_s, mask):
        return run_blocks_ragged(blocks, x, cache, pos, active,
                                 rope_c, rope_s, mask, config)

    return ragged_decode(params, tokens, pos, active, cache, rope, config,
                         runner)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def decode_step_ragged(params, tokens, pos, active, cache: KVCache,
                       rope: RopeTables, config: LlamaConfig):
    """Jitted ragged decode step (compiles once per batch size)."""
    return forward_ragged(params, tokens, cache, pos, active, rope, config)


def forward_window_ragged(params, tokens, cache: KVCache, pos0, active,
                          rope: RopeTables, config: LlamaConfig):
    """Score a W-token window per row, each row at its OWN start
    position — the batched speculative verify (one target pass scores
    every slot's [last_tok, drafts] burst concurrently, where the
    per-slot engine path ran B separate batch-1 passes, streaming the
    weights B times per round).

    tokens: [B, W]; pos0: [B] absolute start positions; active: [B].
    Row b's token j sits at position pos0[b]+j, attends cache slots
    <= pos0[b]+j, and writes its KV there. Returns
    (logits [B, W, V] f32, cache). Sliding-window configs are not
    supported (speculation is gated off them upstream)."""
    B, W = tokens.shape
    T = cache.max_seq_len
    x = jnp.take(params["embed"], tokens, axis=0)          # [B, W, D]
    # per-(row, offset) rope rows: [B, W, hd/2]
    p = pos0[:, None] + jnp.arange(W)[None]                # [B, W]
    p = jnp.clip(p, 0, T - 1)
    rope_c = jnp.take(rope.cos, p, axis=0)
    rope_s = jnp.take(rope.sin, p, axis=0)
    # [B, W, T]: query j of row b sees cache slots <= pos0[b]+j
    kj = jax.lax.broadcasted_iota(jnp.int32, (B, W, T), 2)
    mask = kj <= p[:, :, None]

    x, cache = run_blocks_ragged(
        params["blocks"], x, cache, pos0, active, rope_c, rope_s, mask,
        config, cache_update=update_layer_cache_window_per_row)
    x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
    logits = qmatmul(x, params["lm_head"]).astype(jnp.float32)
    return logits, cache


def forward_ragged_ring(params, tokens, cache: KVCache, pos, active,
                        rope: RopeTables, config: LlamaConfig):
    """forward_ragged over a ring (sliding-window) cache: positions map
    to slot p % W and validity is ring-slot liveness
    (ops/attention.ring_decode_mask_per_row)."""
    def runner(blocks, x, cache, pos, active, rope_c, rope_s, mask):
        return run_blocks_ragged(blocks, x, cache, pos, active,
                                 rope_c, rope_s, mask, config, ring=True)

    return ragged_decode(params, tokens, pos, active, cache, rope, config,
                         runner, ring=True)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def decode_step_ragged_ring(params, tokens, pos, active, cache: KVCache,
                            rope: RopeTables, config: LlamaConfig):
    """Jitted ragged decode step over a ring cache (the engine's
    sliding-window serving path: KV memory = window, not max_seq)."""
    return forward_ragged_ring(params, tokens, cache, pos, active, rope,
                               config)


def slot_prefill(params, tokens, prompt_len, slot, cache: KVCache,
                 forward_fn, prefix: Optional[Tuple] = None, pos0=None):
    """Prefill ONE request into batch slot `slot` of a shared cache.

    tokens: [1, S_padded]; prompt_len: [1]; slot: traced scalar. The slot's
    cache lines are sliced out, prefilled via
    forward_fn(params, tokens, sub_cache, pos0, last_idx) -> (logits, sub),
    and written back — other slots' state is untouched, so requests can be
    admitted while their neighbors are mid-decode (continuous batching).
    Shared by the single-device and pipelined engine prefills; the slot
    slice/write-back splice lives in _slot_view/_slot_writeback.

    prefix: optional (k, v) [L, 1, P, KV, hd] — a cached prompt head
    installed into positions 0..P-1 first, with the window then starting
    at position P (prefix caching). pos0: optional traced start position
    for the window (chunked prefill); mutually exclusive with prefix.
    """
    assert prefix is None or pos0 is None, "prefix implies its own pos0"
    sub = _slot_view(cache, slot)
    if prefix is not None:
        sub = _install_prefix(sub, *prefix)
        pos0 = jnp.int32(prefix[0].shape[2])
    elif pos0 is None:
        pos0 = jnp.int32(0)
    last_idx = (prompt_len - 1).astype(jnp.int32)
    logits, sub = forward_fn(params, tokens, sub, pos0, last_idx)
    return logits, _slot_writeback(cache, sub, slot)


@jax.named_scope("kv")
def _slot_view(cache: KVCache, slot) -> KVCache:
    """Slice one batch slot's cache lines out ([L, 1, T, KV, hd])."""
    return KVCache(
        k=lax.dynamic_slice_in_dim(cache.k, slot, 1, axis=1),
        v=lax.dynamic_slice_in_dim(cache.v, slot, 1, axis=1),
    )


@jax.named_scope("kv")
def _install_prefix(sub: KVCache, pk, pv) -> KVCache:
    """Write cached-prefix KV [L, 1, P, KV, hd] at positions 0..P-1."""
    return KVCache(
        k=lax.dynamic_update_slice(
            sub.k, pk.astype(sub.k.dtype), (0, 0, 0, 0, 0)),
        v=lax.dynamic_update_slice(
            sub.v, pv.astype(sub.v.dtype), (0, 0, 0, 0, 0)),
    )


@jax.named_scope("kv")
def _slot_writeback(cache: KVCache, sub: KVCache, slot) -> KVCache:
    """Splice one slot's updated lines back into the shared cache."""
    return KVCache(
        k=lax.dynamic_update_slice_in_dim(cache.k, sub.k, slot, axis=1),
        v=lax.dynamic_update_slice_in_dim(cache.v, sub.v, slot, axis=1),
    )


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def prefill_slot(params, tokens, prompt_len, slot, cache: KVCache,
                 rope: RopeTables, config: LlamaConfig):
    """Jitted single-device slot prefill (compiles once per bucket length)."""
    def fwd(p, t, sub, pos, last_idx):
        return forward(p, t, sub, pos, rope, config,
                       last_idx=last_idx, is_prefill=True)

    return slot_prefill(params, tokens, prompt_len, slot, cache, fwd)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def prefill_slot_chunk(params, tokens, n_real, slot, pos0,
                       cache: KVCache, rope: RopeTables,
                       config: LlamaConfig):
    """One fixed-size prefill window into batch slot `slot` at absolute
    position `pos0` (engine-side chunked prefill: every chunk of every
    prompt in any slot hits ONE compiled program per window shape).
    tokens: [1, C]; n_real: [1] count of real tokens in the window.
    """
    def fwd(p, t, sub, pos, last_idx):
        return forward(p, t, sub, pos, rope, config,
                       last_idx=last_idx, is_prefill=True, chunked=True)

    return slot_prefill(params, tokens, n_real, slot, cache, fwd,
                        pos0=pos0)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def prefill_slot_chunk_ring(params, tokens, n_real, slot, pos0,
                            cache: KVCache, rope: RopeTables,
                            config: LlamaConfig):
    """prefill_slot_chunk over a ring (sliding-window) cache: queries
    attend the pre-write ring + fresh window (ops/attention
    .ring_concat_mask), then the window writes ring slots (pos0+i) % W
    with junk-masked padding. Every prompt in ring mode walks through
    this (windows <= W keep scatter indices unique)."""
    def fwd(p, t, sub, pos, last_idx):
        return forward(p, t, sub, pos, rope, config,
                       last_idx=last_idx, is_prefill=True, chunked=True,
                       ring=True, write_len=n_real[0])

    return slot_prefill(params, tokens, n_real, slot, cache, fwd,
                        pos0=pos0)


@partial(jax.jit, donate_argnames=("cache",))
def install_prefix_slot(cache: KVCache, prefix_k, prefix_v, slot):
    """Copy cached-prefix KV [L, 1, P, KV, hd] into slot `slot` at
    positions 0..P-1 (prefix caching + chunked suffix: the install and
    the windows are separate programs)."""
    sub = _install_prefix(_slot_view(cache, slot), prefix_k, prefix_v)
    return _slot_writeback(cache, sub, slot)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def prefill_slot_prefixed(params, tokens, suffix_len, slot,
                          prefix_k, prefix_v, cache: KVCache,
                          rope: RopeTables, config: LlamaConfig):
    """Slot prefill continuing a cached prefix (prefix/prompt caching).

    prefix_k/v: [L, 1, P, KV, hd] precomputed KV of the shared prompt
    head — installed into the slot's cache lines at positions 0..P-1,
    then the suffix window `tokens` [1, S_padded] prefills at position P
    through the cache-aware (chunked) path. Compiles once per
    (P, suffix bucket) pair; P is a registered-prefix property, so the
    set stays small.
    """
    def fwd(p, t, sub, pos, last_idx):
        return forward(p, t, sub, pos, rope, config,
                       last_idx=last_idx, is_prefill=True, chunked=True)

    return slot_prefill(params, tokens, suffix_len, slot, cache, fwd,
                        prefix=(prefix_k, prefix_v))
