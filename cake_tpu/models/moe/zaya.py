"""ZAYA1 (`model_type: zaya`) on the paged engine: the step programs.

The equations are models/reference/zaya.py's; this is how the served
path computes them over the page pool and, beside it, the rows' conv
tail (models/llama/paged.HybridPagedCache says what a row's tail is).

Both step programs run ONE trunk over a flat list of tokens, each with
its row (slot) and its place in its row's tokens of this dispatch: a
decode step's B tokens, or a mixed step's packed axis (paged.pack_plan).
The 40 layers are alike, so the trunk is one `lax.scan`; the page pools,
the tails and the router's state are its CARRY (a carried buffer that a
layer scatters into is one buffer from the donated input to the output:
paged.scan_layers_paged_stats), the experts' weights stay out of the
scan as (stack, layer) for the grouped matmul (ops/moe.LayerOf). A
layer:

  * `qkv`: ONE projection of every token into the latent,
    [q | k | v1 | v2] (`w_cca`, int8 per channel).
  * `cca_mix` (inside `attn`): everything between that projection and
    RoPE. The convolutions' and the value shift's taps are GATHERS: a
    token's predecessor is the packed position before it while that is
    its own row's, else the row's stored tail (zeros where the row's
    first token sits at position 0: a request that takes the slot, no
    launch of its own). So any number of rows may hold any number of
    tokens in one dispatch. A row's tail is written from its last token
    in the same program; a row with no token in the dispatch keeps its
    bits. The first convolution's output is rounded to the activations'
    type before the second reads it, in the window and in the tail
    alike, so that a window boundary changes no bit.
  * K and V, as they stand after conv, mean, shift, norm and RoPE, go
    to the pool's pages; `cake_mixed_attn` / `cake_decode_attn` (or the
    XLA fold) read them as they read any GQA model's.
  * `router` (inside `ffn`): float32 throughout (top-1 is a discrete
    choice); its state is a carry of the layer loop. The expert
    sublayer is ops/moe.moe_mlp with these logits.

Departures from the reference, all of them in HOW: the cache and the
tail in place of whole sequences; bf16 activations and int8 weights
where the engine is asked for them (the reference takes the served
leaves dequantized); the fused projection; the experts through the
sorted dispatch and `cake_moe_gmm`.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cake_tpu.models.family import Family, cannot_move
from cake_tpu.models.llama import paged
from cake_tpu.models.llama.paged import HybridPagedCache
from cake_tpu.models.moe.config import ZayaConfig
from cake_tpu.models.moe.nemotron_h import Rows, dequantized
from cake_tpu.models.step_programs import (
    make_decode_scan, make_mixed_sampled,
)
from cake_tpu.ops.moe import EXPERT_LEAVES, LayerOf, moe_mlp
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import qmatmul
from cake_tpu.ops.rope import apply_rope

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
# the record keys of the vector a step program returns, in trunk's
# order: the expert counters' five, then the tails read and the choices
# the router's bias changed
COUNTERS = paged.MOE_COUNTERS + ("cca_tail_rows", "router_choice_by_bias")


def split_cca(w_cca, config: ZayaConfig):
    """The fused projection's columns as (W_q, W_k, W_v1, W_v2)."""
    c = config
    hq = c.num_attention_heads * c.head_dim
    hk = c.num_key_value_heads * c.head_dim
    cuts = np.cumsum([hq, hk, hk // 2])
    return jnp.split(w_cca, cuts, axis=-1)


def reference_layers(blocks, config: ZayaConfig):
    """The per-layer float32 dicts models/reference/zaya.forward walks,
    one at a time (a generator: a caller at published widths holds one
    layer's float32 weights at a time): the served leaves dequantized,
    the fused projection split."""
    for i in range(config.num_hidden_layers):
        lp = {k: dequantized(jax.tree.map(lambda a: a[i], v))
              for k, v in blocks.items()}
        lp["wq"], lp["wk"], lp["wv1"], lp["wv2"] = split_cca(
            lp.pop("w_cca"), config)
        yield lp


def reference_config(config: ZayaConfig) -> dict:
    c = config
    return {"rms_norm_eps": c.rms_norm_eps,
            "num_attention_heads": c.num_attention_heads,
            "num_key_value_heads": c.num_key_value_heads,
            "head_dim": c.head_dim,
            "partial_rotary_factor": c.partial_rotary_factor,
            "rope_theta": c.rope_theta,
            "num_experts_per_tok": c.num_experts_per_tok}


# -- the sublayers -------------------------------------------------------------


def cca_mix(lp, proj, tail, slot, col, rows: Rows, config: ZayaConfig):
    """proj [T, (H + 2K) d]: the projection's [q | k | v1 | v2] of every
    token; tail [B, cca_tail_width]: each row's [c | a | v2] of the
    token before this dispatch's -> (q [T, H, d], k, v [T, K, d] before
    RoPE, the rows' new tail [B, W])."""
    c = config
    T, B = proj.shape[0], tail.shape[0]
    H, K, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    G, Cc = H // K, c.cca_channels
    dtype = proj.dtype
    lat, v1, v2 = proj[:, :Cc], proj[:, Cc:Cc + K * d // 2], \
        proj[:, Cc + K * d // 2:]
    tail = tail.astype(dtype)
    # a token's predecessor: the packed position before it while that
    # is its row's own, else its row's tail
    t = jnp.arange(T, dtype=jnp.int32)
    src = jnp.where(col >= 1, B + t - 1, slot)

    def before(stored, cur):
        return jnp.take(jnp.concatenate([stored, cur], axis=0), src, axis=0)

    lat_prev = before(tail[:, :Cc], lat)
    v2_prev = before(tail[:, 2 * Cc:], v2)
    w0 = lp["conv0_w"].astype(F32)
    a = (w0[0] * lat_prev.astype(F32) + w0[1] * lat.astype(F32)
         + lp["conv0_b"].astype(F32)).astype(dtype)
    a_prev = before(tail[:, Cc:2 * Cc], a)
    w1 = lp["conv1_w"].astype(dtype)                         # [H+K, 2, d, d]
    taps = jnp.concatenate([a_prev.reshape(T, H + K, d),
                            a.reshape(T, H + K, d)], axis=-1)
    b = jnp.einsum("tgi,gio->tgo", taps, w1.reshape(H + K, 2 * d, d),
                   preferred_element_type=F32)
    b = b + lp["conv1_b"].astype(F32).reshape(H + K, d)
    qc = lat[:, :H * d].astype(F32).reshape(T, K, G, d)
    kc = lat[:, H * d:].astype(F32).reshape(T, K, d)
    q = b[:, :H].reshape(T, K, G, d) + 0.5 * (qc + kc[:, :, None])
    k = b[:, H:] + 0.5 * (jnp.mean(qc, axis=2) + kc)
    q = q.reshape(T, H, d)
    # (no epsilon, as the reference: a zero latent would be NaN there too)
    q = q * (np.sqrt(d) * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True)))
    k = k * (lp["k_temp"].astype(F32)[None, :, None] * np.sqrt(d)
             * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True)))
    v = jnp.concatenate([v1, v2_prev], axis=-1).reshape(T, K, d)
    # the rows' last tokens, for the dispatch after this one
    last = jnp.clip(rows.first + rows.n - 1, 0, T - 1)
    new = jnp.concatenate([lat[last], a[last], v2[last]], axis=-1)
    new = jnp.where((rows.n > 0)[:, None], new, tail)
    return q.astype(dtype), k.astype(dtype), v, new


def router_logits(lp, m, r_prev, eps: float):
    """m [T, D] (normed), r_prev [T, R] f32 (zeros at layer 0: no term)
    -> (logits [T, E] f32, the state r [T, R] f32)."""
    def mm(x, w):
        return jnp.dot(x, w.astype(F32), precision=HIGHEST)

    r = (mm(m.astype(F32), lp["r_dn"]) + lp["r_dn_b"].astype(F32)
         + lp["r_gamma"].astype(F32) * r_prev)
    s = rms_norm(r, lp["r_norm"], eps)
    hid = jax.nn.gelu(mm(s, lp["r_w1"]) + lp["r_b1"].astype(F32),
                      approximate=False)
    hid = jax.nn.gelu(mm(hid, lp["r_w2"]) + lp["r_b2"].astype(F32),
                      approximate=False)
    return mm(hid, lp["r_w3"]), r


def scaled_sum(res, x, y):
    """(alpha_r * x + beta_r) + (alpha_y * y + beta_y), res [4, D]."""
    res = res.astype(F32)
    return ((res[0] * x.astype(F32) + res[1])
            + (res[2] * y.astype(F32) + res[3])).astype(x.dtype)


class TrunkOut(NamedTuple):
    """x [T, D] after the final norm; cache; counters in the order of
    COUNTERS; and each layer's routing [L, T, k], for a tool that
    compares it with the reference's (chip_compare.py; a step program
    drops it)."""

    x: jnp.ndarray
    cache: HybridPagedCache
    counters: jnp.ndarray
    experts: jnp.ndarray


def trunk(params, token_ids, slot, col, position, real, rows: Rows,
          cache: HybridPagedCache, rope, config: ZayaConfig,
          attend) -> TrunkOut:
    """Embed, every layer, final norm, over T tokens: token_ids, slot,
    col (a token's index among its row's tokens of this dispatch),
    position [T] int32, real [T] bool (a token that is not real writes
    nothing, is not routed, and its output is garbage nobody reads).
    attend(layer, pool_k, pool_v, q, k, v) -> (o [T, H, d], pool_k,
    pool_v): write the layer's K and V, attend."""
    c = config
    blocks = params["blocks"]
    H, d = c.num_attention_heads, c.head_dim
    T = token_ids.shape[0]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], token_ids, axis=0)
    at = jnp.minimum(position, rope.cos.shape[0] - 1)
    rope_c = jnp.take(rope.cos, at, axis=0)[None]            # [1, T, R/2]
    rope_s = jnp.take(rope.sin, at, axis=0)[None]
    has = rows.n > 0
    fresh = has & (rows.pos == 0)
    stacked = {k: blocks[k] for k in EXPERT_LEAVES}
    scanned = {k: v for k, v in blocks.items() if k not in stacked}

    def body(carry, lp):
        x, r, layer, pk, pv, conv = carry
        with jax.named_scope("attn_norm"):
            u = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
        with jax.named_scope("qkv"):
            proj = qmatmul(u, lp["w_cca"])
        with jax.named_scope("attn"):
            with jax.named_scope("cca_mix"):
                tail = jnp.where(fresh[:, None], 0, conv[layer, :, 0])
                q, k, v, tail = cca_mix(lp, proj, tail, slot, col, rows, c)
                conv = conv.at[layer, :, 0].set(tail.astype(conv.dtype))
            q = apply_rope(q[None], rope_c, rope_s)[0]
            k = apply_rope(k[None], rope_c, rope_s)[0]
            o, pk, pv = attend(layer, pk, pv, q, k, v)
        with jax.named_scope("o_proj"):
            y = qmatmul(o.reshape(T, H * d), lp["wo"])
            x = scaled_sum(lp["res_attn"], x, y)
        with jax.named_scope("ffn"):
            m = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps)
            with jax.named_scope("router"):
                logits, r = router_logits(lp, m, r, c.rms_norm_eps)
            lp = dict(lp, **{k: LayerOf(w, layer)
                             for k, w in stacked.items()})
            f, stats = moe_mlp(lp, m[None], c.num_experts_per_tok,
                               c.norm_topk_prob, token_mask=real[None],
                               logits=logits[None])
            with jax.named_scope("router"):
                by_bias = jnp.sum(
                    real & (jnp.argmax(logits, axis=-1)
                            != stats.experts[:, 0]), dtype=F32)
            x = scaled_sum(lp["res_moe"], x, f[0])
        return (x, r, layer + 1, pk, pv, conv), (stats, by_bias)

    r0 = jnp.zeros((T, c.router_hidden_size), F32)
    with jax.named_scope("layers"):
        (x, _, _, pool_k, pool_v, conv), (moe, by_bias) = lax.scan(
            body, (x, r0, jnp.int32(0), cache.k, cache.v, cache.conv),
            scanned)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    counters = jnp.stack([
        jnp.sum(moe.rows), jnp.sum(moe.rows_padded),
        jnp.mean(moe.load_max), jnp.mean(moe.load_mean),
        jnp.sum(moe.touched),
        c.num_hidden_layers * jnp.sum(has, dtype=F32),
        jnp.sum(by_bias)]).astype(F32)
    return TrunkOut(x, cache._replace(k=pool_k, v=pool_v, conv=conv),
                    counters, moe.experts)


# -- the step programs ---------------------------------------------------------


def mixed_trunk(params, tokens, pos, q_len, active,
                cache: HybridPagedCache, rope, config: ZayaConfig,
                attn: str, n_tokens: int):
    """The mixed step's trunk on the packed axis [n_tokens] ->
    (TrunkOut, PackPlan)."""
    C = tokens.shape[1]
    plan = paged.pack_plan(q_len, active, n_tokens, C)
    n = jnp.where(active, q_len, 0).astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    def attend(layer, pk, pv, q, k, v):
        pk, pv = paged.write_packed_pages(pk, pv, layer, k, v, plan, pos,
                                          q_len, active, cache.table)
        out = paged.paged_attention_mixed(
            paged._unpack_windows(q, plan), pk, pv, layer, cache.table,
            pos, n, impl=attn)
        return (jnp.take(out.reshape((-1,) + out.shape[2:]),
                         plan.row * plan.width + plan.col, axis=0), pk, pv)

    out = trunk(params, tokens[plan.row, plan.col], plan.row, plan.col,
                pos[plan.row] + plan.col, plan.real,
                Rows(plan.start, n, pos), cache, rope, config, attend)
    return out, plan


@partial(jax.jit, static_argnames=("config", "attn", "n_tokens"),
         donate_argnames=("cache",))
def mixed_step_cca(params, tokens, pos, q_len, active,
                   cache: HybridPagedCache, rope, config: ZayaConfig,
                   attn: str = "fold", n_tokens: Optional[int] = None):
    """paged.mixed_step_paged's contract: tokens [B, C] right-padded
    windows, pos/q_len [B], active [B] -> (logits [B, V] of each row's
    last real token, cache, counters). n_tokens, the packed size, is
    required: the taps are gathers along the packed axis."""
    if n_tokens is None:
        raise ValueError("the CCA mixed step runs on the packed axis: "
                         "pass n_tokens")
    out, plan = mixed_trunk(params, tokens, pos, q_len, active, cache, rope,
                            config, attn, n_tokens)
    with jax.named_scope("head"):
        last = (jnp.maximum(q_len, 1) - 1).astype(jnp.int32)
        last = jnp.take(out.x, jnp.minimum(plan.start + last, n_tokens - 1),
                        axis=0)
        logits = qmatmul(last, params["lm_head"]).astype(F32)
    return logits, out.cache, out.counters


def decode_trunk(params, tokens, cache: HybridPagedCache, pos, active, rope,
                 config: ZayaConfig, attn: str) -> TrunkOut:
    """One token a row: tokens [B, 1], pos/active [B]."""
    B = tokens.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    pos = pos.astype(jnp.int32)

    def attend(layer, pk, pv, q, k, v):
        pk, pv = paged.update_pool_per_row(pk, pv, layer, k[:, None],
                                           v[:, None], pos, active,
                                           cache.table)
        return (paged.paged_attention(q[:, None], pk, pv, layer,
                                      cache.table, pos, impl=attn)[:, 0],
                pk, pv)

    return trunk(params, tokens[:, 0], rows, jnp.zeros_like(rows), pos,
                 active, Rows(rows, active.astype(jnp.int32), pos), cache,
                 rope, config, attend)


def forward_ragged_cca(params, tokens, cache: HybridPagedCache, pos, active,
                       rope, config: ZayaConfig, attn: str = "fold"):
    """paged.forward_ragged_paged(..., counters=True)'s contract: what
    step_programs.make_decode_scan builds the sampled decode programs
    from -> (logits [B, V], cache, counters)."""
    out = decode_trunk(params, tokens, cache, pos, active, rope, config,
                       attn)
    with jax.named_scope("head"):
        logits = qmatmul(out.x, params["lm_head"]).astype(F32)
    return logits, out.cache, out.counters


@partial(jax.jit, static_argnames=("config", "attn"),
         donate_argnames=("cache",))
def decode_step_cca(params, tokens, pos, active, cache: HybridPagedCache,
                    rope, config: ZayaConfig, attn: str = "fold"):
    """paged.decode_step_ragged_paged's contract (the synchronous
    decode step)."""
    return forward_ragged_cca(params, tokens, cache, pos, active, rope,
                              config, attn)


# -- what the engine reads of this family (models/family.py) ----------------


def create_cache(config: ZayaConfig, slots: int, n_pages: int,
                 page_size: int, max_seq_len: int, width, dtype):
    """Pages and a conv tail a row in EVERY layer, no recurrent
    state."""
    c = config
    L = c.num_hidden_layers
    return HybridPagedCache.zeros(
        (L, n_pages, page_size, c.num_key_value_heads * c.head_dim),
        slots, max_seq_len // page_size, dtype, ssm=None,
        conv=(L, slots, max(c.cca_time0, c.cca_time1) - 1,
              c.cca_tail_width))


# The taps are gathers along the packed axis, so a dispatch holds two
# prefilling rows as the dense path's does, and K and V go through the
# GQA kernels as any GQA model's (the engine's own rule resolves them).
# ONE packed size, the two-window one: two programs of different shape
# round differently, and the choice of one expert of 16 is discrete, so
# with one program a row's bits do not depend on its company.
FAMILY = Family(
    name="zaya", decode_step=decode_step_cca,
    decode_programs=make_decode_scan(forward_ragged_cca),
    mixed_step=mixed_step_cca,
    mixed_sampled=make_mixed_sampled(mixed_step_cca),
    create_cache=create_cache, counters=COUNTERS, prefill_rows=(2,),
    beside=("conv tails", "cca_tail_bytes"), impl="paged-cca-",
    what="a conv tail a row beside the page pool",
    refuses=cannot_move(
        "tail",
        register_prefix=(
            "a conv tail (zaya) has no prefix reuse yet: a shared head "
            "has pages but no tail at its last page's edge (ROADMAP.md)"),
        reconfigure=(
            "a conv tail (zaya) lives beside the page pool: a rebuilt "
            "pool cannot replay it")))
