"""Sequence/context parallelism: ring attention over an "sp" mesh axis.

The reference has no long-context story at all — a hard MAX_SEQ_LEN = 4096
(llama3/config.rs:6) and the whole sequence resident on whichever device
owns a layer (SURVEY.md §5 "Long-context"). Here long context is first
class: the token sequence is sharded over the `sp` mesh axis, each device
computes attention for its query chunk while KV chunks rotate around the
ring over ICI (`lax.ppermute`), accumulated with online softmax — so the
context length a model can serve scales with the number of chips, and the
per-hop transfer (one KV chunk) overlaps with the chunk's attention
compute.

Decode after a context-parallel prefill keeps the prefilled KV sharded
where it was computed and gives every device a small replicated "tail"
cache for newly generated tokens: a decode step computes partial attention
(m, l, o) against the local context shard, merges the per-shard statistics
with a logsumexp reduction over `sp` (two psums), and adds the tail — no
resharding of the long context, ever.

All functions here are *per-device* bodies meant to run under
`jax.shard_map`; `make_sp_forward` wraps the whole Llama forward.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.model import RopeTables, block_skeleton
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import qmatmul
from cake_tpu.ops.rope import apply_rope

NEG_INF = -1e30

# host-side dispatch counters/timers for the sp/stage-sp engine step
# fns: the forwards themselves are jitted (no per-call Python), so the
# instrumentation wraps the dispatch wrappers — one inc + one wall
# observation per device program launch, labeled by op and serving
# mode. Shared with sp_pipeline via the fn factories.
_SP_DISPATCH = obs_metrics.counter(
    "cake_sp_dispatch_total",
    "Device-program dispatches of the sp engine step fns",
    labelnames=("op", "mode"))
_SP_DISPATCH_SECONDS = obs_metrics.histogram(
    "cake_sp_dispatch_seconds",
    "Wall seconds per sp engine step-fn dispatch",
    labelnames=("op", "mode"))


def _counted(fn, op: str, mode: str):
    import functools
    import time as _time
    child = _SP_DISPATCH.labels(op=op, mode=mode)
    hist = _SP_DISPATCH_SECONDS.labels(op=op, mode=mode)

    # functools.wraps exposes __wrapped__, so obs/steps.lower_cost can
    # reach the jitted fn through this wrapper for MFU cost accounting
    @functools.wraps(fn)
    def wrapper(*args, **kw):
        child.inc()
        t0 = _time.perf_counter()
        try:
            return fn(*args, **kw)
        finally:
            hist.observe(_time.perf_counter() - t0)
    return wrapper


def instrument_sp_engine(step_fns, mode: str, ctx_len: int,
                         tail_len: int):
    """Shared observability tail of every sp-engine step-fn factory
    (plain sp here, stage x sp in sp_pipeline): wrap EVERY step fn's
    dispatch with the op counter + wall histogram and publish the
    window-layout gauges — one definition, so the two factories'
    metrics cannot drift. Takes and returns the engine step-fn tuple
    (prefill_slot, decode_ragged, decode_scan); None entries pass
    through untouched."""
    obs_metrics.gauge(
        "cake_sp_ctx_window_tokens",
        "Sequence-sharded prompt window of the sp engine",
        labelnames=("mode",)).labels(mode=mode).set(ctx_len)
    obs_metrics.gauge(
        "cake_sp_tail_window_tokens",
        "Replicated decode tail of the sp engine",
        labelnames=("mode",)).labels(mode=mode).set(tail_len)
    ops = ("prefill", "decode", "decode_scan")
    return tuple(
        _counted(fn, op, mode) if fn is not None else None
        for fn, op in zip(step_fns, ops))


def _chunk_scores(q, k, *, scale):
    """[B,Sq,KV,G,hd] x [B,Sk,KV,hd] -> f32 [B,KV,G,Sq,Sk]."""
    return jnp.einsum("bskgd,btkd->bkgst", q, k,
                      preferred_element_type=jnp.float32) * scale


def ring_attention(q, k, v, axis_name: str = "sp", *, causal: bool = True,
                   scale: float | None = None):
    """Ring attention for one device's query chunk (runs under shard_map).

    q:   [B, Sl, H, hd] local query chunk (global rows idx*Sl..)
    k,v: [B, Sl, KV, hd] local key/value chunk
    Rotates k/v around the `axis_name` ring sp times; each step computes the
    partial attention of the local queries against the visiting chunk and
    folds it into online-softmax state. Masking uses *global* positions, so
    the result equals full causal attention over the gathered sequence.
    """
    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, Sl, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    qg = q.reshape(B, Sl, KV, G, hd)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    m0 = jnp.full((B, KV, G, Sl, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sl, 1), jnp.float32)
    acc0 = jnp.zeros((B, KV, G, Sl, hd), jnp.float32)

    def fold(t, m, l, acc, k_cur, v_cur):
        src = (idx - t) % sp                 # chunk id currently held
        s = _chunk_scores(qg, k_cur, scale=scale)
        if causal:
            qi = idx * Sl + lax.broadcasted_iota(jnp.int32, (Sl, Sl), 0)
            kj = src * Sl + lax.broadcasted_iota(jnp.int32, (Sl, Sl), 1)
            mask = (kj <= qi)[None, None, None]
            s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        # exp(NEG_INF - NEG_INF) would be 1 for fully-masked rows; zero the
        # probabilities explicitly instead
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(v_cur.dtype), v_cur,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    def body(t, carry):
        m, l, acc, k_cur, v_cur = carry
        m, l, acc = fold(t, m, l, acc, k_cur, v_cur)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return m, l, acc, k_nxt, v_nxt

    # sp-1 rotated hops, then fold the final visiting chunk without paying
    # for a rotation whose result would be discarded
    m, l, acc, k_last, v_last = lax.fori_loop(
        0, sp - 1, body, (m0, l0, acc0, k, v))
    m, l, acc = fold(sp - 1, m, l, acc, k_last, v_last)
    l = jnp.where(l == 0.0, 1.0, l)
    # [B, KV, G, Sl, hd] -> [B, Sl, KV, G, hd] -> [B, Sl, H, hd]
    out = jnp.transpose(acc / l, (0, 3, 1, 2, 4)).reshape(B, Sl, H, hd)
    return out.astype(q.dtype)


def partial_attention_stats(q, k, v, valid, *, scale: float | None = None):
    """Partial attention of q against a local KV shard, returning
    unnormalised online-softmax stats for cross-shard merging.

    q: [B, S, H, hd]; k, v: [B, T, KV, hd]; valid: bool [B, 1, 1, S, T]
    (or broadcastable) marking which local slots may be attended.
    Returns (m, l, o): [B,KV,G,S,1], [B,KV,G,S,1], [B,KV,G,S,hd] f32.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    if k.dtype != q.dtype:
        # fp8 KV storage (--kv-dtype): upcast on read, fused into the dot
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    qg = q.reshape(B, S, KV, G, hd)
    s = _chunk_scores(qg, k, scale=scale)
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgst,btkd->bkgsd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def merge_attention_stats(stats_list):
    """Merge per-shard (m, l, o) stats (already psum'd or local list)."""
    ms = jnp.stack([m for m, _, _ in stats_list])
    m_g = jnp.max(ms, axis=0)
    l_g = 0.0
    o_g = 0.0
    for m, l, o in stats_list:
        scale = jnp.exp(m - m_g)
        l_g = l_g + scale * l
        o_g = o_g + scale * o
    l_g = jnp.where(l_g == 0.0, 1.0, l_g)
    return o_g / l_g


def sp_merged_attention(q, ctx_k, ctx_v, tail_k, tail_v, ctx_valid,
                        tail_valid, axis_name: str = "sp"):
    """Decode attention over (sharded context) + (replicated tail).

    Runs under shard_map. Computes local partial stats against this
    device's context shard, reduces (m, l, o) across `sp` with a
    numerically-stable logsumexp merge (pmax + two psums), folds in the
    replicated tail stats, and normalises.

    q: [B, S, H, hd] (replicated); ctx_k/v: [B, Tl, KV, hd] local shard;
    tail_k/v: [B, Ttail, KV, hd] replicated.
    Returns [B, S, H, hd] in q.dtype (replicated).
    """
    B, S, H, hd = q.shape

    m_c, l_c, o_c = partial_attention_stats(q, ctx_k, ctx_v, ctx_valid)
    # stable cross-device merge of the context shards
    m_g = lax.pmax(m_c, axis_name)
    scale = jnp.exp(m_c - m_g)
    l_cg = lax.psum(scale * l_c, axis_name)
    o_cg = lax.psum(scale * o_c, axis_name)

    m_t, l_t, o_t = partial_attention_stats(q, tail_k, tail_v, tail_valid)
    out = merge_attention_stats([(m_g, l_cg, o_cg), (m_t, l_t, o_t)])
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, S, H, hd).astype(
        q.dtype)


# -- shared per-layer bodies --------------------------------------------------
# Single source for the sp layer step, decode masks, and K-step decode
# scan: make_sp_forward (("sp",)/("sp","tp") meshes) and
# sp_pipeline.make_sp_stage_forward (("stage","sp"[,"tp"])) both build
# from these, so a fix to one path cannot silently miss the other.


def sp_prefill_layer(config: LlamaConfig, rope_c, rope_s, kv_dtype,
                     tp_axis):
    """lax.scan layer fn for ring-attention prefill: h, lp -> h, (k, v).
    Runs under shard_map with an "sp" axis in scope."""
    def layer(h, lp):
        def attn_fn(q, k, v):
            q = apply_rope(q, rope_c, rope_s)
            k = apply_rope(k, rope_c, rope_s)
            out = ring_attention(q, k, v, "sp", causal=True)
            # cast to the storage dtype HERE so the scan stacks the
            # cache directly at fp8 width — casting after the scan
            # would hold full-precision and fp8 copies concurrently,
            # raising peak HBM instead of halving it
            if kv_dtype is not None:
                k = k.astype(kv_dtype)
                v = v.astype(kv_dtype)
            return out, (k, v)
        return block_skeleton(lp, h, config, attn_fn, tp_axis=tp_axis)
    return layer


def sp_decode_layer(config: LlamaConfig, rope_c, rope_s, t_slot,
                    ctx_valid, tail_valid, tp_axis, tail_update=None):
    """lax.scan layer fn for merged-stats decode:
    h, (lp, ck, cv, tk, tv) -> h, (tk', tv').

    tail_update(tk, tv, k, v) -> (tk', tv') writes the step's KV into
    the tail cache; the default is the lockstep batch write at scalar
    slot `t_slot` (the --sp generator adapter). The continuous-batching
    engine passes a per-row active-masked writer instead — everything
    else (rope, merged-stats attention, block skeleton) is THIS single
    implementation for both."""
    if tail_update is None:
        def tail_update(tk, tv, k, v):
            tk2 = lax.dynamic_update_slice_in_dim(
                tk, k.astype(tk.dtype), t_slot, axis=1)
            tv2 = lax.dynamic_update_slice_in_dim(
                tv, v.astype(tv.dtype), t_slot, axis=1)
            return tk2, tv2

    def layer(h, xs):
        lp, ck, cv, tk, tv = xs

        def attn_fn(q, k, v):
            q = apply_rope(q, rope_c, rope_s)
            k = apply_rope(k, rope_c, rope_s)
            tk2, tv2 = tail_update(tk, tv, k, v)
            out = sp_merged_attention(q, ck, cv, tk2, tv2,
                                      ctx_valid, tail_valid, "sp")
            return out, (tk2, tv2)

        return block_skeleton(lp, h, config, attn_fn, tp_axis=tp_axis)
    return layer


def sp_decode_masks(idx, Sl: int, plen, tail_T: int, t_slot, B: int):
    """(ctx_valid, tail_valid) for one decode step: context slots below
    each row's prompt length (global slot ids from this device's sp
    index), tail slots up to and including the one being written.
    t_slot: scalar (lockstep batch — the --sp generator adapter) or [B]
    per-row (the continuous-batching sp engine's ragged decode)."""
    slot_g = idx * Sl + jnp.arange(Sl)
    ctx_valid = (slot_g[None] < plen[:, None])[:, None, None, None, :]
    t = jnp.asarray(t_slot)
    if t.ndim == 0:
        t = t[None]
    tail_valid = jnp.arange(tail_T)[None] <= t[:, None]
    tail_valid = jnp.broadcast_to(
        tail_valid, (B, tail_T))[:, None, None, None, :]
    return ctx_valid, tail_valid


def make_sp_prefill_body(config: LlamaConfig, kv_dtype, tp_axis,
                         Sl: int):
    """THE ring-prefill shard_map body — single source for
    make_sp_forward (the --sp generator adapter, [B, Sl] rows) and
    make_sp_engine_step_fns (the continuous-batching engine, [1, Sl]
    per-slot prefill), so a layer/mask fix to one cannot miss the
    other."""
    def prefill_body(blocks, embed, final_norm, lm_head, tokens, plen,
                     cos, sin):
        idx = lax.axis_index("sp")
        x = jnp.take(embed, tokens, axis=0)             # [B, Sl, D]
        rope_c = lax.dynamic_slice_in_dim(cos, idx * Sl, Sl, axis=0)
        rope_s = lax.dynamic_slice_in_dim(sin, idx * Sl, Sl, axis=0)
        layer = sp_prefill_layer(config, rope_c, rope_s, kv_dtype,
                                 tp_axis)
        x, (ks, vs) = lax.scan(layer, x, blocks)
        x = rms_norm(x, final_norm, config.rms_norm_eps)
        logits = sp_select_last(x, plen, idx, Sl, lm_head)
        return logits, ks, vs
    return prefill_body


def sp_select_last(x, plen, idx, Sl: int, lm_head):
    """Select the hidden state at plen-1 (it lives on ONE sp shard),
    psum it to every shard, and project: [B, Sl, D] -> logits [B, V]."""
    B = x.shape[0]
    last = (plen - 1).astype(jnp.int32)
    local = jnp.clip(last - idx * Sl, 0, Sl - 1)
    val = jnp.take_along_axis(x, local.reshape(B, 1, 1), axis=1)[:, 0]
    mine = (last >= idx * Sl) & (last < (idx + 1) * Sl)
    val = lax.psum(jnp.where(mine[:, None], val, 0.0), "sp")
    return qmatmul(val, lm_head).astype(jnp.float32)


def make_sp_decode_scan(decode_sm, ctx_len: int):
    """K decode+sample steps as ONE compiled program — the long-context
    analog of the engine's decode scan: host dispatch amortizes
    across num_steps tokens instead of paying a round-trip per token
    (the dominant cost of sp serving at small batch). Sampling (incl.
    the repeat-penalty ring) runs inside the scan with the same ops the
    host loop uses. Shared by the plain-sp and stage x sp factories."""
    @partial(jax.jit, static_argnames=("num_steps", "sampling"),
             donate_argnames=("cache",))
    def sp_decode_scan(params, token, pos0, plen, cache: SPCache,
                       rope: RopeTables, rng, ring, num_steps: int,
                       sampling):
        from cake_tpu.ops.sampling import sample_tokens, update_ring

        def body(carry, step):
            tok, pos, tk, tv, ring, rng = carry
            logits, tk, tv = decode_sm(
                params["blocks"], params["embed"], params["final_norm"],
                params["lm_head"], tok, pos, plen,
                cache.ctx_k, cache.ctx_v, tk, tv, rope.cos, rope.sin)
            rng, sub = jax.random.split(rng)
            nxt = sample_tokens(sub, logits, ring, sampling)
            ring = update_ring(ring, nxt, step)
            return (nxt[:, None], pos + 1, tk, tv, ring, rng), nxt

        # ring steps continue from the input token's step index (the
        # pos0 operand encodes it: k0 = pos0 - ctx_len), so a mid-session
        # continuation writes the same penalty-ring slots the host loop
        # would
        k0 = pos0 - ctx_len
        (tok, pos, tk, tv, ring, rng), toks = lax.scan(
            body,
            (token, pos0, cache.tail_k, cache.tail_v, ring, rng),
            k0 + jnp.arange(1, num_steps + 1))
        return (jnp.transpose(toks, (1, 0)),
                SPCache(cache.ctx_k, cache.ctx_v, tk, tv), ring, rng)

    return sp_decode_scan


# -- whole-model sequence-parallel forward -----------------------------------


class SPCache(NamedTuple):
    """Long-context KV cache: prefilled context sharded over sp, decode tail
    replicated. ctx_*: [L, B, S_ctx, KV, hd] (seq axis sharded over "sp");
    tail_*: [L, B, T_tail, KV, hd] (replicated)."""
    ctx_k: jnp.ndarray
    ctx_v: jnp.ndarray
    tail_k: jnp.ndarray
    tail_v: jnp.ndarray

    def fresh(self) -> "SPCache":
        """Zeroed cache with identical spec/sharding (the generator's
        session-reset contract, models/llama/cache.KVCache.fresh)."""
        return SPCache(*(jnp.zeros_like(x) for x in self))



def sp_block_specs(config: LlamaConfig, tp: bool, params=None):
    """THE block-param specs for the sp mesh — single source for both
    make_sp_forward's shard_map in_specs and place_sp_params' placement,
    so the two cannot drift. With tp and quantized params, QTensor
    leaves expand to (q, scale) spec pairs; tp + quant REQUIRES the
    params example tree (without it the specs stay unexpanded and
    shard_map fails with a structural mismatch)."""
    from cake_tpu.models.llama.params import block_param_keys, block_specs
    if not tp:
        return {kk: P() for kk in block_param_keys(config)}
    specs = block_specs(block_param_keys(config), stage_axis=None,
                        tp_axis="tp")
    if params is not None:
        from cake_tpu.ops.quant import expand_specs_for_quant
        specs = {k: specs[k] for k in params["blocks"]}
        specs = expand_specs_for_quant(params["blocks"], specs)
    return specs


def make_sp_forward(mesh: Mesh, config: LlamaConfig, ctx_len: int,
                    tail_len: int, kv_dtype=None, tp: bool = False,
                    params=None, dp: bool = False):
    """Build (sp_prefill, sp_decode) jitted over the mesh's "sp" axis.

    tp: the mesh also carries a "tp" axis — attention/ffn heads shard
    Megatron-style within each sequence shard (block_skeleton's tp
    psums), so ring attention rotates KV chunks of LOCAL heads: sp x tp
    composes sequence and tensor parallelism on one mesh. dp: the mesh
    also carries a "dp" axis — the BATCH shards over it and each dp
    group runs its own sp ring (no cross-group collectives: the ring
    ppermutes and the last-token psum name only "sp", so shard_map
    scopes them per group). Long-context batched serving: dp x sp(x tp)
    on one mesh. (stage x sp lives in parallel/sp_pipeline; stage x dp
    remains excluded.)

    kv_dtype: storage dtype for the SPCache (fp8 halves the sharded
    long-context cache — the dominant allocation of this mode); values
    upcast into attention on read. None = compute dtype.

    sp_prefill(params, tokens [B, ctx_len], plen [B], rope)
        -> (logits [B, V] f32, SPCache)   # tokens right-padded to ctx_len;
                                          # allocates the cache itself
    sp_decode(params, token [B, 1], pos scalar, plen [B], cache, rope)
        -> (logits, SPCache)              # pos in [ctx_len, ctx_len+tail);
                                          # cache is donated
    """
    sp_size = mesh.shape["sp"]
    assert ctx_len % sp_size == 0, (ctx_len, sp_size)
    Sl = ctx_len // sp_size
    tp_axis = "tp" if tp else None

    prefill_body = make_sp_prefill_body(config, kv_dtype, tp_axis, Sl)

    def decode_body(blocks, embed, final_norm, lm_head, token, pos, plen,
                    ctx_k, ctx_v, tail_k, tail_v, cos, sin):
        idx = lax.axis_index("sp")
        B = token.shape[0]
        x = jnp.take(embed, token, axis=0)                  # [B, 1, D]
        rope_c = lax.dynamic_slice_in_dim(cos, pos, 1, axis=0)
        rope_s = lax.dynamic_slice_in_dim(sin, pos, 1, axis=0)
        t_slot = pos - ctx_len                               # tail write slot
        ctx_valid, tail_valid = sp_decode_masks(
            idx, Sl, plen, tail_k.shape[2], t_slot, B)
        layer = sp_decode_layer(config, rope_c, rope_s, t_slot,
                                ctx_valid, tail_valid, tp_axis)
        x, (tk_new, tv_new) = lax.scan(
            layer, x, (blocks, ctx_k, ctx_v, tail_k, tail_v))
        x = rms_norm(x, final_norm, config.rms_norm_eps)
        logits = qmatmul(x[:, -1], lm_head).astype(jnp.float32)
        return logits, tk_new, tv_new

    dp_axis = "dp" if dp else None
    ctx_spec = P(None, dp_axis, "sp", tp_axis, None)
    tail_spec = (P(None, dp_axis, None, tp_axis, None) if (tp or dp)
                 else P())
    batch = P(dp_axis)                       # plen / logits rows
    rep = P()
    blocks_spec = sp_block_specs(config, tp, params)

    prefill_sm = jax.shard_map(
        prefill_body, mesh=mesh,
        in_specs=(blocks_spec, rep, rep, rep, P(dp_axis, "sp"), batch,
                  rep, rep),
        out_specs=(batch, ctx_spec, ctx_spec),
        check_vma=False,
    )
    decode_sm = jax.shard_map(
        decode_body, mesh=mesh,
        in_specs=(blocks_spec, rep, rep, rep, P(dp_axis, None), rep,
                  batch, ctx_spec, ctx_spec, tail_spec, tail_spec, rep,
                  rep),
        out_specs=(batch, tail_spec, tail_spec),
        check_vma=False,
    )

    @jax.jit
    def sp_prefill(params, tokens, plen, rope: RopeTables):
        logits, ks, vs = prefill_sm(
            params["blocks"], params["embed"], params["final_norm"],
            params["lm_head"], tokens, plen, rope.cos, rope.sin)
        B = tokens.shape[0]
        KV, hd = config.num_key_value_heads, config.head_dim
        store = ks.dtype  # prefill_body already stacks at the storage dtype
        # two separate allocations: aliased tail_k/tail_v would make the
        # first donated sp_decode try to donate one buffer twice (JAX
        # falls back to a copy, defeating the donation)
        shape = (config.num_hidden_layers, B, tail_len, KV, hd)
        tspec = NamedSharding(mesh, tail_spec)
        tail_k = lax.with_sharding_constraint(jnp.zeros(shape, store),
                                              tspec)
        tail_v = lax.with_sharding_constraint(jnp.zeros(shape, store),
                                              tspec)
        return logits, SPCache(ks, vs, tail_k, tail_v)

    @partial(jax.jit, donate_argnames=("cache",))
    def sp_decode(params, token, pos, plen, cache: SPCache,
                  rope: RopeTables):
        logits, tk, tv = decode_sm(
            params["blocks"], params["embed"], params["final_norm"],
            params["lm_head"], token, pos, plen,
            cache.ctx_k, cache.ctx_v, cache.tail_k, cache.tail_v,
            rope.cos, rope.sin)
        return logits, SPCache(cache.ctx_k, cache.ctx_v, tk, tv)

    sp_prefill.decode_scan = make_sp_decode_scan(decode_sm, ctx_len)
    return sp_prefill, sp_decode


def place_sp_params(mesh: Mesh, config: LlamaConfig, params,
                    tp: bool = False):
    """device_put the block params with the specs make_sp_forward's
    shard_map expects (tp head sharding when tp; replicated otherwise) —
    the single placement rule for every sp caller, so call sites cannot
    drift from the in_specs."""
    if not tp:
        return params
    from cake_tpu.ops.quant import QTensor
    bspecs = sp_block_specs(config, tp, params)

    def put(leaf, spec):
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    out = dict(params)
    out["blocks"] = {
        k: (QTensor(q=put(v.q, bspecs[k].q), scale=put(v.scale,
                                                       bspecs[k].scale))
            if isinstance(v, QTensor) else put(v, bspecs[k]))
        for k, v in params["blocks"].items()}
    return out


class SPSessionCache(NamedTuple):
    """SPCache + the session's prompt lengths: carrying plen IN the cache
    keeps the adapter stateless, so a scratch-cache generation
    (generate_on_device) cannot clobber a live interactive session's
    decode positions."""
    sp: SPCache
    plen: jnp.ndarray

    def fresh(self) -> "SPSessionCache":
        return SPSessionCache(self.sp.fresh(), jnp.zeros_like(self.plen))


class SPGeneratorForward:
    """forward_fn adapter: (sp_prefill, sp_decode) under the generator's
    pluggable-forward contract, making `--sp N` a serving mode instead of
    a library-only capability (cli --sp N --max-seq-len ...).

    Window layout: the prompt is right-padded into the sp-sharded context
    window [0, ctx_len); generated tokens live in the replicated tail at
    window positions ctx_len+k. With a full prompt (len == ctx_len — the
    long-context case this mode exists for) positions coincide with the
    dense path exactly; shorter prompts carry a positional gap between
    prompt and generation (documented SP-mode semantics, masked
    correctly either way).
    """

    def __init__(self, mesh: Mesh, config: LlamaConfig, ctx_len: int,
                 tail_len: int, kv_dtype=None, tp: bool = False,
                 params=None, stages: int = 1, dp: bool = False):
        if ctx_len % mesh.shape["sp"] != 0:
            raise ValueError(
                f"sp context window {ctx_len} must divide over sp="
                f"{mesh.shape['sp']}")
        if dp and stages > 1:
            raise ValueError("sp x dp does not compose with stages")
        self.ctx_len = ctx_len
        self.tail_len = tail_len
        # bounds the generator enforces: inclusive prompt length at encode
        # time, and the number of decode steps the replicated tail holds
        # (past it, dynamic_update_slice would clamp over live entries)
        self.max_prompt_len = ctx_len
        self.max_decode_tokens = tail_len
        # the prefill allocates its own SPCache and ignores the passed-in
        # cache (generator skips its fresh() copy accordingly)
        self.allocates_cache = True
        # kept for engine_pieces (master.make_engine builds the sp
        # continuous-batching engine from the same mesh/window layout)
        self._mesh = mesh
        self._config = config
        self._kv_dtype = kv_dtype
        self._tp = tp
        self._stages = stages
        self._dp = dp
        if stages > 1:
            # sp x pipeline-stage composition: layers sharded over "stage",
            # sequence over "sp" (parallel/sp_pipeline) — same call
            # contract, so everything below is factory-agnostic
            from cake_tpu.parallel.sp_pipeline import make_sp_stage_forward
            self._prefill, self._decode = make_sp_stage_forward(
                mesh, config, ctx_len, tail_len, kv_dtype=kv_dtype,
                tp=tp, params=params)
        else:
            self._prefill, self._decode = make_sp_forward(
                mesh, config, ctx_len, tail_len, kv_dtype=kv_dtype,
                tp=tp, params=params, dp=dp)

    def __call__(self, params, tokens, cache, pos, rope,
                 last_idx=None, is_prefill: bool = False):
        if is_prefill:
            B, S = tokens.shape
            if S >= self.ctx_len:
                # bucket padding may exceed the window; real tokens cannot
                # (max_prompt_len) — trim pad, keep the window
                toks = tokens[:, : self.ctx_len]
            else:
                toks = jnp.pad(tokens, ((0, 0), (0, self.ctx_len - S)))
            plen = ((last_idx + 1).astype(jnp.int32)
                    if last_idx is not None
                    else jnp.full((B,), S, jnp.int32))
            logits, spc = self._prefill(params, toks, plen, rope)
            return logits, SPSessionCache(spc, plen)
        # generator positions count from the prompt end; SP decode slots
        # count from the context window end
        k = pos - jnp.max(cache.plen)
        logits, spc = self._decode(params, tokens,
                                   jnp.int32(self.ctx_len) + k, cache.plen,
                                   cache.sp, rope)
        return logits, SPSessionCache(spc, cache.plen)

    def engine_pieces(self, slots: int, params):
        """(step_fns, cache, ctx_len, tail_len) for the continuous-
        batching engine over this adapter's mesh. stage x sp routes to
        sp_pipeline's stage-chained factory (the long-context 70B pod
        config, served batched); dp x sp shards the slot axis over dp
        (requires max_slots divisible by dp)."""
        dtype = (self._kv_dtype if self._kv_dtype is not None
                 else params["embed"].dtype)
        if self._dp and slots % self._mesh.shape["dp"] != 0:
            raise ValueError(
                f"--max-slots {slots} must be divisible by --dp "
                f"{self._mesh.shape['dp']} (the sp engine shards "
                f"slots over dp)")
        if self._stages > 1:
            from cake_tpu.parallel.sp_pipeline import (
                create_sp_stage_engine_cache,
                make_sp_stage_engine_step_fns,
            )
            fns = make_sp_stage_engine_step_fns(
                self._mesh, self._config, self.ctx_len, self.tail_len,
                kv_dtype=self._kv_dtype, tp=self._tp, params=params)
            cache = create_sp_stage_engine_cache(
                self._mesh, self._config, slots, self.ctx_len,
                self.tail_len, kv_dtype=dtype, tp=self._tp)
            return fns, cache, self.ctx_len, self.tail_len
        fns = make_sp_engine_step_fns(
            self._mesh, self._config, self.ctx_len, self.tail_len,
            kv_dtype=self._kv_dtype, tp=self._tp, params=params,
            dp=bool(self._dp))
        cache = create_sp_engine_cache(
            self._mesh, self._config, slots, self.ctx_len,
            self.tail_len, kv_dtype=dtype, tp=self._tp,
            dp=bool(self._dp))
        return fns, cache, self.ctx_len, self.tail_len

    def decode_scan(self, params, token, k0: int, cache, rope, rng, ring,
                    num_steps: int, sampling):
        """num_steps on-device decode+sample steps (see sp_decode_scan).
        k0: decode step index of `token` (0 = the prefill's first sampled
        token). Returns (tokens [B, num_steps], cache, ring, rng)."""
        toks, spc, ring, rng = self._prefill.decode_scan(
            params, token, jnp.int32(self.ctx_len + k0), cache.plen,
            cache.sp, rope, rng, ring, num_steps=num_steps,
            sampling=sampling)
        return toks, SPSessionCache(spc, cache.plen), ring, rng


# -- continuous-batching engine over the sp mesh ------------------------------


class SPEngineCache(NamedTuple):
    """SPCache plus the per-slot prompt lengths, so the engine's generic
    step-fn contract (which passes only pos/active) still reaches the
    per-row window layout: ctx region [0, plen[b]) holds slot b's ring-
    prefilled prompt, tail slot t holds its (plen[b]+t)-positioned
    generated token. plen rides the cache pytree through donated decode
    dispatches and chained scans unchanged."""
    ctx_k: jnp.ndarray          # [L, B, S_ctx, KV, hd] seq-sharded "sp"
    ctx_v: jnp.ndarray
    tail_k: jnp.ndarray         # [L, B, T_tail, KV, hd] replicated
    tail_v: jnp.ndarray
    plen: jnp.ndarray           # [B] int32

    def fresh(self) -> "SPEngineCache":
        return SPEngineCache(*(jnp.zeros_like(x) for x in self))


def create_sp_engine_cache(mesh: Mesh, config: LlamaConfig, slots: int,
                           ctx_len: int, tail_len: int,
                           kv_dtype=jnp.bfloat16,
                           tp: bool = False,
                           stage: bool = False,
                           dp: bool = False) -> SPEngineCache:
    """Allocate the engine's multi-slot sp cache with the shardings
    make_sp_engine_step_fns' shard_maps expect (stage=True: the layer
    dim additionally shards over "stage" for the stage x sp engine;
    dp=True: the SLOT dim shards over "dp" — requires slots % dp == 0).
    jit-with-out_shardings (not device_put): each shard allocates in
    place — no full-buffer transient, and it works over a multi-process
    mesh, where device_put to non-addressable devices is invalid
    (create_sharded_cache precedent)."""
    KV, hd = config.num_key_value_heads, config.head_dim
    L = config.num_hidden_layers
    tp_axis = "tp" if tp else None
    stage_axis = "stage" if stage else None
    dp_axis = "dp" if dp else None
    if dp:
        assert slots % mesh.shape["dp"] == 0, (slots, mesh.shape["dp"])
    tail = (P(stage_axis, dp_axis, None, tp_axis, None)
            if (tp or stage or dp) else P())
    shardings = SPEngineCache(
        ctx_k=NamedSharding(mesh, P(stage_axis, dp_axis, "sp", tp_axis,
                                    None)),
        ctx_v=NamedSharding(mesh, P(stage_axis, dp_axis, "sp", tp_axis,
                                    None)),
        tail_k=NamedSharding(mesh, tail),
        tail_v=NamedSharding(mesh, tail),
        plen=NamedSharding(mesh, P(dp_axis)),
    )
    make = jax.jit(
        lambda: SPEngineCache(
            ctx_k=jnp.zeros((L, slots, ctx_len, KV, hd), kv_dtype),
            ctx_v=jnp.zeros((L, slots, ctx_len, KV, hd), kv_dtype),
            tail_k=jnp.zeros((L, slots, tail_len, KV, hd), kv_dtype),
            tail_v=jnp.zeros((L, slots, tail_len, KV, hd), kv_dtype),
            plen=jnp.zeros((slots,), jnp.int32),
        ),
        out_shardings=shardings,
    )
    return make()


def make_sp_engine_step_fns(mesh: Mesh, config: LlamaConfig,
                            ctx_len: int, tail_len: int,
                            kv_dtype=None, tp: bool = False,
                            params=None, dp: bool = False):
    """Engine step-fn contract over the sp(x tp) mesh: long-context
    CONTINUOUS-BATCHING serving — every slot's prompt ring-prefills over
    the sequence shards and concurrent requests decode together with
    merged-stats attention, instead of the single-tenant locked path the
    --sp adapter served through before.

    Returns (prefill_slot_fn, decode_ragged_fn, decode_scan_fn): the
    same signatures as model.prefill_slot / decode_step_ragged /
    step_programs.make_decode_scan's product, over an SPEngineCache.

    Unlike the batch-1 SPGeneratorForward (whose tail positions start at
    ctx_len, leaving a documented rope gap for short prompts), the
    engine layout is position-contiguous: row b's generated token t sits
    at rope position plen[b]+t and tail slot t, so outputs match the
    dense engine exactly for any prompt length. Composition: sp alone,
    sp x tp, dp x sp(x tp) — dp shards the SLOT axis, each dp group
    running its own sp ring (the body's collectives name only "sp"/
    "tp", so shard_map scopes them per group; decode throughput scales
    with dp at long context) — or, via sp_pipeline
    .make_sp_stage_engine_step_fns sharing this layout, stage x sp."""
    sp_size = mesh.shape["sp"]
    assert ctx_len % sp_size == 0, (ctx_len, sp_size)
    Sl = ctx_len // sp_size
    tp_axis = "tp" if tp else None
    mode = "_".join((["dp"] if dp else []) + ["sp"]
                    + (["tp"] if tp else []))
    blocks_spec = sp_block_specs(config, tp, params)
    rep = P()

    # -- ragged decode over [B] per-row positions -------------------------
    def chain(x, layer, blocks, ctx_k, ctx_v, tail_k, tail_v):
        return lax.scan(layer, x, (blocks, ctx_k, ctx_v, tail_k,
                                   tail_v))

    decode_body = make_sp_engine_decode_body(config, tp_axis, Sl, chain)

    dp_axis = "dp" if dp else None
    batch = P(dp_axis)                  # slot-axis sharding over dp
    ctx_spec = P(None, dp_axis, "sp", tp_axis, None)
    tail_spec = (P(None, dp_axis, None, tp_axis, None)
                 if (tp or dp) else P())
    decode_sm = jax.shard_map(
        decode_body, mesh=mesh,
        in_specs=(blocks_spec, rep, rep, rep, batch, batch, batch,
                  ctx_spec, ctx_spec, tail_spec, tail_spec, batch, rep,
                  rep),
        out_specs=(batch, tail_spec, tail_spec),
        check_vma=False,
    )

    decode_ragged_forward, decode_ragged_fn = make_decode_ragged_fns(
        decode_sm, mode=mode)

    # -- slot prefill: ring-prefill one prompt, scatter into the slot -----
    prefill_body = make_sp_prefill_body(config, kv_dtype, tp_axis, Sl)

    # prefill output is a SINGLE slot ([L, 1, Sl, ...]) — its specs
    # never carry the dp axis (a size-1 dim cannot shard over dp); the
    # scatter into the dp-sharded cache happens in the jitted slot
    # wrapper, where XLA reshards the one-slot update onto its owner
    pf_ctx_spec = P(None, None, "sp", tp_axis, None)
    prefill_sm = jax.shard_map(
        prefill_body, mesh=mesh,
        in_specs=(blocks_spec, rep, rep, rep, P(None, "sp"), rep,
                  rep, rep),
        out_specs=(rep, pf_ctx_spec, pf_ctx_spec),
        check_vma=False,
    )
    prefill_slot_fn = make_slot_prefill_fn(prefill_sm, ctx_len,
                                           mode=mode)

    from cake_tpu.models.step_programs import make_decode_scan
    return instrument_sp_engine(
        (prefill_slot_fn, decode_ragged_fn,
         make_decode_scan(decode_ragged_forward)),
        mode, ctx_len, tail_len)


def make_slot_prefill_fn(prefill_sm, ctx_len: int, mode: str = "sp"):
    """The engine's slot-prefill wrapper, shared by the plain-sp and
    stage x sp factories (only their prefill shard_maps differ):
    [1, bucket] prompt -> trim/pad to [1, ctx_len] -> ring prefill ->
    scatter the slot's ctx shards + plen. Bucket padding beyond ctx_len
    is trimmed (real tokens are capped at ctx_len by the engine's
    prompt_limit); shorter buckets zero-pad up to the window."""

    @partial(jax.jit, static_argnames=("config_",),
             donate_argnames=("cache",))
    def prefill_slot_fn(params, tokens, prompt_len, slot,
                        cache: SPEngineCache, rope: RopeTables,
                        config_: LlamaConfig):
        S = tokens.shape[1]
        if S >= ctx_len:
            toks = tokens[:, :ctx_len]
        else:
            toks = jnp.pad(tokens, ((0, 0), (0, ctx_len - S)))
        logits, ks, vs = prefill_sm(
            params["blocks"], params["embed"], params["final_norm"],
            params["lm_head"], toks, prompt_len.astype(jnp.int32),
            rope.cos, rope.sin)
        ctx_k = lax.dynamic_update_slice_in_dim(
            cache.ctx_k, ks.astype(cache.ctx_k.dtype), slot, axis=1)
        ctx_v = lax.dynamic_update_slice_in_dim(
            cache.ctx_v, vs.astype(cache.ctx_v.dtype), slot, axis=1)
        plen = cache.plen.at[slot].set(prompt_len[0].astype(jnp.int32))
        return logits, SPEngineCache(ctx_k, ctx_v, cache.tail_k,
                                     cache.tail_v, plen)

    # instrumentation (dispatch counter + wall histogram) is applied by
    # instrument_sp_engine over the whole step-fn tuple — wrapping here
    # too would double-count every prefill dispatch
    return prefill_slot_fn


def make_sp_engine_decode_body(config: LlamaConfig, tp_axis, Sl: int,
                               chain):
    """THE ragged engine decode shard_map body — single source for the
    plain-sp and stage x sp engine factories, which differ only in how
    the blocks run: chain(x, layer, blocks, ctx_k, ctx_v, tail_k,
    tail_v) -> (x', (tail_k', tail_v')) is lax.scan for plain sp and
    sp_pipeline._stage_chain for the stage pipeline."""
    from cake_tpu.models.llama.cache import update_layer_cache_per_row
    from cake_tpu.ops.rope import rope_rows_per_row

    def decode_body(blocks, embed, final_norm, lm_head, token, pos,
                    active, ctx_k, ctx_v, tail_k, tail_v, plen, cos,
                    sin):
        idx = lax.axis_index("sp")
        B = token.shape[0]
        tail_T = tail_k.shape[2]
        x = jnp.take(embed, token, axis=0)               # [B, 1, D]
        rope_c, rope_s = rope_rows_per_row(cos, sin, pos)
        # contiguous positions: tail slot = generated index = pos - plen
        t_slot = jnp.clip(pos - plen, 0, tail_T - 1)     # [B]
        ctx_valid, tail_valid = sp_decode_masks(idx, Sl, plen, tail_T,
                                                t_slot, B)

        def tail_update(tk, tv, k, v):
            # per-row active-masked write (ragged slots), vs the
            # lockstep scalar-slot default; the tail is this chain's
            # scanned layer, a stack of one to the dense writer
            tk, tv = update_layer_cache_per_row(tk[None], tv[None], 0,
                                                k, v, t_slot, active)
            return tk[0], tv[0]

        layer = sp_decode_layer(config, rope_c, rope_s, None, ctx_valid,
                                tail_valid, tp_axis,
                                tail_update=tail_update)
        x, (tk_new, tv_new) = chain(x, layer, blocks, ctx_k, ctx_v,
                                    tail_k, tail_v)
        x = rms_norm(x, final_norm, config.rms_norm_eps)
        logits = qmatmul(x[:, -1], lm_head).astype(jnp.float32)
        return logits, tk_new, tv_new

    return decode_body


def make_decode_ragged_fns(decode_sm, mode: str = "sp"):
    """(decode_ragged_forward, jitted decode_ragged_fn) over a ragged
    sp decode shard_map — shared by the plain-sp and stage x sp engine
    factories. Only the jitted dispatch wrapper gets dispatch-counted
    (by instrument_sp_engine, over the whole step-fn tuple);
    decode_ragged_forward also gets traced INSIDE decode scans, where a
    host-side counter would be meaningless (and silently ignored)."""

    def decode_ragged_forward(params, tokens, cache: SPEngineCache, pos,
                              active, rope: RopeTables,
                              config_: LlamaConfig):
        logits, tk, tv = decode_sm(
            params["blocks"], params["embed"], params["final_norm"],
            params["lm_head"], tokens, pos.astype(jnp.int32),
            active, cache.ctx_k, cache.ctx_v, cache.tail_k,
            cache.tail_v, cache.plen, rope.cos, rope.sin)
        return logits, SPEngineCache(cache.ctx_k, cache.ctx_v, tk, tv,
                                     cache.plen)

    @partial(jax.jit, static_argnames=("config_",),
             donate_argnames=("cache",))
    def decode_ragged_fn(params, tokens, pos, active,
                         cache: SPEngineCache, rope: RopeTables,
                         config_: LlamaConfig):
        return decode_ragged_forward(params, tokens, cache, pos, active,
                                     rope, config_)

    return decode_ragged_forward, decode_ragged_fn
