"""Sequence parallelism composed with pipeline stages: ("stage","sp"[,"tp"]).

The round-4 gap this closes: long-context serving (`--sp`, ring attention)
and model-capacity sharding (`--topology` stages) were mutually exclusive,
yet the one deployment that needs both — a 70B-class model over a pod at
long context — is exactly their intersection (the reference's distribution
seam being replaced: cake-core/src/cake/topology.rs:50-76 feeding
llama.rs:203-220, which shards *layers* but caps context at 4096).

Design: the stacked block params are layer-sharded over the "stage" mesh
axis (same placement rule as parallel/pipeline.py); within every stage the
context sequence is sharded over "sp", so each stage's sp group runs ring
attention (prefill) / merged-stats decode (parallel/context_parallel.py)
over its own block range. Hidden states hop stage-to-stage with
`lax.ppermute` over ICI. The chain is depth-1 — one request in flight,
matching the reference's sequential layer-range walk — because this mode
exists for capacity + context, not batch throughput (the batching engine's
GPipe path covers that). With "tp" in the mesh, heads additionally shard
Megatron-style inside each (stage, sp) cell; ring hops then move KV chunks
of LOCAL heads only, so the per-hop ICI payload shrinks by 1/tp.

Under SPMD every stage executes every tick (masked where not live —
`jnp.where` keeps cache/output writes of the live stage only); on hardware
the off-tick compute overlaps with nothing and costs no wall-clock vs
stages idling, and XLA still fuses each stage's whole block range into one
computation (the contiguous-op-batching invariant, SURVEY §2.6).

The cache layout is context_parallel.SPCache with one more sharded axis:
ctx_*: [L, B, S_ctx, KV, hd] — L over "stage", S_ctx over "sp"
tail_*: [L, B, T_tail, KV, hd] — L over "stage", tail replicated over sp
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import qmatmul
from cake_tpu.parallel.context_parallel import (
    SPCache, make_sp_decode_scan, sp_decode_layer, sp_decode_masks,
    sp_prefill_layer, sp_select_last,
)


def _stage_chain(h, run_my_blocks, init_state):
    """Depth-1 pipeline over the "stage" axis (runs under shard_map).

    Every tick all stages run `run_my_blocks(h) -> (y, state)` on their
    current buffer; only the live stage (sid == t) keeps its state writes
    and forwards its output over ICI. After nstages ticks the final
    stage's output has visited every block range; it is broadcast back
    with a psum so each device can run the (replicated) lm_head.

    Returns (h_final [replicated over stage], state).
    """
    nstages = lax.axis_size("stage")
    sid = lax.axis_index("stage")
    perm = [(i, (i + 1) % nstages) for i in range(nstages)]

    def tick(t, carry):
        h, out, state = carry
        y, new_state = run_my_blocks(h)
        live = sid == t
        state = jax.tree.map(
            lambda new, old: jnp.where(live, new.astype(old.dtype), old),
            new_state, state)
        # capture the final stage's result on its tick
        out = jnp.where(jnp.logical_and(live, sid == nstages - 1), y, out)
        h = lax.ppermute(jnp.where(live, y, h), "stage", perm)
        return h, out, state

    out0 = jnp.zeros_like(h)
    _, out, state = lax.fori_loop(0, nstages, tick, (h, out0, init_state))
    # broadcast the last stage's hidden state to every stage (tiny vs KV)
    out = lax.psum(jnp.where(sid == nstages - 1, out,
                             jnp.zeros_like(out)), "stage")
    return out, state


def make_sp_stage_prefill_body(config: LlamaConfig, kv_store, tp_axis,
                               Sl: int, nstages: int, tp_size: int):
    """THE stage-chained ring-prefill shard_map body — single source for
    make_sp_stage_forward (the generator adapter) and
    make_sp_stage_engine_step_fns (the batching engine), mirroring
    context_parallel.make_sp_prefill_body's role for the plain-sp
    factories."""
    def prefill_body(blocks, embed, final_norm, lm_head, tokens, plen,
                     cos, sin):
        isp = lax.axis_index("sp")
        B = tokens.shape[0]
        KV_local = config.num_key_value_heads // tp_size
        Ll = config.num_hidden_layers // nstages
        x = jnp.take(embed, tokens, axis=0)                 # [B, Sl, D]
        rope_c = lax.dynamic_slice_in_dim(cos, isp * Sl, Sl, axis=0)
        rope_s = lax.dynamic_slice_in_dim(sin, isp * Sl, Sl, axis=0)
        layer = sp_prefill_layer(config, rope_c, rope_s, kv_store,
                                 tp_axis)

        def run_my_blocks(h):
            return lax.scan(layer, h, blocks)

        store = kv_store or x.dtype
        ks0 = jnp.zeros((Ll, B, Sl, KV_local, config.head_dim), store)
        x, (ks, vs) = _stage_chain(x, run_my_blocks, (ks0, ks0))
        x = rms_norm(x, final_norm, config.rms_norm_eps)
        logits = sp_select_last(x, plen, isp, Sl, lm_head)
        return logits, ks, vs
    return prefill_body


def make_sp_stage_forward(mesh: Mesh, config: LlamaConfig, ctx_len: int,
                          tail_len: int, kv_dtype=None, tp: bool = False,
                          params=None):
    """Build (sp_prefill, sp_decode) jitted over a ("stage","sp"[,"tp"])
    mesh — the same call contract as context_parallel.make_sp_forward, so
    SPGeneratorForward drives either factory unchanged.

    sp_prefill(params, tokens [B, ctx_len], plen [B], rope)
        -> (logits [B, V] f32, SPCache)
    sp_decode(params, token [B, 1], pos, plen, cache, rope)
        -> (logits, SPCache)    # cache donated
    """
    nstages = mesh.shape["stage"]
    sp_size = mesh.shape["sp"]
    assert ctx_len % sp_size == 0, (ctx_len, sp_size)
    assert config.num_hidden_layers % nstages == 0, (
        config.num_hidden_layers, nstages)
    Sl = ctx_len // sp_size
    tp_axis = "tp" if tp else None
    kv_store = kv_dtype

    prefill_body = make_sp_stage_prefill_body(
        config, kv_store, tp_axis, Sl, nstages,
        mesh.shape["tp"] if tp else 1)

    def decode_body(blocks, embed, final_norm, lm_head, token, pos, plen,
                    ctx_k, ctx_v, tail_k, tail_v, cos, sin):
        isp = lax.axis_index("sp")
        B = token.shape[0]
        x = jnp.take(embed, token, axis=0)                  # [B, 1, D]
        rope_c = lax.dynamic_slice_in_dim(cos, pos, 1, axis=0)
        rope_s = lax.dynamic_slice_in_dim(sin, pos, 1, axis=0)
        t_slot = pos - ctx_len
        ctx_valid, tail_valid = sp_decode_masks(
            isp, Sl, plen, tail_k.shape[2], t_slot, B)
        layer = sp_decode_layer(config, rope_c, rope_s, t_slot,
                                ctx_valid, tail_valid, tp_axis)

        def run_my_blocks(h):
            return lax.scan(layer, h, (blocks, ctx_k, ctx_v,
                                       tail_k, tail_v))

        x, (tk_new, tv_new) = _stage_chain(
            x, run_my_blocks, (tail_k, tail_v))
        x = rms_norm(x, final_norm, config.rms_norm_eps)
        logits = qmatmul(x[:, -1], lm_head).astype(jnp.float32)
        return logits, tk_new, tv_new

    # specs: blocks layer-sharded over stage (+ heads over tp) — the SAME
    # rule as the GPipe pipeline, via its quant-aware helper
    from cake_tpu.parallel.pipeline import _blocks_in_specs
    blocks_spec = _blocks_in_specs(config, tp_axis, params)
    ctx_spec = P("stage", None, "sp", tp_axis, None)
    tail_spec = P("stage", None, None, tp_axis, None)
    rep = P()

    prefill_sm = jax.shard_map(
        prefill_body, mesh=mesh,
        in_specs=(blocks_spec, rep, rep, rep, P(None, "sp"), rep, rep, rep),
        out_specs=(rep, ctx_spec, ctx_spec),
        check_vma=False,
    )
    decode_sm = jax.shard_map(
        decode_body, mesh=mesh,
        in_specs=(blocks_spec, rep, rep, rep, rep, rep, rep,
                  ctx_spec, ctx_spec, tail_spec, tail_spec, rep, rep),
        out_specs=(rep, tail_spec, tail_spec),
        check_vma=False,
    )

    @jax.jit
    def sp_prefill(params, tokens, plen, rope: RopeTables):
        logits, ks, vs = prefill_sm(
            params["blocks"], params["embed"], params["final_norm"],
            params["lm_head"], tokens, plen, rope.cos, rope.sin)
        B = tokens.shape[0]
        KV, hd = config.num_key_value_heads, config.head_dim
        store = ks.dtype
        shape = (config.num_hidden_layers, B, tail_len, KV, hd)
        tspec = NamedSharding(mesh, tail_spec)
        # two allocations: aliasing would break tail donation (see
        # context_parallel.make_sp_forward)
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


def place_sp_stage_params(mesh: Mesh, config: LlamaConfig, params,
                          tp: bool = False):
    """device_put a param tree with the specs make_sp_stage_forward's
    shard_map expects: blocks layer-over-"stage" (+ tp heads),
    embed/lm_head/final_norm replicated — pipeline_param_specs IS that
    rule, reused so the two paths cannot drift."""
    from cake_tpu.parallel.pipeline import pipeline_param_specs
    from cake_tpu.parallel.sharding import tree_shard

    specs = pipeline_param_specs(params["blocks"].keys(),
                                 "tp" if tp else None)
    return tree_shard(params, mesh, specs)


# -- continuous-batching engine over the ("stage","sp"[,"tp"]) mesh -----------


def create_sp_stage_engine_cache(mesh: Mesh, config: LlamaConfig,
                                 slots: int, ctx_len: int, tail_len: int,
                                 kv_dtype=jnp.bfloat16,
                                 tp: bool = False):
    """SPEngineCache over the stage x sp mesh — the shared factory with
    the layer dim additionally sharded over "stage" (each stage holds
    only its block range's KV)."""
    from cake_tpu.parallel.context_parallel import create_sp_engine_cache
    return create_sp_engine_cache(mesh, config, slots, ctx_len,
                                  tail_len, kv_dtype=kv_dtype, tp=tp,
                                  stage=True)


def make_sp_stage_engine_step_fns(mesh: Mesh, config: LlamaConfig,
                                  ctx_len: int, tail_len: int,
                                  kv_dtype=None, tp: bool = False,
                                  params=None):
    """Engine step-fn contract over the ("stage","sp"[,"tp"]) mesh —
    the long-context 70B POD deployment (layer ranges over stages, ring
    attention within each stage's sp group), now serving CONCURRENT
    requests through the batching engine instead of the locked path.
    Same signatures/semantics as context_parallel
    .make_sp_engine_step_fns (position-contiguous per-row layout); the
    stage pipeline rides _stage_chain exactly as the generator
    adapter's forward does."""
    nstages = mesh.shape["stage"]
    sp_size = mesh.shape["sp"]
    assert ctx_len % sp_size == 0, (ctx_len, sp_size)
    assert config.num_hidden_layers % nstages == 0, (
        config.num_hidden_layers, nstages)
    Sl = ctx_len // sp_size
    tp_axis = "tp" if tp else None
    kv_store = kv_dtype

    from cake_tpu.parallel.pipeline import _blocks_in_specs
    blocks_spec = _blocks_in_specs(config, tp_axis, params)
    ctx_spec = P("stage", None, "sp", tp_axis, None)
    tail_spec = P("stage", None, None, tp_axis, None)
    rep = P()

    def chain(x, layer, blocks, ctx_k, ctx_v, tail_k, tail_v):
        def run_my_blocks(h):
            return lax.scan(layer, h, (blocks, ctx_k, ctx_v,
                                       tail_k, tail_v))
        return _stage_chain(x, run_my_blocks, (tail_k, tail_v))

    from cake_tpu.parallel.context_parallel import (
        make_sp_engine_decode_body,
    )
    decode_body = make_sp_engine_decode_body(config, tp_axis, Sl, chain)

    decode_sm = jax.shard_map(
        decode_body, mesh=mesh,
        in_specs=(blocks_spec, rep, rep, rep, rep, rep, rep,
                  ctx_spec, ctx_spec, tail_spec, tail_spec, rep, rep,
                  rep),
        out_specs=(rep, tail_spec, tail_spec),
        check_vma=False,
    )

    mode = "stage_sp_tp" if tp else "stage_sp"
    from cake_tpu.parallel.context_parallel import make_decode_ragged_fns
    decode_ragged_forward, decode_ragged_fn = make_decode_ragged_fns(
        decode_sm, mode=mode)

    prefill_body = make_sp_stage_prefill_body(
        config, kv_store, tp_axis, Sl, nstages,
        mesh.shape["tp"] if tp else 1)

    prefill_sm = jax.shard_map(
        prefill_body, mesh=mesh,
        in_specs=(blocks_spec, rep, rep, rep, P(None, "sp"), rep, rep,
                  rep),
        out_specs=(rep, ctx_spec, ctx_spec),
        check_vma=False,
    )

    from cake_tpu.parallel.context_parallel import (
        instrument_sp_engine, make_slot_prefill_fn,
    )
    prefill_slot_fn = make_slot_prefill_fn(prefill_sm, ctx_len,
                                           mode=mode)

    from cake_tpu.models.step_programs import make_decode_scan
    # shared instrumentation tail: every step fn dispatch-counted and
    # wall-timed (cake_sp_dispatch_total/_seconds{op,mode}), identical
    # to the plain-sp factory so the two modes' metrics cannot drift
    return instrument_sp_engine(
        (prefill_slot_fn, decode_ragged_fn,
         make_decode_scan(decode_ragged_forward)),
        mode, ctx_len, tail_len)
