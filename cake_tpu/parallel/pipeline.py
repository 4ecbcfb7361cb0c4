"""Microbatched pipeline parallelism via shard_map + ppermute over ICI.

This is the TPU-native replacement for the reference's distribution model
(SURVEY.md §2.6-2.7): where the reference walks layer-range workers
sequentially over TCP — a depth-1 pipeline with one request in flight
(llama.rs:81-117) — here the stacked block parameters are sharded over a
`stage` mesh axis, hidden states move stage-to-stage with
`lax.ppermute` over ICI, and a GPipe-style schedule keeps every stage busy
once `num_microbatches >= num_stages`. Setting num_microbatches=1
reproduces the reference's depth-1 behavior exactly (useful for latency
comparisons), and the contiguous-block-batching optimization holds by
construction: a stage's whole block range is one fused XLA computation.

Composability: the stage body optionally runs manually tensor-parallel
(`tp` axis, Megatron psums inside the block — see
`model.block_forward(tp_axis=...)`) and data-parallel (`dp` axis shards the
batch; no collectives in the block math), so one shard_mapped program covers
dp x pp x tp.

The cache under the tick: a stage's dense cache [L_local, B, T, KV_local,
hd] is the CARRY of the tick loop and of the layer loop inside it, donated
in and aliased out. The tick hands `run_microbatch` the whole buffers, the
microbatch's row offset and `live`; it never slices, selects or splices a
cache. A bubble tick (a stage with no microbatch yet, or none left) still
runs its blocks, which costs no wall time while another stage works, but
writes no row: the ragged body folds `live` into `active`, the uniform
body puts back the [mb, S] window it would have written
(models/llama/cache.py's layout contract). A page pool under the tick
needs the same form: a pool cannot be select-masked either.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from cake_tpu.models.llama.cache import KVCache
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.model import (
    RopeTables, run_blocks, run_blocks_ragged,
)
from cake_tpu.ops.attention import decode_mask
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import expand_specs_for_quant, qmatmul
from cake_tpu.ops.rope import rope_rows


def _gpipe_stage_loop(k, v, x, run_microbatch, *, num_microbatches: int):
    """Shared GPipe tick schedule (runs under shard_map, per-device views).

    k, v: [L_local, B, T, KV_local, hd], carried whole (module docstring);
    x: [B, S, D] (replicated over stage).
    `run_microbatch(inp, k, v, idx, mb, live)` runs this stage's blocks on
    the microbatch whose rows are idx..idx+mb of the carried buffers and
    returns (y, k, v) written in place; with `live` False (a bubble) it
    writes no row, and its y is discarded here. Callers close over
    whatever per-row state they need and slice it with (idx, mb). Returns
    (out, k, v) with out valid on every stage after the final broadcast.
    """
    nstages = lax.axis_size("stage")
    sid = lax.axis_index("stage")
    M = num_microbatches
    B, S, D = x.shape
    assert B % M == 0, f"local batch {B} not divisible by microbatches {M}"
    mb = B // M

    buf = jnp.zeros((mb, S, D), x.dtype)     # incoming hidden state
    out = jnp.zeros_like(x)                  # final-stage outputs

    def tick(t, state):
        buf, out, k, v = state
        my_mb = t - sid                       # microbatch this stage handles
        live = jnp.logical_and(my_mb >= 0, my_mb < M)  # pipeline bubble?
        idx = jnp.clip(my_mb, 0, M - 1) * mb

        fresh = lax.dynamic_slice_in_dim(x, idx, mb, axis=0)
        inp = jnp.where(sid == 0, fresh, buf)

        y, k, v = run_microbatch(inp, k, v, idx, mb, live)

        is_last = sid == nstages - 1
        cur = lax.dynamic_slice_in_dim(out, idx, mb, axis=0)
        out = lax.dynamic_update_slice_in_dim(
            out, jnp.where(jnp.logical_and(live, is_last), y, cur),
            idx, axis=0,
        )
        # hand this stage's result to the next stage over ICI
        buf = lax.ppermute(
            y, "stage", [(i, (i + 1) % nstages) for i in range(nstages)]
        )
        return buf, out, k, v

    buf, out, k, v = lax.fori_loop(0, M + nstages - 1, tick,
                                   (buf, out, k, v))
    # broadcast the last stage's result to every stage (tiny: [B,S,D])
    out = lax.psum(
        jnp.where(sid == nstages - 1, out, jnp.zeros_like(out)), "stage"
    )
    return out, k, v


def _stage_pipeline_body(blocks, k, v, x, pos, wlen, rope_c, rope_s,
                         mask, *,
                         config: LlamaConfig, num_microbatches: int,
                         tp_axis: Optional[str], is_prefill: bool = False,
                         chunked: bool = False, ring: bool = False):
    """Per-device body for uniform-position forward (prefill / batch
    decode): pos, rope rows and mask are shared across the batch.
    A bubble tick puts back the [mb, S] window it would have written
    (model.run_blocks live=). ring/wlen: sliding-window ring cache
    (stage-local [L_local, B, W] slices; writes wrap at W with wlen
    junk-masking: model.run_blocks ring semantics, identical per stage).
    """
    def run_microbatch(inp, k, v, idx, mb, live):
        y, cache = run_blocks(
            blocks, inp, KVCache(k, v), pos, rope_c, rope_s, mask,
            config, tp_axis=tp_axis, is_prefill=is_prefill,
            chunked=chunked, ring=ring, write_len=wlen, row0=idx,
            live=live,
        )
        return y, cache.k, cache.v

    return _gpipe_stage_loop(k, v, x, run_microbatch,
                             num_microbatches=num_microbatches)


def _blocks_in_specs(config: LlamaConfig, tp_axis, params=None):
    """shard_map in_specs for the stacked block params; QTensor leaves get
    their (q, scale) spec pair expanded when an example params tree is
    given (required for --quant int8 under any topology)."""
    from cake_tpu.models.llama.params import block_param_keys, block_specs
    specs = block_specs(block_param_keys(config),
                        stage_axis="stage", tp_axis=tp_axis)
    if params is not None:
        specs = {k: specs[k] for k in params["blocks"]}
        specs = expand_specs_for_quant({"blocks": params["blocks"]},
                                       {"blocks": specs})["blocks"]
    return specs


def make_pipeline_forward(mesh: Mesh, config: LlamaConfig,
                          num_microbatches: int = 1,
                          tp: bool = False, dp: bool = False,
                          params=None, ring: bool = False):
    """Build a jitted pipelined forward(params, tokens, cache, pos, rope,
    last_idx, is_prefill) -> (logits, cache) for the given mesh.

    Sharding contract:
      params["blocks"]: layer axis over "stage" (+ head/ffn over "tp" if tp)
      cache:            layer over "stage", batch over "dp", kv-heads "tp"
      embed/lm_head/final_norm: replicated (or vocab-sharded by GSPMD)
    params: optional example pytree — pass when weights are int8-quantized
    so the QTensor leaves get matching in_specs.
    """
    tp_axis = "tp" if tp else None
    blocks_specs = _blocks_in_specs(config, tp_axis, params)

    dp_axis = "dp" if dp else None
    cache_spec = P("stage", dp_axis, None, tp_axis, None)
    x_spec = P(dp_axis, None, None)

    def make_stage_fn(is_prefill: bool, chunked: bool = False):
        return jax.shard_map(
            partial(_stage_pipeline_body, config=config,
                    num_microbatches=num_microbatches, tp_axis=tp_axis,
                    is_prefill=is_prefill, chunked=chunked, ring=ring),
            mesh=mesh,
            in_specs=(blocks_specs, cache_spec, cache_spec, x_spec,
                      P(), P(), P(), P(), P()),
            out_specs=(x_spec, cache_spec, cache_spec),
            check_vma=False,
        )

    stage_fns = {(False, False): make_stage_fn(False),
                 (True, False): make_stage_fn(True),
                 (True, True): make_stage_fn(True, chunked=True)}

    def forward_body(params, tokens, cache: KVCache, pos, rope: RopeTables,
                     last_idx=None, is_prefill: bool = False,
                     chunked: bool = False, write_len=None):
        B, S = tokens.shape
        T = cache.max_seq_len
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0)
        rope_c, rope_s = rope_rows(rope.cos, rope.sin, pos, S)
        from cake_tpu.ops.attention import uniform_forward_mask
        mask = uniform_forward_mask(pos, S, T, config.sliding_window,
                                    ring, n_real=write_len)
        wlen = (jnp.int32(S) if write_len is None
                else jnp.asarray(write_len, jnp.int32))
        with jax.named_scope("layers"):
            y, k, v = stage_fns[(is_prefill, chunked)](
                params["blocks"], cache.k, cache.v,
                x, pos, wlen, rope_c, rope_s, mask)
        with jax.named_scope("head"):
            y = rms_norm(y, params["final_norm"], config.rms_norm_eps)
            if last_idx is None:
                last = y[:, -1]
            else:
                last = jnp.take_along_axis(
                    y, last_idx.reshape(B, 1, 1).astype(jnp.int32), axis=1
                )[:, 0]
            logits = qmatmul(last, params["lm_head"]).astype(jnp.float32)
        return logits, KVCache(k, v)

    jitted = jax.jit(forward_body, donate_argnames=("cache",),
                     static_argnames=("is_prefill", "chunked"))

    def pipeline_forward(*args, **kwargs):
        return jitted(*args, **kwargs)

    pipeline_forward.body = forward_body  # un-jitted, for embedding callers
    return pipeline_forward


# -- ragged (continuous-batching) pipeline ------------------------------------


def _stage_pipeline_body_ragged(blocks, k, v, x, pos, active,
                                rope_c, rope_s, mask, *,
                                config: LlamaConfig, num_microbatches: int,
                                tp_axis: Optional[str],
                                ring: bool = False):
    """Per-device GPipe body for per-row-position single-token decode:
    every per-row quantity (pos, active, rope rows, mask) is sliced per
    microbatch and the stage runs `run_blocks_ragged` on that
    microbatch's rows of the carried cache; on a bubble tick no row is
    active. x: [B, 1, D].
    """
    def run_microbatch(inp, k, v, idx, mb, live):
        sl = partial(lax.dynamic_slice_in_dim, start_index=idx,
                     slice_size=mb, axis=0)
        y, cache = run_blocks_ragged(
            blocks, inp, KVCache(k, v), sl(pos), sl(active) & live,
            sl(rope_c), sl(rope_s), sl(mask), config, tp_axis=tp_axis,
            ring=ring, row0=idx,
        )
        return y, cache.k, cache.v

    return _gpipe_stage_loop(k, v, x, run_microbatch,
                             num_microbatches=num_microbatches)


def make_engine_step_fns(mesh: Mesh, config: LlamaConfig,
                         num_microbatches: int = 1, tp: bool = False,
                         params=None, ring: bool = False):
    """Pipelined replacements for the engine's jitted steps.

    Returns (prefill_slot_fn, decode_ragged_fn, decode_scan_fn,
    prefill_chunk_fn) with the exact call signatures of
    model.prefill_slot / model.decode_step_ragged / the engine's
    decode-scan / model.prefill_slot_chunk, so serve/engine.py runs
    continuous batching — including K-step scanned decode and chunked
    prefill — over a topology-sharded model unchanged. The batch (slot)
    axis is NOT dp-sharded — slots are admitted one at a time and sliced
    dynamically, which must stay local.
    """
    tp_axis = "tp" if tp else None
    blocks_specs = _blocks_in_specs(config, tp_axis, params)
    cache_spec = P("stage", None, None, tp_axis, None)
    x_spec = P(None, None, None)

    from cake_tpu.models.llama.model import ragged_decode, slot_prefill

    fwd = make_pipeline_forward(mesh, config, num_microbatches=1, tp=tp,
                                dp=False, params=params, ring=ring)
    model_config = config

    ragged_stage = jax.shard_map(
        partial(_stage_pipeline_body_ragged, config=config,
                num_microbatches=num_microbatches, tp_axis=tp_axis,
                ring=ring),
        mesh=mesh,
        in_specs=(blocks_specs, cache_spec, cache_spec, x_spec,
                  P(), P(), P(), P(), P()),
        out_specs=(x_spec, cache_spec, cache_spec),
        check_vma=False,
    )

    # logits leave the program fully replicated: multi-host serving
    # localizes them per-process (np.asarray) so sampling needs no
    # cross-process collective; single-host this is what GSPMD picks
    # anyway for a [B, V] tensor computed from replicated operands
    logits_repl = NamedSharding(mesh, P())

    def ragged_forward(params, tokens, cache, pos, active, rope, config):
        """model.forward_ragged-shaped pipelined forward (un-jitted:
        traced inside decode_ragged_fn and the decode scan)."""
        def runner(blocks, x, cache, pos, active, rope_c, rope_s, mask):
            with jax.named_scope("layers"):
                y, k, v = ragged_stage(blocks, cache.k, cache.v, x,
                                       pos, active, rope_c, rope_s, mask)
            return y, KVCache(k, v)

        return ragged_decode(params, tokens, pos, active, cache,
                             rope, model_config, runner, ring=ring)

    @partial(jax.jit, donate_argnames=("cache",),
             static_argnames=("config",))
    def prefill_slot_fn(params, tokens, prompt_len, slot, cache: KVCache,
                        rope: RopeTables, config=None):
        if ring:
            # the engine routes EVERY ring prompt through chunk windows;
            # a whole-bucket prefill could exceed the ring capacity
            raise RuntimeError(
                "whole-bucket prefill is not available on the ring "
                "pipelined path (engine forces chunked prefill)")

        def pipelined(p, t, sub, pos, last_idx):
            return fwd.body(p, t, sub, pos, rope,
                            last_idx=last_idx, is_prefill=True)

        logits, cache = slot_prefill(params, tokens, prompt_len, slot,
                                     cache, pipelined)
        return jax.lax.with_sharding_constraint(logits, logits_repl), cache

    @partial(jax.jit, donate_argnames=("cache",),
             static_argnames=("config",))
    def decode_step_ragged_pipelined(params, tokens, pos, active,
                                     cache: KVCache, rope: RopeTables,
                                     config=None):
        # the name is the XLA module's (jit_decode_step_...): the
        # benchmark finds a decode step's device time by that prefix
        logits, cache = ragged_forward(params, tokens, cache, pos, active,
                                       rope, config)
        return jax.lax.with_sharding_constraint(logits, logits_repl), cache

    from cake_tpu.models.step_programs import make_decode_scan
    decode_scan_fn = make_decode_scan(ragged_forward,
                                      out_sharding=logits_repl)

    @partial(jax.jit, donate_argnames=("cache",),
             static_argnames=("config",))
    def prefill_chunk_fn(params, tokens, n_real, slot, pos0,
                         cache: KVCache, rope: RopeTables, config=None):
        """Pipelined analog of model.prefill_slot_chunk: one fixed-size
        window into slot `slot` at absolute position pos0, through the
        cache-aware (chunked) pipelined forward."""
        def pipelined(p, t, sub, pos, last_idx):
            return fwd.body(p, t, sub, pos, rope, last_idx=last_idx,
                            is_prefill=True, chunked=True,
                            write_len=n_real[0] if ring else None)

        logits, cache = slot_prefill(params, tokens, n_real, slot, cache,
                                     pipelined, pos0=pos0)
        return jax.lax.with_sharding_constraint(logits, logits_repl), cache

    return (prefill_slot_fn, decode_step_ragged_pipelined, decode_scan_fn,
            prefill_chunk_fn)


def pipeline_param_specs(blocks_keys, tp_axis: Optional[str] = None):
    """The param PartitionSpec tree make_pipeline_forward expects: stacked
    layer dim over "stage" (the reference's topology.yml block-range
    assignment), heads/ffn over tp; embed/lm_head/norms replicated."""
    from cake_tpu.models.llama.params import block_specs
    return {
        "embed": P(None, None),
        "blocks": block_specs(blocks_keys, stage_axis="stage",
                              tp_axis=tp_axis),
        "final_norm": P(None),
        "lm_head": P(None, None),
    }


def place_for_pipeline(params, cache: KVCache, mesh: Mesh, *,
                       tp: bool = False, dp: bool = False):
    """device_put params/cache with the shardings make_pipeline_forward
    expects. QTensor leaves place via their expanded (q, scale) specs."""
    from cake_tpu.parallel.sharding import tree_shard
    tp_axis = "tp" if tp else None
    dp_axis = "dp" if dp else None

    specs = pipeline_param_specs(params["blocks"].keys(), tp_axis)
    out = tree_shard(params, mesh, specs)
    from cake_tpu.parallel.sharding import shard_cache
    cache = shard_cache(cache, mesh, tp_axis=tp_axis, dp_axis=dp_axis,
                        stage_axis="stage")
    return out, cache
