"""Granite-4.0-H (`granitemoehybrid`, its dense members) on the paged
engine: the step programs.

The equations are models/reference/granite_hybrid.py's; this is how the
served path computes them over the page pool and, beside it, the rows'
recurrent state (models/llama/paged.HybridPagedCache).

Both step programs run ONE trunk over a flat list of tokens, each with
its row (slot) and position, as models/moe/nemotron_h.py's do and with
its pieces, CALLED where they are: `Rows`, `Window`, `window_of`,
`mamba_block` (the conv along each row's tokens, the recurrence in its
two forms, the float32 state and the bf16 tail, the scopes `ssm_*`),
`attention_block` (both ragged paged attention kernels over K/V pages
that hold the attention layers alone, no positional embedding) and
`create_cache`. What this family adds to them:

  * a layer is a mixer AND a dense SwiGLU (models/llama's: `w_gate`,
    `w_up`, `w_down`, under `ffn`), each behind its own RMS norm, each
    branch multiplied by `residual_multiplier` on its way into the
    stream, in float32 (0.22 is not a bfloat16, and an int8 scale is
    per channel: nothing is folded into a weight);
  * the embedding times `embedding_multiplier`, the logits over
    `logits_scaling`, the head the embedding transposed;
  * the attention layers' softmax scale is `attention_multiplier`
    (1/64 at heads of 64, not 1/8), handed to the kernels and the folds
    as their own `scale=`; q is never pre-scaled. The window goes
    through `cake_mixed_attn` in sub-windows of `exaone_moe.query_tile`
    queries: what the kernel's VMEM holds by its own count (128 at 32
    heads of 64 over 128-token pages: 256 ask for 16.9 MiB of 16);
  * a row's single token takes `mamba_block`'s one-step form through
    ops/ssm.step (`cake_ssm_step`: the stepping rows' state alone, once
    each way, in place). At 64 rows of 64 heads in one group XLA made
    two fusions over a layer's state in the decode program (it read
    the state twice) and wrote a whole layer's copy beside the stack in
    the mixed one, where at Nemotron's shape it makes one (PERF.md
    section 6, PR 57): a choice that flips on the row count is nothing
    a served path can stand on.

ONE WINDOW A DISPATCH AND A STEP, as nemotron_h: the chunked scan takes
the one row whose tokens are contiguous on the packed axis and whose
state it starts from, and there is ONE packed size.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from cake_tpu.models.family import Family, Windows, cannot_move
from cake_tpu.models.llama import paged
from cake_tpu.models.llama.paged import HybridPagedCache
from cake_tpu.models.moe import nemotron_h as nh
from cake_tpu.models.moe.config import GraniteHybridConfig
from cake_tpu.models.moe.exaone_moe import query_tile
from cake_tpu.models.moe.nemotron_h import Rows, Window
from cake_tpu.models.step_programs import (
    make_decode_scan, make_mixed_sampled,
)
from cake_tpu.ops import ragged_paged_attention as rpa
from cake_tpu.ops import ssm as ssm_ops
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import qmatmul

LAYER_LEAVES = ("norm", "mlp_norm", "w_gate", "w_up", "w_down")
# the record keys of the vector a step program returns, in trunk's
# order: the rows' recurrent state and the two forms of the scan
# (nemotron_h's last four)
COUNTERS = ("ssm_state_rows", "ssm_tokens_scanned", "ssm_tokens_stepped",
            "ssm_state_resets")
F32 = jnp.float32


def layer_leaves(blocks, config: GraniteHybridConfig, i: int) -> dict:
    """Layer i's leaves out of the stacks (static indices): the norms
    and the SwiGLU [L, ...], the mixer's from its kind's stack."""
    def at(names, j):
        return {k: jax.tree.map(lambda a: a[j], blocks[k]) for k in names}

    lp = at(LAYER_LEAVES, i)
    if config.layer_types[i] == "mamba":
        lp.update(at(nh.MAMBA_LEAVES, config.mamba_layers.index(i)))
    else:
        lp.update(at(nh.ATTN_LEAVES, config.attn_layers.index(i)))
    return lp


def reference_layers(blocks, config: GraniteHybridConfig):
    """The per-layer float32 dicts models/reference/granite_hybrid.
    forward walks, one at a time (a generator: a caller at published
    widths holds one layer's float32 weights at a time): the served
    leaves dequantized, `kind` beside them, the conv's weight in the
    published [channels, K] layout, the SwiGLU's input projection fused
    [gate | up] as published."""
    for i, kind in enumerate(config.layer_types):
        lp = {k: nh.dequantized(v)
              for k, v in layer_leaves(blocks, config, i).items()}
        if kind == "mamba":
            lp["conv_w"] = lp["conv_w"].T
        lp["w_mlp_in"] = jnp.concatenate(
            [lp.pop("w_gate"), lp.pop("w_up")], axis=1)
        lp["w_mlp_out"] = lp.pop("w_down")
        yield dict(lp, kind=kind)


def add_branch(x, out, multiplier: float):
    """x + multiplier * out, in float32, rounded once."""
    return (x.astype(F32) + multiplier * out.astype(F32)).astype(x.dtype)


def subwindow(config: GraniteHybridConfig, width: int, page_size: int,
              q_itemsize: int, kv_itemsize: int) -> int:
    """Queries a sub-window of the mixed attention kernel holds."""
    c = config
    return query_tile(width, c.num_attention_heads, c.num_key_value_heads,
                      c.head_dim, page_size, q_itemsize, kv_itemsize)


class TrunkOut(NamedTuple):
    """x [T, D] after the final norm; cache; counters [4]."""

    x: jnp.ndarray
    cache: HybridPagedCache
    counters: jnp.ndarray


def trunk(params, token_ids, slot, position, real, rows: Rows,
          cache: HybridPagedCache, config: GraniteHybridConfig, attn: str,
          window: Optional[Window] = None) -> TrunkOut:
    """Embed, every layer, final norm, over T tokens: token_ids, slot,
    position [T] int32, real [T] bool (a token that is not real writes
    nothing, moves no state, and its output is garbage nobody reads)."""
    c = config
    blocks = params["blocks"]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], token_ids, axis=0)
        x = (x.astype(F32) * c.embedding_multiplier).astype(x.dtype)
    pool_k, pool_v, table = cache.k, cache.v, cache.table
    ssm, conv = cache.ssm, cache.conv
    sub = None
    if window is not None:
        sub = subwindow(c, window.width, pool_k.shape[2], x.dtype.itemsize,
                        pool_k.dtype.itemsize)
    with jax.named_scope("layers"):
        for i, kind in enumerate(c.layer_types):
            lp = layer_leaves(blocks, c, i)
            with jax.named_scope("attn_norm"):
                h = rms_norm(x, lp["norm"], c.rms_norm_eps)
            if kind == "mamba":
                out, ssm, conv = nh.mamba_block(
                    lp, h, ssm, conv, c.mamba_layers.index(i), slot, real,
                    rows, c, window, step=ssm_ops.step)
            else:
                out, pool_k, pool_v = nh.attention_block(
                    lp, h, pool_k, pool_v, c.attn_layers.index(i), table,
                    slot, position, real, rows, c, attn, window,
                    scale=c.attention_multiplier, subwindow=sub)
            x = add_branch(x, out, c.residual_multiplier)
            with jax.named_scope("ffn"):
                h = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps)
                gate = jax.nn.silu(qmatmul(h, lp["w_gate"]))
                out = qmatmul(gate * qmatmul(h, lp["w_up"]), lp["w_down"])
                x = add_branch(x, out, c.residual_multiplier)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    Lm = len(c.mamba_layers)
    has = rows.n > 0
    counters = jnp.stack([
        Lm * jnp.sum(has, dtype=F32),
        Lm * jnp.sum(jnp.where(rows.n > 1, rows.n, 0), dtype=F32),
        Lm * jnp.sum(rows.n == 1, dtype=F32),
        jnp.sum(has & (rows.pos == 0), dtype=F32)]).astype(F32)
    return TrunkOut(x, cache._replace(k=pool_k, v=pool_v, ssm=ssm, conv=conv),
                    counters)


def logits_of(x, params, config: GraniteHybridConfig):
    """The tied head over normed rows x [n, D], over logits_scaling."""
    return (qmatmul(x, params["lm_head"]).astype(F32)
            / config.logits_scaling)


# -- the step programs ---------------------------------------------------------


def mixed_trunk(params, tokens, pos, q_len, active,
                cache: HybridPagedCache, config: GraniteHybridConfig,
                attn: str, n_tokens: int):
    """The mixed step's trunk on the packed axis [n_tokens] ->
    (TrunkOut, PackPlan)."""
    C = tokens.shape[1]
    plan = paged.pack_plan(q_len, active, n_tokens, C)
    n = jnp.where(active, q_len, 0).astype(jnp.int32)
    out = trunk(params, tokens[plan.row, plan.col], plan.row,
                pos[plan.row] + plan.col, plan.real,
                Rows(plan.start, n, pos.astype(jnp.int32)), cache, config,
                attn, nh.window_of(plan, n))
    return out, plan


@partial(jax.jit, static_argnames=("config", "attn", "n_tokens"),
         donate_argnames=("cache",))
def mixed_step_granite(params, tokens, pos, q_len, active,
                       cache: HybridPagedCache, rope,
                       config: GraniteHybridConfig, attn: str = "fold",
                       n_tokens: Optional[int] = None):
    """paged.mixed_step_paged's contract: tokens [B, C] right-padded
    windows, pos/q_len [B], active [B] -> (logits [B, V] of each row's
    last real token, cache, counters). At most ONE active row may hold
    more than one token (module docstring), and n_tokens, the packed
    size, is required. rope: unused (no positional embedding)."""
    del rope
    if n_tokens is None:
        raise ValueError("the hybrid mixed step runs on the packed axis: "
                         "pass n_tokens")
    out, plan = mixed_trunk(params, tokens, pos, q_len, active, cache,
                            config, attn, n_tokens)
    with jax.named_scope("head"):
        last = (jnp.maximum(q_len, 1) - 1).astype(jnp.int32)
        last = jnp.take(out.x, jnp.minimum(plan.start + last, n_tokens - 1),
                        axis=0)
        logits = logits_of(last, params, config)
    return logits, out.cache, out.counters


def decode_trunk(params, tokens, cache: HybridPagedCache, pos, active,
                 config: GraniteHybridConfig, attn: str) -> TrunkOut:
    """One token a row: tokens [B, 1], pos/active [B]."""
    B = tokens.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    pos = pos.astype(jnp.int32)
    return trunk(params, tokens[:, 0], rows, pos, active,
                 Rows(rows, active.astype(jnp.int32), pos), cache, config,
                 attn)


def forward_ragged_granite(params, tokens, cache: HybridPagedCache, pos,
                           active, rope, config: GraniteHybridConfig,
                           attn: str = "fold"):
    """paged.forward_ragged_paged(..., counters=True)'s contract: what
    step_programs.make_decode_scan builds the sampled decode programs
    from -> (logits [B, V], cache, counters)."""
    del rope
    out = decode_trunk(params, tokens, cache, pos, active, config, attn)
    with jax.named_scope("head"):
        logits = logits_of(out.x, params, config)
    return logits, out.cache, out.counters


@partial(jax.jit, static_argnames=("config", "attn"),
         donate_argnames=("cache",))
def decode_step_granite(params, tokens, pos, active, cache: HybridPagedCache,
                        rope, config: GraniteHybridConfig,
                        attn: str = "fold"):
    """paged.decode_step_ragged_paged's contract (the synchronous
    decode step)."""
    return forward_ragged_granite(params, tokens, cache, pos, active, rope,
                                  config, attn)


# -- what the engine reads of this family (models/family.py) ----------------


def _resolve_attn(config, impl: str, *, explicit: bool, prefill_chunk,
                  slots: int, n_pages: int, page_size: int,
                  max_seq_len: int, q_itemsize: int, kv_itemsize: int):
    """One impl for both step programs: the mixed program runs the
    decode kernel over the rows' single tokens and the mixed kernel
    over the window in sub-windows of `subwindow` queries, so the mixed
    kernel's gate is asked at the sub-window."""
    c = config
    width = prefill_chunk or min(512, max_seq_len)
    sub = subwindow(c, width, page_size, q_itemsize, kv_itemsize)
    if width % sub:
        raise ValueError(
            f"--prefill-chunk {width}: model_type granitemoehybrid hands "
            f"the window to the attention kernel in sub-windows of {sub} "
            "queries (what its VMEM holds at these heads), which must "
            "divide it")
    heads = (c.num_attention_heads, c.num_key_value_heads, c.head_dim)
    max_pages = -(-max_seq_len // page_size)
    ok = (rpa.ragged_paged_supported(
              page_size, *heads, n_pages=n_pages, slots=slots,
              max_pages=max_pages, kv_itemsize=kv_itemsize)
          and rpa.ragged_paged_mixed_supported(
              page_size, *heads, sub, n_pages=n_pages,
              slots=width // sub, max_pages=max_pages,
              q_itemsize=q_itemsize, kv_itemsize=kv_itemsize))
    if impl == "pallas" and not ok:
        if explicit:
            raise ValueError(
                "--paged-attn pallas cannot serve model_type "
                f"granitemoehybrid on this device at page={page_size} "
                f"heads={heads} mixed width={width} in sub-windows of "
                f"{sub} (ops/ragged_paged_attention gates); use "
                "--paged-attn auto or fold")
        impl = "fold"
    return impl, width


FAMILY = Family(
    name="granitemoehybrid", decode_step=decode_step_granite,
    decode_programs=make_decode_scan(forward_ragged_granite),
    mixed_step=mixed_step_granite,
    mixed_sampled=make_mixed_sampled(mixed_step_granite),
    create_cache=nh.create_cache, counters=COUNTERS,
    # one window a dispatch and a step, one packed size (module
    # docstring; family.Windows)
    prefill_rows=(1,), windows=Windows.STEP,
    beside=("recurrent state", "ssm_state_bytes"),
    impl="paged-ssm-", resolve_attn=_resolve_attn,
    # the mixed program hands cake_mixed_attn the window alone, in
    # sub-windows
    kernel_rows=("decode",),
    what="a recurrent state a row beside the page pool",
    refuses=cannot_move(
        "state",
        register_prefix=(
            "a recurrent state (granitemoehybrid) has no prefix reuse "
            "yet: a shared head would need the state snapshotted at its "
            "last page's edge (ROADMAP.md)"),
        reconfigure=(
            "a recurrent state (granitemoehybrid) lives beside the page "
            "pool: a rebuilt pool cannot replay it")))
