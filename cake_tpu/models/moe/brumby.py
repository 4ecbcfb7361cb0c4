"""Brumby (`model_type: brumby`) on the paged engine: the step programs.

The equations are models/reference/brumby.py's; this is how the served
path computes them: every layer is power retention of degree 2 and a
dense SwiGLU, and a row keeps NO K/V page. What a row keeps is a matrix
state a layer and K/V head, float32, that the query heads of a GQA group
share (ops/retention.py: S [L, slots, KV, NB, hd, DB], the symmetric
square of a key against its value, kept transposed and in blocks along
D; z [L, slots, KV, D], the normaliser), in models/llama/paged.
HybridPagedCache's `ssm` and `conv` leaves BESIDE A PAGE POOL OF ZERO
LAYERS: `k` and `v` are [0, N, page, KV * hd], no byte, and the page
table stays the allocator's bookkeeping of positions (max-seq-len, a
window's padding); what bounds admission is the slots.

Both step programs run ONE trunk over a flat list of tokens, each with
its row (slot) and position, as models/moe/granite_hybrid.py's do and
with models/moe/nemotron_h.py's pieces, CALLED where they are: `Rows`,
`Window`, `window_of`, `step_codes`. What this family adds:

  * `retention_block`: q, k, v; an RMSNorm a head on q and k (one
    weight vector a layer) and the rotation (whole head, the model's
    theta), both in front of the mixer; the gate, log gamma =
    logsigmoid(h w_g + b_g), float32, one a K/V head and token. A row's
    single token goes through ops/retention.step (`cake_retention_step`:
    the stepping rows' state alone, once each way, in place; under
    attn="fold", what a CPU serves, `step_fold`), a row's window
    through ops/retention.window, from the state the row held BEFORE
    this layer's step (read behind an optimization barrier, as
    nemotron_h.mamba_block reads its own: fused into the window's
    consumers it would be a read of the old stack after the kernel's
    write in place, and a copy of a 6 GB stack is not a slow step but
    no step at all). A row whose first token sits at position 0 starts
    from zeros inside the step program;
  * the constants config.json has no key for, held from the published
    description of the mechanism (benchmarks/configs/brumby-14b-int8-
    10of40/cell.json, `assumed`): the degree is 2 (ops/retention.phi);
    the gate is a scalar a K/V head; the output is divided by the sum of
    its weights + 1e-6 and meets no norm layer; the softmax's 1/sqrt(hd)
    stands inside the square (folded out of both sums and into the
    epsilon: ops/retention.EPS x head_dim).

Scopes, each inside the shared one the device trace files it under:
`ret_in` (qkv), `ret_step` and `ret_window` (attn), `ret_out` (o_proj).

ONE WINDOW A DISPATCH AND A STEP, as nemotron_h: the window form takes
the one row whose tokens are contiguous on the packed axis and whose
state it starts from, and there is ONE packed size.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from cake_tpu.models.family import Family, Windows, cannot_move
from cake_tpu.models.llama import paged
from cake_tpu.models.llama.paged import HybridPagedCache
from cake_tpu.models.moe import nemotron_h as nh
from cake_tpu.models.moe.config import BrumbyConfig
from cake_tpu.models.moe.glm_dsa import _window_slice
from cake_tpu.models.moe.nemotron_h import Rows, Window
from cake_tpu.models.step_programs import (
    make_decode_scan, make_mixed_sampled,
)
from cake_tpu.ops import ragged_paged_attention as rpa
from cake_tpu.ops import retention
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import qmatmul
from cake_tpu.ops.rope import apply_rope

LAYER_LEAVES = ("norm", "wq", "wk", "wv", "q_norm", "k_norm", "w_g", "b_g",
                "wo", "mlp_norm", "w_gate", "w_up", "w_down")
# the record keys of the vector a step program returns, in trunk's order
COUNTERS = ("retention_state_rows", "retention_tokens_windowed",
            "retention_tokens_stepped", "retention_state_resets")
F32 = jnp.float32


def layer_leaves(blocks, i: int) -> dict:
    """Layer i's leaves out of the stacks (a static index)."""
    return {k: jax.tree.map(lambda a: a[i], blocks[k]) for k in LAYER_LEAVES}


def reference_layers(blocks, config: BrumbyConfig):
    """The per-layer float32 dicts models/reference/brumby.forward
    walks, one at a time (a generator: a caller at published widths
    holds one layer's float32 weights at a time): the served leaves
    dequantized, under the served names."""
    for i in range(config.num_hidden_layers):
        yield {k: nh.dequantized(v)
               for k, v in layer_leaves(blocks, i).items()}


def project(lp, h, cos, sin, real, config: BrumbyConfig):
    """h [T, D] -> q [T, KV, R, hd], k [T, KV, hd], v [T, KV, hd] in the
    activations' type, log gamma [T, KV] float32 (0 for a token that is
    not real: it leaves the state as it is)."""
    c = config
    T = h.shape[0]
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    # (an RMSNorm a head: over head_dim, one weight vector a layer)
    q = rms_norm(qmatmul(h, lp["wq"]).reshape(T, H, hd), lp["q_norm"],
                 c.rms_norm_eps)
    k = rms_norm(qmatmul(h, lp["wk"]).reshape(T, KV, hd), lp["k_norm"],
                 c.rms_norm_eps)
    v = qmatmul(h, lp["wv"]).reshape(T, KV, hd)
    q = apply_rope(q[None], cos, sin)[0]
    k = apply_rope(k[None], cos, sin)[0]
    log_gamma = jax.nn.log_sigmoid(
        jnp.dot(h.astype(F32), lp["w_g"].astype(F32),
                precision=lax.Precision.HIGHEST) + lp["b_g"].astype(F32))
    return (q.reshape(T, KV, H // KV, hd), k, v,
            jnp.where(real[:, None], log_gamma, 0.0))


def retention_block(lp, h, S, z, j: int, cos, sin, slot, real, rows: Rows,
                    config: BrumbyConfig, attn: str,
                    window: Optional[Window]):
    """h [T, D] -> (out [T, D], S, z): layer j of the stacked state,
    over the packed tokens."""
    c = config
    T = h.shape[0]
    with jax.named_scope("qkv"), jax.named_scope("ret_in"):
        q, k, v, log_gamma = project(lp, h, cos, sin, real, c)
    fresh = (rows.n > 0) & (rows.pos == 0)
    step = retention.step if attn == "pallas" else retention.step_fold
    with jax.named_scope("attn"):
        if window is not None:
            # the window's row alone, as stored BEFORE this layer's step
            # (it stays there): 36 MiB at the published widths
            with jax.named_scope("ret_window"):
                S0, z0, S, z = lax.optimization_barrier((
                    jnp.where(fresh[window.row], 0.0, S[j, window.row]),
                    jnp.where(fresh[window.row], 0.0, z[j, window.row]),
                    S, z))
        with jax.named_scope("ret_step"):
            at = jnp.minimum(rows.first, T - 1)
            S, z, y1 = step(S, z, j, nh.step_codes(rows), q[at], k[at],
                            v[at], log_gamma[at])
        if window is None:
            y = y1[slot]
        else:
            with jax.named_scope("ret_window"):
                S_win, z_win, yw = retention.window(
                    S0, z0, *(_window_slice(x, window)
                              for x in (q, k, v, log_gamma)),
                    jnp.arange(window.width) < window.n)
                held = window.n > 1
                S = S.at[j, window.row].set(
                    jnp.where(held, S_win, S[j, window.row]))
                z = z.at[j, window.row].set(
                    jnp.where(held, z_win, z[j, window.row]))
            y = jnp.where(window.member[:, None, None, None],
                          yw[window.col], y1[slot])
    with jax.named_scope("o_proj"), jax.named_scope("ret_out"):
        return qmatmul(y.astype(h.dtype).reshape(T, -1), lp["wo"]), S, z


class TrunkOut(NamedTuple):
    """x [T, D] after the final norm; cache; counters [4]."""

    x: jnp.ndarray
    cache: HybridPagedCache
    counters: jnp.ndarray


def trunk(params, token_ids, slot, position, real, rows: Rows,
          cache: HybridPagedCache, rope, config: BrumbyConfig, attn: str,
          window: Optional[Window] = None) -> TrunkOut:
    """Embed, every layer, final norm, over T tokens: token_ids, slot,
    position [T] int32, real [T] bool (a token that is not real moves no
    state, and its output is garbage nobody reads)."""
    c = config
    blocks = params["blocks"]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], token_ids, axis=0)
    at = jnp.minimum(position, rope.cos.shape[0] - 1)
    cos, sin = jnp.take(rope.cos, at, axis=0), jnp.take(rope.sin, at, axis=0)
    S, z = cache.ssm, cache.conv
    with jax.named_scope("layers"):
        for i in range(c.num_hidden_layers):
            lp = layer_leaves(blocks, i)
            with jax.named_scope("attn_norm"):
                h = rms_norm(x, lp["norm"], c.rms_norm_eps)
            out, S, z = retention_block(lp, h, S, z, i, cos, sin, slot, real,
                                        rows, c, attn, window)
            x = x + out
            with jax.named_scope("ffn"):
                h = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps)
                gate = jax.nn.silu(qmatmul(h, lp["w_gate"]))
                x = x + qmatmul(gate * qmatmul(h, lp["w_up"]), lp["w_down"])
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    L = c.num_hidden_layers
    has = rows.n > 0
    counters = jnp.stack([
        L * jnp.sum(has, dtype=F32),
        L * jnp.sum(jnp.where(rows.n > 1, rows.n, 0), dtype=F32),
        L * jnp.sum(rows.n == 1, dtype=F32),
        jnp.sum(has & (rows.pos == 0), dtype=F32)]).astype(F32)
    return TrunkOut(x, cache._replace(ssm=S, conv=z), counters)


def logits_of(x, params):
    """The untied head over normed rows x [n, D]."""
    return qmatmul(x, params["lm_head"]).astype(F32)


# -- the step programs ---------------------------------------------------------


def mixed_trunk(params, tokens, pos, q_len, active, cache: HybridPagedCache,
                rope, config: BrumbyConfig, attn: str, n_tokens: int):
    """The mixed step's trunk on the packed axis [n_tokens] ->
    (TrunkOut, PackPlan)."""
    plan = paged.pack_plan(q_len, active, n_tokens, tokens.shape[1])
    n = jnp.where(active, q_len, 0).astype(jnp.int32)
    out = trunk(params, tokens[plan.row, plan.col], plan.row,
                pos[plan.row] + plan.col, plan.real,
                Rows(plan.start, n, pos.astype(jnp.int32)), cache, rope,
                config, attn, nh.window_of(plan, n))
    return out, plan


@partial(jax.jit, static_argnames=("config", "attn", "n_tokens"),
         donate_argnames=("cache",))
def mixed_step_brumby(params, tokens, pos, q_len, active,
                      cache: HybridPagedCache, rope, config: BrumbyConfig,
                      attn: str = "fold", n_tokens: Optional[int] = None):
    """paged.mixed_step_paged's contract: tokens [B, C] right-padded
    windows, pos/q_len [B], active [B] -> (logits [B, V] of each row's
    last real token, cache, counters). At most ONE active row may hold
    more than one token (module docstring), and n_tokens, the packed
    size, is required."""
    if n_tokens is None:
        raise ValueError("the retention mixed step runs on the packed "
                         "axis: pass n_tokens")
    out, plan = mixed_trunk(params, tokens, pos, q_len, active, cache, rope,
                            config, attn, n_tokens)
    with jax.named_scope("head"):
        last = (jnp.maximum(q_len, 1) - 1).astype(jnp.int32)
        last = jnp.take(out.x, jnp.minimum(plan.start + last, n_tokens - 1),
                        axis=0)
        logits = logits_of(last, params)
    return logits, out.cache, out.counters


def decode_trunk(params, tokens, cache: HybridPagedCache, pos, active, rope,
                 config: BrumbyConfig, attn: str) -> TrunkOut:
    """One token a row: tokens [B, 1], pos/active [B]."""
    B = tokens.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    pos = pos.astype(jnp.int32)
    return trunk(params, tokens[:, 0], rows, pos, active,
                 Rows(rows, active.astype(jnp.int32), pos), cache, rope,
                 config, attn)


def forward_ragged_brumby(params, tokens, cache: HybridPagedCache, pos,
                          active, rope, config: BrumbyConfig,
                          attn: str = "fold"):
    """paged.forward_ragged_paged(..., counters=True)'s contract: what
    step_programs.make_decode_scan builds the sampled decode programs
    from -> (logits [B, V], cache, counters)."""
    out = decode_trunk(params, tokens, cache, pos, active, rope, config, attn)
    with jax.named_scope("head"):
        logits = logits_of(out.x, params)
    return logits, out.cache, out.counters


@partial(jax.jit, static_argnames=("config", "attn"),
         donate_argnames=("cache",))
def decode_step_brumby(params, tokens, pos, active, cache: HybridPagedCache,
                       rope, config: BrumbyConfig, attn: str = "fold"):
    """paged.decode_step_ragged_paged's contract (the synchronous
    decode step)."""
    return forward_ragged_brumby(params, tokens, cache, pos, active, rope,
                                 config, attn)


# -- what the engine reads of this family (models/family.py) ----------------


def create_cache(config: BrumbyConfig, slots: int, n_pages: int,
                 page_size: int, max_seq_len: int, width, dtype):
    """A page pool of NO layers (`k`, `v` [0, N, page, KV * hd]: the
    table's pages map positions to nothing) and, beside it, the state a
    ROW: `ssm` S [L, slots, KV, NB, hd, DB] and `conv` z [L, slots, KV,
    D], both float32 whatever the pool's type."""
    del width
    c = config
    L, KV, hd = c.num_hidden_layers, c.num_key_value_heads, c.head_dim
    pool = (0, n_pages, page_size, KV * hd)
    return HybridPagedCache(
        k=jnp.zeros(pool, dtype), v=jnp.zeros(pool, dtype),
        table=jnp.full((slots, max_seq_len // page_size), -1, jnp.int32),
        ssm=jnp.zeros((L, slots) + retention.state_shape(KV, hd, hd), F32),
        conv=jnp.zeros((L, slots, KV, retention.state_width(hd)), F32))


def _resolve_attn(config, impl: str, *, explicit: bool, prefill_chunk,
                  slots: int, n_pages: int, page_size: int,
                  max_seq_len: int, q_itemsize: int, kv_itemsize: int):
    """One impl for both step programs: `pallas` is cake_retention_step
    over the rows' single tokens in both (the window form is XLA's under
    either). The kernel takes heads of whole lane tiles on a chip."""
    del slots, n_pages, page_size, q_itemsize, kv_itemsize
    c = config
    width = prefill_chunk or min(512, max_seq_len)
    ok = not rpa._on_tpu() or c.head_dim % retention.LANES == 0
    if impl == "pallas" and not ok:
        if explicit:
            raise ValueError(
                "--paged-attn pallas cannot serve model_type brumby on "
                f"this device at head_dim {c.head_dim} (ops/retention.step "
                "takes heads of whole lane tiles); use --paged-attn auto "
                "or fold")
        impl = "fold"
    return impl, width


FAMILY = Family(
    name="brumby", decode_step=decode_step_brumby,
    decode_programs=make_decode_scan(forward_ragged_brumby),
    mixed_step=mixed_step_brumby,
    mixed_sampled=make_mixed_sampled(mixed_step_brumby),
    create_cache=create_cache, counters=COUNTERS,
    # one window a dispatch and a step, one packed size (module
    # docstring; family.Windows)
    prefill_rows=(1,), windows=Windows.STEP,
    beside=("retention state", "retention_state_bytes"),
    impl="paged-retention-", resolve_attn=_resolve_attn,
    # no attention kernel anywhere: the host counts no page and no tile
    kernel_rows=(),
    what="a retention state a row beside a page pool of no layers",
    refuses=cannot_move(
        "state",
        register_prefix=(
            "a retention state (brumby) has no prefix reuse yet: a shared "
            "head would need the state snapshotted at its last page's "
            "edge, 36 MiB a layer at the published widths (ROADMAP.md)"),
        reconfigure=(
            "a retention state (brumby) lives beside the page pool: a "
            "rebuilt pool cannot replay it")))
