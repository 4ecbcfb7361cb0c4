"""Nemotron-3 (`nemotron_h`) on the paged engine: the step programs.

The equations are models/reference/nemotron_h.py's; this is how the
served path computes them over the page pool and, beside it, the rows'
recurrent state (models/llama/paged.HybridPagedCache says what a row's
state is).

Both step programs run ONE trunk over a flat list of tokens, each with
its row (slot) and position: a decode step's B tokens, or a mixed step's
packed axis (paged.pack_plan). Every block is one mixer behind one norm
and one residual (a Python loop over the stacks per kind of block, as
models/moe/glm_dsa.py's):

  * `M`, Mamba-2. `ssm_in`: one projection of every packed token.
    `ssm_conv`: the causal depthwise conv along each ROW's tokens
    (`causal_conv_rows`, which models/moe/bailing_hybrid.py calls too); the
    K-1 inputs before a row's first token come from the row's stored
    tail, never from the packed neighbour. The recurrence runs in its
    two forms, the same mathematics: `ssm_step`, the one-step update of
    every row that holds ONE token (a decode step's rows; the decode
    rows of a mixed dispatch), and `ssm_scan`, the chunked form (SSD,
    chunks of `chunk_size`) over the dispatch's one window, starting
    from the row's stored state. The one-step form is a trunk's to
    choose (`mamba_block(step=)`): here it is XLA over the layer's
    whole state, which at these shapes compiles to ONE fusion that
    reads the state once and writes it once; models/moe/
    granite_hybrid.py, whose shapes XLA gives two or three passes,
    passes ops/ssm.step (`cake_ssm_step`: the stepping rows' state
    alone, in place). `ssm_state` is every read and write of
    the stored state: read once a block, zeroed on the way in for a row
    whose first token sits at position 0 (a request that takes the
    slot: no launch of its own), written once a block; a row with no
    token in the dispatch keeps its bits. `ssm_gate`: y * silu(z) and
    the RMS norm over each group. `ssm_out`: the output projection.
  * `*`, attention: GQA without a positional embedding over the K/V
    page pool, which holds the attention blocks alone: every row's
    single token through the decode kernel (`cake_decode_attn`), the
    window through the mixed kernel (`cake_mixed_attn`) in sub-windows
    of at most 128 queries (what its VMEM holds at 32 heads of 128),
    each with its XLA fold.
  * `E`, LatentMoE: ops/moe.moe_mlp with the sigmoid rule, the held
    experts, relu² without a gate, the latent projections and the
    shared expert.

ONE WINDOW A DISPATCH, as for latent attention: the chunked scan takes
the one row whose tokens are contiguous on the packed axis and whose
state it starts from, so a mixed dispatch holds at most one row with
more than one token (serve/engine._mixed_groups) and there is ONE packed
size (paged.mixed_token_buckets(..., prefill_rows=(1,))): with one
program a row's bits do not depend on what shares its step, and an E
block's choice of 22 among 512 is discrete.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from cake_tpu.models.family import Family, Windows, cannot_move
from cake_tpu.models.llama import paged
from cake_tpu.models.llama.paged import HybridPagedCache, write_token_rows
from cake_tpu.models.moe.config import NemotronHConfig
from cake_tpu.models.moe.glm_dsa import _window_slice
from cake_tpu.models.step_programs import (
    make_decode_scan, make_mixed_sampled,
)
from cake_tpu.ops import ragged_paged_attention as rpa
from cake_tpu.ops import ssm as ssm_ops
from cake_tpu.ops.moe import LayerOf, moe_mlp
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import QTensor, qmatmul

MAMBA_LEAVES = ("w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                "ssm_norm", "w_out")
ATTN_LEAVES = ("wq", "wk", "wv", "wo")
SPARSE_LEAVES = ("router", "router_bias", "w_fc1", "w_fc2", "ws_up",
                 "ws_down")
EXPERT_LEAVES = ("we_up", "we_down")
# the record keys of the vector a step program returns, in trunk's
# order: the expert counters' five, the routed rows, then the rows'
# recurrent state and the two forms of the scan
COUNTERS = paged.MOE_COUNTERS + (
    "moe_rows_routed", "ssm_state_rows", "ssm_tokens_scanned",
    "ssm_tokens_stepped", "ssm_state_resets")
N_COUNTERS = len(COUNTERS)
# queries a sub-window of the mixed attention kernel holds
ATTN_SUBWINDOW = 128
F32 = jnp.float32


class Rows(NamedTuple):
    """A dispatch as its rows see it, each [B]: first (a row's first
    packed index; an idle row's is its successor's), n (its tokens in
    this dispatch, 0 = none), pos (its first token's position)."""

    first: jnp.ndarray
    n: jnp.ndarray
    pos: jnp.ndarray


class Window(NamedTuple):
    """The one row of a mixed dispatch that holds more than one token:
    row (its slot), start (its first packed index), width (static C),
    n (its tokens, 0 where no row has more than one), member [T] (the
    packed positions that are its tokens), col [T] (a packed position's
    index in its row's tokens)."""

    row: jnp.ndarray
    start: jnp.ndarray
    width: int
    n: jnp.ndarray
    member: jnp.ndarray
    col: jnp.ndarray


def block_leaves(blocks, config: NemotronHConfig, i: int) -> dict:
    """Block i's leaves out of the stacks per kind (static indices);
    the experts as (stack, index) for the grouped matmul."""
    def at(names, j):
        return {k: jax.tree.map(lambda a: a[j], blocks[k]) for k in names}

    lp = at(("norm",), i)
    kind = config.pattern[i]
    if kind == "M":
        lp.update(at(MAMBA_LEAVES, config.mamba_layers.index(i)))
    elif kind == "*":
        lp.update(at(ATTN_LEAVES, config.attn_layers.index(i)))
    else:
        j = config.sparse_layers.index(i)
        lp.update(at(SPARSE_LEAVES, j))
        lp.update({k: LayerOf(blocks[k], jnp.int32(j))
                   for k in EXPERT_LEAVES})
    return lp


def dequantized(leaf):
    """A leaf as float32 [..., in, out]: a per-channel QTensor times
    its scale."""
    if isinstance(leaf, QTensor):
        # (a host leaf crosses as int8 and widens on the device)
        return (jnp.asarray(leaf.q).astype(F32)
                * jnp.expand_dims(jnp.asarray(leaf.scale), leaf.q.ndim - 2))
    return jnp.asarray(leaf).astype(F32)


def reference_blocks(blocks, config: NemotronHConfig):
    """The per-block float32 dicts models/reference/nemotron_h.forward
    walks, one at a time (a generator: a caller at published widths
    holds one block's float32 weights at a time): the served leaves
    dequantized, `kind` beside them, the conv's weight in the published
    [channels, K] layout."""
    for i, kind in enumerate(config.pattern):
        lp = {k: dequantized(jax.tree.map(lambda a: a[int(v.layer)],
                                          v.stacked)
                             if isinstance(v, LayerOf) else v)
              for k, v in block_leaves(blocks, config, i).items()}
        if kind == "M":
            lp["conv_w"] = lp["conv_w"].T
        yield dict(lp, kind=kind)


def _mm(eq: str, a, b, dtype):
    """An einsum of the chunked scan: operands in the activations' type
    (the MXU's), float32 accumulation."""
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=F32)


# -- the recurrence, in its two forms ------------------------------------------


def ssm_step(S, x, Bm, Cm, dt, a, D):
    """One token a row. S [B, H, P, N] f32; x [B, H, P]; Bm, Cm
    [B, G, N]; dt, a = dt * A [B, H] f32; D [H] ->
    (S_new [B, H, P, N] f32, y [B, H, P] f32)."""
    B, H, P, N = S.shape
    G = Bm.shape[1]
    grouped = (B, G, H // G)
    Sg = S.reshape(grouped + (P, N))
    dtx = (dt[..., None] * x.astype(F32)).reshape(grouped + (P, 1))
    S_new = (jnp.exp(a).reshape(grouped + (1, 1)) * Sg
             + dtx * Bm.astype(F32)[:, :, None, None, :])
    y = jnp.sum(S_new * Cm.astype(F32)[:, :, None, None, :], axis=-1)
    y = y.reshape(B, H, P) + D[None, :, None] * x.astype(F32)
    return S_new.reshape(B, H, P, N), y


def step_codes(rows: Rows):
    """What ops/ssm.step (and ops/kda.step) does with each row [B]
    int32: a row that holds one token steps, from zeros if that token
    sits at position 0; any other row (idle, or the dispatch's window)
    stays."""
    return jnp.where(rows.n == 1,
                     jnp.where(rows.pos == 0, ssm_ops.FRESH, ssm_ops.STEP),
                     ssm_ops.STAY).astype(jnp.int32)


def ssm_step_fold(state, j, code, x, Bm, Cm, dt, a, D):
    """ops/ssm.step's contract in XLA over `ssm_step`: layer j of the
    stack read whole, every row stepped, the stepping rows' results
    kept (`mamba_block`'s own select and write-back). The kernel's
    comparison (tests/test_ssm_kernel.py) and tools/ssm_step_bench.py's
    other side; no step program calls it."""
    S_old = lax.dynamic_index_in_dim(state, j, 0, keepdims=False)
    S_new, y = ssm_step(
        jnp.where((code == ssm_ops.FRESH)[:, None, None, None], 0.0, S_old),
        x, Bm, Cm, dt, a, D)
    steps = code != ssm_ops.STAY
    return (lax.dynamic_update_index_in_dim(
                state, jnp.where(steps[:, None, None, None], S_new, S_old),
                j, 0),
            jnp.where(steps[:, None, None], y, 0.0))


def ssm_scan(S0, x, Bm, Cm, dt, a, D, chunk: int):
    """A window of C tokens of ONE row, chunked (SSD). S0 [H, P, N] f32,
    the state the window starts from; x [C, H, P]; Bm, Cm [C, G, N];
    dt, a [C, H] f32 (0 past the row's real tokens: the state passes
    through them unchanged) -> (S_end [H, P, N] f32, y [C, H, P] f32).

    Within a chunk of Q tokens, with cum the running sum of a:
    y_i = sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
          + exp(cum_i) C_i . S_start;
    across chunks S_start' = exp(cum_Q) S_start + sum_j exp(cum_Q -
    cum_j) dt_j x_j (x) B_j."""
    C, H, P = x.shape
    G, N = Bm.shape[1], Bm.shape[2]
    Q = min(chunk, C)
    pad = -C % Q
    if pad:
        x, Bm, Cm, dt, a = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                            for v in (x, Bm, Cm, dt, a))
    nc = (C + pad) // Q
    Hg = H // G
    dtype = x.dtype
    x = x.reshape(nc, Q, G, Hg, P)
    Bm, Cm = Bm.reshape(nc, Q, G, N), Cm.reshape(nc, Q, G, N)
    dt, a = dt.reshape(nc, Q, G, Hg), a.reshape(nc, Q, G, Hg)
    cum = jnp.cumsum(a, axis=1)                              # [nc, Q, G, Hg]
    dtx = dt[..., None] * x.astype(F32)                      # [nc, Q, G, Hg, P]
    # inside a chunk
    cb = _mm("cign,cjgn->cgij", Cm, Bm, dtype)               # [nc, G, Q, Q]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # exp of a difference that is <= 0 wherever it is kept
    decay = jnp.exp(jnp.where(
        causal[None, :, :, None, None],
        cum[:, :, None] - cum[:, None, :], -jnp.inf))        # [nc, i, j, G, Hg]
    m = cb.transpose(0, 2, 3, 1)[..., None] * decay          # [nc, i, j, G, Hg]
    y = _mm("cijgh,cjghp->cighp", m, dtx, dtype)
    # what a chunk adds to the state, and the state each starts from
    to_end = jnp.exp(cum[:, -1:] - cum)                      # [nc, Q, G, Hg]
    added = _mm("cjghp,cjgn->cghpn", to_end[..., None] * dtx, Bm, dtype)
    S = S0.reshape(G, Hg, P, N)
    starts = []
    for c in range(nc):
        starts.append(S)
        S = jnp.exp(cum[c, -1])[..., None, None] * S + added[c]
    y = y + jnp.exp(cum)[..., None] * _mm(
        "cign,cghpn->cighp", Cm, jnp.stack(starts), dtype)
    y = y + D.reshape(G, Hg)[None, None, :, :, None] * x.astype(F32)
    return S.reshape(H, P, N), y.reshape(nc * Q, H, P)[:C]


def causal_conv_rows(x, tail, taps, bias, slot, rows: Rows):
    """The causal depthwise conv along each ROW's tokens, then SiLU:
    x [T, ch] the packed tokens' inputs, tail [B, K-1, ch] each row's
    last K-1 inputs before this dispatch, taps [K, ch], bias [ch] or
    None -> (silu(conv) [T, ch] in x's type, the rows' last K-1 inputs
    for the step after this one [B, K-1, ch]; an idle row's are what it
    had)."""
    T, B, K = x.shape[0], tail.shape[0], taps.shape[0]
    # a token's input d places back in its ROW: the packed neighbour
    # while that is the row's own, else the tail
    col = jnp.arange(T, dtype=jnp.int32) - rows.first[slot]
    ext = jnp.concatenate(
        [tail.reshape(B * (K - 1), -1).astype(x.dtype), x], 0)
    t = jnp.arange(T, dtype=jnp.int32)
    acc = 0.0 if bias is None else bias.astype(F32)[None, :]
    for d in range(K):
        if d == 0:
            x_d = x          # the token's own input
        else:
            src = jnp.where(col >= d, B * (K - 1) + t - d,
                            slot * (K - 1) + col - d + K - 1)
            x_d = jnp.take(ext, src, axis=0)
        acc = acc + taps[K - 1 - d].astype(F32)[None, :] * x_d.astype(F32)
    u = jax.nn.silu(acc).astype(x.dtype)
    # the row's last K-1 inputs, for the step after this one
    back = rows.n[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]
    src = jnp.where(
        back >= 0, B * (K - 1) + rows.first[:, None] + back,
        jnp.arange(B)[:, None] * (K - 1) + back + K - 1)
    return u, jnp.take(ext, src, axis=0)


# -- the blocks ----------------------------------------------------------------


def mamba_block(lp, h, ssm, conv, j: int, slot, real, rows: Rows,
                config: NemotronHConfig, window: Optional[Window],
                step=None):
    """h [T, D] -> (out [T, D], ssm, conv): Mamba block j of the stacked
    state, over the packed tokens. step: the one-step form over the
    stored stack, ops/ssm.step's contract (a stepping row's state in
    place; the window's row read before it and written after it, so no
    layer's state is ever a value); None: `ssm_step` in XLA over the
    layer's whole state."""
    c = config
    T = h.shape[0]
    H, P, G, N = (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                  c.ssm_state_size)
    di = c.d_inner
    with jax.named_scope("qkv"), jax.named_scope("ssm_in"):
        zxd = qmatmul(h, lp["w_in"])
        z, xBC, dt = (zxd[:, :di], zxd[:, di:di + c.conv_dim],
                      zxd[:, di + c.conv_dim:])
    has = rows.n > 0
    fresh = has & (rows.pos == 0)
    with jax.named_scope("attn"):
        with jax.named_scope("ssm_state"):
            tail = jnp.where(fresh[:, None, None], 0, conv[j])   # [B, K-1, ch]
            if step is None:
                S_old = ssm[j]
                S_in = jnp.where(fresh[:, None, None, None], 0.0, S_old)
            elif window is not None:
                # the window's row alone, as stored BEFORE this layer's
                # step (it stays there): 2 MiB at Granite's widths. Read
                # HERE: fused into the scan's consumers it is a read of
                # the old stack after the kernel's write in place, and
                # XLA copies the stack whole to keep one
                S0, ssm = lax.optimization_barrier(
                    (jnp.where(fresh[window.row], 0.0, ssm[j, window.row]),
                     ssm))
        with jax.named_scope("ssm_conv"):
            u, new_tail = causal_conv_rows(xBC, tail, lp["conv_w"],
                                           lp["conv_b"], slot, rows)
            new_tail = new_tail.astype(conv.dtype)
        xs = u[:, :di].reshape(T, H, P)
        Bm = u[:, di:di + G * N].reshape(T, G, N)
        Cm = u[:, di + G * N:].reshape(T, G, N)
        dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"][None, :])
        # a token that is not real leaves the state as it is
        dt = jnp.where(real[:, None], dt, 0.0)
        a = dt * -jnp.exp(lp["A_log"].astype(F32))[None, :]
        D = lp["D"].astype(F32)
        with jax.named_scope("ssm_step"):
            at = jnp.minimum(rows.first, T - 1)
            if step is None:
                S_new, y1 = ssm_step(S_in, xs[at], Bm[at], Cm[at], dt[at],
                                     a[at], D)
            else:
                ssm, y1 = step(ssm, j, step_codes(rows), xs[at], Bm[at],
                               Cm[at], dt[at], a[at], D)
        single = rows.n == 1
        if window is None:
            y = y1[slot]
        else:
            with jax.named_scope("ssm_scan"):
                # the slice runs on into the next rows' tokens: past the
                # window's own the state passes through unchanged
                own = (jnp.arange(window.width) < window.n)[:, None]
                S_win, yw = ssm_scan(
                    S_in[window.row] if step is None else S0,
                    *(_window_slice(v, window) for v in (xs, Bm, Cm)),
                    jnp.where(own, _window_slice(dt, window), 0.0),
                    jnp.where(own, _window_slice(a, window), 0.0),
                    D, c.chunk_size)
            y = jnp.where(window.member[:, None, None], yw[window.col],
                          y1[slot])
        with jax.named_scope("ssm_state"):
            if step is None:
                ssm = ssm.at[j].set(
                    jnp.where(single[:, None, None, None], S_new, S_old))
            conv = conv.at[j].set(new_tail)
            if window is not None:
                ssm = ssm.at[j, window.row].set(
                    jnp.where(window.n > 1, S_win, ssm[j, window.row]))
        with jax.named_scope("ssm_gate"):
            y = (y.reshape(T, di) * jax.nn.silu(z.astype(F32))
                 ).reshape(T, G, di // G)
            y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + c.rms_norm_eps)
            y = (y.reshape(T, di) * lp["ssm_norm"].astype(F32)[None, :]
                 ).astype(h.dtype)
    with jax.named_scope("o_proj"), jax.named_scope("ssm_out"):
        return qmatmul(y, lp["w_out"]), ssm, conv


def attention_block(lp, h, pool_k, pool_v, j: int, table, slot, position,
                    real, rows: Rows, config: NemotronHConfig, attn: str,
                    window: Optional[Window],
                    scale: Optional[float] = None,
                    subwindow: int = ATTN_SUBWINDOW):
    """h [T, D] -> (out [T, D], pool_k, pool_v): attention block j of
    the K/V pool. No positional embedding. scale: the scores'
    multiplier where the model states its own (None: 1/sqrt(hd));
    subwindow: queries a sub-window of the mixed kernel holds (what
    models/moe/granite_hybrid.py passes for its heads of 64)."""
    c = config
    T = h.shape[0]
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    layer = jnp.int32(j)
    with jax.named_scope("qkv"):
        q = qmatmul(h, lp["wq"]).reshape(T, H, hd)
        k, v = qmatmul(h, lp["wk"]), qmatmul(h, lp["wv"])
    with jax.named_scope("attn"):
        pool_k = write_token_rows(pool_k, j, k, slot, position, real, table)
        pool_v = write_token_rows(pool_v, j, v, slot, position, real, table)
        at = jnp.minimum(rows.first, T - 1)
        last_pos = table.shape[1] * pool_k.shape[2] - 1
        pos = jnp.clip(rows.pos, 0, last_pos)
        out = paged.paged_attention(q[at][:, None], pool_k, pool_v, layer,
                                    table, pos, impl=attn,
                                    scale=scale)[:, 0]
        if window is None:
            o = out[slot]
        else:
            C = window.width
            sub = min(C, subwindow)
            n_sub = C // sub
            starts = jnp.arange(n_sub, dtype=jnp.int32) * sub
            win = paged.paged_attention_mixed(
                _window_slice(q, window).reshape(n_sub, sub, H, hd),
                pool_k, pool_v, layer,
                jnp.broadcast_to(table[window.row][None],
                                 (n_sub, table.shape[1])),
                jnp.minimum(pos[window.row] + starts, last_pos),
                jnp.clip(window.n - starts, 0, sub), impl=attn,
                scale=scale)
            o = jnp.where(window.member[:, None, None],
                          win.reshape(C, H, hd)[window.col], out[slot])
    with jax.named_scope("o_proj"):
        return qmatmul(o.reshape(T, H * hd), lp["wo"]), pool_k, pool_v


class TrunkOut(NamedTuple):
    """x [T, D] after the final norm; cache; counters [N_COUNTERS]; and
    each E block's routing [L_E, T, k], for a tool that compares it
    with the reference's (chip_compare.py; a step program drops it)."""

    x: jnp.ndarray
    cache: HybridPagedCache
    counters: jnp.ndarray
    experts: jnp.ndarray


def trunk(params, token_ids, slot, position, real, rows: Rows,
          cache: HybridPagedCache, config: NemotronHConfig, attn: str,
          window: Optional[Window] = None) -> TrunkOut:
    """Embed, every block, final norm, over T tokens: token_ids, slot,
    position [T] int32, real [T] bool (a token that is not real writes
    nothing, is not routed, moves no state, and its output is garbage
    nobody reads)."""
    c = config
    blocks = params["blocks"]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], token_ids, axis=0)
    first_expert = (c.first_routed_expert
                    if c.num_local_experts < c.n_routed_experts_total
                    else None)
    pool_k, pool_v, table = cache.k, cache.v, cache.table
    ssm, conv = cache.ssm, cache.conv
    moe, experts = [], []
    with jax.named_scope("layers"):
        for i, kind in enumerate(c.pattern):
            lp = block_leaves(blocks, c, i)
            with jax.named_scope("attn_norm"):
                h = rms_norm(x, lp["norm"], c.rms_norm_eps)
            if kind == "M":
                out, ssm, conv = mamba_block(
                    lp, h, ssm, conv, c.mamba_layers.index(i), slot, real,
                    rows, c, window)
            elif kind == "*":
                out, pool_k, pool_v = attention_block(
                    lp, h, pool_k, pool_v, c.attn_layers.index(i), table,
                    slot, position, real, rows, c, attn, window)
            else:
                with jax.named_scope("ffn"):
                    out, stats = moe_mlp(
                        lp, h[None], c.num_experts_per_tok,
                        c.norm_topk_prob, token_mask=real[None],
                        first_expert=first_expert, scoring=c.scoring_func,
                        scale=c.routed_scaling_factor, act="relu2")
                    out = out[0]
                moe.append(stats)
                experts.append(stats.experts)
            x = x + out
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)

    def over(field, reduce):
        return reduce(jnp.stack([getattr(s, field) for s in moe]))

    Lm = len(c.mamba_layers)
    has = rows.n > 0
    n_rows = jnp.sum(has, dtype=F32)
    stepped = jnp.sum(rows.n == 1, dtype=F32)
    scanned = jnp.sum(jnp.where(rows.n > 1, rows.n, 0), dtype=F32)
    counters = jnp.stack([
        over("rows", jnp.sum), over("rows_padded", jnp.sum),
        over("load_max", jnp.mean), over("load_mean", jnp.mean),
        over("touched", jnp.sum), over("rows_routed", jnp.sum),
        Lm * n_rows, Lm * scanned, Lm * stepped,
        jnp.sum(has & (rows.pos == 0), dtype=F32)]).astype(F32)
    return TrunkOut(x, cache._replace(k=pool_k, v=pool_v, ssm=ssm, conv=conv),
                    counters, jnp.stack(experts))


# -- the step programs ---------------------------------------------------------


def window_of(plan: paged.PackPlan, n) -> Window:
    """The dispatch's one window: the row with the most tokens."""
    row = jnp.argmax(n).astype(jnp.int32)
    held = jnp.where(n[row] > 1, n[row], 0)
    return Window(row, plan.start[row], plan.width, held,
                  (plan.row == row) & (held > 0) & plan.real, plan.col)


def mixed_trunk(params, tokens, pos, q_len, active,
                cache: HybridPagedCache, config: NemotronHConfig, attn: str,
                n_tokens: int):
    """The mixed step's trunk on the packed axis [n_tokens] ->
    (TrunkOut, PackPlan)."""
    C = tokens.shape[1]
    if C > ATTN_SUBWINDOW and C % ATTN_SUBWINDOW:
        raise ValueError(
            f"a window of {C} tokens: wider than {ATTN_SUBWINDOW} it must "
            f"be a multiple of {ATTN_SUBWINDOW} (the attention blocks' "
            "sub-windows)")
    plan = paged.pack_plan(q_len, active, n_tokens, C)
    n = jnp.where(active, q_len, 0).astype(jnp.int32)
    out = trunk(params, tokens[plan.row, plan.col], plan.row,
                pos[plan.row] + plan.col, plan.real,
                Rows(plan.start, n, pos.astype(jnp.int32)), cache, config,
                attn, window_of(plan, n))
    return out, plan


@partial(jax.jit, static_argnames=("config", "attn", "n_tokens"),
         donate_argnames=("cache",))
def mixed_step_hybrid(params, tokens, pos, q_len, active,
                      cache: HybridPagedCache, rope,
                      config: NemotronHConfig, attn: str = "fold",
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
        logits = qmatmul(last, params["lm_head"]).astype(F32)
    return logits, out.cache, out.counters


def decode_trunk(params, tokens, cache: HybridPagedCache, pos, active,
                 config: NemotronHConfig, attn: str) -> TrunkOut:
    """One token a row: tokens [B, 1], pos/active [B]."""
    B = tokens.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    pos = pos.astype(jnp.int32)
    return trunk(params, tokens[:, 0], rows, pos, active,
                 Rows(rows, active.astype(jnp.int32), pos), cache, config,
                 attn)


def forward_ragged_hybrid(params, tokens, cache: HybridPagedCache, pos,
                          active, rope, config: NemotronHConfig,
                          attn: str = "fold"):
    """paged.forward_ragged_paged(..., counters=True)'s contract: what
    step_programs.make_decode_scan builds the sampled decode programs
    from -> (logits [B, V], cache, counters)."""
    del rope
    out = decode_trunk(params, tokens, cache, pos, active, config, attn)
    with jax.named_scope("head"):
        logits = qmatmul(out.x, params["lm_head"]).astype(F32)
    return logits, out.cache, out.counters


@partial(jax.jit, static_argnames=("config", "attn"),
         donate_argnames=("cache",))
def decode_step_hybrid(params, tokens, pos, active, cache: HybridPagedCache,
                       rope, config: NemotronHConfig, attn: str = "fold"):
    """paged.decode_step_ragged_paged's contract (the synchronous
    decode step)."""
    return forward_ragged_hybrid(params, tokens, cache, pos, active, rope,
                                 config, attn)


# -- what the engine reads of this family (models/family.py) ----------------


def create_cache(config: NemotronHConfig, slots: int, n_pages: int,
                 page_size: int, max_seq_len: int, width, dtype):
    """K/V pages for the attention blocks alone, and a state a ROW for
    each Mamba block: the SSM state and the last conv_kernel - 1 inputs
    of its causal conv."""
    c = config
    L_M = len(c.mamba_layers)
    return HybridPagedCache.zeros(
        (len(c.attn_layers), n_pages, page_size,
         c.num_key_value_heads * c.head_dim),
        slots, max_seq_len // page_size, dtype,
        ssm=(L_M, slots, c.mamba_num_heads, c.mamba_head_dim,
             c.ssm_state_size),
        conv=(L_M, slots, c.conv_kernel - 1, c.conv_dim))


def _resolve_attn(config, impl: str, *, explicit: bool, prefill_chunk,
                  slots: int, n_pages: int, page_size: int,
                  max_seq_len: int, q_itemsize: int, kv_itemsize: int):
    """One impl for both step programs: the mixed program runs the
    decode kernel over the rows' single tokens and the mixed kernel
    over the window in sub-windows (what its VMEM holds), so its gate
    is asked at the sub-window."""
    c = config
    width = prefill_chunk or min(512, max_seq_len)
    if width > ATTN_SUBWINDOW and width % ATTN_SUBWINDOW:
        raise ValueError(
            f"--prefill-chunk {width}: model_type nemotron_h takes a "
            f"window of at most {ATTN_SUBWINDOW} tokens or a multiple of "
            f"{ATTN_SUBWINDOW}")
    heads = (c.num_attention_heads, c.num_key_value_heads, c.head_dim)
    max_pages = -(-max_seq_len // page_size)
    ok = (rpa.ragged_paged_supported(
              page_size, *heads, n_pages=n_pages, slots=slots,
              max_pages=max_pages)
          and rpa.ragged_paged_mixed_supported(
              page_size, *heads, min(width, ATTN_SUBWINDOW),
              n_pages=n_pages, slots=-(-width // ATTN_SUBWINDOW),
              max_pages=max_pages, q_itemsize=q_itemsize,
              kv_itemsize=kv_itemsize))
    if impl == "pallas" and not ok:
        if explicit:
            raise ValueError(
                "--paged-attn pallas cannot serve model_type nemotron_h "
                f"on this device at page={page_size} heads={heads} mixed "
                f"width={width} (ops/ragged_paged_attention gates); use "
                "--paged-attn auto or fold")
        impl = "fold"
    return impl, width


FAMILY = Family(
    name="nemotron_h", decode_step=decode_step_hybrid,
    decode_programs=make_decode_scan(forward_ragged_hybrid),
    mixed_step=mixed_step_hybrid,
    mixed_sampled=make_mixed_sampled(mixed_step_hybrid),
    create_cache=create_cache, counters=COUNTERS,
    # one window a dispatch (module docstring), so one packed size; and
    # one a step, which is what kept this family's callers out of
    # convoys (family.Windows)
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
            "a recurrent state (nemotron_h) has no prefix reuse yet: a "
            "shared head would need the state snapshotted at its last "
            "page's edge (ROADMAP.md)"),
        reconfigure=(
            "a recurrent state (nemotron_h) lives beside the page pool: "
            "a rebuilt pool cannot replay it")))
