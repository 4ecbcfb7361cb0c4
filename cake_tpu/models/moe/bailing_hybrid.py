"""Ling-3.0 (`bailing_hybrid`) on the paged engine: the step programs.

The equations are models/reference/bailing_hybrid.py's; this is how the
served path computes them over the page pool and, beside it, the rows'
matrix state (models/llama/paged.HybridPagedCache: `k` the latent pool
of the MLA layers alone, `v` empty as DeepSeek-V2's, `ssm` the KDA state
[L_kda, slots, H, d_k, d_v] float32, `conv` the q | k | v tails of the
short conv [L_kda, slots, K-1, 3 * width]).

Both step programs run ONE trunk over a flat list of tokens, each with
its row (slot) and position: a decode step's B tokens, or a mixed step's
packed axis (paged.pack_plan). A layer is a mixer behind `attn_norm` and
an FFN behind `mlp_norm`, each with its residual (a Python loop over the
stacks per kind of layer, as models/moe/glm_dsa.py's):

  * a KDA layer (Kimi Delta Attention). `kda_in`: ONE projection of
    every packed token into [q | k | v | decay | output gate], and the
    write strength a head. `kda_conv`: the causal depthwise conv along
    each ROW's tokens over q | k | v and SiLU
    (nemotron_h.causal_conv_rows: the K-1 inputs before a row's first
    token come from the row's stored tail). `kda_gate`: the L2 norms of
    q and k, the bounded decay g = lower_bound * sigmoid(exp(A_log) *
    (a + dt_bias)) a key channel and beta = sigmoid(.) a head, float32.
    The delta rule S <- (I - beta k k^T) Diag(exp(g)) S + beta k v^T,
    o = S^T q runs in its two forms, the same mathematics. `kda_step`:
    the recurrence itself on every row that holds ONE token (a decode
    step's rows; the decode rows of a mixed dispatch), served by
    ops/kda.step (`cake_kda_step`): ONE kernel that fetches a stepping
    row's stored state once, updates it and writes it once, in place
    in the stack; a row that takes the slot (its token at position 0)
    starts from zeros with no read, and a row with no single token is
    neither read nor written. The function `kda_step` below is the same
    operations in jax.numpy: the comparison's form, not the served one.
    `kda_chunk`: the chunked form over the dispatch's one window,
    starting from the row's stored state, served by ops/kda.chunked
    (`cake_kda_chunk`): ONE kernel that holds a head block's state on
    the chip from the window's first chunk to its last. The function
    `kda_chunked` below is the same mathematics in XLA (a batched part,
    then a 32-step scan that carries the state through HBM): the
    comparison's form, not the served one. `kda_state` is
    what is left of the stored state's traffic outside the kernel: the
    conv tails (read and written once a layer), and the window row's
    own state, read before the step and written after the chunks (2 MiB
    each way at the published widths), zeroed on the way in for a row
    whose first token sits at position 0 (a request that takes the
    slot: no launch of its own); a row with no token in the dispatch
    keeps its bits. `kda_out`: the RMS
    norm a head, the sigmoid gate a channel, the output projection;
  * an MLA layer: models/moe/glm_dsa.py's dense kind, CALLED
    (`project_latent` with a full-rank query, `attend_dense`: a row's
    single token walks its live pages with `cake_mla_decode_attn`, the
    window attends under causality with `cake_mla_window_attn`;
    `gate_heads`: dots3's sigmoid a head);
  * the FFN (`glm_dsa.ffn`): dense SwiGLU, or ops/moe.moe_mlp with the
    sigmoid rule, the choice bias, the groups by the sum of their two
    best, the held experts and the shared expert.

ONE WINDOW A DISPATCH AND A STEP, as for nemotron_h: the chunked rule
takes the one row whose tokens are contiguous on the packed axis and
whose state it starts from, so there is ONE packed size and a row's bits
do not depend on what shares its step.
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
from cake_tpu.models.moe import glm_dsa
from cake_tpu.models.moe.config import BailingHybridConfig
from cake_tpu.models.moe.glm_dsa import Window, _window_slice
from cake_tpu.models.moe.nemotron_h import (
    Rows, causal_conv_rows, dequantized, step_codes,
)
from cake_tpu.models.step_programs import (
    make_decode_scan, make_mixed_sampled,
)
from cake_tpu.ops import kda
from cake_tpu.ops.moe import LayerOf
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import qmatmul

NORM_LEAVES = ("attn_norm", "mlp_norm")
KDA_LEAVES = ("w_kda_in", "w_kda_beta", "kda_conv_w", "A_log", "dt_bias",
              "kda_norm", "w_kda_out")
MLA_LEAVES = ("wq_a", "q_a_norm", "wq_b", "wq", "wkv_a", "kv_a_norm",
              "wkv_b_k", "wkv_b_v", "wo", glm_dsa.GATE_LEAF)
# the record keys of the vector a step program returns, in trunk's
# order: DeepSeek-V2's eight (the held experts' five, the routed rows,
# the tokens whose groups include a held one, the keys the single-token
# rows attended), then the two forms of the delta rule and the rows
# whose state a step read and wrote
COUNTERS = glm_dsa.DENSE_COUNTERS + (
    "kda_tokens_chunked", "kda_tokens_stepped", "kda_state_rows")
# tokens a chunk of the chunked rule holds. exp(-G) of a chunk's summed
# log-decays must stay finite in float32: at the published bound of -5 a
# token that is 16 tokens (5 x 16 = 80 < 88): the kernel's own constant
CHUNK = kda.CHUNK
L2_EPS = 1e-6
F32 = jnp.float32
_mm = partial(jnp.einsum, precision=lax.Precision.HIGHEST)


def layer_leaves(blocks, config: BailingHybridConfig, i: int) -> dict:
    """Layer i's leaves out of the stacks per kind (static indices); the
    experts as (stack, index) for the grouped matmul."""
    def at(names, j):
        return {k: jax.tree.map(lambda a: a[j], blocks[k]) for k in names
                if k in blocks}

    c = config
    lp = at(NORM_LEAVES, i)
    if c.indexer_types[i] == "kda":
        lp.update(at(KDA_LEAVES, c.kda_layers.index(i)))
    else:
        lp.update(at(MLA_LEAVES, c.latent_layers.index(i)))
    if c.mlp_layer_types[i] == "sparse":
        j = c.sparse_layers.index(i)
        lp.update(at(glm_dsa.SPARSE_LEAVES, j))
        lp.update({k: LayerOf(blocks[k], jnp.int32(j))
                   for k in glm_dsa.EXPERT_LEAVES})
    else:
        lp.update(at(glm_dsa.DENSE_LEAVES,
                     i - sum(s < i for s in c.sparse_layers)))
    return lp


def reference_layers(blocks, config: BailingHybridConfig):
    """The per-layer float32 dicts models/reference/bailing_hybrid.forward
    walks, one at a time (a generator: a caller at published widths
    holds one layer's float32 weights at a time): the served leaves
    dequantized, `kind` beside them, the fused KDA projection split
    into the published five, the conv's taps in the published
    [channels, K] layout."""
    W = config.kda_width
    for i, kind in enumerate(config.indexer_types):
        lp = {k: dequantized(jax.tree.map(lambda a: a[int(v.layer)],
                                          v.stacked)
                             if isinstance(v, LayerOf) else v)
              for k, v in layer_leaves(blocks, config, i).items()}
        if kind == "kda":
            fused = lp.pop("w_kda_in")
            lp.update({f"w_kda_{n}": fused[:, j * W:(j + 1) * W]
                       for j, n in enumerate(("q", "k", "v", "f", "g"))})
            taps = lp.pop("kda_conv_w").T
            lp.update({f"kda_conv_{n}": taps[j * W:(j + 1) * W]
                       for j, n in enumerate(("q", "k", "v"))})
        yield dict(lp, kind="kda" if kind == "kda" else "mla")


# -- the delta rule, in its two forms ------------------------------------------


def kda_gate(a, dt_bias, A_log, lower_bound: float):
    """The bounded log-decay a key channel, float32: a [T, H, dk] the
    decay projection, dt_bias [H * dk], A_log [H] -> g in (lower_bound,
    0)."""
    T, H, dk = a.shape
    x = a.astype(F32) + dt_bias.astype(F32).reshape(1, H, dk)
    return lower_bound * jax.nn.sigmoid(
        jnp.exp(A_log.astype(F32))[None, :, None] * x)


def l2_normed(x):
    """x / |x| over the last axis, float32 (eps inside the root)."""
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_step(S, q, k, v, g, beta):
    """One token a row: the recurrence itself, in jax.numpy. The form
    every comparison holds the served path to (the tests, the float32
    reference's, an interpreter run); the served path itself runs
    ops/kda.step, these operations in this order inside one kernel
    over the stored state. S [B, H, dk, dv] f32; q, k [B, H, dk] f32
    (normed); v [B, H, dv]; g [B, H, dk] f32 (log-decay, 0: none); beta
    [B, H] f32 (0: the state passes unchanged) -> (S_new [B, H, dk, dv]
    f32, o [B, H, dv] f32). Products and sums on the vector unit in
    float32: the state's bytes are what it costs."""
    S = jnp.exp(g)[..., None] * S
    u = beta[..., None] * (v.astype(F32)
                           - jnp.sum(k[..., None] * S, axis=-2))
    S = S + k[..., None] * u[..., None, :]
    return S, jnp.sum(q[..., None] * S, axis=-2)


def kda_step_fold(state, j, code, q, k, v, g, beta):
    """ops/kda.step's contract in XLA over `kda_step`: layer j of the
    stack read whole, every row stepped, the stepping rows' results
    kept. The kernel's comparison (tests/test_kda_kernel.py) and
    tools/kda_step_bench.py's other side; no step program calls it."""
    S_old = lax.dynamic_index_in_dim(state, j, 0, keepdims=False)
    S_new, o = kda_step(
        jnp.where((code == kda.FRESH)[:, None, None, None], 0.0, S_old),
        q, k, v, g, beta)
    steps = code != kda.STAY
    return (lax.dynamic_update_index_in_dim(
                state, jnp.where(steps[:, None, None, None], S_new, S_old),
                j, 0),
            jnp.where(steps[:, None, None], o, 0.0))


def kda_chunked(S0, q, k, v, g, beta, chunk: int = CHUNK):
    """A window of C tokens of ONE row, chunked (the WY / UT form of the
    Kimi Linear paper). S0 [H, dk, dv] f32, the state the window starts
    from; q, k [C, H, dk] f32; v [C, H, dv]; g [C, H, dk] f32; beta
    [C, H] f32 (g and beta 0 past the row's real tokens: the state
    passes through them unchanged) -> (S_end [H, dk, dv] f32, o
    [C, H, dv] f32).

    Within a chunk of Q tokens, G the running sum of g from the chunk's
    start, K+ = k e^G, K- = k e^-G, Q+ = q e^G (|G| <= 5 Q = 80: both
    finite in float32, and every product that is kept is e^(G_i - G_j)
    with j <= i):
        A = beta_i tril(K+ K-^T, -1),   P = tril(Q+ K-^T)
        T = (I + A)^-1                  (unit lower triangular: forward
                                         substitution, row by row)
        W_v = T (beta V),  W_k = T (beta K+)
        U = W_v - W_k S,   o = Q+ S + P U,
        S' = Diag(e^G_Q) S + (k e^(G_Q - G))^T U.
    All float32 at the highest matmul precision: 2 GFLOP a layer and
    window at the published widths. The form every comparison holds the
    served path to (the tests, an interpreter run,
    tools/kda_chunk_bench.py's other side); the served path runs
    ops/kda.chunked, this mathematics inside one kernel. No step
    program calls it."""
    C, H, dk = k.shape
    Q = min(chunk, C)
    pad = -C % Q
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, g, beta))
    nc = (C + pad) // Q

    def chunks(x):                      # [C, H, d] -> [nc, H, Q, d]
        return x.reshape((nc, Q) + x.shape[1:]).swapaxes(1, 2)

    q, k, v, g = chunks(q), chunks(k), chunks(v.astype(F32)), chunks(g)
    beta = chunks(beta[..., None])                          # [nc, H, Q, 1]
    G = jnp.cumsum(g, axis=2)
    fade = jnp.exp(G)
    k_fade, k_grow, q_fade = k * fade, k * jnp.exp(-G), q * fade
    lower = jnp.tril(jnp.ones((Q, Q), bool), -1)
    A = jnp.where(lower, _mm("chik,chjk->chij", k_fade, k_grow), 0.0) * beta
    P = jnp.where(lower | jnp.eye(Q, dtype=bool),
                  _mm("chik,chjk->chij", q_fade, k_grow), 0.0)
    # T's row i = e_i - sum_{j<i} A_ij T_j (rows >= i are still zero)
    eye = jnp.eye(Q, dtype=F32)
    T = jnp.zeros_like(A)
    for i in range(Q):
        T = T.at[:, :, i].set(eye[i] - _mm("chj,chjq->chq", A[:, :, i], T))
    w_v = _mm("chij,chjv->chiv", T, beta * v)
    w_k = _mm("chij,chjk->chik", T, beta * k_fade)
    k_end = k * jnp.exp(G[:, :, -1:] - G)

    def chunk_step(S, xs):
        q_c, p_c, wv_c, wk_c, kend_c, fade_c = xs
        U = wv_c - _mm("hik,hkv->hiv", wk_c, S)
        o = _mm("hik,hkv->hiv", q_c, S) + _mm("hij,hjv->hiv", p_c, U)
        S = fade_c[..., None] * S + _mm("hik,hiv->hkv", kend_c, U)
        return S, o

    S, o = lax.scan(chunk_step, S0,
                    (q_fade, P, w_v, w_k, k_end, fade[:, :, -1]))
    return S, o.swapaxes(1, 2).reshape(nc * Q, H, -1)[:C]


# -- the layers ----------------------------------------------------------------


def kda_layer(lp, h, state, tails, j: int, slot, real, rows: Rows, first,
              config: BailingHybridConfig, window: Optional[Window]):
    """h [T, D] -> (out [T, D], state, tails): KDA layer j of the
    stacked state, over the packed tokens. first [B]: each row's first
    packed token."""
    c = config
    T = h.shape[0]
    H, dk, W = c.num_attention_heads, c.kda_head_dim, c.kda_width
    with jax.named_scope("qkv"), jax.named_scope("kda_in"):
        proj = qmatmul(h, lp["w_kda_in"])
        qkv, a, z = proj[:, :3 * W], proj[:, 3 * W:4 * W], proj[:, 4 * W:]
        b = qmatmul(h, lp["w_kda_beta"])
    fresh = (rows.n > 0) & (rows.pos == 0)
    with jax.named_scope("attn"):
        with jax.named_scope("kda_state"):
            tail = jnp.where(fresh[:, None, None], 0, tails[j])
            if window is not None:
                # the window's row alone, as stored BEFORE this layer's
                # step: 2 MiB at the published widths
                S0 = jnp.where(fresh[window.row], 0.0, state[j, window.row])
        with jax.named_scope("kda_conv"):
            u, new_tail = causal_conv_rows(qkv, tail, lp["kda_conv_w"], None,
                                           slot, rows)
        with jax.named_scope("kda_gate"):
            q, k, v = (u[:, n * W:(n + 1) * W].reshape(T, H, dk)
                       for n in range(3))
            q, k = l2_normed(q) * dk ** -0.5, l2_normed(k)
            # a token that is not real leaves the state as it is
            g = jnp.where(real[:, None, None],
                          kda_gate(a.reshape(T, H, dk), lp["dt_bias"],
                                   lp["A_log"], c.kda_lower_bound), 0.0)
            beta = jnp.where(real[:, None],
                             jax.nn.sigmoid(b.astype(F32)), 0.0)
        with jax.named_scope("kda_step"):
            state, o1 = kda.step(state, j, step_codes(rows), q[first],
                                 k[first], v[first], g[first], beta[first])
        if window is None:
            o = o1[slot]
        else:
            with jax.named_scope("kda_chunk"):
                # the slice runs on into the next rows' tokens: past the
                # window's own the state passes through unchanged
                own = window.real[:, None]
                S_win, ow = kda.chunked(
                    S0,
                    *(_window_slice(x, window) for x in (q, k, v)),
                    jnp.where(own[..., None], _window_slice(g, window), 0.0),
                    jnp.where(own, _window_slice(beta, window), 0.0))
            o = jnp.where(window.member[:, None, None], ow[window.col],
                          o1[slot])
        with jax.named_scope("kda_state"):
            tails = tails.at[j].set(new_tail.astype(tails.dtype))
            if window is not None:
                state = state.at[j, window.row].set(
                    jnp.where(jnp.any(window.real), S_win,
                              state[j, window.row]))
    with jax.named_scope("o_proj"), jax.named_scope("kda_out"):
        o = (o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                           + c.rms_norm_eps)
             * lp["kda_norm"].astype(F32)[None, None, :])
        y = o * jax.nn.sigmoid(z.astype(F32)).reshape(T, H, dk)
        return (qmatmul(y.reshape(T, W).astype(h.dtype), lp["w_kda_out"]),
                state, tails)


def mla_layer(lp, h, cos, sin, pool, j: int, table, slot, position, real,
              first, seen, config: BailingHybridConfig, attn: str,
              window: Optional[Window]):
    """h [T, D] -> (out [T, D], pool): MLA layer j of the latent pool,
    glm_dsa's dense kind of layer with a gate a head."""
    geo = config.geometry(config.latent_layers[j])
    with jax.named_scope("attn"):
        q_cat, pool, _ = glm_dsa.project_latent(
            lp, h, cos, sin, slot, position, real, pool, j, table, config,
            geo)
        o_lat = glm_dsa.attend_dense(q_cat, pool, j, table, slot, first,
                                     seen, geo, attn, window)
        o = glm_dsa.gate_heads(
            lp, h, glm_dsa.unabsorb_value(o_lat, lp["wkv_b_v"]))
    with jax.named_scope("o_proj"):
        return qmatmul(o.reshape(o.shape[0], -1), lp["wo"]), pool


class TrunkOut(NamedTuple):
    """x [T, D] after the final norm; cache; counters [len(COUNTERS)];
    and for a tool that compares them with the reference's
    (chip_compare.py; a step program drops them): experts [L_sparse, T,
    k], each sparse layer's choice, and ffn_in [L_sparse, T, D], each
    sparse layer's normed input (what its router read)."""

    x: jnp.ndarray
    cache: HybridPagedCache
    counters: jnp.ndarray
    experts: jnp.ndarray
    ffn_in: jnp.ndarray


def trunk(params, token_ids, slot, position, real, rows: Rows,
          cache: HybridPagedCache, rope, config: BailingHybridConfig,
          attn: str, window: Optional[Window] = None) -> TrunkOut:
    """Embed, every layer, final norm, over T tokens: token_ids, slot,
    position [T] int32, real [T] bool (a token that is not real writes
    nothing, is not routed, moves no state, and its output is garbage
    nobody reads)."""
    c = config
    blocks = params["blocks"]
    T = token_ids.shape[0]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], token_ids, axis=0)
    at = jnp.minimum(position, rope.cos.shape[0] - 1)
    cos, sin = jnp.take(rope.cos, at, axis=0), jnp.take(rope.sin, at, axis=0)
    pool, table = cache.k, cache.table
    state, tails = cache.ssm, cache.conv
    first = jnp.minimum(rows.first, T - 1)
    seen = glm_dsa.visible_keys(slot, position, real, first, window)
    moe, ffn_in = [], []
    with jax.named_scope("layers"):
        for i, kind in enumerate(c.indexer_types):
            lp = layer_leaves(blocks, c, i)
            with jax.named_scope("attn_norm"):
                h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
            if kind == "kda":
                out, state, tails = kda_layer(
                    lp, h, state, tails, c.kda_layers.index(i), slot, real,
                    rows, first, c, window)
            else:
                out, pool = mla_layer(
                    lp, h, cos, sin, pool, c.latent_layers.index(i), table,
                    slot, position, real, first, seen, c, attn, window)
            x = x + out
            with jax.named_scope("ffn"):
                h = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps)
                out, stats = glm_dsa.ffn(lp, h, real, c)
                if stats is not None:
                    moe.append(stats)
                    ffn_in.append(h)
                x = x + out
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    held = [s.group_held for s in moe if s.group_held is not None]
    Lk, Lm = len(c.kda_layers), len(c.latent_layers)
    counters = jnp.stack(glm_dsa.moe_counters(moe) + [
        jnp.sum(jnp.stack(held)) if held else F32(0),
        Lm * jnp.sum(jnp.maximum(seen + 1, 0), dtype=F32),
        Lk * jnp.sum(jnp.where(rows.n > 1, rows.n, 0), dtype=F32),
        Lk * jnp.sum(rows.n == 1, dtype=F32),
        Lk * jnp.sum(rows.n > 0, dtype=F32)]).astype(F32)
    return TrunkOut(
        x, cache._replace(k=pool, ssm=state, conv=tails), counters,
        jnp.stack([s.experts for s in moe]) if moe else jnp.zeros((0,)),
        jnp.stack(ffn_in) if ffn_in else jnp.zeros((0,)))


# -- the step programs ---------------------------------------------------------


def mixed_trunk(params, tokens, pos, q_len, active, cache: HybridPagedCache,
                rope, config: BailingHybridConfig, attn: str, n_tokens: int):
    """The mixed step's trunk on the packed axis [n_tokens] ->
    (TrunkOut, PackPlan)."""
    plan = paged.pack_plan(q_len, active, n_tokens, tokens.shape[1])
    n = jnp.where(active, q_len, 0).astype(jnp.int32)
    out = trunk(params, tokens[plan.row, plan.col], plan.row,
                pos[plan.row] + plan.col, plan.real,
                Rows(plan.start, n, pos.astype(jnp.int32)), cache, rope,
                config, attn, glm_dsa.window_of(plan, pos, q_len, active))
    return out, plan


@partial(jax.jit, static_argnames=("config", "attn", "n_tokens"),
         donate_argnames=("cache",))
def mixed_step_kda(params, tokens, pos, q_len, active,
                   cache: HybridPagedCache, rope,
                   config: BailingHybridConfig, attn: str = "fold",
                   n_tokens: Optional[int] = None):
    """paged.mixed_step_paged's contract: tokens [B, C] right-padded
    windows, pos/q_len [B], active [B] -> (logits [B, V] of each row's
    last real token, cache, counters). At most ONE active row may hold
    more than one token (module docstring), and n_tokens, the packed
    size, is required."""
    if n_tokens is None:
        raise ValueError("the KDA mixed step runs on the packed axis: "
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
                 config: BailingHybridConfig, attn: str) -> TrunkOut:
    """One token a row: tokens [B, 1], pos/active [B]."""
    B = tokens.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    pos = pos.astype(jnp.int32)
    return trunk(params, tokens[:, 0], rows, pos, active,
                 Rows(rows, active.astype(jnp.int32), pos), cache, rope,
                 config, attn)


def forward_ragged_kda(params, tokens, cache: HybridPagedCache, pos, active,
                       rope, config: BailingHybridConfig,
                       attn: str = "fold"):
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
def decode_step_kda(params, tokens, pos, active, cache: HybridPagedCache,
                    rope, config: BailingHybridConfig, attn: str = "fold"):
    """paged.decode_step_ragged_paged's contract (the synchronous
    decode step)."""
    return forward_ragged_kda(params, tokens, cache, pos, active, rope,
                              config, attn)


# -- what the engine reads of this family (models/family.py) ----------------


def create_cache(config: BailingHybridConfig, slots: int, n_pages: int,
                 page_size: int, max_seq_len: int, width, dtype):
    """One latent row a token in the MLA layers alone (no index-key
    pool: `v` is empty, as DeepSeek-V2's), and a state a ROW for each
    KDA layer: the matrix a head, float32, and the last conv_kernel - 1
    inputs of the conv over q | k | v."""
    c = config
    Lk = len(c.kda_layers)
    return HybridPagedCache(
        k=jnp.zeros((len(c.latent_layers), n_pages, page_size, c.latent_row),
                    dtype),
        v=jnp.zeros((0, n_pages, page_size, 0), dtype),
        table=jnp.full((slots, max_seq_len // page_size), -1, jnp.int32),
        ssm=jnp.zeros((Lk, slots, c.num_attention_heads, c.kda_head_dim,
                       c.kda_head_dim), F32),
        conv=jnp.zeros((Lk, slots, c.conv_kernel - 1, 3 * c.kda_width),
                       dtype))


FAMILY = Family(
    name="bailing_hybrid", decode_step=decode_step_kda,
    decode_programs=make_decode_scan(forward_ragged_kda),
    mixed_step=mixed_step_kda,
    mixed_sampled=make_mixed_sampled(mixed_step_kda),
    create_cache=create_cache, counters=COUNTERS,
    # one window a dispatch (module docstring), so one packed size; and
    # one a step, as the two other families whose windows take turns
    # (family.Windows: every decode row rides every dispatch)
    prefill_rows=(1,), windows=Windows.STEP,
    beside=("KDA state", "kda_state_bytes"),
    impl="paged-kda-", resolve_attn=glm_dsa._resolve_attn,
    # a row's single token walks its live latent pages
    # (cake_mla_decode_attn), counted as cake_decode_attn's are
    kernel_rows=("decode",), window_walk=glm_dsa.window_walk,
    decode_walk=glm_dsa.decode_walk,
    what="a matrix state a row and head beside the latent page pool",
    refuses=cannot_move(
        "KDA state",
        register_prefix=(
            "a KDA state (bailing_hybrid) has no prefix reuse yet: a "
            "shared head would need the matrix state snapshotted at its "
            "last page's edge (ROADMAP.md)"),
        reconfigure=(
            "a KDA state (bailing_hybrid) lives beside the page pool: a "
            "rebuilt pool cannot replay it")))
