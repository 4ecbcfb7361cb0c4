"""Latent attention on the paged engine: the step programs of GLM-5.2
(`glm_moe_dsa`), of dots3-note (`dots3_note`), of DeepSeek-V2
(`deepseek_v2`) and of LongCat-Flash (`longcat_flash`).

The equations are models/reference/glm_moe_dsa.py's,
models/reference/dots3_note.py's, models/reference/deepseek_v2.py's and
models/reference/longcat_flash.py's; this is how the served path computes
them over the page pool (models/llama/paged.py says what a pool row is
here: one latent row a token and layer, and the indexer's key in the
layers that compute an index).

Both step programs run ONE trunk over a flat list of tokens, each with
its row (slot) and position: a decode step's B tokens, or a mixed step's
packed axis (paged.pack_plan). A layer:

  * `mla_q`: the low-rank query path with its norm (or one full-rank
    projection where the config's `q_lora_rank` is null), RoPE on the rope
    part (interleaved pairs), and the key up-projection absorbed into
    the query (q_lat = q_nope W_kvb^K): attention then runs over the
    latent itself, all heads sharing a row;
  * `mla_kv`: the token's latent row (normed c_kv | rotated k_pe),
    written into the latent pool under `kv`;
  * a FULL indexer layer, `indexer`: the index query from the query
    latent, the key (LayerNorm, RoPE) written into the index pool, and
    the scores of every visible key of the token's row, float32: one
    query a row against its pages for the rows' single tokens, and the
    window's queries against their row's pages in blocks of keys
    (ops/mla_attention.py); `index_topk`: the exact top index_topk,
    ties to the lower index (lax.top_k for a row's single token, the
    exact mask of ops/mla_attention.select_window for a window: one
    kernel, `cake_dsa_select`, bounded by the window's last position).
    A SHARED
    layer reuses the Selection the nearest full layer below left: the
    value that travels between layers, which is why the layer loop is a
    Python loop over stacks per kind of layer and not one scan;
  * `mla_attn`: a row's single token gathers its selected rows out of
    the latent pool (`mla_gather`, one XLA gather) and attends them in
    one pass (`cake_mla_attn`); a window's tokens attend their row's
    pages where they lie, under the selection as a bias
    (`cake_mla_window_attn`); each has its XLA fold; the value
    up-projection is applied to the result;
  * the FFN: dense SwiGLU, or ops/moe.moe_mlp with the sigmoid rule,
    the selection bias, the held experts and the shared expert.

KINDS OF LAYER. A layer takes its sizes from its LatentGeometry
(config.geometry(i): heads, ranks, head dims, the RoPE table, the stored
row, the softmax scale's factor), so one trunk serves a model with one
geometry (GLM, DeepSeek-V2) and a model with two (dots3_note). By
config.indexer_types a layer is FULL (its own indexer), SHARED (GLM
only), DENSE (deepseek_v2 only: no indexer and no Selection: every
visible key is attended, `attend_dense`: a row's single token walks its
row's live pages where they lie, `cake_mla_decode_attn`, nothing is
gathered; a window attends under causality alone, which the window
kernel reads off its positions: no bias array; the FFN's router is
limited to groups of experts), or SLIDING (dots3_note only): no indexer,
its rows in the sliding layers' own pool behind the ring table
(paged.WindowedPagedCache), its scopes `swa_q`, `swa_kv`, `swa_gather`,
`swa_attn`. A sliding layer's single token gathers the rows at
positions pos, pos - 1, .. pos - (window - 1) through the ring and
attends the first min(pos + 1, window) in one pass (`cake_swa_attn`:
cake_mla_attn's body at the sliding sizes); its window attends the
ring's R pages where they lie under the band t - (window - 1) <= s <= t
as the bias (`cake_swa_window_attn`). Where the layer has a gate leaf
the un-absorbed heads are multiplied by a sigmoid a head of the layer's
normed input (`attn_gate`); where the geometry has scales the two normed
latents are multiplied by them, so the stored row is the scaled one.

By config.mlp_layer_types a layer's FFN is DENSE, SPARSE, or SHORTCUT
(longcat_flash only, whose layers here are its SUBLAYERS: attention and
a dense SwiGLU each): a shortcut sublayer's normed FFN input also goes
through its layer's routed experts (`shortcut_moe`: ops/moe.moe_mlp
with the zero experts past the router's routed width), and what they
return is carried past the NEXT sublayer and added after its FFN.
Nothing of that sublayer feeds the experts, so the compiler is free to
place them beside it.

ONE WINDOW A DISPATCH. The window's score pass takes the one row whose
keys the queries share, so a mixed dispatch holds at most one row with
more than one token (the engine groups its rows so:
serve/engine._mixed_groups) and there is one packed size
(paged.mixed_token_buckets(..., prefill_rows=(1,))): with one program a
row's bits do not depend on what shares its step, and a layer here makes
two discrete choices (keys, experts) that a rounding can flip. A row's
single token takes the rows' path in the mixed program and in the decode
program alike.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from cake_tpu.models.family import Family, Windows, cannot_move
from cake_tpu.models.llama import paged
from cake_tpu.models.llama.paged import PagedKVCache, write_token_rows
from cake_tpu.models.moe.config import GlmMoeDsaConfig, LatentGeometry
from cake_tpu.models.step_programs import (
    make_decode_scan, make_mixed_sampled,
)
from cake_tpu.ops import mla_attention as mla
from cake_tpu.ops.moe import LayerOf, moe_mlp
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import QTensor, qmatmul

# (a layer with a full-rank query has `wq` in place of the three leaves
# of the low-rank path)
ATTN_LEAVES = ("attn_norm", "wq_a", "q_a_norm", "wq_b", "wq", "wkv_a",
               "kv_a_norm", "wkv_b_k", "wkv_b_v", "wo", "mlp_norm")
INDEX_LEAVES = ("wi_q", "wi_k", "wi_k_norm", "wi_k_bias", "wi_w")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
SPARSE_LEAVES = ("router", "router_bias", "ws_gate", "ws_up", "ws_down")
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")
GATE_LEAF = "w_attn_gate"
# the record keys of the vector a step program returns, in trunk's
# order: the expert counters' five (the held experts' rows alone), the
# routed rows and the indexer's (the last three: the keys the window's
# selections walked, ops/mla_attention.select_walked, the table's
# width beside them, and the keys its score pass visited,
# index_scored); a model with sliding layers appends SWA_COUNTERS, and
# the dsa_* then count its full layers alone
COUNTERS = paged.MOE_COUNTERS + (
    "moe_rows_routed", "dsa_keys_visible", "dsa_keys_selected",
    "dsa_rows_distinct", "dsa_index_layers", "dsa_index_reused",
    "dsa_select_keys_walked", "dsa_select_keys_table",
    "dsa_index_keys_scored")
SWA_COUNTERS = ("swa_keys_visible", "swa_keys_attended", "swa_layers")
N_COUNTERS = len(COUNTERS)
# a model whose layers have no indexer (deepseek_v2): the expert
# counters' five, the routed rows, the tokens whose groups include the
# held one, and the keys its single-token rows attended
DENSE_COUNTERS = paged.MOE_COUNTERS + (
    "moe_rows_routed", "moe_tokens_group_held", "mla_keys_attended")
# a model of shortcut layers (longcat_flash): no groups; the routed
# pairs that chose a zero expert in the group counter's place
SHORTCUT_COUNTERS = paged.MOE_COUNTERS + (
    "moe_rows_routed", "moe_pairs_zero", "mla_keys_attended")


class Window(NamedTuple):
    """The one row of a mixed dispatch that holds a window: row (its
    slot), start (its first packed index), width (static C), member [T]
    (packed positions that are its tokens, when it has more than one),
    col [T] (a position's index in the window); and on the window's own
    axis [C]: positions, real (which of the C are its tokens), last_pos
    (its last token's position)."""

    row: jnp.ndarray
    start: jnp.ndarray
    width: int
    member: jnp.ndarray
    col: jnp.ndarray
    positions: jnp.ndarray
    real: jnp.ndarray
    last_pos: jnp.ndarray


def layer_leaves(blocks, config: GlmMoeDsaConfig, i: int) -> dict:
    """Layer i's leaves out of the stacks per kind (static indices); the
    experts as (stack, index) for the grouped matmul."""
    def at(names, j, stack=blocks):
        return {k: jax.tree.map(lambda a: a[j], stack[k]) for k in names
                if k in stack}

    # the attention leaves are stacked per kind of layer: the sliding
    # layers' (their own shapes) under "swa"
    if config.indexer_types[i] == "sliding":
        lp = at(ATTN_LEAVES + (GATE_LEAF,),
                config.sliding_layers.index(i), blocks["swa"])
    else:
        lp = at(ATTN_LEAVES + (GATE_LEAF,), config.latent_layers.index(i))
    if config.indexer_types[i] == "full":
        lp.update(at(INDEX_LEAVES, config.full_layers.index(i)))
    def routed(j):
        return {**at(SPARSE_LEAVES, j),
                **{k: LayerOf(blocks[k], jnp.int32(j))
                   for k in EXPERT_LEAVES}}

    if config.mlp_layer_types[i] == "sparse":
        lp.update(routed(config.sparse_layers.index(i)))
    else:
        lp.update(at(DENSE_LEAVES, i - sum(s < i
                                           for s in config.sparse_layers)))
    if config.mlp_layer_types[i] == "shortcut":
        # the layer's routed experts beside this sublayer's dense FFN
        lp["shortcut"] = routed(config.shortcut_layers.index(i))
    return lp


def rope_pairs(x, cos, sin):
    """RoPE on interleaved pairs: x [T, ..., d], cos/sin [T, d/2]; the
    pair (x[2i], x[2i+1]) turns by the i-th angle."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1] // 2,)
    c = cos.astype(jnp.float32).reshape(shape)
    s = sin.astype(jnp.float32).reshape(shape)
    even = x[..., 0::2].astype(jnp.float32)
    odd = x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([even * c - odd * s, odd * c + even * s], -1)
    return out.reshape(x.shape).astype(x.dtype)


def _rope_head(x, cos, sin, n_rope: int):
    return jnp.concatenate(
        [rope_pairs(x[..., :n_rope], cos, sin), x[..., n_rope:]], -1)


def _layernorm(x, weight, bias, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _per_head(w, heads: int):
    """A [R, H*d] up-projection as (values [R, H, d] in the compute
    type's width, per-channel scale [H, d] or None)."""
    if isinstance(w, QTensor):
        return (w.q.reshape(w.q.shape[0], heads, -1),
                w.scale.reshape(heads, -1))
    return w.reshape(w.shape[0], heads, -1), None


def absorb_query(q_nope, wkv_b_k):
    """q_nope [T, H, dn] through the key up-projection [R, H*dn] ->
    q_lat [T, H, R]: q_nope . k_nope[s] == q_lat . c_kv[s]. A
    per-channel int8 weight's scale lies on the contracted axis, so it
    multiplies the query first."""
    w, scale = _per_head(wkv_b_k, q_nope.shape[1])
    if scale is not None:
        q_nope = (q_nope.astype(jnp.float32) * scale).astype(q_nope.dtype)
    return jnp.einsum("thd,rhd->thr", q_nope, w.astype(q_nope.dtype),
                      preferred_element_type=jnp.float32
                      ).astype(q_nope.dtype)


def unabsorb_value(o_lat, wkv_b_v):
    """The attended latent [T, H, R] through the value up-projection
    [R, H*dv] -> [T, H, dv]."""
    w, scale = _per_head(wkv_b_v, o_lat.shape[1])
    out = jnp.einsum("thr,rhv->thv", o_lat, w.astype(o_lat.dtype),
                     preferred_element_type=jnp.float32)
    if scale is not None:
        out = out * scale
    return out.astype(o_lat.dtype)


class Selection(NamedTuple):
    """The key sets a full indexer layer leaves for the shared layers
    after it. idx [B, K] / n_valid [B]: each row's SINGLE token's
    selected positions in the row, best first, and how many are real
    (a decode step's tokens; in a mixed dispatch every row's first
    packed token). bias [C, S] float32: the window's sets as the bias
    ops/mla_attention.attend_window takes (0 on a query's selected
    keys, -1e30 elsewhere), or None where there is no window."""

    idx: jnp.ndarray
    n_valid: jnp.ndarray
    bias: Optional[jnp.ndarray]


def _window_slice(x, window: Window):
    """The window's C entries of a packed [T, ...] array (padded by a
    window so that the slice never clamps)."""
    x = jnp.pad(x, ((0, window.width),) + ((0, 0),) * (x.ndim - 1))
    return lax.dynamic_slice_in_dim(x, window.start, window.width, axis=0)


def select_keys(lp, h, c_q, cos, sin, slot, position, real, first,
                pool_idx, layer_f: int, table, config: GlmMoeDsaConfig,
                window: Optional[Window]):
    """A full indexer layer's key sets: writes the tokens' index keys,
    scores every visible key of each token's row, takes the top
    index_topk. first [B]: each row's single token on the token axis.
    Returns (pool_idx, Selection, distinct: the cache rows this dispatch
    selected, counted once each)."""
    c = config
    T = h.shape[0]
    nI, dI, dr = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim
    P, max_pages = pool_idx.shape[2], table.shape[1]
    S = max_pages * P
    K = min(c.index_topk, S)
    span = jnp.arange(S)[None, :]
    with jax.named_scope("indexer"):
        qI = _rope_head(qmatmul(c_q, lp["wi_q"]).reshape(T, nI, dI),
                        cos, sin, dr)
        kI = _rope_head(_layernorm(qmatmul(h, lp["wi_k"]), lp["wi_k_norm"],
                                   lp["wi_k_bias"]), cos, sin, dr)
        w = (jnp.dot(h.astype(jnp.float32), lp["wi_w"].astype(jnp.float32))
             * (nI ** -0.5) * (dI ** -0.5))
        pool_idx = write_token_rows(pool_idx, layer_f, kI, slot, position,
                                    real, table)
        # every row's keys as one [S, dI] range (an unmapped page reads
        # page 0: it lies beyond every visible position)
        keys = pool_idx.at[layer_f, jnp.maximum(table, 0)].get(
            mode="promise_in_bounds").reshape(table.shape[0], S, dI)
        rows = mla.index_scores_rows(qI[first], keys, w[first])   # [B, S]
        # (an idle row's first packed index is its successor's: not its)
        rows_pos = position[first]
        rows_real = real[first] & (slot[first] == jnp.arange(first.shape[0]))
        rows = jnp.where(span <= rows_pos[:, None], rows, -jnp.inf)
        if window is not None:
            win = mla.index_scores_window(
                _window_slice(qI, window), keys[window.row],
                _window_slice(w, window), window.last_pos)
    with jax.named_scope("index_topk"):
        _, idx = lax.top_k(rows, K)
        n_valid = jnp.minimum(rows_pos + 1, K).astype(jnp.int32)
        single = rows_real
        bias = None
        distinct = jnp.float32(0)
        if window is not None:
            picked = mla.select_window(win, window.positions,
                                       window.last_pos, K)
            bias = jnp.where(picked, 0.0, mla.NEG_INF).astype(jnp.float32)
            # distinct cache rows selected: a window's tokens share their
            # row's keys, a single token's are its own
            distinct = jnp.sum(
                jnp.any(picked & window.real[:, None], axis=0),
                dtype=jnp.float32)
            single = single & ~((jnp.arange(table.shape[0]) == window.row)
                                & jnp.any(window.real))
        distinct = distinct + jnp.sum(jnp.where(single, n_valid, 0),
                                      dtype=jnp.float32)
    return pool_idx, Selection(idx.astype(jnp.int32), n_valid, bias), distinct


def project_latent(lp, h, cos, sin, slot, position, real, pool_lat,
                   layer: int, table, config,
                   geo: Optional[LatentGeometry] = None,
                   ring: bool = False):
    """The query path and the token's latent row, written into the
    pool (layer `layer` of pool_lat, through table; ring: the table is
    the row's ring of window pages). geo: the layer's sizes (None: the
    config's one geometry). Returns (q_cat [T, H, row]: the
    absorbed query | the rotated rope part | zeros over the stored
    row's padding, pool_lat, c_q: the query latent the indexer reads;
    None where the query projection is full-rank, leaf `wq`)."""
    T = h.shape[0]
    geo, eps = geo or config.geometry(0), config.rms_norm_eps
    H, R = geo.heads, geo.kv_lora_rank
    dn, dr = geo.qk_nope_head_dim, geo.qk_rope_head_dim
    with jax.named_scope(f"{geo.scope}_q"):
        if "wq" in lp:
            c_q, q = None, qmatmul(h, lp["wq"]).reshape(T, H, dn + dr)
        else:
            c_q = rms_norm(qmatmul(h, lp["wq_a"]), lp["q_a_norm"], eps)
            if geo.q_scale != 1.0:
                c_q = c_q * geo.q_scale
            q = qmatmul(c_q, lp["wq_b"]).reshape(T, H, dn + dr)
        # zeros where the stored row has its padding (config.latent_row)
        pad = pool_lat.shape[-1] - R - dr
        q_cat = jnp.concatenate(
            [absorb_query(q[..., :dn], lp["wkv_b_k"]),
             rope_pairs(q[..., dn:], cos, sin),
             jnp.zeros((T, H, pad), q.dtype)], -1)          # [T, H, row]
    with jax.named_scope(f"{geo.scope}_kv"):
        kva = qmatmul(h, lp["wkv_a"])
        c_kv = rms_norm(kva[:, :R], lp["kv_a_norm"], eps)
        if geo.kv_scale != 1.0:
            c_kv = c_kv * geo.kv_scale
        row = jnp.concatenate(
            [c_kv, rope_pairs(kva[:, R:], cos, sin),
             jnp.zeros((T, pad), kva.dtype)], -1)           # [T, row]
        pool_lat = write_token_rows(pool_lat, layer, row, slot, position,
                                    real, table, ring)
    return q_cat, pool_lat, c_q


def attend(q_cat, pool_lat, layer: int, table, slot, first,
           selection: Selection, config, attn: str,
           window: Optional[Window],
           geo: Optional[LatentGeometry] = None):
    """Every token over its selected rows -> the attended latent
    [T, H, R]. A row's single token: its rows gathered (`mla_gather`)
    and attended in one pass (`cake_mla_attn`). The window's tokens:
    their row's pages where they lie, under the selection's bias
    (`cake_mla_window_attn`)."""
    geo = geo or config.geometry(0)
    P = pool_lat.shape[2]
    scale = geo.softmax_scale
    with jax.named_scope("mla_gather"):
        # a list's tail past n_valid may name an unmapped page: read
        # page 0 there (finite, never attended) rather than pay a
        # select over the gathered rows for a fill value
        rows = jnp.arange(first.shape[0])[:, None]
        pages = jnp.maximum(table[rows, selection.idx // P], 0)
        kv = pool_lat.at[layer, pages, selection.idx % P].get(
            mode="promise_in_bounds")
    with jax.named_scope("mla_attn"):
        out = mla.attend_selected(q_cat[first], kv.astype(q_cat.dtype),
                                  selection.n_valid, geo.kv_lora_rank,
                                  scale, impl=attn)
        if window is None:
            return out
        win = mla.attend_window(
            _window_slice(q_cat, window), pool_lat, jnp.int32(layer),
            table[window.row], selection.bias, window.last_pos,
            geo.kv_lora_rank, scale, impl=attn)
        return jnp.where(window.member[:, None, None], win[window.col],
                         out[slot])


def visible_keys(slot, position, real, first, window: Optional[Window]):
    """What a layer with no indexer attends, the same in every such
    layer of a dispatch: each row's single token's position [B] (-1:
    the row has none here: idle, or the window's row). The window's
    tokens attend under causality, which its positions say."""
    rows = jnp.arange(first.shape[0])
    # (an idle row's first packed index is its successor's: not its)
    single = real[first] & (slot[first] == rows)
    if window is not None:
        single = single & ~((rows == window.row) & jnp.any(window.real))
    return jnp.where(single, position[first], -1)


def attend_dense(q_cat, pool_lat, layer: int, table, slot, first,
                 rows_pos, geo: LatentGeometry, attn: str,
                 window: Optional[Window]):
    """Every token over EVERY visible key of its row -> the attended
    latent [T, H, R]; nothing is gathered. A row's single token
    (rows_pos: visible_keys) walks the row's live pages where they lie
    (`cake_mla_decode_attn`), in the decode program and in the mixed
    program alike. The window's tokens: their row's pages under
    causality, which the kernel reads off their positions
    (`cake_mla_window_attn`)."""
    scale = geo.softmax_scale
    with jax.named_scope("mla_attn"):
        out = mla.attend_pages(q_cat[first], pool_lat, jnp.int32(layer),
                               table, rows_pos, geo.kv_lora_rank, scale,
                               impl=attn)
        if window is None:
            return out
        win = mla.attend_window(
            _window_slice(q_cat, window), pool_lat, jnp.int32(layer),
            table[window.row], None, window.last_pos, geo.kv_lora_rank,
            scale, impl=attn, positions=window.positions)
        return jnp.where(window.member[:, None, None], win[window.col],
                         out[slot])


def gate_heads(lp, h, o):
    """The un-absorbed heads o [T, H, dv] times a sigmoid a head of the
    layer's normed input h."""
    with jax.named_scope("attn_gate"):
        gate = jax.nn.sigmoid(qmatmul(h, lp[GATE_LEAF]).astype(jnp.float32))
        return (o * gate[..., None]).astype(o.dtype)


def gathered_keys(window: int) -> int:
    """The gathered axis of a sliding layer's single token: its window
    of keys padded to whole tiles (513 -> 640; a test's handful to 8s)."""
    tile = 128 if window > 128 else 8
    return -(-window // tile) * tile


def ring_key_positions(last_pos, page: int, ring_pages: int):
    """The position whose row each of a ring's R * page slots holds,
    [R * page], for a row whose newest written position is last_pos:
    ring entry j holds the newest logical page congruent to j that the
    row has reached (negative where it has reached none: nothing lies
    there). Slots of the newest page past last_pos still hold the page
    R before it; their positions read > last_pos here, which causality
    masks."""
    last_page = last_pos // page
    j = jnp.arange(ring_pages)
    logical = last_page - (last_page - j) % ring_pages
    return (logical[:, None] * page + jnp.arange(page)[None, :]).reshape(-1)


def attend_sliding(q_cat, pool_w, layer: int, wtable, slot, position,
                   first, geo: LatentGeometry, attn: str,
                   window: Optional[Window]):
    """Every token over the last geo.window keys of its row (its own
    included) -> the attended latent [T, H, R]. A row's single token:
    the rows at positions pos, pos - 1, .. gathered through the ring
    (`swa_gather`), newest first, so that its valid rows are the first
    min(pos + 1, window), attended in one pass (`cake_swa_attn`). The
    window's tokens: the ring's pages where they lie, under the band as
    the bias (`cake_swa_window_attn`)."""
    P, R = pool_w.shape[2], wtable.shape[1]
    W, scale = geo.window, geo.softmax_scale
    with jax.named_scope("swa_gather"):
        rows_pos = position[first]
        idx = jnp.maximum(
            rows_pos[:, None] - jnp.arange(gathered_keys(W))[None, :], 0)
        rows = jnp.arange(first.shape[0])[:, None]
        pages = wtable[rows, (idx // P) % R]
        kv = pool_w.at[layer, pages, idx % P].get(mode="promise_in_bounds")
        n_valid = jnp.minimum(rows_pos + 1, W).astype(jnp.int32)
    with jax.named_scope("swa_attn"):
        out = mla.attend_selected(q_cat[first], kv.astype(q_cat.dtype),
                                  n_valid, geo.kv_lora_rank, scale,
                                  impl=attn, scope=geo.scope)
        if window is None:
            return out
        s = ring_key_positions(window.last_pos, P, R)[None, :]
        t = window.positions[:, None]
        bias = jnp.where((s >= 0) & (s <= t) & (s > t - W), 0.0,
                         mla.NEG_INF).astype(jnp.float32)
        win = mla.attend_window(
            _window_slice(q_cat, window), pool_w, jnp.int32(layer),
            wtable[window.row], bias, jnp.int32(R * P - 1),
            geo.kv_lora_rank, scale, impl=attn, scope=geo.scope)
        return jnp.where(window.member[:, None, None], win[window.col],
                         out[slot])


def held_from(config) -> Optional[int]:
    """The first held expert where the layers hold a SHARE of their
    router's experts, None where they hold all."""
    c = config
    return (c.first_routed_expert
            if c.num_local_experts < c.n_routed_experts_total else None)


def ffn(lp, h, real, config):
    """A layer's FFN on its normed input h [T, D] -> (out [T, D],
    MoEStats or None): ops/moe.moe_mlp by the config's rule where the
    layer has a router, else a dense SwiGLU."""
    c = config
    if "router" not in lp:
        gate = jax.nn.silu(qmatmul(h, lp["w_gate"]))
        return qmatmul(gate * qmatmul(h, lp["w_up"]), lp["w_down"]), None
    out, stats = moe_mlp(
        lp, h[None], c.num_experts_per_tok, c.norm_topk_prob,
        token_mask=real[None], first_expert=held_from(c),
        scoring=c.scoring_func, scale=c.routed_scaling_factor,
        n_group=c.n_group, topk_group=c.topk_group, group_top=c.group_top,
        # a router wider than its routed experts: zero experts past them
        zero_from=(c.n_routed_experts_total
                   if getattr(c, "zero_expert_num", 0) else None))
    return out[0], stats


def moe_counters(moe: list) -> list:
    """paged.MOE_COUNTERS' five and the routed rows, over the sparse
    layers' MoEStats (sums, but the loads: means over the layers)."""
    def over(field, reduce):
        return (reduce(jnp.stack([getattr(s, field) for s in moe]))
                if moe else jnp.float32(0))

    return [over("rows", jnp.sum), over("rows_padded", jnp.sum),
            over("load_max", jnp.mean), over("load_mean", jnp.mean),
            over("touched", jnp.sum), over("rows_routed", jnp.sum)]


class TrunkOut(NamedTuple):
    """x [T, D] after the final norm; cache; counters [N_COUNTERS];
    and the two choices themselves, for a tool that compares them with
    the reference's (chip_compare.py; a step program drops them):
    experts [L_sparse, T, k]; selected [L_full, B, K] / n_selected [B],
    each row's single token's keys; selected_window [L_full, C, S]
    bool, the window's (empty where there is no window); probe: the
    first sliding layer from the inside, each [T, D]: its normed
    input, its attention's output before the residual, and its FFN's
    normed input (of the first shortcut sublayer where the model has
    those; () for a model with neither), so that the tool can hand the
    reference's layer, or its router, the served path's own input."""

    x: jnp.ndarray
    cache: PagedKVCache
    counters: jnp.ndarray
    experts: jnp.ndarray
    selected: jnp.ndarray
    n_selected: jnp.ndarray
    selected_window: jnp.ndarray
    probe: tuple


def trunk(params, token_ids, slot, position, real, cache: PagedKVCache,
          rope, config: GlmMoeDsaConfig, attn: str,
          window: Optional[Window] = None, first=None) -> TrunkOut:
    """Embed, every layer, final norm, over T tokens: token_ids, slot,
    position [T] int32, real [T] bool (a token that is not real writes
    nothing, is not routed, and its output is garbage nobody reads).
    first [B]: each row's single token on the token axis (with a window:
    its first packed token); without a window T == B and token t is
    row t's."""
    c = config
    blocks = params["blocks"]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], token_ids, axis=0)
    at = jnp.minimum(position, rope.cos.shape[0] - 1)
    cos, sin = jnp.take(rope.cos, at, axis=0), jnp.take(rope.sin, at, axis=0)
    # the rows of each RoPE table a kind of layer rotates by
    turned = {("cos", "sin"): (cos, sin)}
    if c.sliding_layers:
        turned["swa_cos", "swa_sin"] = (jnp.take(rope.swa_cos, at, axis=0),
                                        jnp.take(rope.swa_sin, at, axis=0))
        pool_w, wtable = cache.w, cache.wtable
    pool_lat, pool_idx, table = cache.k, cache.v, cache.table
    if first is None:
        first = jnp.arange(x.shape[0])
    selection = None
    dense = "dense" in c.indexer_types
    if dense:
        seen = visible_keys(slot, position, real, first, window)
    moe, experts, selected, windows, probe = [], [], [], [], ()
    distinct = jnp.float32(0)
    # what a shortcut sublayer's experts returned, until the sublayer
    # after it has added its FFN
    carried = None
    with jax.named_scope("layers"):
        for i in range(c.num_hidden_layers):
            lp = layer_leaves(blocks, c, i)
            with jax.named_scope("attn_norm"):
                h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
            geo = c.geometry(i)
            with jax.named_scope("attn"):
                if geo.window is not None:
                    j = c.sliding_layers.index(i)
                    q_cat, pool_w, _ = project_latent(
                        lp, h, *turned[geo.rope], slot, position, real,
                        pool_w, j, wtable, c, geo, ring=True)
                    o_lat = attend_sliding(q_cat, pool_w, j, wtable, slot,
                                           position, first, geo, attn,
                                           window)
                else:
                    j = c.latent_layers.index(i)
                    q_cat, pool_lat, c_q = project_latent(
                        lp, h, cos, sin, slot, position, real, pool_lat, j,
                        table, c, geo)
                    if "wi_q" in lp:
                        pool_idx, selection, last_distinct = select_keys(
                            lp, h, c_q, cos, sin, slot, position, real,
                            first, pool_idx, c.full_layers.index(i), table,
                            c, window)
                        selected.append(selection.idx)
                        if selection.bias is not None:
                            windows.append(selection.bias == 0)
                    if dense:
                        o_lat = attend_dense(q_cat, pool_lat, j, table,
                                             slot, first, seen, geo, attn,
                                             window)
                    else:
                        distinct = distinct + last_distinct
                        o_lat = attend(q_cat, pool_lat, j, table, slot,
                                       first, selection, c, attn, window,
                                       geo)
                o = unabsorb_value(o_lat, lp["wkv_b_v"])
                if geo.gated:
                    o = gate_heads(lp, h, o)
            with jax.named_scope("o_proj"):
                attn_out = qmatmul(o.reshape(o.shape[0], -1), lp["wo"])
                x = x + attn_out
            with jax.named_scope("ffn"):
                h_attn, h = h, rms_norm(x, lp["mlp_norm"], c.rms_norm_eps)
                if (geo.window is not None or "shortcut" in lp) and not probe:
                    probe = (h_attn, attn_out, h)
                out, stats = ffn(lp, h, real, c)
                if "shortcut" in lp:
                    with jax.named_scope("shortcut_moe"):
                        carried, stats = ffn(lp["shortcut"], h, real, c)
                if stats is not None:
                    moe.append(stats)
                    experts.append(stats.experts)
                x = x + out
                if carried is not None and "shortcut" not in lp:
                    x, carried = x + carried, None
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    n_real = jnp.sum(real, dtype=jnp.float32)
    # the indexer's counters count the layers of the latent pool
    # (all of them but the sliding ones)
    L, Lf = len(c.latent_layers), len(c.full_layers)
    stepped = (n_real > 0).astype(jnp.float32)
    visible = jnp.where(real, position + 1, 0).astype(jnp.float32)
    f32 = jnp.float32
    counters = moe_counters(moe)
    if dense:
        # the tokens whose groups include the held one, or (a router
        # with zero experts has no groups) the pairs that chose one
        held = [s.group_held if s.pairs_zero is None else s.pairs_zero
                for s in moe]
        held = [n for n in held if n is not None]
        counters += [
            jnp.sum(jnp.stack(held)) if held else f32(0),
            L * jnp.sum(jnp.maximum(seen + 1, 0),
                        dtype=jnp.float32)]
    else:
        S = table.shape[1] * pool_lat.shape[2]
        # what the window's selections walked, the table's width, and
        # the keys their score passes visited
        walked = [0, 0, 0] if window is None else [
            Lf * mla.select_walked(window.last_pos, window.width, S), Lf * S,
            Lf * mla.index_scored(window.last_pos, S)]
        counters += [
            L * jnp.sum(visible),
            L * jnp.sum(jnp.minimum(visible, min(c.index_topk, S))),
            distinct, Lf * stepped, (L - Lf) * stepped] + walked
    counters = jnp.stack(counters).astype(f32)
    cache = cache._replace(k=pool_lat, v=pool_idx)
    if c.sliding_layers:
        Lw = len(c.sliding_layers)
        counters = jnp.concatenate([counters, jnp.stack([
            Lw * jnp.sum(visible),
            Lw * jnp.sum(jnp.minimum(visible, c.sliding_window_size)),
            Lw * stepped]).astype(f32)])
        cache = cache._replace(w=pool_w)
    return TrunkOut(x, cache, counters,
                    jnp.stack(experts) if experts else jnp.zeros((0,)),
                    (jnp.stack(selected) if selected
                     else jnp.zeros((0,), jnp.int32)),
                    (seen + 1 if selection is None
                     else selection.n_valid),
                    jnp.stack(windows) if windows else jnp.zeros((0,), bool),
                    probe)


def window_of(plan: paged.PackPlan, pos, q_len, active) -> Window:
    """The dispatch's one window: the row with the most tokens."""
    n = jnp.where(active, q_len, 0)
    row = jnp.argmax(n).astype(jnp.int32)
    cols = jnp.arange(plan.width)
    real = (cols < n[row]) & (n[row] > 1)
    return Window(row, plan.start[row], plan.width,
                  (plan.row == row) & (n[row] > 1) & plan.real, plan.col,
                  pos[row] + cols, real,
                  pos[row] + jnp.maximum(n[row], 1) - 1)


def mixed_trunk(params, tokens, pos, q_len, active, cache: PagedKVCache,
                rope, config: GlmMoeDsaConfig, attn: str, n_tokens: int):
    """The mixed step's trunk on the packed axis [n_tokens] ->
    (TrunkOut, PackPlan)."""
    plan = paged.pack_plan(q_len, active, n_tokens, tokens.shape[1])
    out = trunk(params, tokens[plan.row, plan.col], plan.row,
                pos[plan.row] + plan.col, plan.real, cache, rope, config,
                attn, window_of(plan, pos, q_len, active),
                jnp.minimum(plan.start, n_tokens - 1))
    return out, plan


@partial(jax.jit, static_argnames=("config", "attn", "n_tokens"),
         donate_argnames=("cache",))
def mixed_step_latent(params, tokens, pos, q_len, active,
                      cache: PagedKVCache, rope, config: GlmMoeDsaConfig,
                      attn: str = "fold", n_tokens: Optional[int] = None):
    """paged.mixed_step_paged's contract for latent attention: tokens
    [B, C] right-padded windows, pos/q_len [B], active [B] -> (logits
    [B, V] of each row's last real token, cache, counters). At most ONE
    active row may hold more than one token (module docstring), and
    n_tokens, the packed size, is required."""
    if n_tokens is None:
        raise ValueError("the latent mixed step runs on the packed axis: "
                         "pass n_tokens")
    out, plan = mixed_trunk(params, tokens, pos, q_len, active, cache, rope,
                            config, attn, n_tokens)
    with jax.named_scope("head"):
        last = (jnp.maximum(q_len, 1) - 1).astype(jnp.int32)
        last = jnp.take(out.x, jnp.minimum(plan.start + last, n_tokens - 1),
                        axis=0)
        logits = qmatmul(last, params["lm_head"]).astype(jnp.float32)
    return logits, out.cache, out.counters


def decode_trunk(params, tokens, cache: PagedKVCache, pos, active, rope,
                 config: GlmMoeDsaConfig, attn: str) -> TrunkOut:
    """One token a row: tokens [B, 1], pos/active [B]."""
    B = tokens.shape[0]
    return trunk(params, tokens[:, 0], jnp.arange(B, dtype=jnp.int32),
                 pos.astype(jnp.int32), active, cache, rope, config, attn)


def forward_ragged_latent(params, tokens, cache: PagedKVCache, pos, active,
                          rope, config: GlmMoeDsaConfig,
                          attn: str = "fold"):
    """paged.forward_ragged_paged(..., counters=True)'s contract: what
    step_programs.make_decode_scan builds the sampled decode programs
    from -> (logits [B, V], cache, counters)."""
    out = decode_trunk(params, tokens, cache, pos, active, rope, config,
                       attn)
    with jax.named_scope("head"):
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
    return logits, out.cache, out.counters


@partial(jax.jit, static_argnames=("config", "attn"),
         donate_argnames=("cache",))
def decode_step_latent(params, tokens, pos, active, cache: PagedKVCache,
                       rope, config: GlmMoeDsaConfig, attn: str = "fold"):
    """paged.decode_step_ragged_paged's contract (the synchronous
    decode step)."""
    return forward_ragged_latent(params, tokens, cache, pos, active, rope,
                                 config, attn)


# -- what the engine reads of this family (models/family.py) ----------------


def create_cache(config: GlmMoeDsaConfig, slots: int, n_pages: int,
                 page_size: int, max_seq_len: int, width, dtype):
    """One latent row a token in every latent layer, and the indexer's
    key in the layers that compute an index; with sliding layers, the
    pools by kind of layer. Slot i owns a ring of the window pool for
    good (WindowedPagedCache.create maps it): that pool is slots x ring
    whatever max_seq_len, and admission, release and a rebuild never
    touch it."""
    c = config
    if c.sliding_layers:
        if width is None:
            raise ValueError(
                "a model with sliding-window latent layers keeps a pool "
                "and a table by kind of layer, its ring sized by the "
                "mixed step's window: pass width, or call "
                "WindowedPagedCache.create")
        return paged.WindowedPagedCache.create(
            c, slots, n_pages, page_size, max_seq_len,
            c.window_ring_pages(page_size, width), dtype=dtype)
    return PagedKVCache.zeros(
        (c.num_hidden_layers, n_pages, page_size, c.latent_row),
        (len(c.full_layers), n_pages, page_size, c.index_head_dim),
        slots, max_seq_len // page_size, dtype)


def _resolve_attn(config, impl: str, *, prefill_chunk, max_seq_len: int,
                  **_shapes):
    """One impl for both step kinds: the selected rows are gathered in
    XLA and attended by cake_mla_attn, or a row's live pages walked by
    cake_mla_decode_attn (pallas), or the XLA folds; their VMEM does
    not depend on the mixed width. 512 is the widest window whose
    gathered rows (width x index_topk x latent row) stay near a
    gigabyte, and the one the window kernel's bias [width, keys] was
    sized at."""
    return impl, prefill_chunk or min(512, max_seq_len)


def window_walk(config, cache, width: int):
    """What the engine counts into a mixed record (Family.window_walk):
    a window's last position -> (pages, folds) a query tile of the
    window kernel walks in one dispatch, over every layer: the row's
    live pages in a layer of the latent pool, the whole ring in a
    sliding layer, at the pages a fold that the kernel takes for that
    kind of layer's shapes (ops/mla_attention.window_tiles, as
    attend / attend_dense / attend_sliding call it)."""
    P = cache.k.shape[2]
    kinds = []      # (layers, the fixed last index of a ring, pages, block)

    def kind(layers, pool, table, biased: bool, ring: bool):
        geo, pages = config.geometry(layers[0]), table.shape[1]
        _tq, block = mla.window_tiles(
            width, geo.heads, pool.shape[-1], geo.kv_lora_rank, P, pages,
            pool.dtype.itemsize, biased)
        kinds.append((len(layers), pages * P - 1 if ring else None, pages,
                      block))

    if config.latent_layers:
        kind(config.latent_layers, cache.k, cache.table,
             "dense" not in config.indexer_types, False)
    if config.sliding_layers:
        kind(config.sliding_layers, cache.w, cache.wtable, True, True)

    def walk(last_pos: int):
        pages = folds = 0
        for layers, ring_last, max_pages, block in kinds:
            p, f = mla.window_walk(
                last_pos if ring_last is None else ring_last, P, max_pages,
                block)
            pages, folds = pages + layers * p, folds + layers * f
        return pages, folds

    return walk


def decode_walk(config, cache):
    """What the engine counts into a decode or mixed record
    (Family.decode_walk): a single-token row's position -> (pages,
    folds) it walks through the page kernel over the layers with no
    indexer, at the pages a fold that the kernel takes for the call's
    shapes (ops/mla_attention.decode_block, as attend_dense's
    attend_pages calls it)."""
    layers = config.indexer_types.count("dense")
    geo = config.geometry(config.indexer_types.index("dense"))
    P, max_pages = cache.k.shape[2], cache.table.shape[1]
    block = mla.decode_block(geo.heads, cache.k.shape[-1], geo.kv_lora_rank,
                             P, max_pages, cache.k.dtype.itemsize)

    def walk(pos: int):
        pages, folds = mla.pages_walk(pos, P, max_pages, block)
        return layers * pages, layers * folds

    return walk


_DECODE_PROGRAMS = make_decode_scan(forward_ragged_latent)
_MIXED_SAMPLED = make_mixed_sampled(mixed_step_latent)


def _family(name: str, counters: tuple, beside=None, *,
            impl: str = "paged-dsa-", kernel_rows: tuple = (),
            stored: str = "latent row and index key",
            prefix_needs: str = (
                "a shared head would need its latent rows and its index "
                "keys (and a windowed model's ring) mapped together"),
            windows: Windows = Windows.DISPATCH,
            decode_walk=None, says=None) -> Family:
    """impl, kernel_rows: what the step records call the attention, and
    the step kinds whose rows a kernel walks page by page (the host
    counts those pages as it does cake_decode_attn's: the same rule);
    stored, prefix_needs: what a token leaves in the pool, and why a
    prefix cannot be mapped onto it yet."""
    pool = f"the latent page pool ({name})"
    return Family(
        name=name, decode_step=decode_step_latent,
        decode_programs=_DECODE_PROGRAMS, mixed_step=mixed_step_latent,
        mixed_sampled=_MIXED_SAMPLED, create_cache=create_cache,
        counters=counters,
        # one window a dispatch (module docstring), so one packed size
        prefill_rows=(1,), windows=windows, beside=beside,
        impl=impl, resolve_attn=_resolve_attn, kernel_rows=kernel_rows,
        window_walk=window_walk, decode_walk=decode_walk, says=says,
        what="latent attention over the page pool",
        refuses=cannot_move(
            stored,
            register_prefix=(
                f"{pool} has no prefix pages yet: {prefix_needs} "
                "(ROADMAP.md)"),
            reconfigure=(
                f"{pool} serves on pages only: there is no dense or "
                "quantized pool to switch to")))


FAMILY = _family("glm_moe_dsa", COUNTERS)
WINDOWED = _family("dots3_note", COUNTERS + SWA_COUNTERS,
                   ("window pool (a ring a row)", None))
# one window a STEP: the prompts mid-prefill take turns, and every
# decode row rides every dispatch. Read against DISPATCH on the chip
# at a steady state of dsv2.code-closed's traffic (its ramp stretched
# to 80 s, the same two seeds under each; PERF.md section 6, PR 45):
# DISPATCH reads the lower tpot_p50_ms (68.5 / 71.9 ms against 94.6 /
# 94.3) and 2.7 % more out_tok_s (336.3 / 337.0 against 327.6 / 327.7),
# and holds a request's first token back 12.5 / 12.7 s (1.4 / 1.5 with
# turns) and every decoding row 0.92 - 0.99 s at each mixed step of 6 -
# 7 dispatches (with turns its longest gap is one dispatch, 156 ms).
# Turns are taken for the first token and the longest gap, against
# both of the cell's judged readings
DENSE = _family("deepseek_v2", DENSE_COUNTERS, impl="paged-mla-",
                kernel_rows=("decode",), stored="latent row",
                prefix_needs=("the prefix path prefills and maps K/V "
                              "pages, not latent rows"),
                windows=Windows.STEP, decode_walk=decode_walk)
def _shortcut_share(config) -> str:
    """The start-up sentence of a model of shortcut layers: its latent
    layers and the share of its router it holds."""
    c = config
    first, held, total = (c.first_routed_expert, c.num_local_experts,
                          c.n_routed_experts_total)
    return (f"{c.num_hidden_layers} latent layers of {c.num_layers} "
            f"shortcut-connected layers; routed experts {first}.."
            f"{first + held - 1} ({held} of {total}) + {c.zero_expert_num} "
            f"zero experts in a router {total + c.zero_expert_num} wide, "
            f"{c.num_experts_per_tok} a token")


# one window a STEP as DENSE: the same trunk, kernels and traffic shape
# (a window's dispatch carries every decode row); not read against
# DISPATCH on the chip for this family (PERF.md section 7)
SHORTCUT = _family("longcat_flash", SHORTCUT_COUNTERS, impl="paged-mla-",
                   kernel_rows=("decode",), stored="latent row",
                   prefix_needs=("the prefix path prefills and maps K/V "
                                 "pages, not latent rows"),
                   windows=Windows.STEP, decode_walk=decode_walk,
                   says=_shortcut_share)
