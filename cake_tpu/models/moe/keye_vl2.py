"""Keye-VL-2.0's language model (`KeyeVL2`) on the paged engine: the
step programs.

The equations are models/reference/keye_vl2.py's; this is how the served
path computes them over ORDINARY K and V pages plus a third pool of the
indexer's keys on the same page table (models/llama/paged.PagedKVCache:
`k`, `v`, `idx`): a page id names the same token range in all three, so
admission, release and a rebuild move them together and the allocator
knows one pool.

Both step programs run ONE trunk over a flat list of tokens, each with
its row (slot) and position: a decode step's B tokens, or a mixed step's
packed axis (paged.pack_plan). All layers are alike, so the trunk is one
`lax.scan` (zaya's form): the three pools are its carry, the experts'
weights stay out of it as (stack, layer) for the grouped matmul, and a
step program of 8 layers compiles in the time of one. A layer is
attention behind `attn_norm` and the experts behind `mlp_norm`, each
with its residual:

  * `gqa_proj`: q, k, v; an RMSNorm a head on q and k; the rotation
    (half-split pairs; M-RoPE's three position streams are equal for
    text, so it IS the ordinary rotation: the reference shows it);
  * `indexer`: the index query from the normed hidden state (16 heads
    of 64, rotated whole by frequencies of their own width), the key
    (LayerNorm, rotated) written into `idx`, and the scores of every
    visible key of the token's row in float32: a row's single token
    by ops/mla_attention.index_scores_rows, the dispatch's window by
    index_scores_window (`cake_dsa_index`: one kernel that stores the
    window's [queries, keys] scores where it computes them and visits
    the key blocks up to the window's last position, the rest written
    zeros; GLM's and dots3's too); `index_topk`: the exact top
    `index_topk`, ties to the
    lower index, as a MASK over the row's table
    (ops/mla_attention.select_window, `cake_dsa_select`, whose work
    follows the last position it is given and not the table's width):
    one call a layer for the dispatch's window, and one for the rows'
    single tokens, ONE tile of B queries that differ in row and
    position;
  * `gqa_full`: the write of every real token's k and v into its page,
    then attention over the SELECTED keys alone, WHERE THEY LIE. A
    row's single token: `cake_decode_attn` walks the row's own live
    pages of the layer's own pools and attends, of each, the keys its
    mask marks (`selected=`: the row's [max_pages, page] block beside
    its query). While a row holds no more than index_topk keys its mask
    marks all it sees and the result is the unselected kernel's, bit
    for bit. WHY A WALK AND NOT A GATHER: moving the selected rows out
    of the pools (a full sort for `lax.top_k`, a second for their order,
    a page look-up and two XLA gathers of [rows, index_topk, KV * hd] a
    layer) read 0.98 ms a call of 8 rows at every length on a v5e, the
    selection and the walk 0.15 ms at 4k keys a row, 0.26 at 8k, 0.48
    at 16k and 0.92 at a table's end of 33,280
    (tools/decode_selected_bench.py holds both forms; PERF.md section
    6, PR 66). The walk costs 0.42 us a page and row, so past ~36k keys
    a row the gather would win again: a table wider than that is where
    this choice is owed a second look. The
    dispatch's one window: `cake_mixed_attn` over the row's pages where
    they lie, in entries of `exaone_moe.query_tile` queries, with the
    selection streamed in beside the pages as a per-(query, key) mask
    (`selected=`): gathering 512 x 2,048 rows of 2 KB would be 2.1 GB a
    layer, the mask is 68 MB;
  * the experts (`glm_dsa.ffn`): softmax over all of them, the k best
    renormalised, every expert held, none shared.

ONE WINDOW A DISPATCH (family.Windows.DISPATCH), as GLM's: one packed
size; a step of k prompts mid-prefill is k dispatches, of which a decode
row rides one.
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
from cake_tpu.models.moe import glm_dsa
from cake_tpu.models.moe.config import KeyeVL2Config
from cake_tpu.models.moe.exaone_moe import _resolve_attn, query_tile
from cake_tpu.models.moe.glm_dsa import _layernorm, _window_slice
from cake_tpu.models.moe.nemotron_h import (
    Rows, Window, dequantized, window_of,
)
from cake_tpu.models.step_programs import (
    make_decode_scan, make_mixed_sampled,
)
from cake_tpu.ops import mla_attention as mla
from cake_tpu.ops import ragged_paged_attention as rpa
from cake_tpu.ops.moe import LayerOf
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import qmatmul
from cake_tpu.ops.rope import apply_rope

# the record keys of the vector a step program returns, in trunk's
# order: the experts' five and the routed rows; the indexer's (GLM's
# keys: the same quantities); what the single-token rows attended and
# what their indexers scored; those rows, and the pages the dispatch's
# rows' contexts fill; the keys the window's selection walked, and the
# table's width beside them; the pages the single-token rows walked
# under their masks, and the rows that did
COUNTERS = paged.MOE_COUNTERS + (
    "moe_rows_routed", "dsa_keys_visible", "dsa_keys_selected",
    "dsa_rows_distinct", "dsa_index_layers", "dsa_keys_single",
    "dsa_keys_scanned_single", "gqa_rows_single", "gqa_full_pages_live",
    "dsa_select_keys_walked", "dsa_select_keys_table",
    "dsa_index_keys_scored", "dsa_walk_pages_single", "dsa_walk_rows_single")
F32 = jnp.float32


def reference_layers(blocks, config: KeyeVL2Config):
    """The per-layer float32 dicts models/reference/keye_vl2.forward
    walks, one at a time (a generator: a caller at published widths
    holds one layer's float32 weights at a time): the served leaves
    dequantized."""
    for i in range(config.num_hidden_layers):
        yield {k: dequantized(jax.tree.map(lambda a: a[i], v))
               for k, v in blocks.items()}


def reference_config(config: KeyeVL2Config) -> dict:
    """What the reference reads of the config, under the published
    keys (a plain dict: it imports nothing of this package)."""
    c = config
    return {"num_attention_heads": c.num_attention_heads,
            "num_key_value_heads": c.num_key_value_heads,
            "head_dim": c.head_dim, "rope_theta": c.rope_theta,
            "rms_norm_eps": c.rms_norm_eps,
            "mrope_section": list(c.mrope_section),
            "indexer_num_heads": c.index_n_heads,
            "indexer_head_dim": c.index_head_dim, "topk": c.index_topk,
            "num_experts_per_tok": c.num_experts_per_tok,
            "norm_topk_prob": c.norm_topk_prob}


class Selection(NamedTuple):
    """A layer's key sets, as masks over a row's table. rows [B, S]
    bool: each row's SINGLE token's set among its own keys (a row with
    no single token selects nothing); picked [C, S] bool: the window's
    sets (None where there is no window)."""

    rows: jnp.ndarray
    picked: Optional[jnp.ndarray]


def select_keys(lp, h, cos, sin, slot, position, real, first, single_pos,
                pool_idx, layer, table, config: KeyeVL2Config,
                window: Optional[Window], win_pos, win_last):
    """A layer's key sets: writes the tokens' index keys, scores every
    visible key of each token's row, takes the top index_topk. first
    [B]: each row's first packed token; single_pos [B]: its single
    token's position (-1: it has none here); win_pos / win_last: the
    window's first and last positions. Returns (pool_idx,
    Selection, distinct: the cache rows this dispatch selected, counted
    once each)."""
    c = config
    T = h.shape[0]
    nI, dI = c.index_n_heads, c.index_head_dim
    P, max_pages = pool_idx.shape[2], table.shape[1]
    S = max_pages * P
    K = min(c.index_topk, S)
    with jax.named_scope("indexer"):
        qI = apply_rope(qmatmul(h, lp["wi_q"]).reshape(1, T, nI, dI),
                        cos, sin)[0]
        kI = apply_rope(
            _layernorm(qmatmul(h, lp["wi_k"]), lp["wi_k_norm"],
                       lp["wi_k_bias"]).reshape(1, T, 1, dI), cos, sin)[0, :, 0]
        w = (jnp.dot(h.astype(F32), lp["wi_w"].astype(F32))
             * (nI ** -0.5) * (dI ** -0.5))
        pool_idx = write_token_rows(pool_idx, layer, kI, slot, position,
                                    real, table)
        # every row's keys as one [S, dI] range (an unmapped page reads
        # page 0: it lies beyond every visible position)
        keys = pool_idx.at[layer, jnp.maximum(table, 0)].get(
            mode="promise_in_bounds").reshape(table.shape[0], S, dI)
        rows = mla.index_scores_rows(qI[first], keys, w[first])   # [B, S]
        if window is not None:
            win = mla.index_scores_window(
                _window_slice(qI, window), keys[window.row],
                _window_slice(w, window), win_last)
    with jax.named_scope("index_topk"):
        # the rows' sets by the window's kernel: ONE tile of B queries,
        # each with its own scores and position (a row sees the keys at
        # or before its single token, one without sees and selects
        # nothing), the walk bounded by the longest of them
        own = mla.select_window(rows, single_pos, jnp.max(single_pos), K)
        picked = None
        distinct = jnp.sum(jnp.minimum(single_pos + 1, K), dtype=F32)
        if window is not None:
            picked = mla.select_window(
                win, win_pos + jnp.arange(window.width), win_last, K)
            in_window = jnp.arange(window.width) < window.n
            distinct = distinct + jnp.sum(
                jnp.any(picked & in_window[:, None], axis=0), dtype=F32)
    return pool_idx, Selection(own, picked), distinct


def attend_rows(q, pool_k, pool_v, layer, table, single_pos, own, attn: str):
    """Each row's single query q [B, H, hd] over ITS selected keys
    where they lie: `cake_decode_attn` walks the row's own live pages
    up to single_pos and attends, of each, the keys `own` [B, S] marks
    (a row with no single token: -1, no trip, zeros) -> [B, H, hd]."""
    B = q.shape[0]
    return paged.paged_attention(
        q[:, None], pool_k, pool_v, layer, table, single_pos, impl=attn,
        selected=own.astype(F32).reshape(B, table.shape[1], -1))[:, 0]


def attend_window(q, pool_k, pool_v, layer, table_row, first_pos, n, picked,
                  attn: str):
    """A dispatch's one window through `cake_mixed_attn`: q [C, H, hd],
    its first token at first_pos, n real tokens, handed over as C / tile
    entries of `query_tile` queries that share the row's table and
    differ in position (exaone_moe.attend_window's form), each with its
    queries' rows of the selection `picked` [C, S] -> [C, H, hd]."""
    C, H, hd = q.shape
    P, KV = pool_k.shape[2], pool_k.shape[3] // hd
    S = table_row.shape[0] * P
    tile = query_tile(C, H, KV, hd, P, q.dtype.itemsize,
                      pool_k.dtype.itemsize)
    n_sub = C // tile
    starts = jnp.arange(n_sub, dtype=jnp.int32) * tile
    # (a padded last entry stays inside the row's table)
    at = jnp.minimum(first_pos + starts, S - 1)
    win = paged.paged_attention_mixed(
        q.reshape(n_sub, tile, H, hd), pool_k, pool_v, layer,
        jnp.broadcast_to(table_row[None], (n_sub, table_row.shape[0])), at,
        jnp.clip(n - starts, 0, tile), impl=attn,
        # by page: the block a grid step reads is then one copy
        selected=jnp.transpose(
            picked.astype(F32).reshape(n_sub, tile, S // P, P),
            (0, 2, 1, 3)))
    return win.reshape(C, H, hd)


class TrunkOut(NamedTuple):
    """x [T, D] after the final norm; cache; counters [len(COUNTERS)];
    and for a tool that compares them with the reference's
    (chip_compare.py; a step program drops them): experts [L, T, k],
    each layer's choice; ffn_in [L, T, D], each layer's normed input
    (what its router read); n_selected [B], the keys each row's single
    token selects; selected [L, B, S] bool, those rows' sets, and
    selected_window [L, C, S] bool, the window's (both empty unless the
    trunk was asked to `probe`: the window's are 17 MB a layer at the
    cell's sizes)."""

    x: jnp.ndarray
    cache: PagedKVCache
    counters: jnp.ndarray
    experts: jnp.ndarray
    ffn_in: jnp.ndarray
    n_selected: jnp.ndarray
    selected: jnp.ndarray
    selected_window: jnp.ndarray


def trunk(params, token_ids, slot, position, real, rows: Rows,
          cache: PagedKVCache, rope, config: KeyeVL2Config, attn: str,
          window: Optional[Window] = None, probe: bool = False) -> TrunkOut:
    """Embed, every layer, final norm, over T tokens: token_ids, slot,
    position [T] int32, real [T] bool (a token that is not real writes
    nothing, is not routed, and its output is garbage nobody reads)."""
    c = config
    blocks = params["blocks"]
    T = token_ids.shape[0]
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], token_ids, axis=0)
    at = jnp.minimum(position, rope.cos.shape[0] - 1)
    cos, sin = jnp.take(rope.cos, at, axis=0), jnp.take(rope.sin, at, axis=0)
    icos = jnp.take(rope.index_cos, at, axis=0)
    isin = jnp.take(rope.index_sin, at, axis=0)
    table = cache.table
    first = jnp.minimum(rows.first, T - 1)
    # a row's single token; the window's row and an idle row have none
    single_pos = jnp.where(rows.n == 1, rows.pos, -1)
    win_pos = win_last = None
    if window is not None:
        win_pos = rows.pos[window.row]
        win_last = win_pos + jnp.maximum(window.n, 1) - 1
    stacked = {k: blocks[k] for k in glm_dsa.EXPERT_LEAVES}
    scanned = {k: v for k, v in blocks.items() if k not in stacked}

    def body(carry, lp):
        x, layer, pool_k, pool_v, pool_idx = carry
        with jax.named_scope("attn_norm"):
            h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
        with jax.named_scope("qkv"), jax.named_scope("gqa_proj"):
            # (an RMSNorm a head: over head_dim, one weight vector a
            # layer), then the rotation
            q = apply_rope(rms_norm(
                qmatmul(h, lp["wq"]).reshape(1, T, H, hd), lp["q_norm"],
                c.rms_norm_eps), cos, sin)[0]
            k = apply_rope(rms_norm(
                qmatmul(h, lp["wk"]).reshape(1, T, KV, hd), lp["k_norm"],
                c.rms_norm_eps), cos, sin)[0]
            v = qmatmul(h, lp["wv"])
        with jax.named_scope("attn"):
            pool_idx, selection, distinct = select_keys(
                lp, h, icos, isin, slot, position, real, first, single_pos,
                pool_idx, layer, table, c, window, win_pos, win_last)
            with jax.named_scope("gqa_full"):
                pool_k = write_token_rows(pool_k, layer, k.reshape(T, KV * hd),
                                          slot, position, real, table)
                pool_v = write_token_rows(pool_v, layer, v, slot, position,
                                          real, table)
                out = attend_rows(q[first], pool_k, pool_v, layer, table,
                                  single_pos, selection.rows, attn)
                if window is None:
                    o = out[slot]
                else:
                    win = attend_window(
                        _window_slice(q, window), pool_k, pool_v, layer,
                        table[window.row], win_pos, window.n,
                        selection.picked, attn)
                    o = jnp.where(window.member[:, None, None],
                                  win[window.col], out[slot])
        with jax.named_scope("o_proj"):
            x = x + qmatmul(o.reshape(T, H * hd), lp["wo"])
        with jax.named_scope("ffn"):
            h = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps)
            lp = dict(lp, **{k: LayerOf(w, layer)
                             for k, w in stacked.items()})
            out, stats = glm_dsa.ffn(lp, h, real, c)
            x = x + out
        # (None is no leaf: a step program's scan stacks no masks)
        sets = (selection.rows, selection.picked) if probe else None
        return ((x, layer + 1, pool_k, pool_v, pool_idx),
                (stats, h, distinct, sets))

    with jax.named_scope("layers"):
        (x, _, pool_k, pool_v, pool_idx), (
            moe, ffn_in, distinct, sets) = lax.scan(
                body, (x, jnp.int32(0), cache.k, cache.v, cache.idx), scanned)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    L, P = c.num_hidden_layers, cache.page_size
    S = table.shape[1] * P
    K = min(c.index_topk, S)
    visible = jnp.where(real, position + 1, 0).astype(F32)
    single = rows.n == 1
    n_single = jnp.sum(single, dtype=F32)
    last = rows.pos + rows.n - 1
    # what the window's selection walked, the table's width, and the
    # keys its score pass visited
    walked = [0, 0, 0] if window is None else [
        L * mla.select_walked(win_last, window.width, S), L * S,
        L * mla.index_scored(win_last, S)]
    counters = jnp.stack([
        jnp.sum(moe.rows), jnp.sum(moe.rows_padded), jnp.mean(moe.load_max),
        jnp.mean(moe.load_mean), jnp.sum(moe.touched),
        jnp.sum(moe.rows_routed),
        L * jnp.sum(visible), L * jnp.sum(jnp.minimum(visible, K)),
        jnp.sum(distinct), L * jnp.any(real).astype(F32),
        L * jnp.sum(jnp.where(single, jnp.minimum(last + 1, K), 0),
                    dtype=F32),
        L * jnp.sum(jnp.where(single, last + 1, 0), dtype=F32),
        n_single,
        jnp.sum(jnp.where(rows.n > 0, last // P + 1, 0), dtype=F32),
        *walked,
        # every single-token row attends by the walk under its mask
        L * jnp.sum(jnp.where(single, last // P + 1, 0), dtype=F32),
        n_single,
    ]).astype(F32)
    return TrunkOut(
        x, cache._replace(k=pool_k, v=pool_v, idx=pool_idx), counters,
        moe.experts, ffn_in,
        jnp.minimum(single_pos + 1, K).astype(jnp.int32),
        *(jnp.zeros((0,), bool) if s is None else s
          for s in (sets or (None, None))))


# -- the step programs ---------------------------------------------------------


def mixed_trunk(params, tokens, pos, q_len, active, cache: PagedKVCache,
                rope, config: KeyeVL2Config, attn: str, n_tokens: int,
                probe: bool = False):
    """The mixed step's trunk on the packed axis [n_tokens] ->
    (TrunkOut, PackPlan)."""
    plan = paged.pack_plan(q_len, active, n_tokens, tokens.shape[1])
    n = jnp.where(active, q_len, 0).astype(jnp.int32)
    out = trunk(params, tokens[plan.row, plan.col], plan.row,
                pos[plan.row] + plan.col, plan.real,
                Rows(plan.start, n, pos.astype(jnp.int32)), cache, rope,
                config, attn, window_of(plan, n), probe)
    return out, plan


@partial(jax.jit, static_argnames=("config", "attn", "n_tokens"),
         donate_argnames=("cache",))
def mixed_step_selected(params, tokens, pos, q_len, active,
                        cache: PagedKVCache, rope, config: KeyeVL2Config,
                        attn: str = "fold", n_tokens: Optional[int] = None):
    """paged.mixed_step_paged's contract: tokens [B, C] right-padded
    windows, pos/q_len [B], active [B] -> (logits [B, V] of each row's
    last real token, cache, counters). At most ONE active row may hold
    more than one token (module docstring), and n_tokens, the packed
    size, is required."""
    if n_tokens is None:
        raise ValueError("the selected mixed step runs on the packed axis: "
                         "pass n_tokens")
    out, plan = mixed_trunk(params, tokens, pos, q_len, active, cache, rope,
                            config, attn, n_tokens)
    with jax.named_scope("head"):
        last = (jnp.maximum(q_len, 1) - 1).astype(jnp.int32)
        last = jnp.take(out.x, jnp.minimum(plan.start + last, n_tokens - 1),
                        axis=0)
        logits = qmatmul(last, params["lm_head"]).astype(F32)
    return logits, out.cache, out.counters


def decode_trunk(params, tokens, cache: PagedKVCache, pos, active, rope,
                 config: KeyeVL2Config, attn: str,
                 probe: bool = False) -> TrunkOut:
    """One token a row: tokens [B, 1], pos/active [B]."""
    B = tokens.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    pos = pos.astype(jnp.int32)
    return trunk(params, tokens[:, 0], rows, pos, active,
                 Rows(rows, active.astype(jnp.int32), pos), cache, rope,
                 config, attn, probe=probe)


def forward_ragged_selected(params, tokens, cache: PagedKVCache, pos, active,
                            rope, config: KeyeVL2Config, attn: str = "fold"):
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
def decode_step_selected(params, tokens, pos, active, cache: PagedKVCache,
                         rope, config: KeyeVL2Config, attn: str = "fold"):
    """paged.decode_step_ragged_paged's contract (the synchronous
    decode step)."""
    return forward_ragged_selected(params, tokens, cache, pos, active, rope,
                                   config, attn)


# -- what the engine reads of this family (models/family.py) ----------------


def create_cache(config: KeyeVL2Config, slots: int, n_pages: int,
                 page_size: int, max_seq_len: int, width, dtype):
    """K, V and index-key pools over one page table: a token leaves
    KV * hd of each of the first two and index_head_dim of the third in
    every layer."""
    c = config
    row = (c.num_hidden_layers, n_pages, page_size,
           c.num_key_value_heads * c.head_dim)
    return PagedKVCache.zeros(
        row, row, slots, max_seq_len // page_size, dtype,
        shape_idx=row[:3] + (c.index_head_dim,))


def mixed_attn_walk(config, cache, width: int):
    """What the engine counts into a mixed record
    (Family.mixed_attn_walk): (the window's first position, its tokens)
    -> (pages, table entries, folds) of a layer's `cake_mixed_attn`
    call, as attend_window makes it: the window in entries of
    `query_tile` queries over its row's table, at the pages a fold the
    kernel takes for these shapes."""
    c = config
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    P, max_pages = cache.k.shape[2], cache.table.shape[1]
    q_size = kv_size = cache.k.dtype.itemsize
    tile = query_tile(width, H, KV, hd, P, q_size, kv_size)
    block = rpa.mixed_block(P, H, KV, hd, tile, max_pages, q_size, kv_size,
                            selecting=True)

    def walk(first_pos: int, n: int):
        return rpa.mixed_entries_walk(first_pos, n, width, tile, P,
                                      max_pages, block)

    return walk


FAMILY = Family(
    name="KeyeVL2", decode_step=decode_step_selected,
    decode_programs=make_decode_scan(forward_ragged_selected),
    mixed_step=mixed_step_selected,
    mixed_sampled=make_mixed_sampled(mixed_step_selected),
    create_cache=create_cache, counters=COUNTERS,
    # one window a dispatch (the score pass takes the one row whose
    # keys the queries share), so one packed size; a step of k prompts
    # is k dispatches (GLM's and dots3's form)
    prefill_rows=(1,), windows=Windows.DISPATCH,
    impl="paged-dsa-gqa-", resolve_attn=_resolve_attn,
    # no step kind's rows go through the kernels AS THEY ARE: both
    # calls carry a mask (a single token's walks its row's own pages
    # since PR 66, and the step program counts them itself:
    # dsa_walk_pages_single)
    kernel_rows=(), mixed_attn_walk=mixed_attn_walk,
    what="selected keys over K/V pages and an index-key pool beside them",
    refuses=cannot_move(
        "index-key pool",
        register_prefix=(
            "an index-key pool (KeyeVL2) has no prefix pages yet: the "
            "prefix path prefills and maps K and V pages, not the third "
            "pool's (ROADMAP.md R2)"),
        reconfigure=(
            "an index-key pool (KeyeVL2) serves on float pages only: "
            "there is no dense or quantized pool that holds it")))
