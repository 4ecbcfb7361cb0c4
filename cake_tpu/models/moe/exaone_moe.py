"""K-EXAONE (`exaone_moe`) on the paged engine: the step programs.

The equations are models/reference/exaone_moe.py's; this is how the
served path computes them over K and V pages BY KIND OF LAYER
(models/llama/paged.WindowedKVCache): the full layers' keys and values
in `k` / `v` [L_full, ...] on the allocator's pages through `table`, the
sliding-window layers' in `wk` / `wv` [L_sliding, slots * R, ...] through
the ring `wtable` (logical page p of a row lies in entry p mod R; slot i
owns its R pages for good, so admission, release and a rebuild never
touch them, and a row costs R pages in nine layers of twelve whatever
its context).

Both step programs run ONE trunk over a flat list of tokens, each with
its row (slot) and position: a decode step's B tokens, or a mixed step's
packed axis (paged.pack_plan). A layer is attention behind `attn_norm`
and an FFN behind `mlp_norm`, each with its residual:

  * attention, both kinds: `gqa_proj` (q, k, v; an RMSNorm a head on q
    and k; in a sliding layer the rotation, half-split pairs), the
    write of every real token's k and v into its page
    (paged.write_token_rows, through the ring in a sliding layer), then
    the two ragged paged attention kernels over the pool where it lies.
    A row's single token (every row of a decode step, the decode rows
    of a mixed dispatch) goes through `cake_decode_attn`; the
    dispatch's one window through `cake_mixed_attn`, handed over as
    width / tile entries of `query_tile` queries that share the row's
    table and differ in position (the kernel's VMEM holds 64 queries of
    64 heads, not 512: `query_tile`; the window's K and V are written
    before anything attends, and the mask is per entry). In a sliding
    layer both kernels take the BAND (`window=`): the decode row walks
    the two pages that hold its last 128 keys, a window entry the three
    that hold its queries' bands, through the ring. The scopes
    `gqa_window` and `gqa_full` tell the kinds apart in a device trace;
  * the FFN (`glm_dsa.ffn`): layer 0 a dense SwiGLU, then
    ops/moe.moe_mlp with the sigmoid rule, the choice bias, the held
    experts and the shared expert.

ONE WINDOW A DISPATCH AND A STEP (family.Windows.STEP), as nemotron_h,
deepseek_v2 and bailing_hybrid: one packed size, every decode row rides
every dispatch, and the ring's R stands on a dispatch writing at most
one window of a row.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from cake_tpu.models.family import Family, Windows, cannot_move
from cake_tpu.models.llama import paged
from cake_tpu.models.llama.paged import WindowedKVCache, write_token_rows
from cake_tpu.models.moe import glm_dsa
from cake_tpu.models.moe.config import ExaoneMoeConfig
from cake_tpu.models.moe.glm_dsa import _window_slice
from cake_tpu.models.moe.nemotron_h import (
    Rows, Window, dequantized, window_of,
)
from cake_tpu.models.step_programs import (
    make_decode_scan, make_mixed_sampled,
)
from cake_tpu.ops import ragged_paged_attention as rpa
from cake_tpu.ops.moe import LayerOf
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.quant import qmatmul
from cake_tpu.ops.rope import apply_rope

ATTN_LEAVES = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
               "mlp_norm")
# the record keys of the vector a step program returns, in trunk's
# order: the held experts' five and the routed rows; the sliding layers'
# keys visible and attended (dots3's keys: the same quantities); then
# the full layers' keys; what the single-token rows (the decode kernel's
# calls) attended and the pages they walked, by kind of layer, and those
# rows; and the pages of each pool that hold live keys
GQA_COUNTERS = ("gqa_full_keys_attended", "gqa_window_keys_single",
                "gqa_full_keys_single", "gqa_window_pages_walked",
                "gqa_full_pages_walked", "gqa_rows_single",
                "gqa_ring_pages_live", "gqa_full_pages_live")
COUNTERS = (paged.MOE_COUNTERS + ("moe_rows_routed",)
            + glm_dsa.SWA_COUNTERS + GQA_COUNTERS)
F32 = jnp.float32


def layer_leaves(blocks, config: ExaoneMoeConfig, i: int) -> dict:
    """Layer i's leaves out of the stacks (static indices); the experts
    as (stack, index) for the grouped matmul."""
    def at(names, j):
        return {k: jax.tree.map(lambda a: a[j], blocks[k]) for k in names}

    lp = at(ATTN_LEAVES, i)
    if config.mlp_layer_types[i] == "sparse":
        j = config.sparse_layers.index(i)
        lp.update(at(glm_dsa.SPARSE_LEAVES, j))
        lp.update({k: LayerOf(blocks[k], jnp.int32(j))
                   for k in glm_dsa.EXPERT_LEAVES})
    else:
        lp.update(at(glm_dsa.DENSE_LEAVES,
                     i - sum(s < i for s in config.sparse_layers)))
    return lp


def reference_layers(blocks, config: ExaoneMoeConfig):
    """The per-layer float32 dicts models/reference/exaone_moe.forward
    walks, one at a time (a generator: a caller at published widths
    holds one layer's float32 weights at a time): the served leaves
    dequantized, `kind` ("sliding" | "full") beside them."""
    for i, kind in enumerate(config.indexer_types):
        lp = {k: dequantized(jax.tree.map(lambda a: a[int(v.layer)],
                                          v.stacked)
                             if isinstance(v, LayerOf) else v)
              for k, v in layer_leaves(blocks, config, i).items()}
        yield dict(lp, kind=kind)


def reference_config(config: ExaoneMoeConfig) -> dict:
    """What the reference reads of the config, under the published
    keys (a plain dict: it imports nothing of this package)."""
    c = config
    return {"num_attention_heads": c.num_attention_heads,
            "num_key_value_heads": c.num_key_value_heads,
            "head_dim": c.head_dim, "sliding_window": c.sliding_window_size,
            "rope_theta": c.rope_theta, "rms_norm_eps": c.rms_norm_eps,
            "num_experts_per_tok": c.num_experts_per_tok,
            "norm_topk_prob": c.norm_topk_prob,
            "routed_scaling_factor": c.routed_scaling_factor}


def query_tile(width: int, H: int, KV: int, hd: int, page_size: int,
               q_itemsize: int, kv_itemsize: int) -> int:
    """Queries an entry of the window's mixed-kernel call holds: the
    widest of width, width / 2, width / 4, ... whose scratch, q / out
    and page blocks fit the kernel's scoped VMEM by its own count
    (rpa.mixed_vmem_bytes): 64 at 64 heads of 128 (11.5 MiB; 128 asks
    22), the whole window at a test's sizes. A function of shapes
    alone, so the CPU and the chip tile a window alike."""
    tile = width
    while tile > 8 and tile % 2 == 0 and rpa.mixed_vmem_bytes(
            page_size, H, KV, hd, tile, q_itemsize,
            kv_itemsize) > rpa._VMEM_SCOPED_LIMIT:
        tile //= 2
    return tile


def attend_window(q, pool_k, pool_v, layer, table_row, first_pos, n,
                  attn: str, band: Optional[int]):
    """A dispatch's one window through `cake_mixed_attn`: q [C, H, hd],
    its first token at first_pos, n real tokens, handed over as C / tile
    entries of `query_tile` queries that share the row's table
    (`table_row` [pages], a ring under a band) and differ in position
    (the window's K and V are in the pool already, and the mask is per
    entry) -> [C, H, hd]."""
    C, H, hd = q.shape
    P, KV = pool_k.shape[2], pool_k.shape[3] // hd
    tile = query_tile(C, H, KV, hd, P, q.dtype.itemsize,
                      pool_k.dtype.itemsize)
    n_sub = C // tile
    starts = jnp.arange(n_sub, dtype=jnp.int32) * tile
    at = first_pos + starts
    if band is None:
        # (a padded last entry stays inside the row's table; a ring is
        # read modulo its length)
        at = jnp.minimum(at, table_row.shape[0] * P - 1)
    win = paged.paged_attention_mixed(
        q.reshape(n_sub, tile, H, hd), pool_k, pool_v, layer,
        jnp.broadcast_to(table_row[None], (n_sub, table_row.shape[0])), at,
        jnp.clip(n - starts, 0, tile), impl=attn, window=band)
    return win.reshape(C, H, hd)


def attention(lp, h, cos, sin, pool_k, pool_v, j: int, table, slot,
              position, real, first, single_pos, win_pos,
              config: ExaoneMoeConfig, attn: str, window: Optional[Window],
              band: Optional[int]):
    """h [T, D] -> (out [T, D], pool_k, pool_v): layer j of ITS kind's
    pools. band: the sliding layers' window (then `table` is the ring
    and q, k are rotated), None in a full layer. single_pos [B]: each
    row's single token's position (-1: it has none here, and walks no
    page); win_pos: the window's first position."""
    c = config
    T = h.shape[0]
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    layer = jnp.int32(j)
    ring = band is not None
    with jax.named_scope("qkv"), jax.named_scope("gqa_proj"):
        # (an RMSNorm a head: over head_dim, one weight vector a layer)
        q = rms_norm(qmatmul(h, lp["wq"]).reshape(T, H, hd), lp["q_norm"],
                     c.rms_norm_eps)
        k = rms_norm(qmatmul(h, lp["wk"]).reshape(T, KV, hd), lp["k_norm"],
                     c.rms_norm_eps)
        v = qmatmul(h, lp["wv"])
        if ring:
            q = apply_rope(q[None], cos, sin)[0]
            k = apply_rope(k[None], cos, sin)[0]
    with jax.named_scope("attn"), jax.named_scope(
            "gqa_window" if ring else "gqa_full"):
        pool_k = write_token_rows(pool_k, j, k.reshape(T, KV * hd), slot,
                                  position, real, table, ring=ring)
        pool_v = write_token_rows(pool_v, j, v, slot, position, real, table,
                                  ring=ring)
        out = paged.paged_attention(q[first][:, None], pool_k, pool_v, layer,
                                    table, single_pos, impl=attn,
                                    window=band)[:, 0]
        if window is None:
            o = out[slot]
        else:
            win = attend_window(_window_slice(q, window), pool_k, pool_v,
                                layer, table[window.row], win_pos, window.n,
                                attn, band)
            o = jnp.where(window.member[:, None, None],
                          win[window.col], out[slot])
    with jax.named_scope("o_proj"):
        return qmatmul(o.reshape(T, H * hd), lp["wo"]), pool_k, pool_v


class TrunkOut(NamedTuple):
    """x [T, D] after the final norm; cache; counters [len(COUNTERS)];
    and for a tool that compares them with the reference's
    (chip_compare.py; a step program drops them): experts [L_sparse, T,
    k], each sparse layer's choice, and ffn_in [L_sparse, T, D], each
    sparse layer's normed input (what its router read)."""

    x: jnp.ndarray
    cache: WindowedKVCache
    counters: jnp.ndarray
    experts: jnp.ndarray
    ffn_in: jnp.ndarray


def walk_counters(position, real, rows: Rows, config: ExaoneMoeConfig,
                  page_size: int, ring_pages: int) -> list:
    """glm_dsa.SWA_COUNTERS + GQA_COUNTERS of one dispatch, float32
    scalars: functions of the positions alone (what the band and the
    ring do to a row's walk is arithmetic, so the count is exact and
    costs the device a few reductions over [T] and [B])."""
    c = config
    W, P = c.sliding_window_size, page_size
    Ls, Lf = len(c.sliding_layers), len(c.full_layers)
    visible = jnp.sum(jnp.where(real, position + 1, 0), dtype=F32)
    attended = jnp.sum(jnp.where(real, jnp.minimum(position + 1, W), 0),
                       dtype=F32)
    single = rows.n == 1
    last = rows.pos + rows.n - 1
    band_pages = last // P - jnp.maximum(last - (W - 1), 0) // P + 1
    live = jnp.where(rows.n > 0, last // P + 1, 0)
    return [Ls * visible, Ls * attended, F32(Ls), Lf * visible,
            Ls * jnp.sum(jnp.where(single, jnp.minimum(last + 1, W), 0),
                         dtype=F32),
            Lf * jnp.sum(jnp.where(single, last + 1, 0), dtype=F32),
            Ls * jnp.sum(jnp.where(single, band_pages, 0), dtype=F32),
            Lf * jnp.sum(jnp.where(single, live, 0), dtype=F32),
            jnp.sum(single, dtype=F32),
            jnp.sum(jnp.minimum(live, ring_pages), dtype=F32),
            jnp.sum(live, dtype=F32)]


def trunk(params, token_ids, slot, position, real, rows: Rows,
          cache: WindowedKVCache, rope, config: ExaoneMoeConfig, attn: str,
          window: Optional[Window] = None) -> TrunkOut:
    """Embed, every layer, final norm, over T tokens: token_ids, slot,
    position [T] int32, real [T] bool (a token that is not real writes
    nothing, is not routed, and its output is garbage nobody reads)."""
    c = config
    blocks = params["blocks"]
    T = token_ids.shape[0]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], token_ids, axis=0)
    at = jnp.minimum(position, rope.cos.shape[0] - 1)
    cos, sin = jnp.take(rope.cos, at, axis=0), jnp.take(rope.sin, at, axis=0)
    pools = {"full": (cache.k, cache.v, cache.table, None),
             "sliding": (cache.wk, cache.wv, cache.wtable,
                         c.sliding_window_size)}
    index = {"full": c.full_layers, "sliding": c.sliding_layers}
    first = jnp.minimum(rows.first, T - 1)
    # a row's single token; the window's row and an idle row have none
    single_pos = jnp.where(rows.n == 1, rows.pos, -1)
    win_pos = None if window is None else rows.pos[window.row]
    moe, ffn_in = [], []
    with jax.named_scope("layers"):
        for i, kind in enumerate(c.indexer_types):
            lp = layer_leaves(blocks, c, i)
            with jax.named_scope("attn_norm"):
                h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
            pk, pv, table, band = pools[kind]
            out, pk, pv = attention(
                lp, h, cos, sin, pk, pv, index[kind].index(i), table, slot,
                position, real, first, single_pos, win_pos, c, attn, window,
                band)
            pools[kind] = (pk, pv, table, band)
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
    counters = jnp.stack(
        glm_dsa.moe_counters(moe)
        + walk_counters(position, real, rows, c, cache.page_size,
                        cache.ring_pages)).astype(F32)
    (k, v, _, _), (wk, wv, _, _) = pools["full"], pools["sliding"]
    return TrunkOut(
        x, cache._replace(k=k, v=v, wk=wk, wv=wv), counters,
        jnp.stack([s.experts for s in moe]) if moe else jnp.zeros((0,)),
        jnp.stack(ffn_in) if ffn_in else jnp.zeros((0,)))


# -- the step programs ---------------------------------------------------------


def mixed_trunk(params, tokens, pos, q_len, active, cache: WindowedKVCache,
                rope, config: ExaoneMoeConfig, attn: str, n_tokens: int):
    """The mixed step's trunk on the packed axis [n_tokens] ->
    (TrunkOut, PackPlan)."""
    plan = paged.pack_plan(q_len, active, n_tokens, tokens.shape[1])
    n = jnp.where(active, q_len, 0).astype(jnp.int32)
    out = trunk(params, tokens[plan.row, plan.col], plan.row,
                pos[plan.row] + plan.col, plan.real,
                Rows(plan.start, n, pos.astype(jnp.int32)), cache, rope,
                config, attn, window_of(plan, n))
    return out, plan


@partial(jax.jit, static_argnames=("config", "attn", "n_tokens"),
         donate_argnames=("cache",))
def mixed_step_windowed(params, tokens, pos, q_len, active,
                        cache: WindowedKVCache, rope,
                        config: ExaoneMoeConfig, attn: str = "fold",
                        n_tokens: Optional[int] = None):
    """paged.mixed_step_paged's contract: tokens [B, C] right-padded
    windows, pos/q_len [B], active [B] -> (logits [B, V] of each row's
    last real token, cache, counters). At most ONE active row may hold
    more than one token (module docstring), and n_tokens, the packed
    size, is required."""
    if n_tokens is None:
        raise ValueError("the windowed mixed step runs on the packed axis: "
                         "pass n_tokens")
    out, plan = mixed_trunk(params, tokens, pos, q_len, active, cache, rope,
                            config, attn, n_tokens)
    with jax.named_scope("head"):
        last = (jnp.maximum(q_len, 1) - 1).astype(jnp.int32)
        last = jnp.take(out.x, jnp.minimum(plan.start + last, n_tokens - 1),
                        axis=0)
        logits = qmatmul(last, params["lm_head"]).astype(F32)
    return logits, out.cache, out.counters


def decode_trunk(params, tokens, cache: WindowedKVCache, pos, active, rope,
                 config: ExaoneMoeConfig, attn: str) -> TrunkOut:
    """One token a row: tokens [B, 1], pos/active [B]."""
    B = tokens.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    pos = pos.astype(jnp.int32)
    return trunk(params, tokens[:, 0], rows, pos, active,
                 Rows(rows, active.astype(jnp.int32), pos), cache, rope,
                 config, attn)


def forward_ragged_windowed(params, tokens, cache: WindowedKVCache, pos,
                            active, rope, config: ExaoneMoeConfig,
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
def decode_step_windowed(params, tokens, pos, active, cache: WindowedKVCache,
                         rope, config: ExaoneMoeConfig, attn: str = "fold"):
    """paged.decode_step_ragged_paged's contract (the synchronous
    decode step)."""
    return forward_ragged_windowed(params, tokens, cache, pos, active, rope,
                                   config, attn)


# -- what the engine reads of this family (models/family.py) ----------------


def create_cache(config: ExaoneMoeConfig, slots: int, n_pages: int,
                 page_size: int, max_seq_len: int, width, dtype):
    """K and V pages by kind of layer: the full layers' pools of
    `n_pages` pages that the allocator hands out, the sliding layers' of
    slots x R ring pages that nothing hands out (slot i owns its ring;
    R from the mixed step's window: config.window_ring_pages)."""
    c = config
    if width is None:
        raise ValueError(
            "a model with sliding-window layers keeps a K/V pool and a "
            "table by kind of layer, its ring sized by the mixed step's "
            "window: pass width")
    return WindowedKVCache.create(
        len(c.full_layers), len(c.sliding_layers),
        c.num_key_value_heads * c.head_dim, slots, n_pages, page_size,
        max_seq_len, c.window_ring_pages(page_size, width), dtype=dtype)


def _resolve_attn(config, impl: str, *, explicit: bool, prefill_chunk,
                  slots: int, n_pages: int, page_size: int,
                  max_seq_len: int, q_itemsize: int, kv_itemsize: int):
    """One impl for both step programs. The window stays as wide as
    asked (512 by default) whatever the head count: the mixed program
    runs the decode kernel over the rows' single tokens and the mixed
    kernel over the window in entries of `query_tile` queries, so the
    mixed kernel's gate is asked at the tile, over the full layers'
    table (the ring's is shorter)."""
    c = config
    width = prefill_chunk or min(512, max_seq_len)
    heads = (c.num_attention_heads, c.num_key_value_heads, c.head_dim)
    tile = query_tile(width, *heads, page_size, q_itemsize, kv_itemsize)
    if width % tile:
        raise ValueError(
            f"--prefill-chunk {width}: model_type {c.family.name} hands the "
            f"window to the attention kernel in entries of {tile} queries "
            "(what its VMEM holds at these heads), which must divide it")
    max_pages = -(-max_seq_len // page_size)
    ok = (rpa.ragged_paged_supported(
              page_size, *heads, n_pages=n_pages, slots=slots,
              max_pages=max_pages, kv_itemsize=kv_itemsize)
          and rpa.ragged_paged_mixed_supported(
              page_size, *heads, tile, n_pages=n_pages,
              slots=width // tile, max_pages=max_pages,
              q_itemsize=q_itemsize, kv_itemsize=kv_itemsize))
    if impl == "pallas" and not ok:
        if explicit:
            raise ValueError(
                f"--paged-attn pallas cannot serve model_type {c.family.name} "
                f"on this device at page={page_size} heads={heads} mixed "
                f"width={width} in entries of {tile} "
                "(ops/ragged_paged_attention gates); use --paged-attn "
                "auto or fold")
        impl = "fold"
    return impl, width


FAMILY = Family(
    name="exaone_moe", decode_step=decode_step_windowed,
    decode_programs=make_decode_scan(forward_ragged_windowed),
    mixed_step=mixed_step_windowed,
    mixed_sampled=make_mixed_sampled(mixed_step_windowed),
    create_cache=create_cache, counters=COUNTERS,
    # one window a dispatch, so one packed size; and one a step: the
    # ring's R stands on it (module docstring)
    prefill_rows=(1,), windows=Windows.STEP,
    beside=("window K/V pool (a ring a row)", "gqa_window_pool_bytes"),
    impl="paged-swa-", resolve_attn=_resolve_attn,
    # a row's single token walks its live pages in the FULL layers as
    # cake_decode_attn always did (the host counts those); the mixed
    # program hands cake_mixed_attn the window alone, in entries
    kernel_rows=("decode",),
    what="a K/V ring a row for its sliding-window layers beside the page "
         "pool",
    refuses=cannot_move(
        "K/V ring",
        register_prefix=(
            "a K/V ring (exaone_moe) has no prefix pages yet: a shared "
            "head would need the last window of its keys copied into "
            "each row's ring beside the mapped full-layer pages "
            "(ROADMAP.md R3)"),
        reconfigure=(
            "a K/V ring (exaone_moe) lives beside the page pool: a "
            "rebuilt pool cannot replay it")))
