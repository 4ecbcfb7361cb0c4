"""Sparse mixture-of-experts FFN: published routing, experts computed
only where routed.

The reference is dense-only (`mlp.rs:7-11` — SURVEY.md §2.6 lists expert
parallelism as absent); this is a capability extension, shared by the
Mixtral and OLMoE families (models/moe). One layer, on N tokens:

  * `route` = `choose` over `router_logits`: float32 router logits
    (the linear router's, or what the family hands `moe_mlp` as
    `logits`) and the family's rule, which is
    data (`scoring`, `norm_topk_prob`, `scale`, a bias leaf): scores by
    softmax over ALL experts (Mixtral,
    OLMoE) or by sigmoid (GLM), the k largest scores and their indices
    (GLM selects by score + a learned bias and weighs by the score
    alone), the weights divided by their sum only if the family says so
    (`norm_topk_prob`: Mixtral's softmax over the top-k logits is
    exactly that; OLMoE keeps the raw probabilities), times a scale.
    Returns `(weights [N,k], experts [N,k])`.
  * `dispatch_plan`: the N*k (token, expert) pairs sorted by expert.
    Tokens outside `token_mask` (a mixed step's padded positions, idle
    rows) and, under expert parallelism, pairs whose expert lives on
    another shard fall into a null group behind the last expert and
    take no expert's time. Shapes depend on N and k only, so a new
    routing never compiles anything; the sorts and the row gathers run
    over all N*k pairs, so a caller with many empty positions packs its
    tokens first (the mixed step does: paged.pack_plan).
  * `grouped_matmul` (`cake_moe_gmm`): one Pallas matmul per projection
    over the sorted rows. The grid walks (row tile, expert) VISITS: a
    tile of `tm` sorted rows that straddles two experts is visited once
    for each and stores only that expert's rows, so work is proportional
    to N*k plus at most one tile per expert, never to N*E. The grid is
    (N_out / tn, V): V = M/tm + E - 1 visits for each block of `tn`
    output columns, and a grid step costs its fixed part whatever it
    holds, so `tn` is the widest multiple of 128 that divides the
    output width and whose blocks fit `GMM_VMEM_BUDGET` by
    `gmm_vmem_bytes`' count (`out_tile`: a function of K, the width,
    `tm` and the two element widths; `gmm_grid` gives a call's tile,
    column tiles, visits and grid steps from shapes alone). Weights are
    read as stored: the kernel takes the layer's STACKED leaf
    `[L, E, in, out]` and the layer index as a scalar-prefetch operand
    (a scan-sliced operand of a custom call would be copied every
    layer), int8 blocks are widened to the activation type in VMEM and
    the per-channel scale multiplies the f32 accumulator.
  * the combine gathers each token's k rows back and sums them under
    the routing weights.

A layer may hold fewer experts than its router names. Under `shard_map`
pass `ep_axis`: each shard holds an `[E/ep, ...]` slice of the expert
weights, computes the pairs routed to its experts and `psum`s the
partial outputs over the axis (a shard_map with `check_vma=False`, as
parallel/pipeline.py's are: the kernel's result carries no varying-axes
annotation). On one chip pass `first_expert`: the layer is one share of
an expert-parallel deployment, routes over all the router's experts and
computes the pairs routed to `first_expert .. first_expert + E_local - 1`;
what the absent experts would add is left out (there is no exchange to
run). A shared expert (`ws_*` leaves) is added to every token.

Every call also returns the layer's counters (`MoEStats`), computed on
the device from the same group sizes the kernel walks.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops import ragged_paged_attention as rpa
from cake_tpu.ops.quant import QTensor, is_groupwise

EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


class LayerOf(NamedTuple):
    """A layer's expert weights as (the stacked leaf [L, E, in, out],
    the layer index): what a layer loop hands `moe_mlp` instead of a
    slice, so that the kernel indexes the stack where it lies."""

    stacked: object
    layer: jnp.ndarray


class MoEStats(NamedTuple):
    """One layer's counters, float32 scalars. rows: (token, expert)
    pairs computed; rows_padded: rows the kernel's visits cover (tile
    padding included); load_max / load_mean: tokens on the busiest
    expert / on the average expert (of those on this shard); touched:
    experts with at least one token, whose weights the step reads.
    experts [N, k] int32 is the routing itself, for a tool that
    compares it with a reference's (chip_compare.py); a step program
    returns the counters alone and the compiler drops it.
    rows_routed: the real tokens' pairs over ALL the router's experts
    (== rows unless the layer holds a share of them). group_held: the
    real tokens whose chosen groups include the held experts' (group-
    limited routing on a share; None elsewhere). pairs_zero: the real
    tokens' pairs that chose a zero expert (a router wider than its
    experts; None elsewhere): counted in rows_routed, never in rows."""

    rows: jnp.ndarray
    rows_padded: jnp.ndarray
    load_max: jnp.ndarray
    load_mean: jnp.ndarray
    touched: jnp.ndarray
    experts: jnp.ndarray
    rows_routed: jnp.ndarray
    # (None is no leaf: a layer scan's outputs are what they were)
    group_held: Optional[jnp.ndarray] = None
    pairs_zero: Optional[jnp.ndarray] = None


def router_logits(x, router_w):
    """x [N, D], router_w [D, E] -> float32 logits [N, E] whatever the
    activations' type: the linear router every family but one has."""
    return jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)


def top_groups(probs, n_group: int, topk_group: int, group_top: int = 1):
    """The groups a token may choose its experts from: probs [N, E],
    the E experts in n_group equal groups of neighbours -> [N,
    topk_group] int32, the groups with the largest score, best first,
    ties to the lower index. A group's score is the sum of its
    `group_top` best: 1, its BEST (DeepSeek-V2's group_limited_greedy);
    2, the sum of its two best (noaux_tc: DeepSeek-V3, Ling)."""
    N, E = probs.shape
    grouped = probs.reshape(N, n_group, E // n_group)
    if group_top == 1:
        score = jnp.max(grouped, axis=-1)
    else:
        score = jnp.sum(lax.top_k(grouped, group_top)[0], axis=-1)
    return lax.top_k(score, topk_group)[1].astype(jnp.int32)


def choose_in_groups(logits, k: int, norm_topk_prob: bool,
                     scoring: str = "softmax", scale: float = 1.0, bias=None,
                     n_group: int = 1, topk_group: int = 1,
                     group_top: int = 1):
    """The family's rule on float32 logits [N, E], however they were
    made -> (weights [N, k] f32, experts [N, k] int32, groups). Scores
    over all E experts, by `scoring` ("softmax" or "sigmoid"); the top
    k; renormalised over the k only if `norm_topk_prob`; times `scale`.
    bias [E] f32 (GLM's e_score_correction_bias, ZAYA's balancing
    bias, Ling's expert_bias): added to the scores for the CHOICE only
    (of groups and of experts), the weights are the unbiased scores.
    n_group > 1 (DeepSeek-V2, Ling): the choice is limited to the
    `topk_group` groups of `top_groups`, which are returned ([N,
    topk_group] int32; None where the rule has no groups); a group not
    taken is never chosen (its scores are set to 0 before the top k;
    under a bias, which may leave a taken score below 0, to -inf)."""
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown scoring {scoring!r}")
    choice = probs if bias is None else probs + bias.astype(jnp.float32)
    groups = None
    if n_group > 1:
        E = probs.shape[-1]
        groups = top_groups(choice, n_group, topk_group, group_top)
        taken = jnp.any(
            (jnp.arange(E) // (E // n_group))[None, None, :]
            == groups[:, :, None], axis=1)
        choice = jnp.where(taken, choice, 0.0 if bias is None else -jnp.inf)
    if bias is None:
        weights, experts = lax.top_k(choice, k)
    else:
        _, experts = lax.top_k(choice, k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk_prob:
        norm = jnp.sum(weights, axis=-1, keepdims=True)
        # GLM's guard against an all-zero sigmoid row; softmax's sum
        # of k probabilities needs none and keeps its bits
        weights = weights / (norm + 1e-20 if scoring == "sigmoid" else norm)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32), groups


def choose(logits, k: int, norm_topk_prob: bool,
           scoring: str = "softmax", scale: float = 1.0, bias=None,
           n_group: int = 1, topk_group: int = 1, group_top: int = 1):
    """`choose_in_groups` -> (weights, experts) alone."""
    return choose_in_groups(logits, k, norm_topk_prob, scoring, scale, bias,
                            n_group, topk_group, group_top)[:2]


def route(x, router_w, k: int, norm_topk_prob: bool,
          scoring: str = "softmax", scale: float = 1.0, bias=None,
          n_group: int = 1, topk_group: int = 1, group_top: int = 1):
    """x [N, D], router_w [D, E] -> `choose` over the linear router's
    logits."""
    return choose(router_logits(x, router_w), k, norm_topk_prob, scoring,
                  scale, bias, n_group, topk_group, group_top)


# -- the sorted dispatch -------------------------------------------------------


def row_tile(n_rows: int) -> int:
    """Rows per kernel tile, from the static row count alone: a decode
    step's few rows take the smallest tile both activation types tile
    to, a mixed step's thousands fill the MXU's 128."""
    return 128 if n_rows >= 1024 else 16


class DispatchPlan(NamedTuple):
    """The sorted order and the kernel's walk over it.

    src_token [M]: the token each sorted row reads (M = N*k rounded up
    to the tile); slot_of [N, k]: the sorted row of each pair (0 for a
    dropped pair, whose weight is zeroed); valid [N, k]; counts [E];
    visit_* [V]: the (row tile, expert, first row, one past the last
    row) of each kernel visit, V = M/tm + E - 1, unused visits last
    with an empty row range."""

    src_token: jnp.ndarray
    slot_of: jnp.ndarray
    valid: jnp.ndarray
    counts: jnp.ndarray
    visit_tile: jnp.ndarray
    visit_expert: jnp.ndarray
    visit_lo: jnp.ndarray
    visit_hi: jnp.ndarray
    tm: int


def dispatch_plan(experts, n_experts: int, valid=None,
                  tm: Optional[int] = None) -> DispatchPlan:
    """experts [N, k] int32 in [0, n_experts); valid [N, k] bool or
    None. Pairs that are not valid sort behind every expert and are
    neither gathered nor computed."""
    N, k = experts.shape
    E = n_experts
    tm = tm or row_tile(N * k)
    M = -(-(N * k) // tm) * tm
    flat = experts.reshape(N * k)
    if valid is not None:
        flat = jnp.where(valid.reshape(N * k), flat, E)
    flat = jnp.pad(flat, (0, M - N * k), constant_values=E)
    # sorted row -> pair, and its inverse (a second sort: a permutation
    # scatter is serial on the TPU)
    pair_of = jnp.argsort(flat, stable=True).astype(jnp.int32)
    slot_of = jnp.argsort(pair_of).astype(jnp.int32)[:N * k]
    counts = jnp.sum(flat[:, None] == jnp.arange(E)[None, :], axis=0,
                     dtype=jnp.int32)                        # [E]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    src_token = jnp.minimum(pair_of // k, N - 1)
    ok = (flat < E)[:N * k].reshape(N, k)
    slot_of = jnp.where(ok, slot_of.reshape(N, k), 0)

    # the walk: expert e covers tiles starts[e]//tm .. (ends[e]-1)//tm
    first = starts // tm
    n_vis = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    vis_end = jnp.cumsum(n_vis)
    vis_start = vis_end - n_vis
    V = M // tm + E - 1
    v = jnp.arange(V, dtype=jnp.int32)
    e_of = jnp.sum(v[:, None] >= vis_end[None, :], axis=1,
                   dtype=jnp.int32)                          # [V]
    used = v < vis_end[-1]
    e_of = jnp.minimum(e_of, E - 1)
    tile = first[e_of] + (v - vis_start[e_of])
    lo = jnp.maximum(starts[e_of], tile * tm)
    hi = jnp.minimum(ends[e_of], (tile + 1) * tm)
    # unused visits repeat the last used one's blocks (no DMA) with an
    # empty row range (no compute, no store)
    last = jnp.maximum(vis_end[-1] - 1, 0)
    tile = jnp.where(used, tile, tile[last])
    e_of = jnp.where(used, e_of, e_of[last])
    lo = jnp.where(used, lo, 0)
    hi = jnp.where(used, hi, 0)
    return DispatchPlan(src_token, slot_of, ok, counts,
                        tile.astype(jnp.int32), e_of.astype(jnp.int32),
                        lo.astype(jnp.int32), hi.astype(jnp.int32), tm)


def plan_stats(plan: DispatchPlan, experts, rows_routed=None) -> MoEStats:
    counts = plan.counts.astype(jnp.float32)
    visits = jnp.sum(plan.visit_hi > plan.visit_lo)
    rows = jnp.sum(counts)
    return MoEStats(rows=rows,
                    rows_padded=(visits * plan.tm).astype(jnp.float32),
                    load_max=jnp.max(counts), load_mean=jnp.mean(counts),
                    touched=jnp.sum(counts > 0).astype(jnp.float32),
                    experts=experts,
                    rows_routed=rows if rows_routed is None else rows_routed)


# -- the grouped matmul --------------------------------------------------------


# What one grid step may hold in VMEM by `gmm_vmem_bytes`' count: GLM's
# [6144, 512] int8 block beside a [128, 6144] bf16 tile, the fullest
# step a served model ran under the {512, 256, 128} rule, counts 15.53
# MiB and compiles under the compiler's 16 MiB of scoped VMEM (Mosaic
# widens the block a slice at a time: the count is an upper bound).
GMM_VMEM_BUDGET = 15 * 1024 * 1024 + 768 * 1024


def gmm_vmem_bytes(tm: int, K: int, tn: int, x_bytes: int, w_bytes: int,
                   scaled: bool) -> int:
    """Bytes a `cake_moe_gmm` grid step holds at once: the activation
    tile [tm, K] and the weight block [K, tn] as stored (and its scales,
    one row padded to eight) twice each, for the pipeline's two buffers,
    the block widened to the activation type where it is stored
    narrower, the float32 accumulator and the output block twice."""
    held = 2 * tm * K * x_bytes + 2 * K * tn * w_bytes
    if scaled:
        held += 2 * 8 * tn * 4
    if w_bytes != x_bytes:
        held += K * tn * x_bytes
    return held + tm * tn * 4 + 2 * tm * tn * x_bytes


def out_tile(K: int, n_out: int, tm: int, x_bytes: int, w_bytes: int,
             scaled: bool) -> int:
    """Output columns a grid step computes: the widest multiple of 128
    that divides `n_out` and fits `GMM_VMEM_BUDGET` (128 where none
    does; an `n_out` that is no multiple of 128 is one block)."""
    if n_out % 128:
        return n_out
    fits = [tn for tn in range(128, n_out + 1, 128) if n_out % tn == 0
            and gmm_vmem_bytes(tm, K, tn, x_bytes, w_bytes, scaled)
            <= GMM_VMEM_BUDGET]
    return max(fits, default=128)


class GmmGrid(NamedTuple):
    """One `grouped_matmul` call's walk: `steps` = `column_tiles` x
    `visits` grid steps over blocks of `tn` output columns."""

    tn: int
    column_tiles: int
    visits: int
    steps: int


def gmm_grid(n_pairs: int, n_experts: int, K: int, n_out: int,
             x_bytes: int, w_bytes: int, scaled: bool) -> GmmGrid:
    """The grid of one projection K -> n_out over `n_pairs` (token,
    expert) pairs and `n_experts` held experts, from shapes and element
    widths alone: what `dispatch_plan` and `grouped_matmul` build."""
    tm = row_tile(n_pairs)
    visits = -(-n_pairs // tm) + n_experts - 1
    tn = out_tile(K, n_out, tm, x_bytes, w_bytes, scaled)
    return GmmGrid(tn, n_out // tn, visits, n_out // tn * visits)


def _gmm_kernel(layer_ref, tile_ref, expert_ref, lo_ref, hi_ref,
                x_ref, w_ref, *rest, tm: int, scaled: bool):
    del layer_ref, expert_ref
    if scaled:
        s_ref, o_ref = rest
    else:
        (o_ref,) = rest
    v = pl.program_id(1)
    lo, hi = lo_ref[v], hi_ref[v]

    @pl.when(hi > lo)
    def _():
        x = x_ref[...]
        w = w_ref[...].astype(x.dtype)
        # bf16 operands pin DEFAULT precision (Mosaic refuses a bf16
        # lhs under the test lane's process-wide `highest`; see
        # ragged_paged_attention._dot)
        acc = lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            precision=(lax.Precision.DEFAULT
                       if x.dtype == jnp.bfloat16 else None),
            preferred_element_type=jnp.float32)
        if scaled:
            acc = acc * s_ref[...]
        rows = tile_ref[v] * tm + lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (rows >= lo) & (rows < hi)
        # a tile that straddles experts is visited once for each: keep
        # what the earlier visits stored
        o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_matmul(x, w, layer, visit_tile, visit_expert, visit_lo,
                   visit_hi, *, tm: int, interpret: Optional[bool] = None):
    """Rows [M, K] sorted by expert times the experts' weights.

    w: the STACKED leaf [L, E, K, N] — an array in x's type, or a
    per-channel QTensor (q int8 [L, E, K, N], scale f32 [L, E, N]);
    layer: int32 scalar. Row r of the result is x[r] @ w[layer, e(r)]
    for the rows inside some visit's [lo, hi); the rest are unspecified.
    """
    if interpret is None:
        interpret = not rpa._on_tpu()
    scaled = isinstance(w, QTensor)
    if scaled and is_groupwise(w):
        raise NotImplementedError(
            "group-wise (int4) expert weights are not supported; "
            "use --quant int8 for MoE models")
    q = w.q if scaled else w
    M, K = x.shape
    _, _, Kw, N = q.shape
    assert K == Kw and M % tm == 0, (x.shape, q.shape, tm)
    tn = out_tile(K, N, tm, x.dtype.itemsize, q.dtype.itemsize,
                  scaled)
    V = visit_tile.shape[0]

    def x_map(j, v, layer, tile, expert, lo, hi):
        return tile[v], 0

    def w_map(j, v, layer, tile, expert, lo, hi):   # the scales' too
        return layer[0], expert[v], 0, j

    def o_map(j, v, layer, tile, expert, lo, hi):
        return tile[v], j

    in_specs = [pl.BlockSpec((tm, K), x_map),
                pl.BlockSpec((None, None, K, tn), w_map)]
    operands = [x, q]
    if scaled:
        in_specs.append(pl.BlockSpec((None, None, 1, tn), w_map))
        operands.append(w.scale[:, :, None, :])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, scaled=scaled),
        name="cake_moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, V),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, tn), o_map)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), visit_tile, visit_expert,
      visit_lo, visit_hi, *operands)


# -- the layer -----------------------------------------------------------------


def _stacked(leaf):
    """(stacked weights [L, E, in, out], layer) of an expert leaf given
    as a LayerOf or as one layer's own slice."""
    if isinstance(leaf, LayerOf):
        return leaf.stacked, leaf.layer
    return jax.tree.map(lambda a: a[None], leaf), jnp.int32(0)


def _held_experts(leaf) -> int:
    """The experts an expert leaf holds (its E axis), LayerOf or slice."""
    w = getattr(leaf, "stacked", leaf)
    return getattr(w, "q", w).shape[-3]


def relu2(x):
    return jnp.square(jax.nn.relu(x))


ACTIVATIONS = {"silu": jax.nn.silu, "relu2": relu2}


def _experts_ffn(x, weights, experts, valid, stacks, layer, e_local: int,
                 act: str = "silu"):
    """The routed experts on tokens x [N, D] -> (out [N, D] f32, plan).
    weights/experts/valid: [N, k]; stacks: the stacked leaves (gate or
    None, up, down): with a gate act(gate) * up (SwiGLU), without one
    act(up)."""
    N, D = x.shape
    k = experts.shape[1]
    w_gate, w_up, w_down = stacks
    with jax.named_scope("moe_dispatch"):
        plan = dispatch_plan(experts, e_local, valid)
        xs = jnp.take(x, plan.src_token, axis=0)             # [M, D]
    with jax.named_scope("experts"):
        walk = (plan.visit_tile, plan.visit_expert, plan.visit_lo,
                plan.visit_hi)
        # (the gated form's calls in the order they always had: the
        # compiler's schedule follows it)
        if w_gate is not None:
            gate = grouped_matmul(xs, w_gate, layer, *walk, tm=plan.tm)
        up = grouped_matmul(xs, w_up, layer, *walk, tm=plan.tm)
        hidden = (ACTIVATIONS[act](up) if w_gate is None
                  else ACTIVATIONS[act](gate) * up)
        ys = grouped_matmul(hidden, w_down, layer, *walk, tm=plan.tm)
    with jax.named_scope("moe_combine"):
        picked = jnp.take(ys, plan.slot_of.reshape(N * k), axis=0)
        wk = jnp.where(plan.valid, weights, 0.0)             # [N, k]
        # a dropped pair reads row 0, which nothing may have written:
        # select, never multiply, what is not valid
        picked = jnp.where(plan.valid.reshape(N * k, 1), picked, 0)
        out = jnp.einsum("nkd,nk->nd",
                         picked.reshape(N, k, D).astype(jnp.float32), wk)
    return out, plan


def moe_mlp(lp, h, num_experts_per_tok: int, norm_topk_prob: bool = True,
            ep_axis: Optional[str] = None, token_mask=None,
            first_expert: Optional[int] = None, scoring: str = "softmax",
            scale: float = 1.0, act: str = "silu", logits=None,
            n_group: int = 1, topk_group: int = 1, group_top: int = 1,
            zero_from: Optional[int] = None):
    """Sparse FFN over experts -> (out [B, S, D], MoEStats).

    lp leaves: router [D, E], the linear router, unless the family made
    `logits` [B, S, E] float32 itself (ZAYA's MLP over a state that
    runs down the layers: models/moe/zaya.router_logits); we_gate/we_up [E_local, D, F]; we_down
    [E_local, F, D], each an array, a per-channel QTensor, or a LayerOf
    around the stacked leaf; optionally router_bias [E] (the choice's
    bias, `route`) and ws_gate/ws_up/ws_down, a shared expert every
    token takes. What an expert computes is data of the leaves and of
    `act` ("silu" | "relu2"): with `we_gate` (and `ws_gate`) the gated
    act(gate) * up (SwiGLU: Mixtral, OLMoE, GLM), without them
    act(up) (nemotron_h: relu², no gate). With `w_fc1` [D, R] / `w_fc2`
    [R, D] the routed experts live in an R-wide LATENT (we_up [E, R, F],
    we_down [E, F, R]): every token is projected down once, the shares'
    sums meet in the latent (the psum under `ep_axis`) and are projected
    back once (without `w_fc2` the result stays in the latent: one
    share's part of the sum); the router and the shared expert read h
    itself.
    norm_topk_prob, scoring, scale, n_group, topk_group, group_top: the
    family's rule (`choose`). E_local == E except where the layer holds a share: under
    shard_map EP each shard holds its contiguous slice and `ep_axis`
    names the mesh axis; on one chip `first_expert` (static) is the
    first of the E_local held. token_mask [B, S] bool: positions that are not real
    (padding of a mixed window, idle rows) are not routed and come back
    zero. zero_from (static; None: the router has none): the router's
    first ZERO expert: an index at or past it holds no matrix, its pair
    is neither sorted nor multiplied and adds its weight times the
    layer's own input (an identity expert). Every share of a layer
    computes that part alike, as it does a shared expert: it is added
    once, after the sum. Returns the *unreduced-over-tp* output: when F
    is additionally Megatron-sharded the caller (block_skeleton) psums over tp, exactly
    as for the dense path — EP and TP reductions compose.
    """
    B, S, D = h.shape
    N, k = B * S, num_experts_per_tok
    x = h.reshape(N, D)
    with jax.named_scope("router"):
        if logits is None:
            logits = router_logits(x, lp["router"])
        logits = logits.reshape(N, -1)
        weights, experts, groups = choose_in_groups(
            logits, k, norm_topk_prob, scoring, scale,
            lp.get("router_bias"), n_group, topk_group, group_top)
        routed = experts
        took = None
        if groups is not None and first_expert is not None:
            # the tokens whose groups include one of the held experts'
            # (whole groups are held: one, or a run of neighbours)
            per = logits.shape[-1] // n_group
            held = first_expert // per
            last = (first_expert + _held_experts(lp["we_up"]) - 1) // per
            took = jnp.any(groups == held if last == held
                           else (groups >= held) & (groups <= last), axis=-1)

    from cake_tpu.ops.quant import qmatmul

    w_up, layer = _stacked(lp["we_up"])
    stacks = (_stacked(lp["we_gate"])[0] if "we_gate" in lp else None,
              w_up, _stacked(lp["we_down"])[0])
    e_local = _held_experts(lp["we_up"])
    x_in = x
    if "w_fc1" in lp:
        with jax.named_scope("moe_latent"):
            x_in = qmatmul(x, lp["w_fc1"])
    mask = None if token_mask is None else token_mask.reshape(N)
    valid = None if mask is None else jnp.broadcast_to(mask[:, None], (N, k))
    rows_routed = zero_weight = pairs_zero = None
    if zero_from is not None:
        zero = experts >= zero_from
        if valid is not None:
            zero = zero & valid
        zero_weight = jnp.sum(jnp.where(zero, weights, 0.0), axis=-1)
        pairs_zero = jnp.sum(zero, dtype=jnp.float32)
        rows_routed = (jnp.float32(N * k) if valid is None
                       else jnp.sum(valid, dtype=jnp.float32))
        valid = ~zero if valid is None else valid & ~zero
        experts = jnp.minimum(experts, zero_from - 1)
    if ep_axis is not None or first_expert is not None:
        if rows_routed is None:
            rows_routed = (jnp.float32(N * k) if valid is None
                           else jnp.sum(valid, dtype=jnp.float32))
        first = (first_expert if ep_axis is None
                 else lax.axis_index(ep_axis) * e_local)
        experts = experts - first
        here = (experts >= 0) & (experts < e_local)
        valid = here if valid is None else valid & here
        experts = jnp.clip(experts, 0, e_local - 1)

    out, plan = _experts_ffn(x_in, weights, experts, valid, stacks, layer,
                             e_local, act)
    stats = plan_stats(plan, routed, rows_routed)
    if took is not None:
        stats = stats._replace(group_held=jnp.sum(
            took if mask is None else took & mask, dtype=jnp.float32))
    if pairs_zero is not None:
        stats = stats._replace(pairs_zero=pairs_zero)
    if mask is not None:
        out = jnp.where(mask[:, None], out, 0.0)
    if ep_axis is not None:
        out = lax.psum(out, ep_axis)
    if zero_weight is not None:
        # (a token outside the mask has no zero pair: its weight is 0)
        with jax.named_scope("zero_experts"):
            out = out + zero_weight[:, None] * x.astype(jnp.float32)
    if "w_fc2" in lp:
        with jax.named_scope("moe_latent"):
            out = qmatmul(out.astype(h.dtype),
                          lp["w_fc2"]).astype(jnp.float32)
    if "ws_up" in lp:
        # every shard computes it alike: added once, after the sum
        with jax.named_scope("shared_expert"):
            if "ws_gate" in lp:
                gate = ACTIVATIONS[act](qmatmul(x, lp["ws_gate"]))
                hidden = gate * qmatmul(x, lp["ws_up"])
            else:
                hidden = ACTIVATIONS[act](qmatmul(x, lp["ws_up"]))
            out = out + qmatmul(hidden, lp["ws_down"]).astype(jnp.float32)
    return out.reshape(B, S, -1).astype(h.dtype), stats
