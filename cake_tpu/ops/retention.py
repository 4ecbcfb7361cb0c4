"""Power retention of degree 2 over the stored state: the symmetric
feature map, a row's single token in place (`step`,
`cake_retention_step`) and a row's window (`window`).

A retention layer (models/moe/brumby.py; the equations are
models/reference/brumby.py's) weighs a key by (q . k)^2 under a decay a
K/V head and token. (q . k)^2 = phi(q) . phi(k) for phi the symmetric
square of a head, so the layer is a linear-attention layer over a state
a row, layer and K/V head that the R query heads of a GQA group share:

    S <- gamma S + phi(k) v^T      [D, dv]
    z <- gamma z + phi(k)          [D]
    y^a = phi(q^a)^T S / (phi(q^a)^T z + head_dim * 1e-6),  a = 1..R

(the reference's 1/head_dim inside the square is folded out of both sums
and into the epsilon). What is new against ops/kda.py and ops/ssm.py:

  * THE FEATURE MAP. A head of hd numbers is cut into hd / 16 tiles of
    16; phi holds, for every pair of tiles ti <= tj, the 256 products
    x[16 ti + i] x[16 tj + j], times sqrt 2 where ti < tj (a diagonal
    pair holds both orders of its off-diagonal products itself): D =
    256 x (hd / 16)(hd / 16 + 1) / 2, 9,216 at heads of 128, whole lane
    tiles, against the 8,256 an exact upper triangle (`phi_exact`: the
    least any layout holds) would take. phi is never stored a token:
    `expand` lays a head out as 2 hd / 16 rows of 256 lanes (each tile
    with its numbers repeated, and tiled), 4,096 numbers from which a
    pair's 256 are ONE elementwise product of two rows, and the kernel
    forms a block's phi(k) and phi(q) from them where it needs them;
  * THE STATE IS KEPT TRANSPOSED AND IN BLOCKS: S [L, B, G, NB, dv, DB]
    float32, D cut into NB blocks of DB lanes (whole pairs of tiles,
    within kda.STEP_BLOCK_BYTES: 9 blocks of 1,024 at heads of 128), dv
    on the sublanes. With D on the lanes everything small is a ROW (a
    block's phi is [1, DB] or [R, DB], z is [1, DB]) that broadcasts
    along the sublanes, and v is the one column; a block [dv, DB] is
    what one copy moves. z [L, B, G, D] float32 beside it;
  * the copies are ops/kda.py's, called where they are
    (`kda._step_kernel`: the stack aliased in and out, a stepping row's
    blocks through a ring of VMEM slots, a FRESH row from zeros with its
    stored blocks never read, a STAYING row starting no copy): its grid
    runs over (row, K/V head) pairs, each a "row" of NB "heads" to it, so
    a head's 4.5 MiB state goes through the ring in blocks along D;
  * the arithmetic is the vector unit's, float32: a block's update is 3
    operations a number and the R query heads' products 2 R more, summed
    over the block's lane tiles in registers and carried across a head's
    blocks in a scratch [R, dv, 128]; the last block adds the 128 lanes
    up on the matrix unit (ones at the highest precision, as
    ssm._lane_sum), divides by the normaliser (carried the same way, [R,
    128]) and stores y [R, dv] once. z's blocks come and go through the
    pipeline's own copies (aliased too; a staying row's pass through).
    On the chip the copies bound the kernel and not the arithmetic: at
    16 rows x 8 heads x [9, 128, 1024] a call read 2,148.8 us and the
    same copies with no arithmetic at all 2,149.9 (1.21 GB moved: 69 %
    of the HBM rate, what a plain elementwise pass reaches; PERF.md
    section 6, PR 63), so the five heads' products stay where they are.

`step_fold` is the same contract in XLA: the layer's state read whole,
every row stepped, the stepping rows' results kept; the interpreted
tests' reference and what a CPU serves (attn="fold"). A stepping row's
S and z are the kernel's to one rounding (the same operations in the
same order; a compiler's fused multiply-add on one side or the other);
y to the round-off of its sums.

`window` is a row's window of C tokens from (S0, z0): inside the window
the quadratic form under the decay mask, plus phi(Q) S0 scaled by each
query's cumulative decay, then S <- Gamma S0 + sum_s (decay from s to
the window's end) phi(k_s) v_s^T; a K/V group at a time (phi(Q) of ONE
group is R x C x D x 4 B: 94 MB at 5 x 512 x 9,216), in XLA, float32
operands at the highest precision.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops import kda
from cake_tpu.ops import ragged_paged_attention as rpa
from cake_tpu.ops.kda import FRESH, STAY, STEP  # noqa: F401  (the codes)

F32 = jnp.float32
# numbers a tile of a head, products a pair of tiles, lanes a vreg
TILE = 16
PAIR = TILE * TILE
LANES = 128
SQRT2 = math.sqrt(2.0)
# the normaliser's epsilon, times head_dim (module docstring)
EPS = 1e-6
HIGHEST = lax.Precision.HIGHEST


# -- the feature map -----------------------------------------------------------


def tile_pairs(hd: int) -> tuple:
    """The pairs (ti, tj), ti <= tj, of a head's hd / 16 tiles, in the
    order the state keeps them along D."""
    if hd % TILE:
        raise ValueError(f"a head of {hd} is not whole tiles of {TILE}")
    n = hd // TILE
    return tuple((ti, tj) for ti in range(n) for tj in range(ti, n))


def state_width(hd: int) -> int:
    """D as the state keeps it: 256 a pair of tiles."""
    return len(tile_pairs(hd)) * PAIR


def exact_width(hd: int) -> int:
    """The least any layout of the symmetric square holds."""
    return hd * (hd + 1) // 2


def block_pairs(hd: int, dv: int) -> int:
    """Pairs of tiles a block of the state: the largest divisor of their
    count whose [dv, 256 pairs] float32 fit kda.STEP_BLOCK_BYTES (one
    where a single pair is larger)."""
    P = len(tile_pairs(hd))
    fit = max(1, kda.STEP_BLOCK_BYTES // (dv * PAIR * 4))
    return max(d for d in range(1, P + 1) if P % d == 0 and d <= fit)


def state_shape(G: int, hd: int, dv: int) -> tuple:
    """A row and layer's S: [G, NB, dv, DB]."""
    tb = block_pairs(hd, dv)
    return (G, len(tile_pairs(hd)) // tb, dv, tb * PAIR)


def expand(x):
    """[..., hd] -> [..., 2 hd / 16, 256]: rows 0 .. n - 1 each tile with
    its numbers repeated 16 times each, rows n .. 2 n - 1 each tile
    tiled 16 times: pair (ti, tj)'s 256 products are row ti times row
    n + tj."""
    n = x.shape[-1] // TILE
    t = x.reshape(x.shape[:-1] + (n, TILE))
    return jnp.concatenate(
        [jnp.repeat(t, TILE, axis=-1), jnp.tile(t, TILE)], axis=-2)


def _pair_rows(row, pairs):
    """phi over `pairs` [(ti, tj, tiles a head)] from `row(i)`, the
    expanded form's row i: a list of [..., 256], a pair each."""
    out = []
    for ti, tj, n in pairs:
        p = row(ti) * row(n + tj)
        out.append(p if ti == tj else SQRT2 * p)
    return out


def phi(x):
    """[..., hd] -> [..., D], the tiled symmetric square: phi(a) . phi(b)
    = (a . b)^2."""
    hd = x.shape[-1]
    n = hd // TILE
    e = expand(x)
    return jnp.concatenate(
        _pair_rows(lambda i: e[..., i, :],
                   [(ti, tj, n) for ti, tj in tile_pairs(hd)]), axis=-1)


def phi_exact(x):
    """[..., hd] -> [..., hd (hd + 1) / 2], the upper triangle itself
    (off-diagonal entries times sqrt 2): the same inner products in the
    least room (what the roofline counts; no served path keeps it)."""
    hd = x.shape[-1]
    i, j = np.triu_indices(hd)
    w = np.where(i == j, 1.0, SQRT2).astype(np.float32)
    return x[..., i] * x[..., j] * w


# -- a row's single token, in place ---------------------------------------------


def _update(g_ref, kx_ref, qx_ref, v_ref, z_ref, y_ref, zo_ref, ring, slot,
            i: int, H: int, hb: int, vcol, acc, den):
    """Block i of a (row, K/V head), in place in ring[slot] [1, dv, DB].
    g_ref [1, 1, 2] SMEM: gamma, and 0.0 where the row starts FRESH
    (its stored z is not read); kx_ref [1, 2 n, 256], qx_ref [1, 2 n, R,
    256]: k and the group's queries expanded; v_ref [1, 1, dv]; z_ref,
    zo_ref [1, NB, DB]; y_ref [1, R, dv], written by the last block.
    vcol [dv, LANES]: v down the sublanes, every lane; acc [R, dv,
    LANES], den [R, LANES]: the products so far, a lane tile's sums."""
    del hb
    R = qx_ref.shape[2]
    n = kx_ref.shape[1] // 2
    dv, DB = ring.shape[2:]
    tb = DB // PAIR
    pairs = [(ti, tj, n) for ti, tj in tile_pairs(n * TILE)]
    pairs = pairs[i * tb:(i + 1) * tb]
    g = g_ref[0, 0, 0]
    if i == 0:
        vcol[...] = jnp.broadcast_to(v_ref[0], (LANES, dv)).T
        acc[...] = jnp.zeros_like(acc)
        den[...] = jnp.zeros_like(den)
    pk = jnp.concatenate(
        _pair_rows(lambda r: kx_ref[0, r:r + 1, :], pairs), axis=1)
    pq = jnp.concatenate(_pair_rows(lambda r: qx_ref[0, r], pairs), axis=1)
    z_old = jnp.where(g_ref[0, 0, 1] > 0.0, z_ref[0, i:i + 1, :], 0.0)
    z_new = g * z_old + pk
    zo_ref[0, i:i + 1, :] = z_new
    tiles = [slice(lo, lo + LANES) for lo in range(0, DB, LANES)]
    pz = pq * z_new
    den[...] += functools.reduce(lambda a, b: a + b,
                                 [pz[:, t] for t in tiles])

    def sublanes(s, carry):
        """A sublane tile of the block, its lane tiles in turn: the R
        products' sums stay in registers across them. (A LOOP, not
        Python's: unrolled, the body is traced dv / 8 times a block, a
        block NB times a kernel, a kernel a layer and program, and a
        server's start spent two minutes tracing.)"""
        rows = pl.ds(pl.multiple_of(s * 8, 8), 8)
        vb = vcol[rows, :]
        part = [None] * R
        for t in tiles:
            St = g * ring[slot, 0, rows, t] + vb * pk[:, t]
            ring[slot, 0, rows, t] = St
            for a in range(R):
                term = St * pq[a:a + 1, t]
                part[a] = term if part[a] is None else part[a] + term
        for a in range(R):
            acc[a, rows, :] += part[a]
        return carry

    lax.fori_loop(0, dv // 8, sublanes, 0)
    if i == H - 1:
        ones = jnp.ones((8, acc.shape[2]), F32)
        eps = n * TILE * EPS
        for a in range(R):
            num = lax.dot_general(ones, acc[a], (((1,), (1,)), ((), ())),
                                  precision=HIGHEST,
                                  preferred_element_type=F32)
            d = jnp.sum(den[a:a + 1, :], axis=1, keepdims=True)
            y_ref[0, a:a + 1, :] = num[0:1] / (d + eps)


def _stay(g_ref, kx_ref, qx_ref, v_ref, z_ref, y_ref, zo_ref):
    """A (row, head) that stays: its z passes through, its y is zero."""
    y_ref[...] = jnp.zeros_like(y_ref)
    zo_ref[...] = z_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(S, z, j, code, q, k, v, log_gamma, *, interpret: bool):
    L, B, G, NB, dv, DB = S.shape
    R, hd = q.shape[2:]
    n2 = 2 * hd // TILE
    BG = B * G
    code = jnp.repeat(code.astype(jnp.int32), G)
    gamma = jnp.exp(log_gamma.astype(F32)).reshape(BG)
    g = jnp.stack([gamma, jnp.where(code == FRESH, 0.0, 1.0)],
                  axis=-1)[:, None, :]
    # expanded (module docstring), the group's R queries on the sublanes
    kx = expand(k.astype(F32)).reshape(BG, n2, PAIR)
    qx = expand(q.astype(F32)).reshape(BG, R, n2, PAIR).swapaxes(1, 2)

    def zspec():
        return pl.BlockSpec((None, 1, NB, DB),
                            lambda b, j, code: (j[0], b, 0, 0))

    S, y, z = pl.pallas_call(
        functools.partial(
            kda._step_kernel, depth=kda.RING_DEPTH, hb=1,
            update=_update, outs=2,
            scratch=3, stay=_stay),
        name="cake_retention_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BG,),
            in_specs=[pl.BlockSpec((1, 1, 2), lambda b, *_: (b, 0, 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((1, n2, PAIR), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec((1, n2, R, PAIR),
                                   lambda b, *_: (b, 0, 0, 0)),
                      pl.BlockSpec((1, 1, dv), lambda b, *_: (b, 0, 0)),
                      zspec(),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec((1, R, dv), lambda b, *_: (b, 0, 0)),
                       zspec()],
            scratch_shapes=[
                pltpu.VMEM((kda.RING_DEPTH, 1, dv, DB), F32),
                pltpu.SemaphoreType.DMA((2, kda.RING_DEPTH)),
                pltpu.SMEM((4,), jnp.int32),
                pltpu.VMEM((dv, LANES), F32),
                pltpu.VMEM((R, dv, LANES), F32),
                pltpu.VMEM((R, LANES), F32)]),
        out_shape=[jax.ShapeDtypeStruct((L, BG, NB, dv, DB), F32),
                   jax.ShapeDtypeStruct((BG, R, dv), F32),
                   jax.ShapeDtypeStruct((L, BG, NB, DB), F32)],
        # operands count the two prefetched scalars: z is the seventh,
        # the state the eighth
        input_output_aliases={7: 0, 6: 2},
        # the ring's copies run ahead into the next (row, head)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(j, (1,)).astype(jnp.int32), code, g, kx, qx,
      v.astype(F32).reshape(BG, 1, dv), z.reshape(L, BG, NB, DB),
      S.reshape(L, BG, NB, dv, DB))
    return (S.reshape(L, B, G, NB, dv, DB), z.reshape(L, B, G, NB * DB),
            y.reshape(B, G, R, dv))


def step(S, z, j, code, q, k, v, log_gamma,
         interpret: Optional[bool] = None):
    """One token a row, in place: S [L, B, G, NB, dv, DB] and z [L, B,
    G, D] f32, the stacks (donate them); j the layer (an int or a traced
    scalar); code [B] int32, STAY / STEP / FRESH a row; q [B, G, R, hd]
    (normed, rotated: the group's R query heads); k [B, G, hd]; v [B, G,
    dv]; log_gamma [B, G] f32 (0: no decay) -> (S, z, y [B, G, R, dv]
    f32). Layer j's stepping rows hold what `step_fold` gives from their
    stored state (FRESH: from zeros) to one rounding, and `y` to the
    round-off of its sums; every other row and layer keeps its bits, and
    a staying row's `y` is zero."""
    if interpret is None:
        interpret = not rpa._on_tpu()
    dv, DB = S.shape[4:]
    if not interpret and (dv % LANES or DB % LANES):
        raise ValueError(
            f"cake_retention_step cannot run on this chip at a {dv} x {DB} "
            f"block of the state: both must be multiples of {LANES}")
    return _step_pallas(S, z, jnp.asarray(j, jnp.int32), code, q, k, v,
                        log_gamma, interpret=interpret)


def _blocked(p, NB: int):
    """[..., D] -> [..., NB, DB]."""
    return p.reshape(p.shape[:-1] + (NB, p.shape[-1] // NB))


def read_state(S, z, pq):
    """phi(q) against a state: pq [..., R, D], S [..., NB, dv, DB], z
    [..., D] -> (numerator [..., R, dv], normaliser [..., R]) f32."""
    NB = S.shape[-3]
    num = jnp.einsum("...rnd,...nvd->...rv", _blocked(pq, NB), S,
                     precision=HIGHEST, preferred_element_type=F32)
    den = jnp.einsum("...rd,...d->...r", pq, z, precision=HIGHEST,
                     preferred_element_type=F32)
    return num, den


def step_fold(S, z, j, code, q, k, v, log_gamma):
    """`step`'s contract in XLA (module docstring): layer j of the
    stacks read whole, every row stepped, the stepping rows' results
    kept."""
    NB = S.shape[3]
    hd = q.shape[-1]
    S_old = lax.dynamic_index_in_dim(S, j, 0, keepdims=False)
    z_old = lax.dynamic_index_in_dim(z, j, 0, keepdims=False)
    fresh, steps = code == FRESH, code != STAY
    g = jnp.exp(log_gamma.astype(F32))
    pk = phi(k.astype(F32))
    S_new = (g[:, :, None, None, None]
             * jnp.where(fresh[:, None, None, None, None], 0.0, S_old)
             + v.astype(F32)[:, :, None, :, None]
             * _blocked(pk, NB)[:, :, :, None, :])
    z_new = g[:, :, None] * jnp.where(fresh[:, None, None], 0.0, z_old) + pk
    num, den = read_state(S_new, z_new, phi(q.astype(F32)))
    y = num / (den[..., None] + hd * EPS)
    return (lax.dynamic_update_index_in_dim(
                S, jnp.where(steps[:, None, None, None, None], S_new, S_old),
                j, 0),
            lax.dynamic_update_index_in_dim(
                z, jnp.where(steps[:, None, None], z_new, z_old), j, 0),
            jnp.where(steps[:, None, None, None], y, 0.0))


# -- a row's window --------------------------------------------------------------


def window(S0, z0, q, k, v, log_gamma, own):
    """A window of C tokens of ONE row from (S0, z0) (module docstring).
    S0 [G, NB, dv, DB], z0 [G, D] f32; q [C, G, R, hd]; k [C, G, hd]; v
    [C, G, dv]; log_gamma [C, G] f32; own [C] bool, the row's own tokens
    (the rest neither decay nor write, and their y is garbage nobody
    reads) -> (S_end, z_end, y [C, G, R, dv] f32)."""
    C, G, R, hd = q.shape
    NB = S0.shape[1]
    lg = jnp.where(own[:, None], log_gamma.astype(F32), 0.0)
    cum = jnp.cumsum(lg, axis=0)                        # [C, G], inclusive
    t = jnp.arange(C)
    causal = (t[None, :] <= t[:, None]) & own[None, :]   # [query, key]

    def group(args):
        S, z, qg, kg, vg, cg = args     # [NB, dv, DB], [D], [C, R, hd] ...
        qg, kg, vg = qg.astype(F32), kg.astype(F32), vg.astype(F32)
        # inside the window: (q . k)^2 under the decay from s to t
        sc = jnp.einsum("trh,sh->rts", qg, kg, precision=HIGHEST,
                        preferred_element_type=F32)
        w = jnp.where(causal[None], sc * sc * jnp.exp(
            jnp.where(causal, cg[:, None] - cg[None, :], 0.0))[None], 0.0)
        num = jnp.einsum("rts,sv->trv", w, vg, precision=HIGHEST,
                         preferred_element_type=F32)
        den = jnp.sum(w, axis=2).T                       # [C, R]
        # the state the window starts from, under each query's decay
        carried = jnp.exp(cg)
        n0, d0 = read_state(S, z, phi(qg))               # [C, R, dv], [C, R]
        num = num + carried[:, None, None] * n0
        den = den + carried[:, None] * d0
        # the state it leaves
        left = jnp.where(own, jnp.exp(cg[-1] - cg), 0.0)
        pk = phi(kg) * left[:, None]                     # [C, D]
        S_end = jnp.exp(cg[-1]) * S + jnp.einsum(
            "snd,sv->nvd", _blocked(pk, NB), vg, precision=HIGHEST,
            preferred_element_type=F32)
        z_end = jnp.exp(cg[-1]) * z + jnp.sum(pk, axis=0)
        return S_end, z_end, num / (den[..., None] + hd * EPS)

    S_end, z_end, y = lax.map(group, (
        S0, z0, q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
        cum.T))
    return S_end, z_end, y.swapaxes(0, 1)
