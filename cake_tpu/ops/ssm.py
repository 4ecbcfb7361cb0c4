"""Mamba-2's one-token form over the stored state, in place.

A Mamba layer (models/moe/nemotron_h.mamba_block; the equations are
models/reference/nemotron_h.py's) keeps a matrix state a row and head,
float32, stacked over the layers: [L, B, H, P, N]. A row that holds ONE
token in a dispatch takes the recurrence itself,

    S <- e^a S + (dt x) B^T;   y = S C + D x,

which reads and writes the row's whole state and does five operations a
number: the state's bytes are what it costs. In XLA the layer's update
is whatever fusions the compiler chooses that day: one pass over the
state each way at (32 rows, 128 heads, 8 groups), the state read TWICE
at (64, 64, 1), and a whole layer's copy written beside the stack in a
mixed program (PERF.md section 6, PR 57). `step` (`cake_ssm_step`) moves
a stepping row's bytes once each way whatever the shape:

  * the copies are ops/kda.py's, called where they are (`kda.
    _step_kernel`: the stack aliased in and out, grid (B,), a stepping
    row's blocks of whole heads through a ring of VMEM slots, a FRESH
    row from zeros with its stored block never read, a STAYING row
    starting no copy and reading `y` zero, the same three codes);
  * the arithmetic is `nemotron_h.ssm_step`'s own, float32: a head's
    [P, N] block with P on the sublanes and N on the lanes; e^a a
    scalar a head (SMEM), dt x a COLUMN (the caller's small transpose:
    [B, P, H], a head a lane) that broadcasts along the lanes; the
    group's B and C as rows [1, N] (`G` groups of H / G heads, G from
    the operands' shape) that broadcast along the sublanes. The state
    and the products S C are the vector unit's, ssm_step's operations
    in ssm_step's order: a stepping row's state is XLA's bit for bit.
    y's sum runs along the LANES (KDA's two run over the sublanes),
    and the vector unit's cross-lane reduce is what showed beside the
    copies (572 us a call at Granite's widths where the copies alone
    take 442: PERF.md section 6, PR 57), so the MATRIX unit adds the
    128 products up: (S C) @ ones at the highest precision, which
    splits each float32 product in three bfloat16 pieces exactly (the
    ones are exact) and accumulates in float32. `y` is ssm_step's to
    float32 round-off of that one sum (its order differs), and every
    lane of the result holds it, so a head's `y` goes into lane `head`
    of the row's [P, H] block by a select and the block is stored once.

A block is `kda.block_heads`' (whole heads within kda.STEP_BLOCK_BYTES:
at a 32 KiB head, 16 heads in 512 KiB) and the ring kda.RING_DEPTH deep:
2 MiB of VMEM, beside the double-buffered blocks of a row's columns,
rows and `y` (at 64 heads of 64 x 128: 2 x (3 x 16 + 1) KiB), under the
compiler's default scoped limit, so the kernel sets none of its own. On
a chip P must fill sublane tiles (a multiple of 8) and N lane tiles (of
128); the interpreter (no chip in sight: the tests) takes any width.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops import kda
from cake_tpu.ops import ragged_paged_attention as rpa
from cake_tpu.ops.kda import FRESH, STAY, STEP  # noqa: F401  (the codes)

F32 = jnp.float32
LANES = 128


def _lane_sum(v):
    """[P, N] -> [P, LANES], a row's sum in every lane: by the matrix
    unit (module docstring; tools/ssm_step_bench.py times the kernel
    without it)."""
    return jnp.dot(v, jnp.ones((v.shape[1], LANES), F32),
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=F32)


def _update(decay_ref, dtx_ref, dx_ref, rows_ref, o_ref, ring, slot, i: int,
            H: int, hb: int):
    """Block i of a row, in place in ring[slot] [hb, P, N]: ssm_step's
    operations, a head at a time. decay_ref [1, 1, H] SMEM: e^a;
    dtx_ref, dx_ref [1, P, H]: dt x and D x, a head a lane; rows_ref
    [1, 2 G, N]: B | C, a group a row. o_ref [1, P, H] holds D x + y
    for the heads of the blocks so far (D x for the rest)."""
    G = rows_ref.shape[1] // 2
    P = dtx_ref.shape[1]
    src = dx_ref if i == 0 else o_ref
    # the row's [P, H] block in tiles of LANES heads
    edges = [(lo, min(lo + LANES, H)) for lo in range(0, H, LANES)]
    tiles = [src[0, :, lo:hi] for lo, hi in edges]
    lanes = [lax.broadcasted_iota(jnp.int32, (P, hi - lo), 1)
             for lo, hi in edges]
    for h in range(hb):
        at = i * hb + h
        g = at // (H // G)
        S = (decay_ref[0, 0, at] * ring[slot, h]
             + dtx_ref[0, :, at:at + 1] * rows_ref[0, g:g + 1, :])
        ring[slot, h] = S
        y = _lane_sum(S * rows_ref[0, G + g:G + g + 1, :])
        t = at // LANES
        lo, hi = edges[t]
        tiles[t] = jnp.where(lanes[t] == at - lo, y[:, :hi - lo] + tiles[t],
                             tiles[t])
    for (lo, hi), tile in zip(edges, tiles):
        o_ref[0, :, lo:hi] = tile


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(state, j, code, x, Bm, Cm, dt, a, D, *, interpret: bool):
    L, B, H, P, N = state.shape
    G = Bm.shape[1]
    hb = kda.block_heads(H, P * N * state.dtype.itemsize)
    x = x.astype(F32)

    def tile():     # a head a lane: [B, P, H]
        return pl.BlockSpec((1, P, H), lambda b, *_: (b, 0, 0))

    state, y = pl.pallas_call(
        functools.partial(kda._step_kernel, depth=kda.RING_DEPTH, hb=hb,
                          update=_update),
        name="cake_ssm_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, 1, H), lambda b, *_: (b, 0, 0),
                                   memory_space=pltpu.SMEM),
                      tile(), tile(),
                      pl.BlockSpec((1, 2 * G, N), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), tile()],
            scratch_shapes=[
                pltpu.VMEM((kda.RING_DEPTH, hb, P, N), state.dtype),
                pltpu.SemaphoreType.DMA((2, kda.RING_DEPTH)),
                pltpu.SMEM((4,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, P, H), F32)],
        # operands count the two prefetched scalars: the state is the seventh
        input_output_aliases={6: 0},
        # the ring's copies run ahead into the next row
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(j, (1,)).astype(jnp.int32), code.astype(jnp.int32),
      jnp.exp(a)[:, None, :], (dt[..., None] * x).swapaxes(1, 2),
      (D[None, :, None] * x).swapaxes(1, 2),
      jnp.concatenate([Bm, Cm], axis=1).astype(F32), state)
    return state, y.swapaxes(1, 2)


def step(state, j, code, x, Bm, Cm, dt, a, D,
         interpret: Optional[bool] = None):
    """One token a row, in place: state [L, B, H, P, N] f32, the stack
    (donate it); j the layer (an int or a traced scalar); code [B]
    int32, STAY / STEP / FRESH a row; x [B, H, P]; Bm, Cm [B, G, N];
    dt, a = dt * A [B, H] f32 (0: the state passes unchanged); D [H]
    f32 -> (state, y [B, H, P] f32). Layer j's stepping rows hold what
    nemotron_h.ssm_step gives from their stored state (FRESH: from
    zeros), bit for bit, and `y` to the round-off of its one sum;
    every other row and layer keeps its bits, and a staying row's `y`
    is zero."""
    if interpret is None:
        interpret = not rpa._on_tpu()
    P, N = state.shape[3:]
    if not interpret and (P % 8 or N % 128):
        raise ValueError(
            f"cake_ssm_step cannot run on this chip at a {P} x {N} state "
            "a head: P must be a multiple of 8 and N of 128")
    return _step_pallas(state, jnp.asarray(j, jnp.int32), code, x, Bm, Cm,
                        dt, a, D, interpret=interpret)
