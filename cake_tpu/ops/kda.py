"""The delta rule's one-token form over the stored state, in place.

A KDA layer (models/moe/bailing_hybrid.py; the equations are
models/reference/bailing_hybrid.py's) keeps a matrix state a row and
head, float32, stacked over the layers: [L, B, H, dk, dv]. A row that
holds ONE token in a dispatch takes the recurrence itself,

    S <- e^g S;   u = beta (v - k^T S);   S <- S + k u;   o = q^T S,

which reads and writes the row's whole state and does a handful of
operations a number: the state's bytes are what it costs. `step`
(`cake_kda_step`) moves them once each way:

  * the stacked state lies whole in HBM and is the kernel's input AND
    its output (`input_output_aliases`: the step program donates its
    cache, so the update is in place and every other layer, and every
    row that does not step, is never touched);
  * grid (B,): one step a ROW, told by its code what to do. A row that
    STEPS (1) has its state fetched in blocks of a few heads by the
    kernel's own copies into a ring of VMEM slots, updated where it
    lies in the slot, and written back from there: block i + 1 and
    i + 2 on their way in and block i - 1 on its way out while block i
    computes, on into the next stepping row (the cursor is carried in
    SMEM from row to row, as rpa.walk_live_pages carries its own). A
    row that steps from a FRESH state (2: its first token sits at
    position 0, a request that takes the slot) starts from zeros and
    its stored block is never read. A row that STAYS (0: idle, or the
    row that holds the dispatch's window) starts no copy; its `o` is
    zero;
  * the arithmetic is `kda_step`'s own, in its order, float32 on the
    vector unit: a head's [dk, dv] block with dk on the sublanes, the
    decay, k and q as COLUMNS (the caller's small transposes: [B, dk,
    3 H]) that broadcast along the lanes, v and beta as rows, the two
    sums over dk. On the chip the copies bound the kernel and not the
    arithmetic (a body with no arithmetic at all read 244.3 us a call
    where the real one read 242.0: PERF.md section 6, PR 51 and 52).

A block is the most whole heads, a divisor of H, within
STEP_BLOCK_BYTES: a function of the state's own shape (bytes a head).
VMEM: the ring, RING_DEPTH x STEP_BLOCK_BYTES = 2 MiB, and the double-
buffered blocks of a row's columns, rows and `o` (at 32 heads of
128 x 128: 2 x (64 + 32 + 16) KiB), 2.2 MiB in all under the compiler's
default scoped limit of 16 MiB, so the kernel sets no limit of its own.
On a chip dk must fill sublane tiles (a multiple of 8) and dv lane
tiles (of 128); the interpreter (no chip in sight: the tests) takes any
width.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops import ragged_paged_attention as rpa

# what a row's code says (int32 [B], scalar prefetch)
STAY, STEP, FRESH = 0, 1, 2
# a block of the ring: whole heads of one row's state. Read on the chip
# at 32 rows x 32 heads of 128 x 128 (PR 51, PR 52): 512 KiB, 4 slots
STEP_BLOCK_BYTES = 512 * 1024
RING_DEPTH = 4
F32 = jnp.float32


def block_heads(H: int, head_bytes: int) -> int:
    """Heads a block: the largest divisor of H whose heads fit
    STEP_BLOCK_BYTES (one head where a single head is larger)."""
    fit = max(1, STEP_BLOCK_BYTES // head_bytes)
    return max(d for d in range(1, H + 1) if H % d == 0 and d <= fit)


def _update(cols_ref, rows_ref, o_ref, ring, slot, i: int, H: int, hb: int):
    """Block i of a row, in place in ring[slot] [hb, dk, dv]: kda_step's
    operations in kda_step's order, a head at a time. cols_ref [1, dk,
    3 H]: e^g | k | q, a head a lane; rows_ref [1, 2 H, dv]: v | beta."""
    outs = []
    for h in range(hb):
        at = i * hb + h

        def col(part):
            return cols_ref[0, :, part * H + at:part * H + at + 1]

        def row(part):
            return rows_ref[0, part * H + at:part * H + at + 1, :]

        decay, k, q = col(0), col(1), col(2)
        S = decay * ring[slot, h]
        u = row(1) * (row(0) - jnp.sum(k * S, axis=0, keepdims=True))
        S = S + k * u
        ring[slot, h] = S
        outs.append(jnp.sum(q * S, axis=0, keepdims=True))
    o_ref[0, i * hb:(i + 1) * hb, :] = jnp.concatenate(outs, axis=0)


def _step_kernel(j_ref, code_ref, *refs, depth: int, hb: int, update=_update):
    """One grid step: one ROW.

    j_ref [1], code_ref [B]: the layer of the stack, each row's code
    refs: the row's operands (here cols_ref, rows_ref), then
    state_in / state_ref: [L, B, H, dk, dv] in HBM, ONE buffer (aliased)
    o_ref: the row's output block
    ring [depth, hb, dk, dv] VMEM; sem DMA [2, depth]: in, out
    cur SMEM int32 [4]: the copies' cursor (row, block, count of blocks
        started) and the count of blocks updated, carried row to row
    update: a block's arithmetic (ops/ssm.py rides these copies with
        its own operands and its own)
    """
    *operands, state_in, state_ref, o_ref, ring, sem, cur = refs
    del state_in
    b, nb = pl.program_id(0), pl.num_programs(0)
    H = state_ref.shape[2]
    nblk = H // hb
    ahead = depth // 2
    j = j_ref[0]

    def fetch(row, blk, slot):
        return pltpu.make_async_copy(
            state_ref.at[j, row, pl.ds(blk * hb, hb)], ring.at[slot],
            sem.at[0, slot])

    def store(row, blk, slot):
        return pltpu.make_async_copy(
            ring.at[slot], state_ref.at[j, row, pl.ds(blk * hb, hb)],
            sem.at[1, slot])

    def next_stepping(row):
        return lax.while_loop(
            lambda r: jnp.logical_and(
                r < nb, code_ref[jnp.minimum(r, nb - 1)] == STAY),
            lambda r: r + 1, row)

    def start_next():
        row, blk, count = cur[0], cur[1], cur[2]

        @pl.when(row < nb)
        def _():
            slot = count % depth

            # the slot's last tenant, `depth` blocks ago, has left
            @pl.when(count >= depth)
            def _():
                store(row, blk, slot).wait()

            @pl.when(code_ref[row] == STEP)
            def _():
                fetch(row, blk, slot).start()

            cur[2] = count + 1
            row_ends = blk + 1 == nblk

            @pl.when(row_ends)
            def _():
                cur[0] = next_stepping(row + 1)
                cur[1] = 0

            @pl.when(jnp.logical_not(row_ends))
            def _():
                cur[1] = blk + 1

    @pl.when(b == 0)
    def _():
        cur[0] = next_stepping(0)
        cur[1] = 0
        cur[2] = 0
        cur[3] = 0

        def prime(_, carry):
            start_next()
            return carry

        lax.fori_loop(0, ahead, prime, 0)

    code = code_ref[b]

    @pl.when(code == STAY)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(code != STAY)
    def _():
        first = cur[3]
        cur[3] = first + nblk
        for i in range(nblk):
            start_next()
            slot = (first + i) % depth

            @pl.when(code == STEP)
            def _():
                fetch(b, i, slot).wait()

            @pl.when(code == FRESH)
            def _():
                ring[slot] = jnp.zeros(ring.shape[1:], F32)

            update(*operands, o_ref, ring, slot, i, H, hb)
            store(b, i, slot).start()

    # every slot that held a block still has its last store in flight
    @pl.when(b == nb - 1)
    def _():
        for slot in range(depth):
            @pl.when(slot < cur[3])
            def _():
                store(b, 0, slot).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(state, j, code, q, k, v, g, beta, *, interpret: bool):
    L, B, H, dk, dv = state.shape
    hb = block_heads(H, dk * dv * state.dtype.itemsize)
    # a head a lane: a column of these broadcasts along a head's dv
    cols = jnp.concatenate([jnp.exp(g), k, q], axis=1).swapaxes(1, 2)
    rows = jnp.concatenate(
        [v.astype(F32), jnp.broadcast_to(beta[..., None], (B, H, dv))],
        axis=1)
    return pl.pallas_call(
        functools.partial(_step_kernel, depth=RING_DEPTH, hb=hb),
        name="cake_kda_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, dk, 3 * H), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec((1, 2 * H, dv), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec((1, H, dv), lambda b, *_: (b, 0, 0))],
            scratch_shapes=[pltpu.VMEM((RING_DEPTH, hb, dk, dv), state.dtype),
                            pltpu.SemaphoreType.DMA((2, RING_DEPTH)),
                            pltpu.SMEM((4,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, dv), F32)],
        # operands count the two prefetched scalars: the state is the fifth
        input_output_aliases={4: 0},
        # the ring's copies run ahead into the next row
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(j, (1,)).astype(jnp.int32), code.astype(jnp.int32),
      cols, rows, state)


def step(state, j, code, q, k, v, g, beta, interpret: Optional[bool] = None):
    """One token a row, in place: state [L, B, H, dk, dv] f32, the
    stack (donate it); j the layer (an int or a traced scalar); code [B]
    int32, STAY / STEP / FRESH a row; q, k [B, H, dk] f32 (normed); v
    [B, H, dv]; g [B, H, dk] f32 (log-decay, 0: none); beta [B, H] f32
    (0: the state passes unchanged) -> (state, o [B, H, dv] f32). Layer
    j's stepping rows hold what bailing_hybrid.kda_step gives from
    their stored state (FRESH: from zeros), bit for bit; every other
    row and layer keeps its bits, and a staying row's `o` is zero."""
    if interpret is None:
        interpret = not rpa._on_tpu()
    dk, dv = state.shape[3:]
    if not interpret and (dk % 8 or dv % 128):
        raise ValueError(
            f"cake_kda_step cannot run on this chip at a {dk} x {dv} state "
            "a head: dk must be a multiple of 8 and dv of 128")
    return _step_pallas(state, jnp.asarray(j, jnp.int32), code, q, k, v, g,
                        beta, interpret=interpret)
