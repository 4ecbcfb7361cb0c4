"""The delta rule over the stored state: a row's single token in place
(`step`, `cake_kda_step`) and a row's window chunked, the state held on
the chip (`chunked`, `cake_kda_chunk`, the second half of this file).

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

A row that holds a WINDOW of a prompt takes the chunked form of the same
rule (bailing_hybrid.kda_chunked states it: chunks of 16 tokens, a unit-
lower-triangular solve a chunk by forward substitution, float32 operands
and state, every product at the highest precision). In XLA that is a
batched part and then a 32-step scan that carries the 2 MiB state
through HBM and launches three 16-row products a step (650 us a layer
and window on the chip). `chunked` (`cake_kda_chunk`) is the same
mathematics with the state on the chip:

  * grid (H / 8 head blocks, C / 128 chunk blocks), the chunk axis
    innermost and sequential. A head block's state lies TRANSPOSED in a
    VMEM scratch [dv, 8 dk] from the window's first chunk to its last:
    S0 is read once and S_end written once (their blocks' indices do
    not move along the chunk axis), q, k, v, g stream in once as they
    lie ([C, H, d] in blocks of [128, 8, d]: a head's rows are strided
    sublane loads) and `o` streams out once;
  * what does not depend on the state runs first, a head at a time over
    the block's 8 chunks: G (the running sum of g: a product with a
    block-diagonal triangle of ones), K+, K-, Q+, K_end, e^G_Q, the
    decayed scores of the block against itself in ONE product (a
    chunk's own are its diagonal blocks: A, strictly lower, and P),
    and (I + A) [W_v | W_k] = beta [V | K+] solved by forward
    substitution on the vector unit: A's column s broadcasts along the
    lanes, row s along the sublanes, 15 steps a chunk;
  * what does runs a chunk after the other: [W_k; Q+] S a head (ONE
    product of 32 rows), U = W_v - W_k S, and then the rank-16 updates
    K_end^T U of all 8 heads as ONE product of [8 x 16, dv]^T against
    K_end laid out block-diagonally [8 x 16, 8 dk], eight lane tiles
    wide: a product one tile wide keeps one of the four matrix units
    busy (eight [dv, 16] x [16, dk] products read 365 us a window where
    the wide one reads 112); S <- e^G_Q S + that; o = Q+ S + P U over
    the block at the end.

What bounds it on the chip (PERF.md section 6, PR 59): 372 us a window
and layer on float32 operands (393 with XLA's cast of a bfloat16 v in
front) against XLA's 651 and 98 for a body that only moves the bytes. The copies are hidden; the matrix unit's six bfloat16 passes of
each float32 product are not: the state's update alone is 112 us (its
contraction is 16 deep in a unit 128 deep, and the highest precision is
the cell's stated arithmetic).
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


def _step_kernel(j_ref, code_ref, *refs, depth: int, hb: int, update=_update,
                 outs: int = 1, scratch: int = 0, stay=None):
    """One grid step: one ROW.

    j_ref [1], code_ref [B]: the layer of the stack, each row's code
    refs: the row's operands (here cols_ref, rows_ref), then
    state_in / state_ref: [L, B, H, dk, dv] in HBM, ONE buffer (aliased)
    o_refs: the row's `outs` output blocks (here one, o_ref)
    ring [depth, hb, dk, dv] VMEM; sem DMA [2, depth]: in, out
    cur SMEM int32 [4]: the copies' cursor (row, block, count of blocks
        started) and the count of blocks updated, carried row to row
    then `scratch` refs of the caller's own, handed to `update` last
    update: a block's arithmetic (ops/ssm.py and ops/retention.py ride
        these copies with their own operands and their own)
    stay: what a STAYING row leaves in its output blocks, stay(*operands,
        *o_refs); None: zeros in the first
    """
    more = refs[len(refs) - scratch:]
    refs = refs[:len(refs) - scratch]
    ring, sem, cur = refs[-3:]
    o_refs = refs[-3 - outs:-3]
    state_ref = refs[-4 - outs]
    operands = refs[:-5 - outs]
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
        if stay is None:
            o_refs[0][...] = jnp.zeros_like(o_refs[0])
        else:
            stay(*operands, *o_refs)

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

            update(*operands, *o_refs, ring, slot, i, H, hb, *more)
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


# -- the chunked form over a window, the state held on the chip ----------------

# tokens a chunk (the cell's configuration states it: at the bound of -5
# a token e^-G of 16 summed log-decays is finite in float32), tokens a
# grid step (whole chunks: 256 read 447 us a window on the chip where
# 128 reads 372 and 64 386) and heads a program (a whole sublane tile
# of the operands as they lie, [C, H, d]; 16 read what 8 do)
CHUNK = 16
CHUNK_BLOCK = 128
CHUNK_HEADS = 8
# the kernel's scoped VMEM, past the compiler's default of 16 MiB: at 8
# heads of 128 x 128 the double-buffered blocks of q, k, v, g, o, S0 and
# S_end are 7 MiB, the scratch 7.6 MiB (4 MiB of it the chunks' K_end
# laid out block-diagonally), and the compiler's temporaries
CHUNK_VMEM_BYTES = 32 * 1024 * 1024


def chunk_heads(H: int) -> int:
    """Heads a program of the chunked kernel: the largest divisor of H
    within CHUNK_HEADS."""
    return max(d for d in range(1, min(H, CHUNK_HEADS) + 1) if H % d == 0)


def _mm(a, b, contract):
    """a . b over `contract` (a's axes, b's axes), float32 operands at
    the highest precision."""
    return lax.dot_general(a, b, (contract, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=F32)


def _chunk_kernel(s0_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, send_ref,
                  o_ref, st, xs, wv, kbd, pm, us, oq, fe, *, Q: int,
                  arith: bool):
    """One grid step: hb heads x one block of n chunks of Q tokens.

    s0_ref, send_ref [hb, dk, dv]: the state the window starts from and
        leaves (a head block's, fetched and stored once a window)
    q_ref, k_ref, g_ref [Cb, hb, dk], v_ref, o_ref [Cb, hb, dv]: the
        block's tokens as they lie; beta_ref [Cb, hb]
    st [dv, hb dk] VMEM: the heads' states TRANSPOSED and side by side
        (a head a lane tile, dk on the lanes: a chunk's decay e^G_Q a
        channel is a row that broadcasts along the sublanes), carried
        from block to block
    xs [hb, n, 2 Q, dk]: a chunk's W_k over its Q+ (ONE product with S)
    wv, us, oq [hb, Cb, dv]: W_v, U and Q+ S
    kbd [n, hb Q, hb dk]: a chunk's K_end of every head, block-diagonal
        (head h's Q rows in lane tile h, zeros beside them)
    pm [hb, Cb, Cb]: P, block-diagonal; fe [n, hb dk]: e^G_Q
    """
    c, nc = pl.program_id(1), pl.num_programs(1)
    hb, dk, dv = s0_ref.shape
    Cb = q_ref.shape[0]
    n = Cb // Q

    def head(h):        # head h's lane tile of the state
        return slice(h * dk, (h + 1) * dk)

    @pl.when(c == 0)
    def _():
        for h in range(hb):
            st[:, head(h)] = s0_ref[h].T

    if not arith:       # the bytes alone (tools/kda_chunk_bench.py)
        o_ref[...] = v_ref[...]
    else:
        row = lax.broadcasted_iota(jnp.int32, (Cb, Cb), 0)
        col = lax.broadcasted_iota(jnp.int32, (Cb, Cb), 1)
        same = (row // Q) == (col // Q)
        lower, strict = same & (col <= row), same & (col < row)
        ones = jnp.where(lower, 1.0, 0.0).astype(F32)
        zero = jnp.zeros((Q, dk), F32)
        # what does not depend on S, a head at a time over the block
        for h in range(hb):
            q, k, g = q_ref[:, h, :], k_ref[:, h, :], g_ref[:, h, :]
            beta = beta_ref[:, h:h + 1]
            # G: the running sum of g from each chunk's start
            G = _mm(ones, g, ((1,), (0,)))
            G3 = G.reshape(n, Q, dk)
            G_end = G3[:, Q - 1:Q, :]
            fade = jnp.exp(G)
            k_fade, k_grow, q_fade = k * fade, k * jnp.exp(-G), q * fade
            k_end = (k.reshape(n, Q, dk)
                     * jnp.exp(G_end - G3)).reshape(Cb, dk)
            fe[:, head(h)] = jnp.exp(G_end).reshape(n, dk)
            # Q+ K-^T over K+ K-^T, the block's tokens against the
            # block's: a chunk's own are the diagonal blocks
            sc = _mm(jnp.concatenate([q_fade, k_fade], axis=0), k_grow,
                     ((1,), (1,)))
            pm[h] = jnp.where(lower, sc[:Cb], 0.0)
            A = jnp.where(strict, sc[Cb:], 0.0) * beta
            # (I + A) [W_v | W_k] = beta [V | K+] by forward
            # substitution, a chunk at a time: row s is final when its
            # turn comes, and A's column s is zero down to row s
            W = jnp.concatenate([beta * v_ref[:, h, :], beta * k_fade],
                                axis=1)
            for i in range(n):
                at = slice(i * Q, (i + 1) * Q)
                Wc = W[at]
                for s in range(Q - 1):
                    Wc = Wc - A[at, i * Q + s:i * Q + s + 1] * Wc[s:s + 1]
                wv[h, at] = Wc[:, :dv]
                xs[h, i, :Q] = Wc[:, dv:]
                xs[h, i, Q:] = q_fade[at]
                kbd[i, h * Q:(h + 1) * Q, :] = jnp.concatenate(
                    [zero] * h + [k_end[at]] + [zero] * (hb - h - 1), axis=1)
        # what does: a chunk after the other. U = W_v - W_k S and Q+ S a
        # head; then every head's K_end^T U as ONE product, [hb Q, dv]^T
        # against the block-diagonal [hb Q, hb dk]: 8 lane tiles wide
        # (eight products of [dv, Q] by [Q, dk] read 365 us a window on
        # the chip where this one reads 112)
        for i in range(n):
            at = slice(i * Q, (i + 1) * Q)
            U = []
            for h in range(hb):
                XS = _mm(xs[h, i], st[:, head(h)], ((1,), (1,)))
                U.append(wv[h, at] - XS[:Q])
                us[h, at] = U[h]
                oq[h, at] = XS[Q:]
            st[...] = st[...] * fe[i:i + 1, :] + _mm(
                jnp.concatenate(U, axis=0), kbd[i], ((0,), (0,)))
        # o = Q+ S + P U, the block's chunks at once
        for h in range(hb):
            o_ref[:, h, :] = oq[h] + _mm(pm[h], us[h], ((1,), (0,)))

    @pl.when(c == nc - 1)
    def _():
        for h in range(hb):
            send_ref[h] = st[:, head(h)].T


@functools.partial(jax.jit, static_argnames=("heads", "interpret", "arith"))
def _chunk_pallas(S0, q, k, v, g, beta, *, heads: int, interpret: bool,
                  arith: bool = True):
    C, H, dk = k.shape
    dv = v.shape[-1]
    Q, hb = CHUNK, heads
    # whole chunks, and whole blocks past one: a padded token (g = 0,
    # beta = 0) passes the state through
    Cp = -(-C // Q) * Q
    Cb = min(Cp, CHUNK_BLOCK)
    Cp = -(-Cp // Cb) * Cb
    if Cp != C:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, Cp - C),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, g, beta))
    n = Cb // Q

    def tokens(d):
        return pl.BlockSpec((Cb, hb, d), lambda h, c: (c, h, 0))

    def state():
        return pl.BlockSpec((hb, dk, dv), lambda h, c: (h, 0, 0))

    S_end, o = pl.pallas_call(
        functools.partial(_chunk_kernel, Q=Q, arith=arith),
        name="cake_kda_chunk",
        grid=(H // hb, Cp // Cb),
        in_specs=[state(), tokens(dk), tokens(dk), tokens(dv), tokens(dk),
                  pl.BlockSpec((None, Cb, hb), lambda h, c: (h, c, 0))],
        out_specs=[state(), tokens(dv)],
        scratch_shapes=[pltpu.VMEM((dv, hb * dk), F32),
                        pltpu.VMEM((hb, n, 2 * Q, dk), F32),
                        pltpu.VMEM((hb, Cb, dv), F32),
                        pltpu.VMEM((n, hb * Q, hb * dk), F32),
                        pltpu.VMEM((hb, Cb, Cb), F32),
                        pltpu.VMEM((hb, Cb, dv), F32),
                        pltpu.VMEM((hb, Cb, dv), F32),
                        pltpu.VMEM((n, hb * dk), F32)],
        out_shape=[jax.ShapeDtypeStruct((H, dk, dv), F32),
                   jax.ShapeDtypeStruct((Cp, H, dv), F32)],
        # a head block's chain over the chunk blocks is sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=CHUNK_VMEM_BYTES),
        interpret=interpret,
    )(S0, q, k, v.astype(F32), g,
      # a head block's write strengths side by side: [H / hb, Cp, hb]
      beta.reshape(Cp, H // hb, hb).swapaxes(0, 1))
    return S_end, o[:C]


def chunked(S0, q, k, v, g, beta, interpret: Optional[bool] = None):
    """A window of C tokens of ONE row, chunked: bailing_hybrid.
    kda_chunked's contract and its mathematics (chunks of CHUNK tokens,
    the unit-lower-triangular solve by forward substitution, float32
    operands and state, every product at the highest precision), the
    state on the chip from the window's first chunk to its last. S0 [H,
    dk, dv] f32; q, k [C, H, dk] f32; v [C, H, dv]; g [C, H, dk] f32;
    beta [C, H] f32 (g and beta 0 past the row's real tokens) -> (S_end
    [H, dk, dv] f32, o [C, H, dv] f32)."""
    if interpret is None:
        interpret = not rpa._on_tpu()
    H, dk, dv = S0.shape
    hb = chunk_heads(H)
    if not interpret and (dk % 128 or dv % 128 or (hb % 8 and hb != H)):
        raise ValueError(
            f"cake_kda_chunk cannot run on this chip at {H} heads of a "
            f"{dk} x {dv} state: dk and dv must be multiples of 128 and "
            f"the heads a program ({hb}) whole sublane tiles")
    return _chunk_pallas(S0, q, k, v, g, beta, heads=hb,
                         interpret=interpret)
