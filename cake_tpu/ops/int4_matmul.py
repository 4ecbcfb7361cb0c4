"""Pallas TPU matmul over nibble-packed int4 weights (decode hot path).

Why a kernel: batch-1 decode streams the whole weight set per token, so
tok/s == HBM bandwidth / weight bytes (SURVEY.md §6). int4 storage halves
int8's traffic, but XLA cannot consume packed nibbles: the S4 dtype cannot
cross a jit boundary on this backend, and an unpack-then-dot graph
materialises the dequantized copy in HBM — costing MORE traffic than int8.
This kernel reads the packed bytes into VMEM, sign-extends the nibbles in
registers, and runs the two half-dots per group tile; dequantized weights
never exist in HBM. The reference has no quantization at all (f16 floor,
cake/mod.rs:54-60).

Storage layout ("group-halves", produced by ops.quant.quantize_group):
a weight [In, Out] is grouped into G = In/g row groups; within group gi,
input row j (j < g/2) packs into the LOW nibble and row j + g/2 into the
HIGH nibble of packed byte [gi*g/2 + j, out]. Both nibble-mates share the
group's scale row, so a tile's two dots are scaled by one [1, block_out]
row, and the kernel slices x contiguously (x_group[:, :g/2] / [g/2:]) —
no strided loads. Scales are f32 [G, Out].

The kernel is matvec-shaped (M <= MAX_KERNEL_M rows): decode batches pad
M up to a sublane multiple and the grid streams (Out/block_out, G) tiles
with the group axis innermost, accumulating in an f32 VMEM scratch.
Prefill (large M) takes the XLA dequantize path instead — it is
MXU-bound there, and the per-layer dequantized copy is amortised by the
[S, In] @ [In, Out] compute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# decode/matvec shapes only; larger M falls back to the dequantize path
MAX_KERNEL_M = 64


def pack_int4(q: jnp.ndarray, g: int) -> jnp.ndarray:
    """Pack int4 values (int8 array in [-8, 7], contract dim -2) into
    uint8 bytes using the group-halves layout, BIASED by +8 (nibbles store
    v+8 in [0, 15]). The bias lets the kernel unpack with one mask/shift
    per nibble instead of a sign-extending double-shift — the unpack is
    VPU-bound and sets the kernel's speed — while the dot's bias
    contribution folds into a per-group sum(x) correction.
    [.., In, Out] -> [.., In/2, Out]."""
    *lead, In, Out = q.shape
    assert In % g == 0 and g % 2 == 0, (In, g)
    G = In // g
    v = (q.astype(jnp.int32) + 8) & 0xF
    v = v.reshape(*lead, G, g, Out)
    lo = v[..., : g // 2, :]
    hi = v[..., g // 2:, :]
    packed = lo | (hi << 4)
    return packed.astype(jnp.uint8).reshape(*lead, In // 2, Out)


def unpack_int4(packed: jnp.ndarray, g: int) -> jnp.ndarray:
    """Inverse of pack_int4: [.., In/2, Out] uint8 -> [.., In, Out] int8
    (true signed int4 values; the storage bias is removed)."""
    *lead, half, Out = packed.shape
    G = half // (g // 2)
    p = packed.astype(jnp.int32).reshape(*lead, G, g // 2, Out)
    lo = (p & 0xF) - 8
    hi = (p >> 4) - 8
    w = jnp.concatenate([lo, hi], axis=-2)          # [.., G, g, Out]
    return w.astype(jnp.int8).reshape(*lead, G * g, Out)


def _int4_kernel(x_ref, p_ref, s_ref, o_ref, acc_ref, *, g: int, K: int):
    """One (out_block, group_block) tile: K groups' packed bytes resident,
    per group unpack→concat→one [M, g] x [g, bo] dot, scale, accumulate.

    K groups per grid step keeps each packed DMA block large (hundreds of
    KiB) — a one-group grid fragments the weight stream into tiny
    transfers and loses most of the HBM bandwidth to per-step overhead
    (measured 4x slower on an 8B walk)."""
    gi = pl.program_id(1)

    @pl.when(gi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    h = g // 2
    for k in range(K):
        p32 = p_ref[k * h:(k + 1) * h, :].astype(jnp.int32)  # [g/2, bo]
        # nibbles store v+8: one mask/shift each (the unpack is the VPU
        # bottleneck); the +8 bias is removed AFTER the dots via the
        # group's sum(x) — dot(x, w+8) == dot(x, w) + 8*sum(x)
        lo = (p32 & 0xF).astype(x_ref.dtype)
        hi = (p32 >> 4).astype(x_ref.dtype)
        xg = x_ref[:, k * g:(k + 1) * g]                     # [M, g]
        part = jax.lax.dot_general(
            xg[:, :h], lo, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        part = part + jax.lax.dot_general(
            xg[:, h:], hi, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        xsum = jnp.sum(xg.astype(jnp.float32), axis=1, keepdims=True)
        acc_ref[:] += (part - 8.0 * xsum) * s_ref[k, 0]

    @pl.when(gi == pl.num_programs(1) - 1)
    def _finish():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _pick_block_out(out: int) -> int:
    for b in (1024, 512, 256, 128):
        if out % b == 0:
            return b
    return 0


def _pick_k_groups(n_groups: int, g: int) -> int:
    """Groups per grid step: target ~512 packed rows per block."""
    k = max(1, min(n_groups, 1024 // g))
    while k > 1 and n_groups % k:
        k -= 1
    return k


def kernel_supported(m: int, in_dim: int, g: int, out: int) -> bool:
    if in_dim % 128 != 0 and jax.default_backend() == "tpu":
        # the x block's minor (lane) dim is in_dim: sub-128 lanes
        # compile in interpret mode but Mosaic rejects them on real
        # silicon (found running the tiny-shape suite on chip) — fall
        # back to the dequant-matmul path there
        return False
    return (m <= MAX_KERNEL_M and in_dim % g == 0 and g % 2 == 0
            and (g // 2) % 8 == 0 and _pick_block_out(out) > 0)


@functools.partial(jax.jit, static_argnames=("g", "interpret"))
def int4_matmul(x: jnp.ndarray, packed: jnp.ndarray, scale: jnp.ndarray,
                *, g: int, interpret: bool | None = None) -> jnp.ndarray:
    """x [M, In] @ packed-int4 [In/2, Out] with group scales [G, Out].

    Callers must check kernel_supported(...) first. M is padded to a
    sublane multiple internally; returns [M, Out] in x.dtype.
    """
    M, In = x.shape
    half, Out = packed.shape
    G = scale.shape[0]
    assert In == 2 * half and G * g == In, (x.shape, packed.shape, g)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_out = _pick_block_out(Out)
    K = _pick_k_groups(G, g)
    Mp = max(8, -(-M // 8) * 8)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_int4_kernel, g=g, K=K),
        name="cake_int4_matmul",
        grid=(Out // block_out, G // K),
        in_specs=[
            pl.BlockSpec((Mp, K * g), lambda io, gi: (0, gi)),
            pl.BlockSpec((K * (g // 2), block_out), lambda io, gi: (gi, io)),
            # scale as [G, 1, Out]: a (K, 1, block_out) block keeps the
            # last-two block dims TPU-legal (dim -2 equals the array dim)
            pl.BlockSpec((K, 1, block_out), lambda io, gi: (gi, 0, io)),
        ],
        out_specs=pl.BlockSpec((Mp, block_out), lambda io, gi: (0, io)),
        out_shape=jax.ShapeDtypeStruct((Mp, Out), x.dtype),
        scratch_shapes=[pltpu.VMEM((Mp, block_out), jnp.float32)],
        interpret=interpret,
    )(x, packed, scale[:, None, :])
    return out[:M]
