"""Weight-only int8/int4 quantization for decode bandwidth.

Batch-1 decode is HBM-bandwidth-bound: every step streams the full weight
set once (PERF.md §5: the FFN's weight streaming). Storing linear weights as
int8 with per-output-channel scales halves that traffic — the dequantize
happens in registers on the way into the bf16 MXU matmul, so throughput
approaches 2x the bf16 roofline while activations/accumulation stay bf16
(weight-only: no activation quantization, accuracy loss is per-channel
rounding only). int4 with *group-wise* scales (one scale per `group`
input rows per output channel, the GPTQ/AWQ storage layout) halves the
traffic again; per-output-channel scaling alone is too coarse at 4 bits.
The reference has no quantization support at all (f16 is its smallest
dtype, cake/mod.rs:54-60).

`QTensor` is a pytree (NamedTuple), so quantized params flow through
`lax.scan` over stacked layers, jit, and donation unchanged; `qmatmul` /
`qeinsum` dispatch on leaf type so the same model code runs full-precision
and quantized weights. The two layouts are distinguished structurally:
per-channel scales DROP the contracted dim (`scale.ndim < q.ndim`);
group-wise scales KEEP it, shrunk by the group size
(`scale.ndim == q.ndim`). Both keep the scale multiply OUTSIDE the
matmul — `(x @ q) * scale` per channel, `sum_G (x_G @ q_G) * scale_G`
per group — so XLA never materialises a dequantized weight copy in HBM.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Union

import jax.numpy as jnp


class QTensor(NamedTuple):
    """int8 weights + per-output-channel scales.

    q:     int8, original weight shape
    scale: f32, original shape with the contracted (input) dims removed
    """

    q: jnp.ndarray
    scale: jnp.ndarray

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim


Weight = Union[jnp.ndarray, QTensor]


def quantize(w: jnp.ndarray, contract_dims: Sequence[int]) -> QTensor:
    """Symmetric per-channel int8: scale = max|w| / 127 over contract_dims."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=tuple(contract_dims), keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QTensor(q=q, scale=jnp.squeeze(scale, axis=tuple(contract_dims)))


def pick_group(contract_size: int, group: int = 128) -> int:
    """Largest power-of-two group <= `group` dividing the contract dim
    (tiny test configs have dims < 128)."""
    g = group
    while g > 1 and contract_size % g:
        g //= 2
    return g


def quantize_group(w: jnp.ndarray, contract_dim: int,
                   group: int = 128) -> QTensor:
    """Symmetric group-wise int4, nibble-packed: one scale per `group`
    contracted rows per output channel; values packed two-per-byte in the
    group-halves layout (ops/int4_matmul.pack_int4). `contract_dim`
    indexes w's shape and must be the -2 dim (the matmul input dim —
    group-wise is matmul-only); the returned q is uint8 with that dim
    halved, and the scale has it shrunk to n_groups (scale.ndim ==
    q.ndim, which is how consumers recognise the layout)."""
    from cake_tpu.ops.int4_matmul import pack_int4

    contract_dim = contract_dim % w.ndim
    if contract_dim != w.ndim - 2:
        raise ValueError(
            f"group-wise quantization contracts the -2 dim, got "
            f"{contract_dim} of {w.ndim}")
    In = w.shape[contract_dim]
    g = pick_group(In, group)
    if g < 2:
        raise ValueError(f"contract dim {In} cannot form int4 pairs")
    shape = w.shape
    grouped = (shape[:contract_dim] + (In // g, g) + shape[contract_dim + 1:])
    w32 = w.astype(jnp.float32).reshape(grouped)
    amax = jnp.max(jnp.abs(w32), axis=contract_dim + 1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(w32 / scale), -7, 7)
    q = q.astype(jnp.int8).reshape(shape)
    return QTensor(q=pack_int4(q, g),
                   scale=jnp.squeeze(scale, axis=contract_dim + 1))


def group_size(w: "QTensor") -> int:
    """Group size g of a packed group-wise QTensor."""
    return 2 * w.q.shape[-2] // w.scale.shape[-2]


def _group_matmul(x: jnp.ndarray, w: QTensor) -> jnp.ndarray:
    """x @ dequant(w) for the packed group-wise layout ([in/2, out] leaf).

    Matvec-shaped x (decode) goes through the Pallas kernel — packed
    bytes unpack in registers, the dequantized weight never exists in
    HBM. Larger x (prefill) dequantizes per layer and takes a plain
    matmul: MXU-bound there, and the copy is amortised by the compute.
    """
    from cake_tpu.ops import int4_matmul as i4

    g = group_size(w)
    In = 2 * w.q.shape[-2]
    Out = w.q.shape[-1]
    lead = x.shape[:-1]
    M = 1
    for s in lead:
        M *= s
    if w.q.ndim == 2 and i4.kernel_supported(M, In, g, Out):
        out = i4.int4_matmul(x.reshape(M, In), w.q, w.scale, g=g)
        return out.reshape(*lead, Out)
    qg = i4.unpack_int4(w.q, g).astype(x.dtype)
    G = w.scale.shape[-2]
    qg = qg.reshape(*w.q.shape[:-2], G, g, Out)
    wd = (qg * w.scale[..., :, None, :].astype(x.dtype)
          ).reshape(*w.q.shape[:-2], In, Out)
    return x @ wd


def is_groupwise(w: "QTensor") -> bool:
    return w.scale.ndim == w.q.ndim


def qmatmul(x: jnp.ndarray, w: Weight) -> jnp.ndarray:
    """x @ w for a raw array or QTensor ([in, out], contract dim -2)."""
    if isinstance(w, QTensor):
        if is_groupwise(w):
            return _group_matmul(x, w)
        return (x @ w.q.astype(x.dtype)) * w.scale.astype(x.dtype)
    return x @ w


def qeinsum(spec: str, x: jnp.ndarray, w: Weight) -> jnp.ndarray:
    """einsum(spec, x, w) with QTensor support.

    The QTensor's scale must broadcast against the einsum output's trailing
    dims (true for the layouts quantize_params produces: contracted dims
    removed, remaining dims in output order). Group-wise (int4) weights are
    matmul-only: the general-einsum grouped contraction isn't implemented,
    and the MoE expert weights that come through here stay int8."""
    if isinstance(w, QTensor):
        if is_groupwise(w):
            raise NotImplementedError(
                "group-wise (int4) weights support qmatmul only; "
                "quantize einsum weights per-channel (int8)")
        out = jnp.einsum(spec, x, w.q.astype(x.dtype))
        return out * w.scale.astype(x.dtype)
    return jnp.einsum(spec, x, w)


# Per-leaf contracted dims for the stacked [L, ...] block layout
# (models/llama/params.py, models/moe/params.py): matmul weights contract
# their input dim; expert weights contract D (we_gate/we_up) or F (we_down).
_BLOCK_CONTRACT = {
    "wq": (1,), "wk": (1,), "wv": (1,), "wo": (1,),
    "w_gate": (1,), "w_up": (1,), "w_down": (1,),
    "we_gate": (2,), "we_up": (2,), "we_down": (2,),
    # latent attention, the sparse indexer and the shared expert
    # (models/moe: glm_moe_dsa), stacked [layers of the kind, in, out]
    "wq_a": (1,), "wq_b": (1,), "wkv_a": (1,), "wkv_b_k": (1,),
    "wkv_b_v": (1,), "wi_q": (1,), "wi_k": (1,),
    "ws_gate": (1,), "ws_up": (1,), "ws_down": (1,),
    # the head-wise attention gate [layers of the kind, D, H] (dots3_note)
    "w_attn_gate": (1,),
    # Mamba-2's projections and the latent experts' (nemotron_h)
    "w_in": (1,), "w_out": (1,), "w_fc1": (1,), "w_fc2": (1,),
    # CCA's fused projection into the latent, [q | k | v1 | v2] (zaya);
    # its convolutions, router, norms, temperatures and residual scaling
    # are small leaves and stay float (absent here)
    "w_cca": (1,),
    # Kimi Delta Attention's projections (bailing_hybrid): the fused
    # [q | k | v | decay | output gate], the write strength a head, the
    # output; the conv taps, A_log, dt_bias and the head norm stay float
    "w_kda_in": (1,), "w_kda_beta": (1,), "w_kda_out": (1,),
}


def expand_spec(spec, contract_dims: Sequence[int], ndim: int,
                groupwise: bool = False) -> "QTensor":
    """(q_spec, scale_spec) for a quantized weight from its logical spec.

    q keeps the full-precision weight's PartitionSpec unchanged (same
    shape). Per-channel: the scale drops the contracted dims, so its spec
    keeps only the surviving entries — sharding a *contracted* dim shards
    q only (each shard holds complete input columns for its output
    channels, dequantize stays local). Group-wise: the scale keeps every
    dim (the contract dim became the group dim), so it inherits the full
    spec — sharding the contract dim splits whole groups as long as the
    per-shard size stays group-aligned.
    """
    from jax.sharding import PartitionSpec as P

    entries = list(spec) + [None] * (ndim - len(spec))
    if groupwise:
        return QTensor(q=P(*entries), scale=P(*entries))
    scale_entries = [e for i, e in enumerate(entries)
                     if i not in tuple(contract_dims)]
    return QTensor(q=P(*entries), scale=P(*scale_entries))


def contract_dims_for_path(path) -> Sequence[int]:
    """Contracted dims of a quantized leaf, keyed by its pytree path.

    Stacked block leaves use _BLOCK_CONTRACT by name; the lm_head contracts
    its input dim 0 (see quantize_params).
    """
    for entry in reversed(tuple(path)):
        name = getattr(entry, "key", None)
        if name in _BLOCK_CONTRACT:
            return _BLOCK_CONTRACT[name]
        if name == "lm_head":
            return (0,)
    raise KeyError(
        f"no contract-dim rule for quantized leaf at path {path!r}")


def expand_specs_for_quant(params, spec_tree):
    """Return spec_tree with QTensor(q_spec, scale_spec) nodes wherever
    `params` holds a QTensor, so the two trees match structurally for
    tree.map / shard_map in_specs / pjit shardings."""
    import jax

    def f(path, x, s):
        if isinstance(x, QTensor):
            return expand_spec(s, contract_dims_for_path(path), x.q.ndim,
                               groupwise=is_groupwise(x))
        return s

    return jax.tree_util.tree_map_with_path(
        f, params, spec_tree,
        is_leaf=lambda x: x is None or isinstance(x, QTensor),
    )


def quantize_params(params: dict, bits: int = 8, group: int = 128) -> dict:
    """Quantize every linear weight in a text-model pytree.

    bits=8: per-output-channel int8. bits=4: group-wise int4 (GPTQ/AWQ
    storage layout; matmul weights only — MoE expert trees need the
    einsum path and stay int8). Embedding, norms, and the (tiny) MoE
    router stay full precision; the lm_head and all block matmul weights
    become QTensors.
    """
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if bits == 4:
        if any(k.startswith("we_") for k in params["blocks"]):
            raise NotImplementedError(
                "int4 is matmul-only; MoE expert weights go through "
                "qeinsum — use --quant int8 for MoE models")

        def qz(v, dims):
            return quantize_group(v, dims[0], group)
    else:
        qz = quantize
    out = dict(params)
    out["blocks"] = {
        k: (qz(v, _BLOCK_CONTRACT[k]) if k in _BLOCK_CONTRACT else v)
        for k, v in params["blocks"].items()
    }
    # the lm_head stays per-channel int8 even at bits=4: its vocab width
    # (e.g. 128256 = 2^8*3*167) fragments the kernel's out-blocks into
    # small DMAs, and it is ~12% of the weight bytes — the int8 path
    # already streams it at roofline
    out["lm_head"] = quantize(params["lm_head"], (0,))
    return out


def make_leaf_quantizer(bits: int = 8, group: int = 128):
    """(leaf name, full-precision leaf) -> that leaf as
    quantize_params(bits=bits) would hold it: block matmul weights and
    the lm_head become QTensors, every other leaf passes through. One
    jitted call per leaf, so a loader that applies it as each tensor
    lands (models.load_text_params) never holds more than ONE
    full-precision weight leaf beside the quantized tree — an 8B bf16
    tree is ~15 GiB, most of a v5e's HBM, and must never exist whole."""
    import jax as _jax

    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")

    def qz(name: str, w):
        if name == "lm_head":
            # per-channel int8 even at bits=4 (see quantize_params)
            return _jax.jit(lambda v: quantize(v, (0,)))(w)
        dims = _BLOCK_CONTRACT.get(name)
        if dims is None:
            return w
        if bits == 8:
            return _jax.jit(lambda v: quantize(v, dims))(w)
        if name.startswith("we_"):
            raise NotImplementedError(
                "int4 is matmul-only; MoE expert weights go through "
                "qeinsum — use --quant int8 for MoE models")
        return _jax.jit(lambda v: quantize_group(v, dims[0], group))(w)

    return qz


def quantize_params_leafwise(params: dict, bits: int = 4,
                             group: int = 128) -> dict:
    """quantize_params, one jitted call per leaf, dropping each
    full-precision leaf as its quantized copy lands.

    Use when whole-tree buffer donation cannot alias (int4: every output
    is half-width packed uint8 + group scales, so `jit(..., donate)` on
    the tree warns "donated buffers were not usable" for the leaves and
    frees them only at computation end). Leaf-at-a-time gives the
    peak-HBM bound full fp tree + one quantized leaf, warning-free.

    CONSUMES the input: full-precision leaves are popped from the
    caller's `params["blocks"]` dict itself as their quantized copies
    land — popping a private copy would keep every fp leaf referenced
    through the caller's tree until return, silently losing the bound
    this function exists for.
    """
    qz = make_leaf_quantizer(bits, group)
    if bits == 4 and any(k.startswith("we_") for k in params["blocks"]):
        raise NotImplementedError(
            "int4 is matmul-only; MoE expert weights go through "
            "qeinsum — use --quant int8 for MoE models")
    src = params["blocks"]   # shared: pops drop the caller's refs too
    blocks = {k: qz(k, src.pop(k) if k in _BLOCK_CONTRACT else src[k])
              for k in list(src)}
    out = dict(params)
    out["blocks"] = blocks
    out["lm_head"] = qz("lm_head", params.pop("lm_head"))
    return out
