"""Llama parameter pytree: init, HF-safetensors loading, sharding specs.

Layout decision (TPU-first): all decoder-block weights are **stacked along a
leading layer axis** `[L, ...]` so the block walk compiles as one
`lax.scan` — one XLA while-loop instead of L unrolled block programs
(faster compile, identical steady-state speed) — and a contiguous slice of
the stack *is* a pipeline stage's parameter shard.

Linear weights are stored `[in, out]` (x @ w), transposed from HF's
`[out, in]` at load. On-disk format stays HF safetensors with the exact
tensor names the reference consumes (model.layers.N.self_attn.q_proj.weight
etc. — transformer.rs:28-49), so any reference checkpoint loads unchanged.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cake_tpu.models.llama.config import LlamaConfig


def init_params(config: LlamaConfig, rng: jax.Array, dtype=jnp.bfloat16):
    """Random-init parameter pytree (tests/benches; scale ~ 0.02)."""
    c = config
    L, D, F = c.num_hidden_layers, c.hidden_size, c.intermediate_size
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    keys = jax.random.split(rng, 12)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    params = {
        "embed": w(keys[0], (c.vocab_size, D), D),
        "blocks": {
            "attn_norm": jnp.ones((L, D), dtype),
            "wq": w(keys[1], (L, D, H * hd), D),
            "wk": w(keys[2], (L, D, KV * hd), D),
            "wv": w(keys[3], (L, D, KV * hd), D),
            "wo": w(keys[4], (L, H * hd, D), H * hd),
            "mlp_norm": jnp.ones((L, D), dtype),
            "w_gate": w(keys[5], (L, D, F), D),
            "w_up": w(keys[6], (L, D, F), D),
            "w_down": w(keys[7], (L, F, D), F),
        },
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": w(keys[8], (D, c.vocab_size), D),
    }
    if config.attention_bias:
        # distinct keys: identical bk/bv would hide a k/v bias swap from
        # any value-sensitive test
        params["blocks"]["bq"] = w(keys[9], (L, H * hd), D)
        params["blocks"]["bk"] = w(keys[10], (L, KV * hd), D)
        params["blocks"]["bv"] = w(keys[11], (L, KV * hd), D)
    if config.tie_word_embeddings:
        params["lm_head"] = params["embed"].T
    return params


def init_params_quantized(config: LlamaConfig, rng: jax.Array,
                          dtype=jnp.bfloat16, bits: int = 8):
    """Random quantized params built directly on device (int8 per-channel
    or int4 group-wise, matching ``quantize_params(..., bits=bits)``).

    Produces the same pytree structure as ``quantize_params(init_params(...))``
    without ever materialising the full-precision tree — a bf16 8B tree is
    ~15 GiB, i.e. most of a v5e's HBM, so the quantize-after-init path is
    dead on arrival there. The benchmark's timings do not depend on
    the weights' values, so random weights + constant scales serve as
    well as quantized real weights.
    """
    from cake_tpu.ops.quant import _BLOCK_CONTRACT, QTensor, pick_group

    c = config
    L, D, F = c.num_hidden_layers, c.hidden_size, c.intermediate_size
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    keys = jax.random.split(rng, 12)
    kit = iter(keys)

    def qleaf(shape, contract_dims, fan_in, leaf_bits=None):
        qmax = 127 if (leaf_bits or bits) == 8 else 7
        if (leaf_bits or bits) == 4:
            # random bytes ARE the packed group-halves stream — each
            # nibble is a uniform int4, which is all a weight-value-
            # independent benchmark needs
            cd = contract_dims[0]
            g = pick_group(shape[cd])
            q = jax.random.randint(
                next(kit), shape[:cd] + (shape[cd] // 2,) + shape[cd + 1:],
                0, 256, dtype=jnp.uint8)
            scale_shape = (shape[:cd] + (shape[cd] // g,) + shape[cd + 1:])
        else:
            q = jax.random.randint(next(kit), shape, -qmax, qmax + 1,
                                   dtype=jnp.int8)
            scale_shape = tuple(s for i, s in enumerate(shape)
                                if i not in contract_dims)
        # scale chosen so dequantized weights have the init std ~1/sqrt(fan_in)
        scale = jnp.full(scale_shape, 1.0 / (qmax * np.sqrt(fan_in)),
                         jnp.float32)
        return QTensor(q=q, scale=scale)

    def w(shape, fan_in):
        return (jax.random.normal(next(kit), shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    blocks = {
        "attn_norm": jnp.ones((L, D), dtype),
        "wq": qleaf((L, D, H * hd), _BLOCK_CONTRACT["wq"], D),
        "wk": qleaf((L, D, KV * hd), _BLOCK_CONTRACT["wk"], D),
        "wv": qleaf((L, D, KV * hd), _BLOCK_CONTRACT["wv"], D),
        "wo": qleaf((L, H * hd, D), _BLOCK_CONTRACT["wo"], H * hd),
        "mlp_norm": jnp.ones((L, D), dtype),
        "w_gate": qleaf((L, D, F), _BLOCK_CONTRACT["w_gate"], D),
        "w_up": qleaf((L, D, F), _BLOCK_CONTRACT["w_up"], D),
        "w_down": qleaf((L, F, D), _BLOCK_CONTRACT["w_down"], F),
    }
    if c.attention_bias:
        # full-precision, matching quantize_params (biases never quantize)
        blocks["bq"] = w((L, H * hd), D)
        blocks["bk"] = w((L, KV * hd), D)
        blocks["bv"] = w((L, KV * hd), D)
    return {
        "embed": w((c.vocab_size, D), D),
        "blocks": blocks,
        "final_norm": jnp.ones((D,), dtype),
        # lm_head stays int8 at bits=4 (quantize_params parity: the vocab
        # width fragments the int4 kernel's blocks; int8 is roofline there)
        "lm_head": qleaf((D, c.vocab_size), (0,), D, leaf_bits=8),
    }


# ONE program: XLA fuses each leaf's generate-and-cast chain, so the only
# HBM it takes is the tree itself (7.97 GiB out, ~0 temp at 8B int8; run
# op by op the int8 draws materialise word-wide intermediates).
init_params_quantized_jit = jax.jit(
    init_params_quantized, static_argnames=("config", "dtype", "bits"))


# -- HF name mapping ---------------------------------------------------------

def hf_param_layout(config: LlamaConfig):
    """Map our pytree leaves -> (list of HF tensor names, assembler).

    Used both for loading (HF -> pytree) and by the split tool
    (pytree -> HF names).
    """
    L = config.num_hidden_layers
    layout = {
        ("embed",): ("model.embed_tokens.weight", False),
        ("final_norm",): ("model.norm.weight", False),
        ("lm_head",): ("lm_head.weight", True),
    }
    per_layer = {
        "attn_norm": ("input_layernorm.weight", False),
        "wq": ("self_attn.q_proj.weight", True),
        "wk": ("self_attn.k_proj.weight", True),
        "wv": ("self_attn.v_proj.weight", True),
        "wo": ("self_attn.o_proj.weight", True),
        "mlp_norm": ("post_attention_layernorm.weight", False),
        "w_gate": ("mlp.gate_proj.weight", True),
        "w_up": ("mlp.up_proj.weight", True),
        "w_down": ("mlp.down_proj.weight", True),
    }
    if config.attention_bias:
        per_layer.update({
            "bq": ("self_attn.q_proj.bias", False),
            "bk": ("self_attn.k_proj.bias", False),
            "bv": ("self_attn.v_proj.bias", False),
        })
    return layout, per_layer, L


def load_params_from_hf(
    model_dir: str,
    config: LlamaConfig,
    dtype=jnp.bfloat16,
    layer_range: Optional[range] = None,
    put: Optional[Callable[[np.ndarray, object], jax.Array]] = None,
    shardings: Optional[dict] = None,
    finish: Optional[Callable[[str, jax.Array], object]] = None,
):
    """Build the parameter pytree from HF safetensors.

    layer_range: only materialise these blocks (stage-local loading).
    put:         (host_array, sharding_or_None) -> device array; defaults to
                 jnp.asarray (single-device).
    shardings:   optional pytree of NamedShardings matching param_specs().
    finish:      (leaf name, device array) -> the leaf to keep, applied as
                 each tensor lands — ops/quant.make_leaf_quantizer here
                 quantizes leaf by leaf, so the full-precision tree never
                 exists on the device.
    """
    from cake_tpu.utils.loading import load_weights

    layout, per_layer, L = hf_param_layout(config)
    layers = list(layer_range) if layer_range is not None else list(range(L))

    needed = {name for (name, _t) in layout.values()}
    for i in layers:
        for hf_suffix, _t in per_layer.values():
            needed.add(f"model.layers.{i}.{hf_suffix}")
    if config.tie_word_embeddings:
        needed.discard("lm_head.weight")

    host = load_weights(model_dir, filter_fn=lambda n: n in needed)

    if put is None:
        def put(arr, sharding):
            x = jnp.asarray(np.asarray(arr), dtype=dtype)
            return jax.device_put(x, sharding) if sharding is not None else x

    def shard_of(*path):
        node = shardings
        for k in path:
            if node is None:
                return None
            node = node.get(k) if isinstance(node, dict) else None
        return node

    if finish is None:
        def finish(_name, arr):
            return arr

    def leaf(name, transpose, sharding):
        arr = np.asarray(host[name])
        if transpose:
            arr = arr.T
        return put(arr.astype(_np_dtype(dtype)), sharding)

    params: Dict = {"blocks": {}}
    params["embed"] = leaf("model.embed_tokens.weight", False, shard_of("embed"))
    params["final_norm"] = leaf("model.norm.weight", False, shard_of("final_norm"))
    params["lm_head"] = finish("lm_head", (
        params["embed"].T if config.tie_word_embeddings
        else leaf("lm_head.weight", True, shard_of("lm_head"))))

    for key, (hf_suffix, transpose) in per_layer.items():
        stack = np.stack([
            (np.asarray(host[f"model.layers.{i}.{hf_suffix}"]).T
             if transpose else np.asarray(host[f"model.layers.{i}.{hf_suffix}"]))
            for i in layers
        ])
        params["blocks"][key] = finish(key, put(
            stack.astype(_np_dtype(dtype)), shard_of("blocks", key)
        ))
    return params


def _np_dtype(jdtype):
    import ml_dtypes
    return {jnp.bfloat16: ml_dtypes.bfloat16,
            jnp.float16: np.float16,
            jnp.float32: np.float32}.get(jdtype, np.float32)


def make_stream_leaf_builders(host, nd):
    """(simple_leaf, block_leaf) closures for streaming sharded loads —
    shared by the dense and MoE loaders so the slice semantics cannot
    drift. host: name -> mmap view; nd: numpy target dtype."""

    def simple_leaf(name: str, transpose: bool, sharding):
        src = host[name].T if transpose else host[name]

        def cb(index):
            return np.ascontiguousarray(src[index]).astype(nd, copy=False)

        return jax.make_array_from_callback(tuple(src.shape), sharding, cb)

    def block_leaf(names, transpose: bool, sharding):
        views = [host[n] for n in names]
        views = [v.T if transpose else v for v in views]
        L = len(views)
        shape = (L,) + tuple(views[0].shape)

        def cb(index):
            sub = np.stack([np.asarray(views[i][index[1:]])
                            for i in range(L)[index[0]]])
            return sub.astype(nd, copy=False)

        return jax.make_array_from_callback(shape, sharding, cb)

    return simple_leaf, block_leaf


def stream_shard_of(shardings):
    def shard_of(*path):
        node = shardings
        for k in path:
            node = node[k]
        return node
    return shard_of


def load_params_sharded(model_dir: str, config: LlamaConfig, shardings,
                        dtype=jnp.bfloat16):
    """Stream HF safetensors directly onto mesh shards.

    The eager loader (load_params_from_hf) materialises the full tree on
    the default device — at 70B (~140 GiB bf16) that dies long before
    place_for_pipeline runs, even though the *sharded* model fits
    comfortably. This loader never builds a full host or device copy:
    each leaf is a `jax.make_array_from_callback` whose callback slices
    the mmap'd safetensors views, so only the bytes of locally
    addressable shards are ever read (mmap pages fault in per shard
    slice), matching the reference worker's materialise-only-your-layers
    behavior (worker.rs:106-127) per *shard* instead of per host.

    shardings: pytree of jax.sharding.Sharding matching the param tree
    ({"embed", "blocks": {leaf...}, "final_norm", "lm_head"}).
    """
    from cake_tpu.utils.loading import load_weights

    layout, per_layer, L = hf_param_layout(config)
    # host tensors stay zero-copy mmap views; nothing is read here —
    # prefetch=False keeps the native reader from madvise(WILLNEED)ing
    # the whole checkpoint (only shard slices will ever be touched)
    host = load_weights(model_dir, prefetch=False)
    simple_leaf, block_leaf = make_stream_leaf_builders(
        host, _np_dtype(dtype))
    shard_of = stream_shard_of(shardings)

    params: Dict = {
        "blocks": {
            key: block_leaf(
                [f"model.layers.{i}.{hf_suffix}" for i in range(L)],
                transpose, shard_of("blocks", key))
            for key, (hf_suffix, transpose) in per_layer.items()
        },
    }
    for (key,), (hf_name, transpose) in layout.items():
        if key == "lm_head" and config.tie_word_embeddings:
            # read the embed source again transposed instead of an eager
            # .T on the placed array (which would be a cross-process
            # eager op on a multi-host mesh)
            hf_name = "model.embed_tokens.weight"
        params[key] = simple_leaf(hf_name, transpose, shard_of(key))
    return params


# -- sharding ---------------------------------------------------------------

def block_param_keys(config=None, *, moe: Optional[bool] = None) -> tuple:
    """Stacked-block leaf names for a config's family (dense vs MoE)."""
    if moe is None:
        moe = bool(config is not None and config.is_moe)
    keys = ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm"]
    if config is not None and getattr(config, "attention_bias", False):
        keys += ["bq", "bk", "bv"]
    if config is not None and getattr(config, "qk_norm", False):
        keys += ["q_norm", "k_norm"]
    keys += (["router", "we_gate", "we_up", "we_down"] if moe
             else ["w_gate", "w_up", "w_down"])
    return tuple(keys)


def block_specs(keys, stage_axis: Optional[str] = None,
                tp_axis: Optional[str] = None,
                ep_axis: Optional[str] = None):
    """PartitionSpecs for a set of stacked-block leaves, dense or MoE.

    Derives the spec dict from the actual pytree keys so every consumer
    (pipeline shard_map in_specs, placement, fits-in-HBM checks) handles
    both families without hardcoding a leaf list.
    """
    S, T, E = stage_axis, tp_axis, ep_axis
    table = {
        "attn_norm": P(S, None),
        "wq": P(S, None, T),
        "wk": P(S, None, T),
        "wv": P(S, None, T),
        # QKV bias (Qwen2): head dim sharded like the matching weight's
        # output dim
        "bq": P(S, T),
        "bk": P(S, T),
        "bv": P(S, T),
        # query/key norm (OLMoE): over the whole projection, split by
        # heads like it
        "q_norm": P(S, T),
        "k_norm": P(S, T),
        "wo": P(S, T, None),
        "mlp_norm": P(S, None),
        "w_gate": P(S, None, T),
        "w_up": P(S, None, T),
        "w_down": P(S, T, None),
        # MoE leaves (models/moe): router replicated, experts over ep,
        # ffn dim over tp
        "router": P(S, None, None),
        "we_gate": P(S, E, None, T),
        "we_up": P(S, E, None, T),
        "we_down": P(S, E, T, None),
    }
    unknown = set(keys) - set(table)
    if unknown:
        raise KeyError(f"no PartitionSpec rule for block leaves {unknown}")
    return {k: table[k] for k in keys}


def param_specs(tp_axis: str = "tp", stage_axis: Optional[str] = None,
                config: Optional[LlamaConfig] = None):
    """PartitionSpec pytree for Megatron-style tensor parallelism.

    Column-parallel: q/k/v, gate/up (output dim over tp).
    Row-parallel:    o, down (input dim over tp).
    Embedding + lm_head sharded over vocab; norms replicated.
    stage_axis, if given, shards the stacked layer dim (pipeline via scan
    is NOT done this way — see parallel/pipeline.py — but a stage axis on
    the layer dim gives cheap weight-memory sharding for fits-in-HBM checks).
    config: pass the model config so family-dependent leaves (Qwen2's
    bq/bk/bv) get specs; without it the dense biasless set is assumed.
    """
    return {
        "embed": P(tp_axis, None),
        "blocks": block_specs(block_param_keys(config, moe=False),
                              stage_axis=stage_axis, tp_axis=tp_axis),
        "final_norm": P(None),
        "lm_head": P(None, tp_axis),
    }


def cache_specs(tp_axis: str = "tp", dp_axis: str = "dp",
                stage_axis: Optional[str] = None):
    """KVCache PartitionSpecs: [L, B, S, KV, hd] — batch over dp, kv-heads
    over tp."""
    from cake_tpu.models.llama.cache import KVCache
    return KVCache(
        k=P(stage_axis, dp_axis, None, tp_axis, None),
        v=P(stage_axis, dp_axis, None, tp_axis, None),
    )
