"""MoE parameter pytree: init, HF (Mixtral) safetensors loading, EP specs.

Same stacked-[L, ...] layout as the Llama family (models/llama/params.py)
so the block walk is one `lax.scan`; expert weights add an E axis:
router [L, D, E], we_gate/we_up [L, E, D, F], we_down [L, E, F, D].
On-disk format is HF Mixtral safetensors
(model.layers.N.block_sparse_moe.gate.weight, .experts.K.{w1,w2,w3}.weight
— w1=gate, w2=down, w3=up), so public checkpoints load unchanged.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cake_tpu.models.llama.params import _np_dtype
from cake_tpu.models.moe.config import MoEConfig


def init_params(config: MoEConfig, rng: jax.Array, dtype=jnp.bfloat16):
    """Random-init MoE parameter pytree (tests/benches)."""
    c = config
    L, D, F = c.num_hidden_layers, c.hidden_size, c.intermediate_size
    E = c.num_local_experts
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
            "router": w(keys[5], (L, D, E), D),
            "we_gate": w(keys[6], (L, E, D, F), D),
            "we_up": w(keys[7], (L, E, D, F), D),
            "we_down": w(keys[8], (L, E, F, D), F),
        },
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": w(keys[9], (D, c.vocab_size), D),
    }
    if config.tie_word_embeddings:
        params["lm_head"] = params["embed"].T
    return params


MOE_PREFIX = "block_sparse_moe"
# our leaf -> (HF per-layer suffix, transpose); shared by the eager and
# streaming loaders so their trees cannot structurally diverge
MOE_ATTN_LAYOUT = {
    "attn_norm": ("input_layernorm.weight", False),
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
    "mlp_norm": ("post_attention_layernorm.weight", False),
    "router": (f"{MOE_PREFIX}.gate.weight", True),
}
# our expert leaf -> HF expert weight name (w1=gate, w3=up, w2=down)
MOE_EXPERT_LAYOUT = (("we_gate", "w1"), ("we_up", "w3"),
                     ("we_down", "w2"))


def load_params_from_hf(model_dir: str, config: MoEConfig,
                        dtype=jnp.bfloat16,
                        layer_range: Optional[range] = None,
                        finish=None):
    """Build the MoE pytree from HF Mixtral safetensors. finish: (leaf
    name, device array) -> the leaf to keep, applied as each tensor
    lands (see models/llama/params.load_params_from_hf)."""
    from cake_tpu.utils.loading import load_weights

    if finish is None:
        def finish(_name, arr):
            return arr

    c = config
    L, E = c.num_hidden_layers, c.num_local_experts
    layers = list(layer_range) if layer_range is not None else list(range(L))
    nd = _np_dtype(dtype)

    moe = MOE_PREFIX
    needed = {"model.embed_tokens.weight", "model.norm.weight"}
    if not c.tie_word_embeddings:
        needed.add("lm_head.weight")
    attn = MOE_ATTN_LAYOUT
    for i in layers:
        for suffix, _t in attn.values():
            needed.add(f"model.layers.{i}.{suffix}")
        for e in range(E):
            for wn in ("w1", "w2", "w3"):
                needed.add(f"model.layers.{i}.{moe}.experts.{e}.{wn}.weight")

    host = load_weights(model_dir, filter_fn=lambda n: n in needed)

    def t(name, transpose):
        arr = np.asarray(host[name])
        return (arr.T if transpose else arr).astype(nd)

    blocks = {
        key: finish(key, jnp.asarray(np.stack([
            t(f"model.layers.{i}.{suffix}", tr) for i in layers
        ])))
        for key, (suffix, tr) in attn.items()
    }
    # Experts: HF w1 [F, D] = gate, w3 [F, D] = up (both -> [D, F]);
    # w2 [D, F] = down (-> [F, D]).
    for key, wn in MOE_EXPERT_LAYOUT:
        blocks[key] = finish(key, jnp.asarray(np.stack([
            np.stack([
                t(f"model.layers.{i}.{moe}.experts.{e}.{wn}.weight", True)
                for e in range(E)
            ]) for i in layers
        ])))

    params = {
        "blocks": blocks,
        "embed": jnp.asarray(t("model.embed_tokens.weight", False)),
        "final_norm": jnp.asarray(t("model.norm.weight", False)),
    }
    params["lm_head"] = finish("lm_head", (
        params["embed"].T if c.tie_word_embeddings
        else jnp.asarray(t("lm_head.weight", True))))
    return params


def load_params_sharded(model_dir: str, config: MoEConfig, shardings,
                        dtype=jnp.bfloat16):
    """Stream HF Mixtral safetensors directly onto mesh shards — the MoE
    analog of models/llama/params.load_params_sharded: each leaf is a
    jax.make_array_from_callback over mmap views (prefetch disabled), so
    only locally addressable shard bytes are ever read. At Mixtral-8x22B
    scale the full tree (~280 GiB bf16) never fits one device; the
    sharded slices do. Reference behavior: worker-side subset
    materialisation (worker.rs:106-127), per shard.
    """
    from cake_tpu.models.llama.params import (
        make_stream_leaf_builders, stream_shard_of,
    )
    from cake_tpu.utils.loading import load_weights

    c = config
    L, E = c.num_hidden_layers, c.num_local_experts
    host = load_weights(model_dir, prefetch=False)
    nd = _np_dtype(dtype)
    simple_leaf, block_leaf = make_stream_leaf_builders(host, nd)
    shard_of = stream_shard_of(shardings)
    moe = MOE_PREFIX

    def expert_leaf(wn, sharding):
        # [L, E, in, out] stacked from per-expert [out, in] HF tensors
        views = [[host[f"model.layers.{i}.{moe}.experts.{e}.{wn}.weight"].T
                  for e in range(E)] for i in range(L)]
        shape = (L, E) + tuple(views[0][0].shape)

        def cb(index):
            sub = np.stack([
                np.stack([np.asarray(views[i][e][index[2:]])
                          for e in range(E)[index[1]]])
                for i in range(L)[index[0]]
            ])
            return sub.astype(nd, copy=False)

        return jax.make_array_from_callback(shape, sharding, cb)

    blocks = {
        key: block_leaf([f"model.layers.{i}.{suffix}" for i in range(L)],
                        tr, shard_of("blocks", key))
        for key, (suffix, tr) in MOE_ATTN_LAYOUT.items()}
    for key, wn in MOE_EXPERT_LAYOUT:
        blocks[key] = expert_leaf(wn, shard_of("blocks", key))

    params = {
        "blocks": blocks,
        "embed": simple_leaf("model.embed_tokens.weight", False,
                             shard_of("embed")),
        "final_norm": simple_leaf("model.norm.weight", False,
                                  shard_of("final_norm")),
    }
    params["lm_head"] = simple_leaf(
        "model.embed_tokens.weight" if c.tie_word_embeddings
        else "lm_head.weight", True, shard_of("lm_head"))
    return params


def param_specs(tp_axis: str = "tp", ep_axis: Optional[str] = "ep",
                stage_axis: Optional[str] = None):
    """PartitionSpec pytree: experts over ep, Megatron F-dim over tp.

    Under plain jit + NamedSharding, annotating the weights is all EP
    needs — XLA partitions the expert einsums in ops/moe.py and inserts
    the reduction. The router stays replicated (it is [D, E]-tiny).
    """
    from cake_tpu.models.llama.params import block_param_keys, block_specs
    return {
        "embed": P(tp_axis, None),
        "blocks": block_specs(block_param_keys(moe=True),
                              stage_axis=stage_axis, tp_axis=tp_axis,
                              ep_axis=ep_axis),
        "final_norm": P(None),
        "lm_head": P(None, tp_axis),
    }
