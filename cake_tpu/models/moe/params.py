"""MoE parameter pytree: init, HF safetensors loading, EP specs.

Same stacked-[L, ...] layout as the Llama family (models/llama/params.py)
so the block walk is one `lax.scan`; expert weights add an E axis:
router [L, D, E], we_gate/we_up [L, E, D, F], we_down [L, E, F, D]; a
family with query/key norm (OLMoE) adds q_norm [L, H*hd], k_norm
[L, KV*hd]. On-disk formats are the public ones, so checkpoints load
unchanged: Mixtral (model.layers.N.block_sparse_moe.gate.weight,
.experts.K.{w1,w2,w3}.weight — w1=gate, w2=down, w3=up) and OLMoE
(model.layers.N.mlp.gate.weight, .mlp.experts.K.{gate,up,down}_proj.weight,
.self_attn.{q,k}_norm.weight).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cake_tpu.models.llama.params import _np_dtype
from cake_tpu.models.moe.config import MoEConfig


def _draws(rng: jax.Array, dtype, bits: Optional[int], n_keys: int):
    """(w, mat, keys): the float draw `w(shape, fan_in)`, the matmul
    leaf's draw `mat(name, shape, fan_in)` (an int8 per-channel QTensor
    when bits == 8) and the key iterator both take from."""
    from cake_tpu.ops.quant import _BLOCK_CONTRACT, QTensor

    if bits not in (None, 8):
        raise NotImplementedError(
            "MoE expert weights quantize per-channel only; use "
            "--quant int8 for MoE models")
    keys = iter(jax.random.split(rng, n_keys))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    def mat(name, shape, fan_in):
        if not bits:
            return w(shape, fan_in)
        contract = _BLOCK_CONTRACT.get(name, (0,))
        q = jax.random.randint(next(keys), shape, -127, 128, dtype=jnp.int8)
        # uniform int8 has standard deviation 127/sqrt(3): the scale
        # gives the dequantized weights the float draw's 1/sqrt(fan_in)
        scale = jnp.full(
            tuple(n for i, n in enumerate(shape) if i not in contract),
            np.sqrt(3.0) / (127.0 * np.sqrt(fan_in)), jnp.float32)
        return QTensor(q=q, scale=scale)

    return w, mat, keys


def _init_glm_params(config, rng: jax.Array, dtype, bits: Optional[int]):
    """The seeded tree of a GlmMoeDsaConfig (and of the config classes
    over it). Leaves are stacked per
    KIND of layer, so each has the leading axis of the layers that have
    it: the attention leaves [L, ...], the indexer's [L_full, ...]
    (layers whose indexer_types entry is "full"), the dense FFN's
    [L_dense, ...], the router's, the experts' and the shared expert's
    [L_sparse, ...]. Norm weights, the indexer key's LayerNorm and the
    router's selection bias are drawn away from their neutral values,
    so that a test sees them."""
    c = config
    D = c.hidden_size
    # the attention leaves of the layers in the latent pool (all of
    # them but a dots3_note model's sliding layers, which follow below)
    L = len(c.latent_layers)
    H, R, Rq = c.num_attention_heads, c.kv_lora_rank, c.q_lora_rank
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    nI, dI = c.index_n_heads, c.index_head_dim
    Lf, Ls = len(c.full_layers), len(c.sparse_layers)
    Ld = c.num_hidden_layers - Ls
    # a model of shortcut layers (longcat_flash) keeps a dense FFN in
    # every sublayer and its routers and experts in the "shortcut" ones;
    # its router is wider than its experts by the zero experts
    shortcut = len(getattr(c, "shortcut_layers", ()))
    Ls = Ls or shortcut
    E, Et, Fe, Fd = (c.num_local_experts, c.n_routed_experts_total,
                     c.moe_intermediate_size, c.intermediate_size)
    Et += getattr(c, "zero_expert_num", 0)
    # the shared experts are ONE MLP of their summed width
    Fs = c.n_shared_experts * Fe
    w, mat, keys = _draws(rng, dtype, bits, 40)

    def near(shape, centre):
        return (centre + 0.1 * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    def read_at(rank: int, scale: float) -> float:
        """The fan-in a reader of a normed latent is drawn at: the
        rank, times the square of what the latent is multiplied by (a
        rescale by sqrt(hidden / rank) says the latent is read at the
        hidden size's variance: it corrects an init of one sigma for
        every matrix, and under this fan-in draw it would make the
        scores seven times as wide, a softmax that is all but one-hot
        and a model in which one rounding moves every later layer: the
        chip comparison read 4.7e-2 of the logits' range so; PERF.md
        section 6, PR 41). q, k and v then have the variance they have
        without a rescale."""
        return rank * scale ** 2

    g = c.geometry(c.latent_layers[0])
    fq, fkv = read_at(Rq, g.q_scale), read_at(R, g.kv_scale)
    blocks = {
        "attn_norm": near((L, D), 1.0),
        "wq_a": mat("wq_a", (L, D, Rq), D),
        "q_a_norm": near((L, Rq), 1.0),
        "wq_b": mat("wq_b", (L, Rq, H * (dn + dr)), fq),
        "wkv_a": mat("wkv_a", (L, D, R + dr), D),
        "kv_a_norm": near((L, R), 1.0),
        "wkv_b_k": mat("wkv_b_k", (L, R, H * dn), fkv),
        "wkv_b_v": mat("wkv_b_v", (L, R, H * dv), fkv),
        "wo": mat("wo", (L, H * dv, D), H * dv),
        "mlp_norm": near((L, D), 1.0),
        "wi_q": mat("wi_q", (Lf, Rq, nI * dI), fq),
        "wi_k": mat("wi_k", (Lf, D, dI), D),
        "wi_k_norm": near((Lf, dI), 1.0),
        "wi_k_bias": near((Lf, dI), 0.0),
        "wi_w": w((Lf, D, nI), D),
        "router": w((Ls, D, Et), D),
        # the selection bias (e_score_correction_bias): float32 as the
        # scores it is added to, of the size of their spread
        "router_bias": 0.05 * jax.random.normal(
            next(keys), (Ls, Et), jnp.float32),
        "we_gate": mat("we_gate", (Ls, E, D, Fe), D),
        "we_up": mat("we_up", (Ls, E, D, Fe), D),
        "we_down": mat("we_down", (Ls, E, Fe, D), Fe),
        "ws_gate": mat("ws_gate", (Ls, D, Fs), D),
        "ws_up": mat("ws_up", (Ls, D, Fs), D),
        "ws_down": mat("ws_down", (Ls, Fs, D), Fs or 1),
    }
    if not Lf:
        # no layer has an indexer (deepseek_v2), and its softmax rule
        # has no selection bias (longcat_flash's has one)
        for name in ("wi_q", "wi_k", "wi_k_norm", "wi_k_bias", "wi_w",
                     "router_bias"):
            if not (shortcut and name == "router_bias"):
                del blocks[name]
    if shortcut:
        # the router is a float32 leaf whatever the activations' type
        # (a key of its own: the leaves above keep their draws)
        blocks["router"] = jax.random.normal(
            jax.random.fold_in(rng, 2), (Ls, D, Et),
            jnp.float32) / np.sqrt(D)
        # the choice bias at the size of ITS scores' spread: a softmax
        # over Et outputs of unit-variance logits spreads 1.3 / Et
        # where the sigmoid the draw above was sized for spreads 0.21;
        # left at 0.05 it would choose the same k experts for every
        # token
        blocks["router_bias"] = blocks["router_bias"] * (6.0 / Et)
    if not Fs:
        for name in ("ws_gate", "ws_up", "ws_down"):
            del blocks[name]
    if Ld:
        blocks.update({
            "w_gate": mat("w_gate", (Ld, D, Fd), D),
            "w_up": mat("w_up", (Ld, D, Fd), D),
            "w_down": mat("w_down", (Ld, Fd, D), Fd),
        })
    top = {
        "embed": w((c.vocab_size, D), D),
        "blocks": blocks,
        "final_norm": near((D,), 1.0),
        "lm_head": mat("lm_head", (D, c.vocab_size), D),
    }
    if c.sliding_layers or g.gated:
        # dots3_note, drawn from keys of their own so that the leaves
        # above are what a glm_moe_dsa tree of these sizes would hold:
        # the sliding layers' stack under "swa", in ITS geometry, and a
        # head-wise gate [D, H] in every layer
        w, mat, keys = _draws(jax.random.fold_in(rng, 1), dtype, bits, 20)
        if g.gated:
            blocks["w_attn_gate"] = mat("w_attn_gate", (L, D, H), D)
    if c.sliding_layers:
        g = c.geometry(c.sliding_layers[0])
        n, Hs, Rs, Rqs = (len(c.sliding_layers), g.heads, g.kv_lora_rank,
                          g.q_lora_rank)
        fq, fkv = read_at(Rqs, g.q_scale), read_at(Rs, g.kv_scale)
        blocks["swa"] = {
            "attn_norm": near((n, D), 1.0),
            "wq_a": mat("wq_a", (n, D, Rqs), D),
            "q_a_norm": near((n, Rqs), 1.0),
            "wq_b": mat("wq_b", (n, Rqs, Hs * (g.qk_nope_head_dim
                                               + g.qk_rope_head_dim)), fq),
            "wkv_a": mat("wkv_a", (n, D, Rs + g.qk_rope_head_dim), D),
            "kv_a_norm": near((n, Rs), 1.0),
            "wkv_b_k": mat("wkv_b_k", (n, Rs, Hs * g.qk_nope_head_dim), fkv),
            "wkv_b_v": mat("wkv_b_v", (n, Rs, Hs * g.v_head_dim), fkv),
            "wo": mat("wo", (n, Hs * g.v_head_dim, D), Hs * g.v_head_dim),
            "mlp_norm": near((n, D), 1.0),
        }
        if g.gated:
            blocks["swa"]["w_attn_gate"] = mat("w_attn_gate", (n, D, Hs), D)
    return top


def _init_bailing_params(config, rng: jax.Array, dtype, bits: Optional[int]):
    """The seeded tree of a BailingHybridConfig. Leaves are stacked per
    KIND of layer: the two norms [L, D]; the KDA leaves [L_kda, ...]
    (`w_kda_in`, the fused projection with columns [q | k | v | decay |
    output gate], `w_kda_beta`, `kda_conv_w` [K, 3 * width] over q | k |
    v, `A_log` [H], `dt_bias` [width], `kda_norm` [head_dim],
    `w_kda_out`); the MLA leaves [L_mla, ...] as `_init_glm_params`
    names them, `w_attn_gate` among them; the dense FFN's, the router's,
    the experts' and the shared expert's as there. The taps, `A_log`,
    `dt_bias`, the norms, the router and its bias stay float under
    bits=8.

    `A_log` = log U(1, 16) as published for KDA. Under seeded weights
    the decay is placed, not learned: `dt_bias` puts each channel's
    half-life log-uniform in 1 .. 1,000 tokens at a zero input
    (g = lower_bound * sigmoid(exp(A_log) * dt_bias) = -ln 2 /
    half-life), and the decay's columns of `w_kda_in` are drawn 16 times
    narrower than the rest, so that exp(A_log) * (h W_f) moves the
    gate's argument by a sigma of at most one and the state neither dies
    in a token nor stops forgetting. `router_bias` (the published
    `expert_bias`) is 0, as an untrained balancer's."""
    c = config
    L, D = c.num_hidden_layers, c.hidden_size
    H, dk, W, K = (c.num_attention_heads, c.kda_head_dim, c.kda_width,
                   c.conv_kernel)
    Lk, Lm, Ls = len(c.kda_layers), len(c.latent_layers), \
        len(c.sparse_layers)
    Ld = L - Ls
    R, Rq = c.kv_lora_rank, c.q_lora_rank
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    E, Et, Fe, Fd = (c.num_local_experts, c.n_routed_experts_total,
                     c.moe_intermediate_size, c.intermediate_size)
    Fs = c.n_shared_experts * Fe
    w, mat, keys = _draws(rng, dtype, bits, 48)

    def near(shape, centre):
        return (centre + 0.1 * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    from cake_tpu.ops.quant import QTensor

    w_in = mat("w_kda_in", (Lk, D, 5 * W), D)
    narrow = jnp.where((jnp.arange(5 * W) // W) == 3, 1.0 / 16.0, 1.0)
    w_in = (QTensor(q=w_in.q, scale=w_in.scale * narrow)
            if isinstance(w_in, QTensor)
            else (w_in.astype(jnp.float32) * narrow).astype(dtype))
    A_log = jnp.log(jax.random.uniform(next(keys), (Lk, H), jnp.float32,
                                       1.0, 16.0))
    half_life = jnp.exp(jax.random.uniform(
        next(keys), (Lk, W), jnp.float32, 0.0, np.log(1000.0)))
    p = (np.log(2.0) / half_life) / -c.kda_lower_bound
    blocks = {
        "attn_norm": near((L, D), 1.0),
        "mlp_norm": near((L, D), 1.0),
        "w_kda_in": w_in,
        "w_kda_beta": mat("w_kda_beta", (Lk, D, H), D),
        "kda_conv_w": w((Lk, K, 3 * W), K),
        "A_log": A_log,
        "dt_bias": (jnp.log(p) - jnp.log1p(-p)) / jnp.repeat(
            jnp.exp(A_log), dk, axis=-1),
        "kda_norm": near((Lk, dk), 1.0),
        "w_kda_out": mat("w_kda_out", (Lk, W, D), W),
        **({"wq": mat("wq", (Lm, D, H * (dn + dr)), D)} if Rq is None else {
            "wq_a": mat("wq_a", (Lm, D, Rq), D),
            "q_a_norm": near((Lm, Rq), 1.0),
            "wq_b": mat("wq_b", (Lm, Rq, H * (dn + dr)), Rq)}),
        "wkv_a": mat("wkv_a", (Lm, D, R + dr), D),
        "kv_a_norm": near((Lm, R), 1.0),
        "wkv_b_k": mat("wkv_b_k", (Lm, R, H * dn), R),
        "wkv_b_v": mat("wkv_b_v", (Lm, R, H * dv), R),
        "wo": mat("wo", (Lm, H * dv, D), H * dv),
        "w_attn_gate": mat("w_attn_gate", (Lm, D, H), D),
        "router": w((Ls, D, Et), D),
        "router_bias": jnp.zeros((Ls, Et), jnp.float32),
        "we_gate": mat("we_gate", (Ls, E, D, Fe), D),
        "we_up": mat("we_up", (Ls, E, D, Fe), D),
        "we_down": mat("we_down", (Ls, E, Fe, D), Fe),
        "ws_gate": mat("ws_gate", (Ls, D, Fs), D),
        "ws_up": mat("ws_up", (Ls, D, Fs), D),
        "ws_down": mat("ws_down", (Ls, Fs, D), Fs),
    }
    if Ld:
        blocks.update({
            "w_gate": mat("w_gate", (Ld, D, Fd), D),
            "w_up": mat("w_up", (Ld, D, Fd), D),
            "w_down": mat("w_down", (Ld, Fd, D), Fd),
        })
    return {
        "embed": w((c.vocab_size, D), D),
        "blocks": blocks,
        "final_norm": near((D,), 1.0),
        "lm_head": mat("lm_head", (D, c.vocab_size), D),
    }


def _init_exaone_params(config, rng: jax.Array, dtype, bits: Optional[int]):
    """The seeded tree of an ExaoneMoeConfig. Both kinds of attention
    layer have the same shapes, so the attention leaves are ONE stack
    [L, ...] (q_norm / k_norm [L, head_dim]: an RMSNorm a head); the
    dense FFN's [L_dense, ...], the router's, the experts' and the
    shared expert's [L_sparse, ...] as GLM's. Norm weights are drawn
    away from 1 so that a test sees them; the choice bias is 0 (what a
    checkpoint that was never balanced holds: a test replaces it)."""
    c = config
    L, D = c.num_hidden_layers, c.hidden_size
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    Ls = len(c.sparse_layers)
    Ld = L - Ls
    E, Et, Fe, Fd = (c.num_local_experts, c.n_routed_experts_total,
                     c.moe_intermediate_size, c.intermediate_size)
    Fs = c.n_shared_experts * Fe
    w, mat, keys = _draws(rng, dtype, bits, 32)

    def near(shape, centre):
        return (centre + 0.1 * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    blocks = {
        "attn_norm": near((L, D), 1.0),
        "wq": mat("wq", (L, D, H * hd), D),
        "wk": mat("wk", (L, D, KV * hd), D),
        "wv": mat("wv", (L, D, KV * hd), D),
        "q_norm": near((L, hd), 1.0),
        "k_norm": near((L, hd), 1.0),
        "wo": mat("wo", (L, H * hd, D), H * hd),
        "mlp_norm": near((L, D), 1.0),
        "router": w((Ls, D, Et), D),
        "router_bias": jnp.zeros((Ls, Et), jnp.float32),
        "we_gate": mat("we_gate", (Ls, E, D, Fe), D),
        "we_up": mat("we_up", (Ls, E, D, Fe), D),
        "we_down": mat("we_down", (Ls, E, Fe, D), Fe),
        "ws_gate": mat("ws_gate", (Ls, D, Fs), D),
        "ws_up": mat("ws_up", (Ls, D, Fs), D),
        "ws_down": mat("ws_down", (Ls, Fs, D), Fs),
    }
    if Ld:
        blocks.update({
            "w_gate": mat("w_gate", (Ld, D, Fd), D),
            "w_up": mat("w_up", (Ld, D, Fd), D),
            "w_down": mat("w_down", (Ld, Fd, D), Fd),
        })
    return {"embed": w((c.vocab_size, D), D), "blocks": blocks,
            "final_norm": near((D,), 1.0),
            "lm_head": mat("lm_head", (D, c.vocab_size), D)}


def _init_keye_params(config, rng: jax.Array, dtype, bits: Optional[int]):
    """The seeded tree of a KeyeVL2Config: every layer alike, so every
    leaf is ONE stack [L, ...]: K-EXAONE's attention leaves (q_norm /
    k_norm [L, head_dim]: an RMSNorm a head), GLM's indexer leaves
    (`wi_q` here reads the normed hidden state: the model has no query
    latent), the router and the experts. Norm weights and the index
    key's LayerNorm are drawn away from their neutral values so that a
    test sees them."""
    c = config
    L, D = c.num_hidden_layers, c.hidden_size
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    nI, dI = c.index_n_heads, c.index_head_dim
    E, Fe = c.num_local_experts, c.moe_intermediate_size
    w, mat, keys = _draws(rng, dtype, bits, 32)

    def near(shape, centre):
        return (centre + 0.1 * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    blocks = {
        "attn_norm": near((L, D), 1.0),
        "wq": mat("wq", (L, D, H * hd), D),
        "wk": mat("wk", (L, D, KV * hd), D),
        "wv": mat("wv", (L, D, KV * hd), D),
        "q_norm": near((L, hd), 1.0),
        "k_norm": near((L, hd), 1.0),
        "wo": mat("wo", (L, H * hd, D), H * hd),
        "wi_q": mat("wi_q", (L, D, nI * dI), D),
        "wi_k": mat("wi_k", (L, D, dI), D),
        "wi_k_norm": near((L, dI), 1.0),
        "wi_k_bias": near((L, dI), 0.0),
        "wi_w": w((L, D, nI), D),
        "mlp_norm": near((L, D), 1.0),
        "router": w((L, D, E), D),
        "we_gate": mat("we_gate", (L, E, D, Fe), D),
        "we_up": mat("we_up", (L, E, D, Fe), D),
        "we_down": mat("we_down", (L, E, Fe, D), Fe),
    }
    return {"embed": w((c.vocab_size, D), D), "blocks": blocks,
            "final_norm": near((D,), 1.0),
            "lm_head": mat("lm_head", (D, c.vocab_size), D)}


def _init_nemotron_params(config, rng: jax.Array, dtype,
                          bits: Optional[int]):
    """The seeded tree of a NemotronHConfig. Leaves are stacked per KIND
    of block: `norm` [L, D] (every block has one), the Mamba leaves
    [L_M, ...], the attention leaves [L_attn, ...], the router's, the
    latent projections', the experts' and the shared expert's
    [L_E, ...]. `A_log`, `dt_bias` and `D` take the published init
    (A = U(1, 16); dt log-uniform in [time_step_min, time_step_max],
    floored at time_step_floor, through softplus's inverse; D = 1), so
    that seeded states decay over 1-1000 tokens and neither vanish nor
    blow up; they, the conv, the norms and the router's bias stay float
    under bits=8. The conv's weight is stored [K, channels] (the
    published layout is [channels, 1, K])."""
    c = config
    L, D = c.num_hidden_layers, c.hidden_size
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    Lm, La, Le = (len(c.mamba_layers), len(c.attn_layers),
                  len(c.sparse_layers))
    Hm, di, K = c.mamba_num_heads, c.d_inner, c.conv_kernel
    E, Et, R = c.num_local_experts, c.n_routed_experts_total, \
        c.moe_latent_size
    Fe, Fs = c.moe_intermediate_size, c.moe_shared_expert_intermediate_size
    w, mat, keys = _draws(rng, dtype, bits, 40)

    def near(shape, centre, spread=0.1):
        return (centre + spread * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    dt = jnp.exp(jax.random.uniform(
        next(keys), (Lm, Hm), jnp.float32, np.log(c.time_step_min),
        np.log(c.time_step_max)))
    dt = jnp.maximum(dt, c.time_step_floor)
    blocks = {
        "norm": near((L, D), 1.0),
        "w_in": mat("w_in", (Lm, D, c.in_proj_dim), D),
        "conv_w": w((Lm, K, c.conv_dim), K),
        "conv_b": near((Lm, c.conv_dim), 0.0),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(
            next(keys), (Lm, Hm), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((Lm, Hm), jnp.float32),
        "ssm_norm": near((Lm, di), 1.0),
        "w_out": mat("w_out", (Lm, di, D), di),
        "wq": mat("wq", (La, D, H * hd), D),
        "wk": mat("wk", (La, D, KV * hd), D),
        "wv": mat("wv", (La, D, KV * hd), D),
        "wo": mat("wo", (La, H * hd, D), H * hd),
        "router": w((Le, D, Et), D),
        "router_bias": 0.05 * jax.random.normal(
            next(keys), (Le, Et), jnp.float32),
        "w_fc1": mat("w_fc1", (Le, D, R), D),
        "w_fc2": mat("w_fc2", (Le, R, D), R),
        "we_up": mat("we_up", (Le, E, R, Fe), R),
        "we_down": mat("we_down", (Le, E, Fe, R), Fe),
        "ws_up": mat("ws_up", (Le, D, Fs), D),
        "ws_down": mat("ws_down", (Le, Fs, D), Fs),
    }
    return {
        "embed": w((c.vocab_size, D), D),
        "blocks": blocks,
        "final_norm": near((D,), 1.0),
        "lm_head": mat("lm_head", (D, c.vocab_size), D),
    }


def _init_zaya_params(config, rng: jax.Array, dtype, bits: Optional[int]):
    """The seeded tree of a ZayaConfig, every leaf stacked [L, ...]. The
    matmul leaves are `w_cca` (the fused projection into the latent,
    columns [q | k | v1 | v2]), `wo`, the experts and the head; the
    convolutions, the router's MLP, the norms, the keys' temperatures
    and the residual scaling stay float under bits=8. The float leaves
    are drawn away from their neutral values (residual alpha = 1 + 0.1
    N, betas 0.02 N, k_temp in U(0.5, 2), r_gamma in U(0, 1), conv
    weights N(0, 1 / fan-in), the router's bias 0.02 N), so that a
    program that drops the term fails a comparison. The head is TIED:
    the embedding transposed, quantized where the matmul leaves are."""
    from cake_tpu.ops.quant import quantize

    c = config
    L, D, F = c.num_hidden_layers, c.hidden_size, c.intermediate_size
    E, R = c.num_local_experts, c.router_hidden_size
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    Cc, Gc = c.cca_channels, H + KV
    w, mat, keys = _draws(rng, dtype, bits, 40)

    def near(shape, centre, spread=0.1):
        return (centre + spread * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo,
                                  hi).astype(dtype)

    def scaling():
        return jnp.stack([near((L, D), 1.0), near((L, D), 0.0, 0.02),
                          near((L, D), 1.0), near((L, D), 0.0, 0.02)],
                         axis=1)

    blocks = {
        "attn_norm": near((L, D), 1.0),
        "w_cca": mat("w_cca", (L, D, Cc + KV * hd), D),
        "conv0_w": w((L, 2, Cc), 2),
        "conv0_b": near((L, Cc), 0.0, 0.02),
        "conv1_w": w((L, Gc, 2, hd, hd), 2 * hd),
        "conv1_b": near((L, Cc), 0.0, 0.02),
        "k_temp": uniform((L, KV), 0.5, 2.0),
        "wo": mat("wo", (L, H * hd, D), H * hd),
        "res_attn": scaling(),
        "mlp_norm": near((L, D), 1.0),
        "r_dn": w((L, D, R), D),
        "r_dn_b": near((L, R), 0.0, 0.02),
        "r_gamma": uniform((L, R), 0.0, 1.0),
        "r_norm": near((L, R), 1.0),
        "r_w1": w((L, R, R), R),
        "r_b1": near((L, R), 0.0, 0.02),
        "r_w2": w((L, R, R), R),
        "r_b2": near((L, R), 0.0, 0.02),
        "r_w3": w((L, R, E), R),
        "router_bias": 0.02 * jax.random.normal(
            next(keys), (L, E), jnp.float32),
        "we_gate": mat("we_gate", (L, E, D, F), D),
        "we_up": mat("we_up", (L, E, D, F), D),
        "we_down": mat("we_down", (L, E, F, D), F),
        "res_moe": scaling(),
    }
    embed = w((c.vocab_size, D), D)
    if c.tie_word_embeddings:
        head = quantize(embed.T, (0,)) if bits else embed.T
    else:
        head = mat("lm_head", (D, c.vocab_size), D)
    return {"embed": embed, "blocks": blocks,
            "final_norm": near((D,), 1.0), "lm_head": head}


# Mamba-2's published init of dt, which model_type granitemoehybrid
# inherits (its config has no time_step_* keys): log-uniform in
# [min, max], floored
_GRANITE_DT = (0.001, 0.1, 1e-4)


def _init_granite_params(config, rng: jax.Array, dtype,
                         bits: Optional[int]):
    """The seeded tree of a GraniteHybridConfig. The norms and the dense
    SwiGLU (models/llama's leaves) are stacked [L, ...], the Mamba
    leaves [L_M, ...] and the attention leaves [L_attn, ...] as
    _init_nemotron_params stacks them, with the same draws of `A_log`,
    `dt_bias` (dt in _GRANITE_DT) and `D`; they, the conv and the norms
    stay float under bits=8. The head is TIED: the embedding transposed,
    quantized where the matmul leaves are (as ZAYA's)."""
    from cake_tpu.ops.quant import quantize

    c = config
    L, D, F = c.num_hidden_layers, c.hidden_size, c.shared_intermediate_size
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    Lm, La = len(c.mamba_layers), len(c.attn_layers)
    Hm, di, K = c.mamba_num_heads, c.d_inner, c.conv_kernel
    w, mat, keys = _draws(rng, dtype, bits, 24)

    def near(shape, centre, spread=0.1):
        return (centre + spread * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    lo, hi, floor = _GRANITE_DT
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        next(keys), (Lm, Hm), jnp.float32, np.log(lo), np.log(hi))), floor)
    blocks = {
        "norm": near((L, D), 1.0),
        "mlp_norm": near((L, D), 1.0),
        "w_gate": mat("w_gate", (L, D, F), D),
        "w_up": mat("w_up", (L, D, F), D),
        "w_down": mat("w_down", (L, F, D), F),
        "w_in": mat("w_in", (Lm, D, c.in_proj_dim), D),
        "conv_w": w((Lm, K, c.conv_dim), K),
        "conv_b": near((Lm, c.conv_dim), 0.0),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(
            next(keys), (Lm, Hm), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((Lm, Hm), jnp.float32),
        "ssm_norm": near((Lm, di), 1.0),
        "w_out": mat("w_out", (Lm, di, D), di),
        "wq": mat("wq", (La, D, H * hd), D),
        "wk": mat("wk", (La, D, KV * hd), D),
        "wv": mat("wv", (La, D, KV * hd), D),
        "wo": mat("wo", (La, H * hd, D), H * hd),
    }
    embed = w((c.vocab_size, D), D)
    return {"embed": embed, "blocks": blocks,
            "final_norm": near((D,), 1.0),
            "lm_head": quantize(embed.T, (0,)) if bits else embed.T}


# a retention gate's half-life at a zero input, in tokens: log-uniform
# over the contexts the cell serves (benchmarks/configs/brumby-14b-int8-
# 10of40/cell.json, `assumed` (f))
_BRUMBY_HALF_LIFE = (16.0, 4096.0)


def _init_brumby_params(config, rng: jax.Array, dtype, bits: Optional[int]):
    """The seeded tree of a BrumbyConfig: every layer alike, so every
    leaf is stacked [L, ...]. The matmul leaves are the projections, the
    SwiGLU and the untied head; the norms (1 + 0.1 N: the stream's, and
    q's and k's over head_dim, one vector a layer) and the gate stay
    float under bits=8. The gate `w_g` [L, D, KV] is float32 and drawn
    16 times narrower than the matrices, its bias `b_g` so that a K/V
    head's half-life at a zero input is log-uniform in
    _BRUMBY_HALF_LIFE: gamma = 2^(-1 / half-life), b_g = logit(gamma).
    Under weights of variance 1 / fan-in the gate's input term is then a
    few hundredths of b_g's spread: a state neither dies inside a window
    nor holds a whole context at full weight."""
    c = config
    L, D, F = c.num_hidden_layers, c.hidden_size, c.intermediate_size
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    w, mat, keys = _draws(rng, dtype, bits, 24)

    def near(shape, centre, spread=0.1):
        return (centre + spread * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    lo, hi = _BRUMBY_HALF_LIFE
    half_life = jnp.exp(jax.random.uniform(
        next(keys), (L, KV), jnp.float32, np.log(lo), np.log(hi)))
    gamma = jnp.exp2(-1.0 / half_life)
    blocks = {
        "norm": near((L, D), 1.0),
        "wq": mat("wq", (L, D, H * hd), D),
        "wk": mat("wk", (L, D, KV * hd), D),
        "wv": mat("wv", (L, D, KV * hd), D),
        "q_norm": near((L, hd), 1.0),
        "k_norm": near((L, hd), 1.0),
        "w_g": jax.random.normal(next(keys), (L, D, KV), jnp.float32)
        / (16.0 * np.sqrt(D)),
        "b_g": jnp.log(gamma) - jnp.log1p(-gamma),
        "wo": mat("wo", (L, H * hd, D), H * hd),
        "mlp_norm": near((L, D), 1.0),
        "w_gate": mat("w_gate", (L, D, F), D),
        "w_up": mat("w_up", (L, D, F), D),
        "w_down": mat("w_down", (L, F, D), F),
    }
    return {"embed": w((c.vocab_size, D), D), "blocks": blocks,
            "final_norm": near((D,), 1.0),
            "lm_head": mat("lm_head", (D, c.vocab_size), D)}


def init_params(config: MoEConfig, rng: jax.Array, dtype=jnp.bfloat16,
                bits: Optional[int] = None):
    """Random-init MoE parameter pytree (tests, benchmarks, a model
    directory with no weights).

    bits=8 draws the matmul leaves (attention, experts, head) as int8
    per-channel QTensors directly, as `quantize_params(bits=8)` would
    leave them: a full-precision copy of a published-width model never
    exists (OLMoE-1B-7B is 13.8 GB in bf16, 6.9 GB as drawn here). Run
    under jit (`init_params_jit`) each leaf's draw-and-cast fuses, so
    the program holds the tree and nothing beside it. Norm weights are
    drawn around 1 where the family has query/key norm, so that a test
    sees them."""
    if getattr(config, "kda_layers", None):
        return _init_bailing_params(config, rng, dtype, bits)
    if config.hf_layout == "exaone_moe":
        return _init_exaone_params(config, rng, dtype, bits)
    if config.hf_layout == "KeyeVL2":
        return _init_keye_params(config, rng, dtype, bits)
    if getattr(config, "kv_lora_rank", None):
        return _init_glm_params(config, rng, dtype, bits)
    if config.hf_layout == "granitemoehybrid":
        return _init_granite_params(config, rng, dtype, bits)
    if config.hf_layout == "brumby":
        return _init_brumby_params(config, rng, dtype, bits)
    if getattr(config, "mamba_layers", None):
        return _init_nemotron_params(config, rng, dtype, bits)
    if getattr(config, "cca_time0", None):
        return _init_zaya_params(config, rng, dtype, bits)
    c = config
    L, D, F = c.num_hidden_layers, c.hidden_size, c.intermediate_size
    E = c.num_local_experts
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    w, mat, keys = _draws(rng, dtype, bits, 16)

    blocks = {
        "attn_norm": jnp.ones((L, D), dtype),
        "wq": mat("wq", (L, D, H * hd), D),
        "wk": mat("wk", (L, D, KV * hd), D),
        "wv": mat("wv", (L, D, KV * hd), D),
        "wo": mat("wo", (L, H * hd, D), H * hd),
        "mlp_norm": jnp.ones((L, D), dtype),
        "router": w((L, D, E), D),
        "we_gate": mat("we_gate", (L, E, D, F), D),
        "we_up": mat("we_up", (L, E, D, F), D),
        "we_down": mat("we_down", (L, E, F, D), F),
    }
    if c.qk_norm:
        blocks["q_norm"] = (1.0 + 0.1 * jax.random.normal(
            next(keys), (L, H * hd), jnp.float32)).astype(dtype)
        blocks["k_norm"] = (1.0 + 0.1 * jax.random.normal(
            next(keys), (L, KV * hd), jnp.float32)).astype(dtype)
    params = {
        "embed": w((c.vocab_size, D), D),
        "blocks": blocks,
        "final_norm": jnp.ones((D,), dtype),
    }
    params["lm_head"] = (params["embed"].T if c.tie_word_embeddings
                         else mat("lm_head", (D, c.vocab_size), D))
    return params


init_params_jit = jax.jit(init_params,
                          static_argnames=("config", "dtype", "bits"))


def hf_layout(config: MoEConfig):
    """(per-layer {leaf: (HF suffix, transpose)}, expert name
    ((leaf, HF expert tensor) x 3), HF prefix of the experts) for the
    config's family; shared by the eager and streaming loaders so their
    trees cannot structurally diverge."""
    if config.hf_layout == "zaya":
        raise NotImplementedError(
            "model_type zaya: this program does not know the published "
            "checkpoint's tensor names (the fused CCA projection, the "
            "convolutions, the router's MLP and the residual scaling "
            "have no counterpart it can name); it serves the family "
            "from seeded weights only")
    if config.hf_layout == "KeyeVL2":
        raise NotImplementedError(
            "model_type KeyeVL2: the published checkpoint is not in this "
            "repository and its tensor names are not guessed (the "
            "indexer's projections and the per-head q / k norms among "
            "them); it is served from seeded weights only")
    if config.hf_layout == "exaone_moe":
        raise NotImplementedError(
            "model_type exaone_moe: the published checkpoint is not in "
            "this repository and its tensor names are not guessed (the "
            "choice bias and the per-head q / k norms among them); it "
            "is served from seeded weights only")
    if config.hf_layout == "longcat_flash":
        raise NotImplementedError(
            "model_type longcat_flash: the published checkpoint is not in "
            "this repository and its tensor names are not guessed (the "
            "two attentions and FFNs a layer, the router's classifier "
            "and its bias among them); it is served from seeded weights "
            "only")
    if config.hf_layout == "brumby":
        raise NotImplementedError(
            "model_type brumby: the published checkpoint is not in this "
            "repository and its tensor names are not guessed (the "
            "retention gate's projection and the per-head q / k norms "
            "among them); it is served from seeded weights only")
    attn = {
        "attn_norm": ("input_layernorm.weight", False),
        "wq": ("self_attn.q_proj.weight", True),
        "wk": ("self_attn.k_proj.weight", True),
        "wv": ("self_attn.v_proj.weight", True),
        "wo": ("self_attn.o_proj.weight", True),
        "mlp_norm": ("post_attention_layernorm.weight", False),
    }
    if config.hf_layout == "olmoe":
        prefix = "mlp"
        experts = (("we_gate", "gate_proj"), ("we_up", "up_proj"),
                   ("we_down", "down_proj"))
    else:                           # Mixtral: w1=gate, w3=up, w2=down
        prefix = "block_sparse_moe"
        experts = (("we_gate", "w1"), ("we_up", "w3"), ("we_down", "w2"))
    attn["router"] = (f"{prefix}.gate.weight", True)
    if config.qk_norm:
        attn["q_norm"] = ("self_attn.q_norm.weight", False)
        attn["k_norm"] = ("self_attn.k_norm.weight", False)
    return attn, experts, prefix




def load_params_from_hf(model_dir: str, config: MoEConfig,
                        dtype=jnp.bfloat16,
                        layer_range: Optional[range] = None,
                        finish=None):
    """Build the MoE pytree from HF Mixtral or OLMoE safetensors (the
    config says which names). finish: (leaf
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

    attn, expert_names, moe = hf_layout(c)
    needed = {"model.embed_tokens.weight", "model.norm.weight"}
    if not c.tie_word_embeddings:
        needed.add("lm_head.weight")
    for i in layers:
        for suffix, _t in attn.values():
            needed.add(f"model.layers.{i}.{suffix}")
        for e in range(E):
            for _leaf, wn in expert_names:
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
    # Experts: HF gate and up [F, D] (-> [D, F]); down [D, F] (-> [F, D]).
    for key, wn in expert_names:
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
    """Stream HF Mixtral or OLMoE safetensors directly onto mesh shards — the MoE
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
    attn, expert_names, moe = hf_layout(c)

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
        for key, (suffix, tr) in attn.items()}
    for key, wn in expert_names:
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
                stage_axis: Optional[str] = None,
                config: Optional[MoEConfig] = None):
    """PartitionSpec pytree: experts over ep, Megatron F-dim over tp.

    Under plain jit + NamedSharding the annotation places the weights;
    the grouped matmul (ops/moe.py) is one custom call, so XLA gathers
    its operands — the partitioned form is the shard_map one (`ep_axis`).
    The router stays replicated (it is [D, E]-tiny).
    """
    from cake_tpu.models.llama.params import block_param_keys, block_specs
    return {
        "embed": P(tp_axis, None),
        "blocks": block_specs(block_param_keys(config, moe=True),
                              stage_axis=stage_axis, tp_axis=tp_axis,
                              ep_axis=ep_axis),
        "final_norm": P(None),
        "lm_head": P(None, tp_axis),
    }
