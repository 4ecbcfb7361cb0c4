"""Plain float32 reference of the decoder block this repository serves,
with OLMoE's sparse-expert FFN or the dense SwiGLU FFN.

Follows HF `modeling_olmoe.py` (OlmoeForCausalLM) equation for equation.
Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, whole-sequence causal attention, a Python loop over layers
and over experts (each expert on the tokens routed to it, by boolean
mask); no cache, no kernels, no batching, and no import from
`cake_tpu.ops` or `cake_tpu.models.llama`.

One layer, on x [S, D]:

    h = rms(x, attn_norm)
    q = h Wq;  k = h Wk;  v = h Wv                     (+ bq, bk, bv if given)
    q = rms(q, q_norm);  k = rms(k, k_norm)            if the model has them:
                                                       over the WHOLE H*hd-wide
                                                       projection, before the
                                                       split into heads
    q, k = rope(q), rope(k)                            half rotation, theta
    a = softmax(q k^T / sqrt(hd) + causal) v           per head; KV heads
                                                       repeated to H
    x = x + a Wo
    h = rms(x, mlp_norm)
    sparse:  p = softmax_f32(h Wr) over ALL experts
             (p_1..p_k, e_1..e_k) = the k largest p; divided by their sum
             only if norm_topk_prob
             x = x + sum_k p_k * W_down[e_k] (silu(W_gate[e_k] h) * W_up[e_k] h)
    dense:   x = x + W_down (silu(W_gate h) * W_up h)

then logits = rms(x, final_norm) W_head.

Departures from modeling_olmoe.py: none in the mathematics. `forward`
takes a list of sequences and walks them one after another through each
layer (so that a caller may hold one layer's float32 weights at a time);
every product with a weight goes through `mm`, which a tool replaces to
read what a lower precision would give; an expert's mask is applied as a
weight of zero (static shapes: nothing compiles anew for every count).
Weights are
INPUTS: a caller comparing an int8-served model passes the dequantized
weights (`q * scale`), so the comparison measures the served path's
arithmetic and not the rounding of the weights. `clip_qkv` is null in
the published config and is not implemented. Weights are stored [in,
out] (x @ W), as this repository's trees are.

params: {"embed" [V, D], "final_norm" [D], "lm_head" [D, V],
"layers": a list of per-layer dicts of float arrays} — `layers_of` builds
the list from a stacked `blocks` tree. config: a mapping with
`num_attention_heads`, `num_key_value_heads`, `rms_norm_eps`,
`rope_theta`, and for the sparse FFN `num_experts_per_tok`,
`norm_topk_prob`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def mm(x, w):
    """An activation times a weight."""
    return x @ w


def rms(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, positions, theta):
    """x [S, heads, hd]; HF rotate_half: the two halves of a head are
    the rotated pair."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = jnp.asarray(np.asarray(positions, np.float64)[:, None]
                      * inv_freq[None, :], F32)               # [S, hd/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(lp, h, config):
    S = h.shape[0]
    H = config["num_attention_heads"]
    KV = config["num_key_value_heads"]
    eps = config["rms_norm_eps"]
    q, k, v = mm(h, lp["wq"]), mm(h, lp["wk"]), mm(h, lp["wv"])
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    if "q_norm" in lp:
        q, k = rms(q, lp["q_norm"], eps), rms(k, lp["k_norm"], eps)
    hd = q.shape[-1] // H
    pos = np.arange(S)
    q = rope(q.reshape(S, H, hd), pos, config["rope_theta"])
    k = rope(k.reshape(S, KV, hd), pos, config["rope_theta"])
    v = v.reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(hd)
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hst,thd->shd", probs, v).reshape(S, H * hd)
    return mm(out, lp["wo"])


def router(lp, h, config):
    """(weights [S, k], experts [S, k]) as published."""
    k = config["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(h, lp["router"]), axis=-1)
    order = jnp.argsort(-probs, axis=-1)[:, :k]
    weights = jnp.take_along_axis(probs, order, axis=-1)
    if config.get("norm_topk_prob", False):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, order


def swiglu(h, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def moe_ffn(lp, h, config, routing=None):
    """Each expert on the tokens routed to it, by boolean mask: a token
    an expert was not chosen for has weight zero there.
    routing: a list that receives this layer's expert indices [S, k]."""
    weights, experts = router(lp, h, config)
    if routing is not None:
        routing.append(np.asarray(experts))
    out = jnp.zeros_like(h)
    for e in range(lp["we_gate"].shape[0]):
        if not bool(jnp.any(experts == e)):
            continue
        w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)   # [S]
        out = out + w[:, None] * swiglu(h, lp["we_gate"][e], lp["we_up"][e],
                                        lp["we_down"][e])
    return out


def layer(lp, x, config, routing=None):
    eps = config["rms_norm_eps"]
    x = x + attention(lp, rms(x, lp["attn_norm"], eps), config)
    h = rms(x, lp["mlp_norm"], eps)
    if "router" in lp:
        return x + moe_ffn(lp, h, config, routing)
    return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


def layers_of(blocks):
    """A stacked `blocks` tree ({leaf: [L, ...]}) as the list of
    per-layer float32 dicts `forward` walks."""
    L = next(iter(blocks.values())).shape[0]
    return [{k: jnp.asarray(v[i], F32) for k, v in blocks.items()}
            for i in range(L)]


def forward(params, sequences, config, layers=None, routing=None):
    """sequences: a list of token arrays [S_i] -> a list of logits
    [S_i, V] float32, every position (one array in, one array out).

    layers: an iterable of per-layer dicts to walk instead of
    params["layers"] (a generator lets a caller hold one layer's float32
    weights at a time). routing: a list of one list per sequence, which
    receives each sparse layer's expert indices [S_i, k]."""
    single = not isinstance(sequences, (list, tuple))
    if single:
        sequences = [sequences]
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], F32)
        xs = [embed[np.asarray(tokens)] for tokens in sequences]
        for lp in (layers if layers is not None else params["layers"]):
            lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
            xs = [layer(lp, x, config,
                        None if routing is None else routing[i])
                  for i, x in enumerate(xs)]
        norm = jnp.asarray(params["final_norm"], F32)
        head = jnp.asarray(params["lm_head"], F32)
        out = [mm(rms(x, norm, config["rms_norm_eps"]), head) for x in xs]
    return out[0] if single else out
