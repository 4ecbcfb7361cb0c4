"""Plain float32 reference of the DeepSeek-V2 decoder (`model_type:
deepseek_v2`): latent attention (MLA) over every visible key, YaRN on
the rope part, group-limited softmax routing with two shared experts.

Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, a Python loop over layers and over experts, whole-sequence
attention computed in blocks of queries (so that 5k positions at 128
heads fit); no cache, no kernels, no batching, and no import from
`cake_tpu.ops` or `cake_tpu.models.llama`. The keys and values are
up-projected from the latent, per head, as published; the served path
absorbs the up-projection into the query and the output instead, which
is the same mathematics.

One layer, on x [S, D] (`rms` with `rms_norm_eps`; RoPE on interleaved
pairs):

    h      = rms(x, attn_norm)
    c_q    = rms(h W_qa, q_a_norm)                       [q_lora_rank]
    q      = c_q W_qb -> H heads x [q_nope | q_pe];  q_pe = rope(q_pe)
    [c_kv | k_pe] = h W_kva                              [kv_lora_rank | rope]
    c_kv   = rms(c_kv, kv_a_norm);  k_pe = rope(k_pe)    one rope key for all heads
    k_nope = c_kv W_kvb^K,  v = c_kv W_kvb^V             per head
    a[t]   = softmax_{s <= t}((q_nope.k_nope[s] + q_pe.k_pe[s]) * scale) v[s]
    x      = x + concat_heads(a) W_o
    h      = rms(x, mlp_norm)
    dense layer:   x = x + W_down(silu(W_gate h) * W_up h)
    sparse layer:  s = softmax(h W_r)                     float32, all experts
                   g = max of s over each of n_group groups of neighbours
                   G = the topk_group groups of largest g, ties to the lower index
                   s' = s inside G, 0 outside
                   chosen = the num_experts_per_tok largest of s', ties to the lower index
                   w = s[chosen] (/ their sum if norm_topk_prob) * routed_scaling_factor
                   x = x + sum_i w_i E_chosen_i(h) + E_shared(h)

then logits = rms(x, final_norm) W_head. The n_shared_experts shared
experts are ONE SwiGLU of their summed width (a sum of SwiGLUs over
disjoint columns is that).

YaRN (`rope_scaling`: factor, original_max_position_embeddings,
beta_fast, beta_slow, mscale, mscale_all_dim; dim = qk_rope_head_dim):

    f_i    = theta^(-2i/dim)                              i = 0 .. dim/2 - 1
    c(n)   = dim ln(original / (2 pi n)) / (2 ln theta)
    low    = max(floor(c(beta_fast)), 0);  high = min(ceil(c(beta_slow)), dim - 1)
    r_i    = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = (f_i / factor) r_i + f_i (1 - r_i)
    m(s, a) = 0.1 a ln s + 1
    cos, sin times m(factor, mscale) / m(factor, mscale_all_dim)
    scale  = (qk_nope_head_dim + qk_rope_head_dim)^-0.5 * m(factor, mscale_all_dim)^2

THE SHARE. `held = (first, count)` gives the reference one chip's share
of a layer's routed experts: the router keeps its published width, its
groups and its k, the experts `first .. first+count-1` are computed for
the tokens routed to them, and what the absent experts would add is
left out, as the served path leaves it out (`we_*` hold the `count`
held experts). `shared=False` leaves the shared experts out, for the
test that adds the shares up.

Weights are INPUTS, stored [in, out] (x @ W): a caller comparing an
int8-served model passes the dequantized weights.

params: {"embed", "final_norm", "lm_head", "layers": per-layer dicts}.
config: a mapping with `num_attention_heads`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim`, `rms_norm_eps`, `rope_theta`,
`rope_scaling` (a mapping of the keys above, or None), `n_group`,
`topk_group`, `num_experts_per_tok`, `norm_topk_prob`,
`routed_scaling_factor`; and a tool's switches, each of which must fail
its comparison: `softmax_dtype` ("bfloat16": scores and probabilities
rounded), `mscale_in_scale` (False: the scale without m^2), `yarn`
(False: plain RoPE frequencies), `group_limited` (False: the top k over
all experts).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256
NEG = -1e30


def mm(x, w):
    """An activation times a weight."""
    return x @ w


def rms(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def magnitude(scale: float, a: float) -> float:
    """YaRN's m(s, a)."""
    return 1.0 if scale <= 1 else 0.1 * a * math.log(scale) + 1.0


def inv_freq(dim: int, theta: float, scaling) -> np.ndarray:
    """The dim/2 frequencies, float64: theta^(-2i/dim), blended by YaRN
    where `scaling` is given."""
    f = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if not scaling:
        return f

    def c(n):
        return (dim * math.log(scaling["original_max_position_embeddings"]
                               / (n * 2 * math.pi)) / (2 * math.log(theta)))

    low = max(math.floor(c(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(c(scaling.get("beta_slow", 1))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high - low) or 0.001),
                   0.0, 1.0)
    return (f / scaling["factor"]) * ramp + f * (1.0 - ramp)


def rope(x, positions, config):
    """x [S, ..., d], d even: the pairs (x[2i], x[2i+1]) are rotated by
    position * inv_freq_i (the interleaved form)."""
    scaling = config.get("rope_scaling") if config.get("yarn", True) else None
    freq = inv_freq(x.shape[-1], config["rope_theta"], scaling)
    ang = np.asarray(positions, np.float64)[:, None] * freq[None, :]
    factor = 1.0
    if scaling:
        factor = (magnitude(scaling["factor"], scaling.get("mscale", 1))
                  / magnitude(scaling["factor"],
                              scaling.get("mscale_all_dim", 0)))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1] // 2,)
    cos = jnp.asarray(np.cos(ang) * factor, F32).reshape(shape)
    sin = jnp.asarray(np.sin(ang) * factor, F32).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def softmax_scale(config) -> float:
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    scaling = config.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim") and config.get(
            "mscale_in_scale", True):
        scale *= magnitude(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def attend_block(q_nope, q_pe, k_nope, k_pe, v, lo, scale: float,
                 dtype=F32):
    """Queries lo .. lo + T - 1 over every key s <= t (all S keys
    scored, the invisible ones masked: one shape a sequence). dtype:
    what the scores and probabilities are held in (a tool's switch)."""
    T, S = q_nope.shape[0], k_nope.shape[0]
    scores = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
              + jnp.einsum("thd,sd->hts", q_pe, k_pe)) * scale
    mask = jnp.arange(S)[None, :] <= (lo + jnp.arange(T))[:, None]
    scores = jnp.where(mask[None], scores.astype(dtype).astype(F32), NEG)
    probs = jax.nn.softmax(scores.astype(dtype), axis=-1).astype(F32)
    return jnp.einsum("hts,shd->thd", probs, v)


def attention(lp, h, config):
    """MLA over every visible key -> the attention's output [S, D]
    before the residual."""
    S = h.shape[0]
    H = config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    eps = config["rms_norm_eps"]
    pos = np.arange(S)
    c_q = rms(mm(h, lp["wq_a"]), lp["q_a_norm"], eps)
    q = mm(c_q, lp["wq_b"]).reshape(S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, config)
    kva = mm(h, lp["wkv_a"])
    r = kva.shape[-1] - dr
    c_kv = rms(kva[:, :r], lp["kv_a_norm"], eps)
    k_pe = rope(kva[:, r:], pos, config)                         # [S, dr]
    k_nope = mm(c_kv, lp["wkv_b_k"]).reshape(S, H, dn)
    v = mm(c_kv, lp["wkv_b_v"]).reshape(S, H, dv)
    scale = softmax_scale(config)
    dtype = jnp.dtype(config.get("softmax_dtype", "float32"))
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(S, lo + QUERY_BLOCK)
        out.append(attend_block(q_nope[lo:hi], q_pe[lo:hi], k_nope, k_pe,
                                v, lo, scale, dtype))
    return mm(jnp.concatenate(out, 0).reshape(S, H * dv), lp["wo"])


def top_k_stable(scores, k: int):
    """The indices of the k largest of each row, best first, ties to the
    lower index."""
    return jnp.argsort(-scores, axis=-1, stable=True)[:, :k]


def router(lp, h, config, forced=None):
    """(weights [S, k], the experts computed [S, k], this router's own
    choice [S, k], its groups [S, topk_group]) as published, over ALL
    experts of the router's width. forced: experts [S, k] to compute
    instead of the router's choice, weighed by THIS router's scores of
    them (teacher-forced routing: a tool compares along another path's
    trajectory, so that one flipped choice does not move every later
    layer)."""
    k = config["num_experts_per_tok"]
    scores = jax.nn.softmax(mm(h, lp["router"]), axis=-1)
    S, E = scores.shape
    G = config.get("n_group", 1)
    groups = None
    limited = scores
    if G > 1 and config.get("group_limited", True):
        best = jnp.max(scores.reshape(S, G, E // G), axis=-1)
        groups = top_k_stable(best, config["topk_group"])
        taken = jnp.any((jnp.arange(E) // (E // G))[None, None, :]
                        == groups[:, :, None], axis=1)
        limited = jnp.where(taken, scores, 0.0)
    order = top_k_stable(limited, k)
    chosen = order if forced is None else jnp.asarray(forced)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if config.get("norm_topk_prob", False):
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return (weights * config.get("routed_scaling_factor", 1.0), chosen,
            order, groups)


def swiglu(h, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def moe_ffn(lp, h, config, held=None, shared=True, routing=None,
            forced=None):
    """The held experts on the tokens routed to them (by a weight of
    zero elsewhere), plus the shared experts. routing receives the
    router's OWN choice, whatever `forced` made it compute."""
    weights, experts, own, _ = router(lp, h, config, forced)
    if routing is not None:
        routing.append(np.asarray(own))
    n_held = lp["we_gate"].shape[0]
    first = 0 if held is None else held[0]
    out = jnp.zeros_like(h)
    for e in range(n_held):
        if not bool(jnp.any(experts == first + e)):
            continue
        w = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=1)
        out = out + w[:, None] * swiglu(h, lp["we_gate"][e], lp["we_up"][e],
                                        lp["we_down"][e])
    if shared and "ws_gate" in lp:
        out = out + swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out


def layer(lp, x, config, held=None, shared=True, routing=None, forced=None):
    eps = config["rms_norm_eps"]
    x = x + attention(lp, rms(x, lp["attn_norm"], eps), config)
    h = rms(x, lp["mlp_norm"], eps)
    if "router" in lp:
        return x + moe_ffn(lp, h, config, held, shared, routing, forced)
    return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


def forward(params, sequences, config, layers=None, held=None,
            routing=None, forced=None):
    """sequences: a list of token arrays [S_i] -> a list of logits
    [S_i, V] float32, every position (one array in, one array out).

    layers: an iterable of per-layer dicts to walk instead of
    params["layers"] (a generator lets a caller hold one layer's float32
    weights at a time). held: (first, count) of the routed experts the
    `we_*` leaves hold. routing: a list of one list per sequence, which
    receive each sparse layer's expert indices [S_i, k] (the router's
    own choice). forced: one list per sequence of each sparse layer's
    experts [S_i, k] to compute instead of the routers' choices."""
    single = not isinstance(sequences, (list, tuple))
    if single:
        sequences = [sequences]
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], F32)
        xs = [embed[np.asarray(tokens)] for tokens in sequences]
        sparse = 0
        for lp in (layers if layers is not None else params["layers"]):
            lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
            for i, x in enumerate(xs):
                xs[i] = layer(
                    lp, x, config, held=held,
                    routing=None if routing is None else routing[i],
                    forced=(forced[i][sparse]
                            if forced is not None and "router" in lp
                            else None))
            sparse += "router" in lp
        norm = jnp.asarray(params["final_norm"], F32)
        head = jnp.asarray(params["lm_head"], F32)
        out = [mm(rms(x, norm, config["rms_norm_eps"]), head) for x in xs]
    return out[0] if single else out
