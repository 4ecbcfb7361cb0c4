"""Plain float32 reference of the dots3-note decoder (`model_type:
dots3_note`): two kinds of latent attention layer in one model. A FULL
layer is MLA over the keys its own learned sparse indexer (DSA) selects;
a SLIDING layer is latent attention with a geometry of its own (heads,
ranks, head dims, theta) over the last `sliding_window_size` keys. Both
gate each head's output and rescale the two normed latents. Layer 0's
FFN is dense; the others route sigmoid-scored experts beside a shared
one.

Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, a Python loop over layers and over experts, whole-sequence
attention computed in blocks of queries (so that 16.6k positions fit);
no cache, no ring, no kernels, no batching, and no import from
`cake_tpu.ops` or `cake_tpu.models.llama`. The keys and values are
up-projected from the latent, per head, as published; the served path
absorbs the up-projection into the query and the output instead, which
is the same mathematics.

One layer, on x [S, D] (`rms` with `rms_norm_eps`, RoPE on interleaved
pairs; per kind of layer H heads, ranks Rq and R, head dims dn, dr, dv
and theta: the plain keys for a full layer, the `swa_` keys for a
sliding one):

    h      = rms(x, attn_norm)
    c_q    = rms(h W_qa, q_a_norm) * sqrt(D / Rq)
    q      = c_q W_qb -> H heads x [q_nope dn | q_pe dr];  q_pe = rope(q_pe)
    [c_kv | k_pe] = h W_kva
    c_kv   = rms(c_kv, kv_a_norm) * sqrt(D / R);  k_pe = rope(k_pe)
    k_nope = c_kv W_kvb^K,  v = c_kv W_kvb^V                 per head
    full layer:
      qI   = c_q WI_q -> index_n_heads x index_head_dim, rope on the first
             qk_rope_head_dim of each head
      kI   = layernorm(h WI_k) (weight and bias, eps 1e-6), rope on its
             first qk_rope_head_dim
      w    = (h WI_w) * index_n_heads^-0.5 * index_head_dim^-0.5
      I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s]),   s <= t
      S_t  = the index_topk largest of I[t, 0..t], ties to the lower
             index (all of them while t < index_topk)
    sliding layer:
      S_t  = { s : t - (sliding_window_size - 1) <= s <= t }
    a[t]   = softmax_{s in S_t}((q_nope.k_nope[s] + q_pe.k_pe[s]) / sqrt(dn + dr)) v[s]
    g      = sigmoid(h W_g)  [H];   a[t, head] = a[t, head] * g[t, head]
    x      = x + concat_heads(a) W_o
    h      = rms(x, mlp_norm)
    dense layer:   x = x + W_down(silu(W_gate h) * W_up h)
    sparse layer:  s = sigmoid(h W_r)                     float32, all experts
                   chosen = top-k of (s + router_bias)
                   g = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
                   x = x + sum_i g_i E_chosen_i(h) + E_shared(h)

then logits = rms(x, final_norm) W_head.

THE SHARE. `held = (first, count)` gives the reference one chip's share
of a layer's routed experts: the router keeps its published width and
its k, the experts `first .. first+count-1` are computed for the tokens
routed to them, and what the absent experts would add is left out, as
the served path leaves it out (`we_*` hold the `count` held experts).
`shared=False` leaves the shared expert out, for the test that adds the
shares up.

ASSUMED, where the published config names a detail and does not define
it (each also in the cell's `cell.json`): (a) the head-wise gate is the
head-wise variant of "Gated Attention for Large Language Models"
(arXiv:2505.06708): one sigmoid a head from the layer's normed input,
on the head's output before W_o, a [D, H] matrix without bias; (b)
`apply_mla_qkv_lora_rescale` is LongCat-Flash's `mla_scale_q_lora` /
`mla_scale_kv_lora`: the two normed latents times sqrt(D / rank), k_pe
unscaled; (c) `sliding_window_size` counts the query itself; (d) the
indexer is the DeepSeek-V3.2 indexer (LayerNorm with bias on the key,
the scale of w, ties to the lower index), in full layers only; (e) RoPE
on interleaved pairs as in the DeepSeek family. Weights are INPUTS,
stored [in, out] (x @ W): a caller comparing an int8-served model
passes the dequantized weights.

params: {"embed", "final_norm", "lm_head", "layers": per-layer dicts}.
config: a mapping with `hidden_size`, `rms_norm_eps`, `layer_types`
(one of "full" / "sliding" a layer), `sliding_window_size`, the full
layers' `num_attention_heads`, `qk_nope_head_dim`, `qk_rope_head_dim`,
`v_head_dim`, `rope_theta` and the sliding layers' under the same names
with `swa_` in front, `index_n_heads`, `index_head_dim`, `index_topk`,
`num_experts_per_tok`, `norm_topk_prob`, `routed_scaling_factor`, and
optionally `scoring_func` ("sigmoid") and a tool's switches, each of
which must FAIL the comparison: `dense_attention` (full layers attend
every visible key), `gate` False (no head-wise gate), `rescale` False
(latents as normed); a window off by one is `sliding_window_size` +- 1.
"""

from __future__ import annotations

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


def layernorm(x, weight, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def rope(x, positions, theta):
    """x [S, ..., d], d even: the pairs (x[2i], x[2i+1]) are rotated by
    position * theta^(-2i/d) (the interleaved form)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.asarray(positions, np.float64)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = jnp.asarray(np.cos(ang), F32).reshape(shape)
    sin = jnp.asarray(np.sin(ang), F32).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def rope_head(x, positions, theta, n_rope):
    """RoPE on the first n_rope dims of the last axis, the rest as is."""
    return jnp.concatenate(
        [rope(x[..., :n_rope], positions, theta), x[..., n_rope:]], -1)


def geometry(config, kind: str) -> dict:
    """The sizes of one kind of layer: the plain keys (full) or the
    `swa_` keys (sliding)."""
    pre = "swa_" if kind == "sliding" else ""
    return {k: config[pre + k] for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rope_theta")}


def index_block(qI, kI, w):
    """I[t, s] for a block of queries: qI [T, J, d], kI [S, d], w [T, J]
    -> [T, S] float32."""
    dots = jnp.einsum("tjd,sd->tjs", qI, kI)
    return jnp.einsum("tjs,tj->ts", jax.nn.relu(dots), w)


def select_block(scores, lo, topk: int):
    """The key sets of the queries at positions lo .. lo + T - 1 as a
    mask [T, S]: row t holds the topk largest of scores[t, 0 .. lo + t],
    ties to the lower index; every visible key while fewer are visible."""
    T, S = scores.shape
    causal = jnp.arange(S)[None, :] <= (lo + jnp.arange(T))[:, None]
    if S <= topk:
        return causal
    masked = jnp.where(causal, scores, -jnp.inf)
    # a stable sort of the negated scores: equal scores keep index order
    order = jnp.argsort(-masked, axis=-1, stable=True)[:, :topk]
    picked = jnp.zeros((T, S), bool).at[
        jnp.arange(T)[:, None], order].set(True)
    return picked & causal


def attend_block(q_nope, q_pe, k_nope, k_pe, v, mask, scale):
    """A block of queries over every key under mask [T, S] -> [T, H, dv]."""
    scores = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
              + jnp.einsum("thd,sd->hts", q_pe, k_pe)) * scale
    scores = jnp.where(mask[None], scores, NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hts,shd->thd", probs, v)


def attention(lp, h, config, kind: str, selections=None):
    """One layer's attention on h [S, D] (the normed input) -> its
    output [S, D] before the residual. selections: a list that receives
    the key sets this layer attended, a mask [S, S]."""
    S, D = h.shape
    g = geometry(config, kind)
    H, dn, dr, dv = (g["num_attention_heads"], g["qk_nope_head_dim"],
                     g["qk_rope_head_dim"], g["v_head_dim"])
    eps, theta = config["rms_norm_eps"], g["rope_theta"]
    rescale = config.get("rescale", True)
    pos = np.arange(S)
    c_q = rms(mm(h, lp["wq_a"]), lp["q_a_norm"], eps)
    kva = mm(h, lp["wkv_a"])
    r = kva.shape[-1] - dr
    c_kv = rms(kva[:, :r], lp["kv_a_norm"], eps)
    if rescale:
        c_q = c_q * (D / c_q.shape[-1]) ** 0.5
        c_kv = c_kv * (D / r) ** 0.5
    q = mm(c_q, lp["wq_b"]).reshape(S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, theta)
    k_pe = rope(kva[:, r:], pos, theta)                          # [S, dr]
    k_nope = mm(c_kv, lp["wkv_b_k"]).reshape(S, H, dn)
    v = mm(c_kv, lp["wkv_b_v"]).reshape(S, H, dv)
    indexed = kind == "full" and not config.get("dense_attention")
    if indexed:
        nI, dI = config["index_n_heads"], config["index_head_dim"]
        n_rope = config["qk_rope_head_dim"]
        qI = rope_head(mm(c_q, lp["wi_q"]).reshape(S, nI, dI), pos, theta,
                       n_rope)
        kI = rope_head(layernorm(mm(h, lp["wi_k"]), lp["wi_k_norm"],
                                 lp["wi_k_bias"]), pos, theta, n_rope)
        w = mm(h, lp["wi_w"]) * (nI ** -0.5) * (dI ** -0.5)       # [S, nI]
    out, masks = [], []
    keys = jnp.arange(S)[None, :]
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(S, lo + QUERY_BLOCK)
        t = jnp.arange(lo, hi)[:, None]
        if indexed:
            mask = select_block(index_block(qI[lo:hi], kI, w[lo:hi]), lo,
                                config["index_topk"])
        elif kind == "sliding":
            mask = (keys <= t) & (keys > t - config["sliding_window_size"])
        else:
            mask = keys <= t
        if selections is not None:
            masks.append(np.asarray(mask))
        out.append(attend_block(q_nope[lo:hi], q_pe[lo:hi], k_nope, k_pe, v,
                                mask, (dn + dr) ** -0.5))
    a = jnp.concatenate(out, 0)                                  # [S, H, dv]
    if config.get("gate", True) and "w_attn_gate" in lp:
        a = a * jax.nn.sigmoid(mm(h, lp["w_attn_gate"]))[..., None]
    if selections is not None:
        selections.append(np.concatenate(masks, 0))
    return mm(a.reshape(S, H * dv), lp["wo"])


def router(lp, h, config, forced=None):
    """(weights [S, k], the experts computed [S, k], this router's own
    choice [S, k]) as published, over ALL experts of the router's width.
    forced: experts [S, k] to compute instead of the router's choice,
    weighed by THIS router's scores of them (teacher-forced routing: a
    tool compares along another path's trajectory, so that one flipped
    choice does not move every later layer)."""
    k = config["num_experts_per_tok"]
    logits = mm(h, lp["router"])
    if config.get("scoring_func", "sigmoid") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + lp.get("router_bias", 0.0)
    order = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]
    chosen = order if forced is None else jnp.asarray(forced)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * config.get("routed_scaling_factor", 1.0), chosen, order


def swiglu(h, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def moe_ffn(lp, h, config, held=None, shared=True, routing=None,
            forced=None):
    """The held experts on the tokens routed to them (by a weight of
    zero elsewhere), plus the shared expert. routing receives the
    router's OWN choice, whatever `forced` made it compute."""
    weights, experts, own = router(lp, h, config, forced)
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


def layer(lp, x, config, kind: str, held=None, shared=True, routing=None,
          selections=None, forced=None):
    """One layer of `kind` ("full" | "sliding")."""
    eps = config["rms_norm_eps"]
    h = rms(x, lp["attn_norm"], eps)
    x = x + attention(lp, h, config, kind, selections)
    h = rms(x, lp["mlp_norm"], eps)
    if "router" in lp:
        return x + moe_ffn(lp, h, config, held, shared, routing, forced)
    return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


def forward(params, sequences, config, layers=None, held=None,
            routing=None, selections=None, forced=None):
    """sequences: a list of token arrays [S_i] -> a list of logits
    [S_i, V] float32, every position (one array in, one array out).

    layers: an iterable of per-layer dicts to walk instead of
    params["layers"] (a generator lets a caller hold one layer's float32
    weights at a time), in the order of config["layer_types"]. held:
    (first, count) of the routed experts the `we_*` leaves hold.
    routing / selections: lists of one list per sequence, which receive
    each sparse layer's expert indices [S_i, k] (the router's own
    choice) and EVERY layer's attended key sets (masks [S_i, S_i]).
    forced: one list per sequence of each sparse layer's experts
    [S_i, k] to compute instead of the routers' choices."""
    single = not isinstance(sequences, (list, tuple))
    if single:
        sequences = [sequences]
    kinds = tuple(config["layer_types"])
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], F32)
        xs = [embed[np.asarray(tokens)] for tokens in sequences]
        walked = sparse = 0
        for kind, lp in zip(kinds, layers if layers is not None
                            else params["layers"]):
            lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
            for i, x in enumerate(xs):
                xs[i] = layer(
                    lp, x, config, kind, held=held,
                    routing=None if routing is None else routing[i],
                    selections=(None if selections is None
                                else selections[i]),
                    forced=(forced[i][sparse]
                            if forced is not None and "router" in lp
                            else None))
            walked += 1
            sparse += "router" in lp
        if walked != len(kinds):
            raise ValueError(f"{walked} layers for layer_types of "
                             f"{len(kinds)}")
        norm = jnp.asarray(params["final_norm"], F32)
        head = jnp.asarray(params["lm_head"], F32)
        out = [mm(rms(x, norm, config["rms_norm_eps"]), head) for x in xs]
    return out[0] if single else out
