"""Plain float32 reference of the K-EXAONE decoder (`model_type:
exaone_moe`): GQA in two kinds of layer (a sliding window with RoPE, or
every visible key with no positions), an RMSNorm a head on q and k,
sigmoid-routed experts with a shared one.

Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, a Python loop over layers and over experts, whole-sequence
attention computed in blocks of queries (so that 9k positions at 64
heads fit); no cache, no kernels, no batching, and no import from
`cake_tpu.ops` or `cake_tpu.models.llama`.

One layer, on x [S, D] (`rms` with `rms_norm_eps`):

    h   = rms(x, attn_norm)
    q   = h W_q -> 64 heads of 128;  k = h W_k, v = h W_v -> 8 heads of 128
    q   = rms(q, q_norm), k = rms(k, k_norm)              a head, over head_dim
    sliding layer:  q, k = rope(q), rope(k)               theta 1e6, pairs (i, i + hd/2)
                    a[t] = softmax_{t - W < s <= t}(q[t].k[s] / sqrt(hd)) v[s]
    full layer:     no rotation
                    a[t] = softmax_{s <= t}(q[t].k[s] / sqrt(hd)) v[s]
    (query head i reads K/V head i // (heads / kv_heads))
    x   = x + concat_heads(a) W_o
    h   = rms(x, mlp_norm)
    dense layer:   x = x + W_down(silu(W_gate h) * W_up h)
    sparse layer:  s = sigmoid(h W_r)                      float32, all experts
                   chosen = the num_experts_per_tok largest of s + b, ties to the lower index
                   w = s[chosen] (/ their sum if norm_topk_prob) * routed_scaling_factor
                   x = x + sum_i w_i E_chosen_i(h) + E_shared(h)

then logits = rms(x, final_norm) W_head. W = `sliding_window` counts the
query itself (128: the query and the 127 keys before it).

ASSUMED (the catalog fixes widths, counts and switches, not these; the
cell's `cell.json` lists them (a)-(g) with where each comes from):
pre-norm residual blocks; the RMSNorm a head on q and k BEFORE the
rotation (the EXAONE 4.0 lineage); rotation in the sliding layers only
(the same lineage: `rope_parameters` has one theta and no key by kind
of layer); the window counts the query; the choice bias b enters the
choice only, never the weight; ties to the lower index; half-split
(non-interleaved) pairs in RoPE.

THE SHARE. `held = (first, count)` gives the reference one chip's share
of a layer's routed experts: the router keeps its published width and
its k, the experts `first .. first+count-1` are computed for the tokens
routed to them, and what the absent experts would add is left out, as
the served path leaves it out (`we_*` hold the `count` held experts).
`shared=False` leaves the shared expert out, for the test that adds the
shares up.

Weights are INPUTS, stored [in, out] (x @ W): a caller comparing an
int8-served model passes the dequantized weights.

params: {"embed", "final_norm", "lm_head", "layers": per-layer dicts,
each with `kind` ("sliding" | "full")}. config: a mapping with
`num_attention_heads`, `num_key_value_heads`, `head_dim`,
`sliding_window`, `rope_theta`, `rms_norm_eps`, `num_experts_per_tok`,
`norm_topk_prob`, `routed_scaling_factor`; and a tool's switches, each
of which must fail its comparison: `softmax_dtype` ("bfloat16": the
scores and the probabilities rounded, which the served kernels hold in
float32), `rope_in_full` (True: the full layers rotate too), `qk_norm`
(False: no norm a head), and a `sliding_window` of 127 or 129.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NEG = -1e30
QUERY_BLOCK = 512


def mm(x, w):
    """An activation times a weight."""
    return x @ w


def rms(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, positions, theta: float):
    """x [S, heads, hd] rotated by position on the pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    angle = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle), F32)[:, None, :]
    sin = jnp.asarray(np.sin(angle), F32)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend_block(q, k, v, lo, window, dtype=F32):
    """Queries lo .. lo + T - 1 over the keys each may see (all S keys
    scored, the others masked: one shape a sequence). q [T, KV, G, hd];
    k, v [S, KV, hd]; window: None, or the keys a query attends, its
    own included. dtype: what the scores and probabilities are held in
    (a tool's switch)."""
    T, S = q.shape[0], k.shape[0]
    scores = jnp.einsum("tkgd,skd->kgts", q, k) * q.shape[-1] ** -0.5
    t = (lo + jnp.arange(T))[:, None]
    s = jnp.arange(S)[None, :]
    mask = s <= t
    if window is not None:
        mask = mask & (s > t - window)
    scores = jnp.where(mask[None, None], scores.astype(dtype).astype(F32),
                       NEG)
    probs = jax.nn.softmax(scores.astype(dtype), axis=-1).astype(F32)
    return jnp.einsum("kgts,skd->tkgd", probs, v)


def attention(lp, h, config):
    """GQA of the layer's kind -> the attention's output [S, D] before
    the residual."""
    S = h.shape[0]
    H, KV, hd = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    eps = config["rms_norm_eps"]
    sliding = lp["kind"] == "sliding"
    dtype = jnp.dtype(config.get("softmax_dtype", "float32"))
    q = mm(h, lp["wq"]).reshape(S, H, hd)
    k = mm(h, lp["wk"]).reshape(S, KV, hd)
    v = mm(h, lp["wv"]).reshape(S, KV, hd)
    if config.get("qk_norm", True):
        q, k = rms(q, lp["q_norm"], eps), rms(k, lp["k_norm"], eps)
    if sliding or config.get("rope_in_full", False):
        pos = np.arange(S)
        q = rope(q, pos, config["rope_theta"])
        k = rope(k, pos, config["rope_theta"])
    q = q.reshape(S, KV, H // KV, hd)
    window = config["sliding_window"] if sliding else None
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        out.append(attend_block(q[lo:lo + QUERY_BLOCK], k, v, lo, window,
                                dtype))
    return mm(jnp.concatenate(out, 0).reshape(S, H * hd), lp["wo"])


def top_k_stable(scores, k: int):
    """The indices of the k largest of each row, best first, ties to the
    lower index."""
    return jnp.argsort(-scores, axis=-1, stable=True)[:, :k]


def router(lp, h, config, forced=None):
    """(weights [S, k], the experts computed [S, k], this router's own
    choice [S, k]) as published, over ALL experts of the router's
    width. forced: experts [S, k] to compute instead of the router's
    choice, weighed by THIS router's scores of them (teacher-forced
    routing: a tool compares along another path's trajectory, so that
    one flipped choice does not move every later layer)."""
    k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(mm(h, lp["router"]))
    order = top_k_stable(scores + lp["router_bias"][None, :], k)
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
            lp = {k: v if k == "kind" else jnp.asarray(v, F32)
                  for k, v in lp.items()}
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
