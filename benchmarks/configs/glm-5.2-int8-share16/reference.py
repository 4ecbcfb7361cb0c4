"""Plain float32 reference of the GLM-5.2 decoder (`model_type:
glm_moe_dsa`): latent attention (MLA), the learned sparse indexer (DSA)
with shared indices, sigmoid-routed experts with a shared one.

Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, a Python loop over layers and over experts, whole-sequence
attention computed in blocks of queries (so that 12.5k positions fit);
no cache, no kernels, no batching, and no import from `cake_tpu.ops` or
`cake_tpu.models.llama`. The keys and values are up-projected from the
latent, per head, as published; the served path absorbs the
up-projection into the query and the output instead, which is the same
mathematics.

One layer, on x [S, D] (`rms` with `rms_norm_eps`, RoPE on interleaved
pairs, theta `rope_theta`):

    h      = rms(x, attn_norm)
    c_q    = rms(h W_qa, q_a_norm)                       [q_lora_rank]
    q      = c_q W_qb -> H heads x [q_nope | q_pe];  q_pe = rope(q_pe)
    [c_kv | k_pe] = h W_kva                              [kv_lora_rank | rope]
    c_kv   = rms(c_kv, kv_a_norm);  k_pe = rope(k_pe)    one rope key for all heads
    k_nope = c_kv W_kvb^K,  v = c_kv W_kvb^V             per head
    full indexer layer (the layer has indexer weights):
      qI   = c_q WI_q -> index_n_heads x index_head_dim, rope on the first
             qk_rope_head_dim of each head
      kI   = layernorm(h WI_k) (weight and bias, eps 1e-6), rope on its
             first qk_rope_head_dim
      w    = (h WI_w) * index_n_heads^-0.5 * index_head_dim^-0.5
      I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s]),   s <= t
      S_t  = the index_topk largest of I[t, 0..t], ties to the lower
             index (all of them while t < index_topk)
    shared indexer layer: S_t = the S_t of the nearest full layer below
    a[t]   = softmax_{s in S_t}((q_nope.k_nope[s] + q_pe.k_pe[s]) / sqrt(qk_head_dim)) v[s]
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

Departures from the published model, each also in the cell's `cell.json`:
the reading of a `"shared"` layer above (IndexShare); LayerNorm on the
indexer key and the scale of `w`, from the DeepSeek-V3.2 indexer this
model type derives from; full-precision indexer keys where the published
kernels hold fp8 behind a Hadamard rotation (orthogonal: it leaves qI.kI
as it is). Weights are INPUTS, stored [in, out] (x @ W): a caller
comparing an int8-served model passes the dequantized weights.

params: {"embed", "final_norm", "lm_head", "layers": per-layer dicts}.
config: a mapping with `num_attention_heads`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim`, `rms_norm_eps`, `rope_theta`,
`index_n_heads`, `index_head_dim`, `index_topk`, `num_experts_per_tok`,
`norm_topk_prob`, `routed_scaling_factor`, and optionally `scoring_func`
("sigmoid"), `dense_attention` (a tool's switch: attend every visible
key, which must fail the comparison above index_topk keys).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512
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


def index_scores(lp, h, c_q, config):
    """I [S, S] float32, the causal part (s <= t) meaningful."""
    S = h.shape[0]
    nI, dI = config["index_n_heads"], config["index_head_dim"]
    n_rope, theta = config["qk_rope_head_dim"], config["rope_theta"]
    pos = np.arange(S)
    qI = rope_head(mm(c_q, lp["wi_q"]).reshape(S, nI, dI), pos, theta, n_rope)
    kI = rope_head(layernorm(mm(h, lp["wi_k"]), lp["wi_k_norm"],
                             lp["wi_k_bias"]), pos, theta, n_rope)
    w = mm(h, lp["wi_w"]) * (nI ** -0.5) * (dI ** -0.5)          # [S, nI]
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(S, lo + QUERY_BLOCK)
        dots = jnp.einsum("tjd,sd->tjs", qI[lo:hi], kI)
        out.append(jnp.einsum("tjs,tj->ts", jax.nn.relu(dots), w[lo:hi]))
    return jnp.concatenate(out, 0)


def select(scores, topk: int):
    """The selected key sets as a mask [S, S]: row t holds the topk
    largest of scores[t, 0..t], ties to the lower index; every visible
    key while t < topk."""
    S = scores.shape[0]
    causal = np.tril(np.ones((S, S), bool))
    if S <= topk:
        return jnp.asarray(causal)
    masked = jnp.where(causal, scores, -jnp.inf)
    # a stable sort of the negated scores: equal scores keep index order
    order = jnp.argsort(-masked, axis=-1, stable=True)[:, :topk]
    picked = jnp.zeros((S, S), bool).at[
        jnp.arange(S)[:, None], order].set(True)
    return picked & causal


def attention(lp, h, config, selected):
    """MLA over the selected keys. selected: mask [S, S] or None (every
    visible key). Returns (the attention's output [S, D] before the
    residual, c_q)."""
    S = h.shape[0]
    H = config["num_attention_heads"]
    d_nope, d_rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    d_v, eps, theta = (config["v_head_dim"], config["rms_norm_eps"],
                       config["rope_theta"])
    pos = np.arange(S)
    c_q = rms(mm(h, lp["wq_a"]), lp["q_a_norm"], eps)
    q = mm(c_q, lp["wq_b"]).reshape(S, H, d_nope + d_rope)
    q_nope, q_pe = q[..., :d_nope], rope(q[..., d_nope:], pos, theta)
    kva = mm(h, lp["wkv_a"])
    r = kva.shape[-1] - d_rope
    c_kv = rms(kva[:, :r], lp["kv_a_norm"], eps)
    k_pe = rope(kva[:, r:], pos, theta)                          # [S, d_rope]
    k_nope = mm(c_kv, lp["wkv_b_k"]).reshape(S, H, d_nope)
    v = mm(c_kv, lp["wkv_b_v"]).reshape(S, H, d_v)
    scale = (d_nope + d_rope) ** -0.5
    mask = np.tril(np.ones((S, S), bool)) if selected is None else selected
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(S, lo + QUERY_BLOCK)
        scores = (jnp.einsum("thd,shd->hts", q_nope[lo:hi], k_nope)
                  + jnp.einsum("thd,sd->hts", q_pe[lo:hi], k_pe)) * scale
        scores = jnp.where(mask[lo:hi][None], scores, NEG)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("hts,shd->thd", probs, v).reshape(
            hi - lo, H * d_v))
    return mm(jnp.concatenate(out, 0), lp["wo"]), c_q


def router(lp, h, config):
    """(weights [S, k], experts [S, k]) as published, over ALL experts
    of the router's width."""
    k = config["num_experts_per_tok"]
    logits = mm(h, lp["router"])
    if config.get("scoring_func", "sigmoid") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + lp.get("router_bias", 0.0)
    order = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]
    weights = jnp.take_along_axis(scores, order, axis=-1)
    if config.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * config.get("routed_scaling_factor", 1.0), order


def swiglu(h, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def moe_ffn(lp, h, config, held=None, shared=True, routing=None):
    """The held experts on the tokens routed to them (by a weight of
    zero elsewhere), plus the shared expert."""
    weights, experts = router(lp, h, config)
    if routing is not None:
        routing.append(np.asarray(experts))
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


def layer(lp, x, config, selected, held=None, shared=True, routing=None,
          selections=None):
    """One layer. selected: the key sets the nearest full layer below
    chose (a mask [S, S]) or None. Returns (x, the key sets this layer
    attended)."""
    eps = config["rms_norm_eps"]
    h = rms(x, lp["attn_norm"], eps)
    if "wi_q" in lp:
        c_q = rms(mm(h, lp["wq_a"]), lp["q_a_norm"], eps)
        selected = select(index_scores(lp, h, c_q, config),
                          config["index_topk"])
    attended = None if config.get("dense_attention") else selected
    if selections is not None:
        selections.append(np.tril(np.ones((x.shape[0],) * 2, bool))
                          if attended is None else np.asarray(attended))
    a, _ = attention(lp, h, config, attended)
    x = x + a
    h = rms(x, lp["mlp_norm"], eps)
    if "router" in lp:
        return x + moe_ffn(lp, h, config, held, shared, routing), selected
    return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), selected


def forward(params, sequences, config, layers=None, held=None,
            routing=None, selections=None):
    """sequences: a list of token arrays [S_i] -> a list of logits
    [S_i, V] float32, every position (one array in, one array out).

    layers: an iterable of per-layer dicts to walk instead of
    params["layers"] (a generator lets a caller hold one layer's float32
    weights at a time). held: (first, count) of the routed experts the
    `we_*` leaves hold. routing / selections: lists of one list per
    sequence, which receive each sparse layer's expert indices [S_i, k]
    and EVERY layer's attended key sets (masks [S_i, S_i]: a shared
    layer's are the full layer's below it)."""
    single = not isinstance(sequences, (list, tuple))
    if single:
        sequences = [sequences]
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], F32)
        xs = [embed[np.asarray(tokens)] for tokens in sequences]
        chosen = [None] * len(xs)
        for lp in (layers if layers is not None else params["layers"]):
            lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
            for i, x in enumerate(xs):
                xs[i], chosen[i] = layer(
                    lp, x, config, chosen[i], held=held,
                    routing=None if routing is None else routing[i],
                    selections=(None if selections is None
                                else selections[i]))
        norm = jnp.asarray(params["final_norm"], F32)
        head = jnp.asarray(params["lm_head"], F32)
        out = [mm(rms(x, norm, config["rms_norm_eps"]), head) for x in xs]
    return out[0] if single else out
