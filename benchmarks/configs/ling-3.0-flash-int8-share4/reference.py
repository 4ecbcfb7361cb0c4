"""Plain float32 reference of the Ling-3.0 decoder (`model_type:
bailing_hybrid`): Kimi Delta Attention (KDA; Kimi Linear,
arXiv:2510.26692, as flash-linear-attention publishes it) with gated
latent attention (MLA) every `layer_group_size`-th layer, sigmoid
routing with a choice bias limited to groups by the sum of their two
best, a shared expert.

Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, a Python loop over layers and over experts, the delta rule
TOKEN BY TOKEN (`lax.scan` over time; no chunked form), MLA up-projected
as published, whole-sequence attention in blocks of queries (so that 9k
positions fit); no cache, no kernels, no batching, and no import from
`cake_tpu.ops`, `cake_tpu.models.llama` or `cake_tpu.models.moe`.

One layer, on x [S, D] (`rms` with `rms_norm_eps`), h = rms(x,
attn_norm), per head of H, d_k = d_v = `head_dim`:

    KDA  q~, k~, v~ = h W_q, h W_k, h W_v
         each through conv_t = silu(sum_j w[:, j] in_{t-K+1+j})   causal, depthwise,
                the K-1 inputs before t = 0 are zeros
         q = q~ / sqrt(|q~|^2 + 1e-6) * d_k^-1/2;  k = k~ / sqrt(|k~|^2 + 1e-6);  v = v~
         g = kda_lower_bound * sigmoid(exp(A_log[head]) * (h W_f + dt_bias))   per key CHANNEL, in (-5, 0)
         beta = sigmoid(h W_beta)                                              per head
         S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T     S_{-1} = 0, [d_k, d_v]
         o_t = S_t^T q_t
         out = concat_heads(rms_head(o_t, kda_norm) * sigmoid(h W_g)) W_o
    MLA  q = h W_q -> H x [q_nope | q_pe]   (full rank where the layer has no W_qa; else
                c_q = rms(h W_qa, q_a_norm), q = c_q W_qb);  q_pe = rope(q_pe)
         [c_kv | k_pe] = h W_kva;  c_kv = rms(c_kv, kv_a_norm);  k_pe = rope(k_pe)   one rope key for all heads
         k_nope = c_kv W_kvb^K,  v = c_kv W_kvb^V                                    per head
         a[t] = softmax_{s <= t}((q_nope.k_nope[s] + q_pe.k_pe[s]) * (d_nope + d_rope)^-1/2) v[s]
         a_head = a_head * sigmoid(h W_gate)[head]
         out = concat_heads(a) W_o
    x = x + out;  h = rms(x, mlp_norm)
    dense layer:   x = x + W_down(silu(W_gate h) * W_up h)
    sparse layer:  s = sigmoid(h W_r)                       float32, all experts
                   c = s + expert_bias
                   a group's score = the sum of its two best c (n_group groups of neighbours)
                   G = the topk_group groups of largest score, ties to the lower index
                   chosen = the num_experts_per_tok largest c inside G, ties to the lower index
                   w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
                   x = x + sum_i w_i E_chosen_i(h) + E_shared(h)

then logits = rms(x, final_norm) W_head. RoPE on interleaved pairs, no
scaling. No rotation in a KDA layer.

DEPARTURES from the published model, each also in the cell's
`cell.json`: the multi-token-prediction module is not computed (it adds
nothing to the next-token logits); the SwiGLU clamp of the layers at
and past 34 (`expert_swiglu_limit_list`) is not computed (the config
class refuses a served layer that has one). ASSUMED (the catalog fixes
widths and counts, not these): (a) layer i is MLA where (i + 1) mod
layer_group_size = 0; (b) KDA is Kimi Linear's as flash-linear-attention
publishes it, `no_kda_lora` read as full-rank W_f and W_g, the safe gate
as that library's bounded form, beta in (0, 1), the query scaled by
d_k^-1/2 after its L2 norm; (c) `use_qk_norm` is KDA's L2 norm of q and
k, an MLA layer norms only its latent; (d) no rotation in a KDA layer,
the MLA rope part is `rotary_dim` = 64; (e) `head_wise` gates the MLA
layers' heads by a sigmoid of the layer's normed input, KDA keeps its
per-channel output gate, `group_norm_size: 1` is the per-head RMS norm
of o; (f) the group rule above, groups not taken never chosen, ties to
the lower index; (g) float32 state.

THE SHARE. `held = (first, count)` gives the reference one chip's share
of a layer's routed experts: the router keeps its published width, its
groups and its k, the experts `first .. first+count-1` are computed for
the tokens routed to them, and what the absent experts would add is
left out, as the served path leaves it out (`we_*` hold the `count`
held experts). `shared=False` leaves the shared expert out and
`mixers=False` the mixers, for the test that adds the shares up.

Weights are INPUTS, stored [in, out] (x @ W), the conv taps [channels,
K]: a caller comparing an int8-served model passes the dequantized
weights.

params: {"embed", "final_norm", "lm_head", "layers": per-layer dicts,
each with "kind" in "kda", "mla"}. config: a mapping with
`num_attention_heads`, `head_dim`, `kda_lower_bound`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`, `rms_norm_eps`,
`rope_theta`, `n_group`, `topk_group`, `num_experts_per_tok`,
`routed_scaling_factor`; and a tool's switches, each of which must FAIL
a comparison with the model (chip_compare.py): `kda_state_dtype`
"bfloat16" (round the carried state every token), `kda_decay_dtype`
"bfloat16" (round g and exp(g)), `kda_gate` "softplus" (the unbounded
gate -exp(A_log) softplus(.)), `group_top` 1 (a group's score is its
best), `head_gate` False (the MLA heads not gated).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 256
NEG = -1e30
L2_EPS = 1e-6


def mm(x, w):
    """An activation times a weight."""
    return x @ w


def rms(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * weight


def rope(x, positions, theta: float):
    """x [S, ..., d], d even: the pairs (x[2i], x[2i+1]) are rotated by
    position * theta^(-2i/d) (the interleaved form), angles float64."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.asarray(positions, np.float64)[:, None] * freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = jnp.asarray(np.cos(ang), F32).reshape(shape)
    sin = jnp.asarray(np.sin(ang), F32).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def short_conv(x, taps, tail=None):
    """silu of the causal depthwise conv: x [S, ch], taps [ch, K]; tail
    [K-1, ch]: the inputs before the sequence (None = zeros) -> (out
    [S, ch], the last K-1 inputs)."""
    S, K = x.shape[0], taps.shape[1]
    if tail is None:
        tail = jnp.zeros((K - 1, x.shape[1]), F32)
    padded = jnp.concatenate([tail, x], 0)
    out = sum(taps[:, j][None, :] * padded[j:j + S] for j in range(K))
    return jax.nn.silu(out), padded[-(K - 1):]


def l2_normed(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _rounded(x, config, key: str):
    """x held in the type a tool's switch names (reduce_precision: a
    convert pair to bfloat16 and back is removed by a compiler that
    allows excess precision)."""
    if config.get(key, "float32") == "bfloat16":
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def kda_core(lp, h, config, state=None, tails=None):
    """One KDA mixer on h [S, D] -> (out [S, D], S_last [H, dk, dv], the
    last K-1 inputs of the three convs). state / tails: what the
    sequence starts from (None = zeros); pure, so that a tool may put it
    under jit."""
    H, dk = config["num_attention_heads"], config["head_dim"]
    S = h.shape[0]
    tails = tails or (None, None, None)
    q, tq = short_conv(mm(h, lp["w_kda_q"]), lp["kda_conv_q"], tails[0])
    k, tk = short_conv(mm(h, lp["w_kda_k"]), lp["kda_conv_k"], tails[1])
    v, tv = short_conv(mm(h, lp["w_kda_v"]), lp["kda_conv_v"], tails[2])
    q = l2_normed(q.reshape(S, H, dk)) * dk ** -0.5
    k = l2_normed(k.reshape(S, H, dk))
    v = v.reshape(S, H, dk)
    x = (mm(h, lp["w_kda_f"]) + lp["dt_bias"][None, :]).reshape(S, H, dk)
    rate = jnp.exp(lp["A_log"])[None, :, None]
    if config.get("kda_gate", "bounded") == "softplus":
        g = -rate * jax.nn.softplus(x)
    else:
        g = config["kda_lower_bound"] * jax.nn.sigmoid(rate * x)
    alpha = _rounded(jnp.exp(_rounded(g, config, "kda_decay_dtype")),
                     config, "kda_decay_dtype")
    beta = jax.nn.sigmoid(mm(h, lp["w_kda_beta"]))               # [S, H]

    def step(S_prev, inp):
        a_t, b_t, q_t, k_t, v_t = inp
        S_t = a_t[:, :, None] * S_prev
        u_t = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, S_t))
        S_t = _rounded(S_t + k_t[:, :, None] * u_t[:, None, :], config,
                       "kda_state_dtype")
        return S_t, jnp.einsum("hk,hkv->hv", q_t, S_t)

    S0 = jnp.zeros((H, dk, dk), F32) if state is None else state
    S_last, o = lax.scan(step, S0, (alpha, beta, q, k, v))
    o = rms(o, lp["kda_norm"], config["rms_norm_eps"])
    o = o * jax.nn.sigmoid(mm(h, lp["w_kda_g"])).reshape(S, H, dk)
    return mm(o.reshape(S, H * dk), lp["w_kda_out"]), S_last, (tq, tk, tv)


def attend_block(q_nope, q_pe, k_nope, k_pe, v, lo, scale: float):
    """Queries lo .. lo + T - 1 over every key s <= t."""
    T, S = q_nope.shape[0], k_nope.shape[0]
    scores = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
              + jnp.einsum("thd,sd->hts", q_pe, k_pe)) * scale
    mask = jnp.arange(S)[None, :] <= (lo + jnp.arange(T))[:, None]
    probs = jax.nn.softmax(jnp.where(mask[None], scores, NEG), axis=-1)
    return jnp.einsum("hts,shd->thd", probs, v)


def mla(lp, h, config):
    """Gated latent attention over every visible key -> the mixer's
    output [S, D] before the residual."""
    S = h.shape[0]
    H = config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    pos = np.arange(S)
    if "wq" in lp:
        q = mm(h, lp["wq"])
    else:
        q = mm(rms(mm(h, lp["wq_a"]), lp["q_a_norm"], eps), lp["wq_b"])
    q = q.reshape(S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, theta)
    kva = mm(h, lp["wkv_a"])
    r = kva.shape[-1] - dr
    c_kv = rms(kva[:, :r], lp["kv_a_norm"], eps)
    k_pe = rope(kva[:, r:], pos, theta)                          # [S, dr]
    k_nope = mm(c_kv, lp["wkv_b_k"]).reshape(S, H, dn)
    v = mm(c_kv, lp["wkv_b_v"]).reshape(S, H, dv)
    out = jnp.concatenate([
        attend_block(q_nope[lo:lo + QUERY_BLOCK], q_pe[lo:lo + QUERY_BLOCK],
                     k_nope, k_pe, v, lo, (dn + dr) ** -0.5)
        for lo in range(0, S, QUERY_BLOCK)], 0)
    if config.get("head_gate", True):
        out = out * jax.nn.sigmoid(mm(h, lp["w_attn_gate"]))[..., None]
    return mm(out.reshape(S, H * dv), lp["wo"])


def top_k_stable(scores, k: int):
    """The indices of the k largest of each row, best first, ties to the
    lower index."""
    return jnp.argsort(-scores, axis=-1, stable=True)[:, :k]


def router(lp, h, config, forced=None):
    """(weights [S, k], the experts computed [S, k], this router's own
    choice [S, k], its groups [S, topk_group]) over ALL experts of the
    router's width. forced: experts [S, k] to compute instead of the
    router's choice, weighed by THIS router's scores of them (teacher-
    forced routing: a tool compares along another path's trajectory, so
    that one flipped choice does not move every later layer)."""
    k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(mm(h, lp["router"]))
    choice = scores + lp.get("router_bias", 0.0)
    S, E = scores.shape
    G = config.get("n_group", 1)
    groups = None
    if G > 1:
        grouped = choice.reshape(S, G, E // G)
        best = -jnp.sort(-grouped, axis=-1)[..., :config.get("group_top", 2)]
        groups = top_k_stable(jnp.sum(best, axis=-1), config["topk_group"])
        taken = jnp.any((jnp.arange(E) // (E // G))[None, None, :]
                        == groups[:, :, None], axis=1)
        choice = jnp.where(taken, choice, -jnp.inf)
    order = top_k_stable(choice, k)
    chosen = order if forced is None else jnp.asarray(forced)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return (weights * config.get("routed_scaling_factor", 1.0), chosen,
            order, groups)


def swiglu(h, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def moe_ffn(lp, h, config, held=None, shared=True, routing=None,
            forced=None):
    """The held experts on the tokens routed to them (by a weight of
    zero elsewhere), plus the shared expert. routing receives the
    router's OWN choice, whatever `forced` made it compute."""
    weights, experts, own, _ = router(lp, h, config, forced)
    if routing is not None:
        routing.append(np.asarray(own))
    first = 0 if held is None else held[0]
    out = jnp.zeros_like(h)
    for e in range(lp["we_gate"].shape[0]):
        if not bool(jnp.any(experts == first + e)):
            continue
        w = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=1)
        out = out + w[:, None] * swiglu(h, lp["we_gate"][e], lp["we_up"][e],
                                        lp["we_down"][e])
    if shared:
        out = out + swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out


def layer(lp, x, config, held=None, shared=True, mixers=True, routing=None,
          forced=None, states=None, start=None):
    """One layer on x [S, D]. start: (state, tails) a KDA layer starts
    from (None = zeros); states: a list that receives a KDA layer's
    (final state, final conv tails)."""
    eps = config["rms_norm_eps"]
    if mixers:
        h = rms(x, lp["attn_norm"], eps)
        if lp["kind"] == "kda":
            out, S_last, tails = kda_core(lp, h, config,
                                          *(start or (None, None)))
            if states is not None:
                states.append((S_last, tails))
        else:
            out = mla(lp, h, config)
        x = x + out
    h = rms(x, lp["mlp_norm"], eps)
    if "router" in lp:
        return x + moe_ffn(lp, h, config, held, shared, routing, forced)
    return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


def forward(params, sequences, config, layers=None, held=None,
            routing=None, forced=None, states=None, starts=None):
    """sequences: a list of token arrays [S_i] -> a list of logits
    [S_i, V] float32, every position (one array in, one array out).

    layers: an iterable of per-layer dicts to walk instead of
    params["layers"] (a generator lets a caller hold one layer's float32
    weights at a time). held: (first, count) of the routed experts the
    `we_*` leaves hold. routing / states: lists of one list per
    sequence, which receive each sparse layer's expert indices [S_i, k]
    (the router's own choice) and each KDA layer's (final state, final
    conv tails). forced: one list per sequence of each sparse layer's
    experts [S_i, k] to compute instead of the routers' choices.
    starts: per sequence, a list of (state, tails) per KDA layer to
    start from (the altered reference whose second request inherits the
    first's state), or None."""
    single = not isinstance(sequences, (list, tuple))
    if single:
        sequences = [sequences]
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], F32)
        xs = [embed[np.asarray(tokens)] for tokens in sequences]
        sparse = n_kda = 0
        for lp in (layers if layers is not None else params["layers"]):
            lp = {k: (v if k == "kind" else jnp.asarray(v, F32))
                  for k, v in lp.items()}
            kda = lp["kind"] == "kda"
            for i, x in enumerate(xs):
                xs[i] = layer(
                    lp, x, config, held=held,
                    routing=None if routing is None else routing[i],
                    forced=(forced[i][sparse]
                            if forced is not None and "router" in lp
                            else None),
                    states=None if states is None else states[i],
                    start=(starts[i][n_kda]
                           if kda and starts is not None
                           and starts[i] is not None else None))
            sparse += "router" in lp
            n_kda += kda
        norm = jnp.asarray(params["final_norm"], F32)
        head = jnp.asarray(params["lm_head"], F32)
        out = [mm(rms(x, norm, config["rms_norm_eps"]), head) for x in xs]
    return out[0] if single else out
