"""Plain float32 reference of the LongCat-Flash decoder (`model_type:
longcat_flash`): shortcut-connected layers of two latent attentions
(MLA) over every visible key, two dense SwiGLUs and one routed MoE
whose router is wider than its experts by the zero-compute (identity)
experts.

Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, a Python loop over layers and over experts, whole-sequence
attention computed in blocks of queries (so that 8k positions at 64
heads fit); no cache, no kernels, no batching, and no import from
`cake_tpu.ops` or `cake_tpu.models.llama`. The keys and values are
up-projected from the latent, per head, as published; the served path
absorbs the up-projection into the query and the output instead, which
is the same mathematics.

One layer, on x [S, D] (`rms` with `rms_norm_eps`; RoPE on interleaved
pairs, plain: theta^(-2i/d)):

    for i in (0, 1):                                     two sublayers
        h      = rms(x, attn_norm[i])
        c_q    = rms(h W_qa[i], q_a_norm[i]) * s_q         s_q  = (D / q_lora_rank)^0.5
        q      = c_q W_qb[i] -> H heads x [q_nope | q_pe]; q_pe = rope(q_pe)
        [c_kv | k_pe] = h W_kva[i]                         [kv_lora_rank | rope]
        c_kv   = rms(c_kv, kv_a_norm[i]) * s_kv            s_kv = (D / kv_lora_rank)^0.5
        k_pe   = rope(k_pe)                                one rope key for all heads, unscaled
        k_nope = c_kv W_kvb^K[i],  v = c_kv W_kvb^V[i]     per head
        a[t]   = softmax_{s <= t}((q_nope.k_nope[s] + q_pe.k_pe[s]) * (dn + dr)^-0.5) v[s]
        x      = x + concat_heads(a) W_o[i]
        h      = rms(x, mlp_norm[i])
        if i == 0:                                         the shortcut is tapped
            p   = softmax(h W_r)                           float32, all routed + zero experts
            idx = the moe_topk largest of p + bias, ties to the lower index
            w   = p[idx] * routed_scaling_factor           NOT renormalised; the bias moves the choice alone
            m   = sum_j w_j (E_idx_j(h) if idx_j < n_routed_experts else h)
        x      = x + W_down[i](silu(W_gate[i] h) * W_up[i] h)
    x = x + m                                              the shortcut returns

then logits = rms(x, final_norm) W_head. An identity expert returns the
MoE's own input h.

THE SHARE. `held = (first, count)` gives the reference one chip's share
of a layer's routed experts: the router keeps its published width and
its k, the experts `first .. first+count-1` are computed for the tokens
routed to them, and what the absent experts would add is left out, as
the served path leaves it out (`we_*` hold the `count` held experts).
The identity part is every share's alike; `identity=False` leaves it
out, for the test that adds the shares up.

Weights are INPUTS, stored [in, out] (x @ W): a caller comparing an
int8-served model passes the dequantized weights.

params: {"embed", "final_norm", "lm_head", "layers": per-SUBLAYER dicts,
two a published layer in order: each its attention leaves and its dense
FFN's (`w_gate`, `w_up`, `w_down`), the first of a pair also
"shortcut": {"router", "router_bias", "we_gate", "we_up", "we_down"}}.
config: a mapping with `num_attention_heads`, `hidden_size`,
`q_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`,
`rms_norm_eps`, `rope_theta`, `mla_scale_q_lora`, `mla_scale_kv_lora`,
`n_routed_experts` (the router's ROUTED width: an index at or past it is
a zero expert), `moe_topk`, `routed_scaling_factor`; and a tool's
switches, each of which must fail its comparison: `softmax_dtype`
("bfloat16": scores and probabilities rounded), `int8_activations`
(every matmul's input rounded to 8 bits a row), `zero_experts` (False:
a zero expert adds nothing), `norm_topk_prob` (True: the weights divided
by their sum), `mla_scale_q_lora` / `mla_scale_kv_lora` (False: a scale
left out), `tap` (1: the MoE reads the SECOND sublayer's FFN input),
`back` (0: the MoE's output is added after the FIRST sublayer's FFN),
`bias_in_weight` (True: the weights are the biased scores).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256
NEG = -1e30
_INT8_ACT = False


def mm(x, w):
    """An activation times a weight (the activation rounded to 8 bits a
    row under the tool's switch)."""
    if _INT8_ACT:
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        x = jnp.round(x / jnp.maximum(scale, 1e-30)) * scale
    return x @ w


def rms(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, positions, theta: float):
    """x [S, ..., d], d even: the pairs (x[2i], x[2i+1]) are rotated by
    position * theta^(-2i/d) (the interleaved form)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.asarray(positions, np.float64)[:, None] * freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = jnp.asarray(np.cos(ang), F32).reshape(shape)
    sin = jnp.asarray(np.sin(ang), F32).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


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
    S, D = h.shape
    H = config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    pos = np.arange(S)
    c_q = rms(mm(h, lp["wq_a"]), lp["q_a_norm"], eps)
    if config.get("mla_scale_q_lora", True):
        c_q = c_q * (D / c_q.shape[-1]) ** 0.5
    q = mm(c_q, lp["wq_b"]).reshape(S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, theta)
    kva = mm(h, lp["wkv_a"])
    r = kva.shape[-1] - dr
    c_kv = rms(kva[:, :r], lp["kv_a_norm"], eps)
    if config.get("mla_scale_kv_lora", True):
        c_kv = c_kv * (D / r) ** 0.5
    k_pe = rope(kva[:, r:], pos, theta)                          # [S, dr]
    k_nope = mm(c_kv, lp["wkv_b_k"]).reshape(S, H, dn)
    v = mm(c_kv, lp["wkv_b_v"]).reshape(S, H, dv)
    scale = (dn + dr) ** -0.5
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
    choice [S, k]) as published, over the router's whole width: the
    routed experts, then the zero experts. forced: experts [S, k] to
    compute instead of the router's choice, weighed by THIS router's
    scores of them (teacher-forced routing: a tool compares along
    another path's trajectory, so that one flipped choice does not move
    every later layer)."""
    scores = jax.nn.softmax(h @ lp["router"], axis=-1)
    biased = scores + lp["router_bias"]
    order = top_k_stable(biased, config["moe_topk"])
    chosen = order if forced is None else jnp.asarray(forced)
    weights = jnp.take_along_axis(
        biased if config.get("bias_in_weight") else scores, chosen, axis=-1)
    if config.get("norm_topk_prob", False):
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * config.get("routed_scaling_factor", 1.0), chosen, order


def swiglu(h, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def moe_ffn(lp, h, config, held=None, identity=True, routing=None,
            forced=None):
    """The held experts on the tokens routed to them (by a weight of
    zero elsewhere), plus the identity part: the summed weights of a
    token's zero experts times h. routing receives the router's OWN
    choice, whatever `forced` made it compute."""
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
    if identity and config.get("zero_experts", True):
        zero = experts >= config["n_routed_experts"]
        out = out + jnp.sum(jnp.where(zero, weights, 0.0),
                            axis=1)[:, None] * h
    return out


def layer(first, second, x, config, held=None, identity=True, routing=None,
          forced=None):
    """One published layer: two sublayers and the shortcut around the
    second."""
    eps = config["rms_norm_eps"]
    tap, back = config.get("tap", 0), config.get("back", 1)
    m = None
    for i, lp in enumerate((first, second)):
        x = x + attention(lp, rms(x, lp["attn_norm"], eps), config)
        h = rms(x, lp["mlp_norm"], eps)
        if i == tap:
            m = moe_ffn(first["shortcut"], h, config, held, identity,
                        routing, forced)
        x = x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        if i == back:
            x = x + m
    return x


def forward(params, sequences, config, layers=None, held=None,
            routing=None, forced=None):
    """sequences: a list of token arrays [S_i] -> a list of logits
    [S_i, V] float32, every position (one array in, one array out).

    layers: an iterable of per-SUBLAYER dicts to walk instead of
    params["layers"] (a generator lets a caller hold one layer's float32
    weights at a time). held: (first, count) of the routed experts the
    `we_*` leaves hold. routing: a list of one list per sequence, which
    receive each layer's expert indices [S_i, k] (the router's own
    choice). forced: one list per sequence of each layer's experts
    [S_i, k] to compute instead of the routers' choices."""
    global _INT8_ACT
    single = not isinstance(sequences, (list, tuple))
    if single:
        sequences = [sequences]

    def f32(tree):
        return jax.tree.map(lambda a: jnp.asarray(a, F32), tree)

    _INT8_ACT = bool(config.get("int8_activations"))
    try:
        with jax.default_matmul_precision("highest"):
            embed = jnp.asarray(params["embed"], F32)
            xs = [embed[np.asarray(tokens)] for tokens in sequences]
            subs = iter(layers if layers is not None else params["layers"])
            for j, first in enumerate(subs):
                first, second = f32(first), f32(next(subs))
                for i, x in enumerate(xs):
                    xs[i] = layer(
                        first, second, x, config, held=held,
                        routing=None if routing is None else routing[i],
                        forced=None if forced is None else forced[i][j])
            norm = jnp.asarray(params["final_norm"], F32)
            head = jnp.asarray(params["lm_head"], F32)
            out = [mm(rms(x, norm, config["rms_norm_eps"]), head)
                   for x in xs]
    finally:
        _INT8_ACT = False
    return out[0] if single else out
