"""Plain float32 reference of the ZAYA1 decoder (`model_type: zaya`, the
ZAYA1-8B layout): compressed convolutional attention (CCA), an MLP
router whose state runs down the layers, one expert a token, learned
residual scaling.

Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, a Python loop over layers and over experts, the
convolutions as plain shifted sums over the whole sequence, whole-
sequence causal attention; no cache, no tail, no kernels, no batching,
and no import from `cake_tpu.ops`, `cake_tpu.models.llama` or
`cake_tpu.models.moe`.

D hidden, H query heads over K key heads of d (G = H / K), E experts of
F, R the router's width; `rms` with `rms_norm_eps`. For layer l on
x [S, D], token t (everything with index -1 is ZERO):

    CCA   u = rms(x, attn_norm)
          qc_t = u_t W_q  (H d);  kc_t = u_t W_k  (K d);  c_t = [qc_t | kc_t]
          a_t  = w0[0] * c_{t-1} + w0[1] * c_t + b0                conv 0: depthwise, kernel 2, causal
          b_t[g] = a_{t-1}[g] W1[g, 0] + a_t[g] W1[g, 1] + b1[g]    conv 1: one group of d a head (H + K groups), kernel 2
          [qh | kh] = b_t
          q_t[h] = qh_t[h] + (qc_t[h] + kc_t[h // G]) / 2           the q-k mean of the latents, added back
          k_t[j] = kh_t[j] + (mean_{h in group j} qc_t[h] + kc_t[j]) / 2
          v_t  = [u_t W_v1 | u_{t-1} W_v2]                          K d wide: KV head 0 is this token's, head 1 the one before's (K = 2)
          q_t[h] <- sqrt(d) q_t[h] / |q_t[h]|;  k_t[j] <- tau[j] sqrt(d) k_t[j] / |k_t[j]|
          rope at position t on dims [0, rho d) of every q and k head, pairs (i, i + rho d / 2); the rest unrotated
          o_t[h] = sum_{s <= t} softmax_s(q_t[h] . k_s[h // G] / sqrt(d)) v_s[h // G]
          y = [o_t[0..H)] W_o
    x'  = (res_attn[0] * x + res_attn[1]) + (res_attn[2] * y + res_attn[3])
    ROUTER m = rms(x', mlp_norm)
          r^l_t = m_t W_dn + b_dn + gamma^l * r^{l-1}_t             (no gamma term at l = 0)
          s = rms(r^l_t, r_norm);  logits = gelu(gelu(s W_1 + b_1) W_2 + b_2) W_3
          p = softmax(logits);  e* = argmax(p + router_bias), ties to the lower index
    EXPERT f_t = p[e*] (silu(m_t W_gate[e*]) * (m_t W_up[e*])) W_down[e*]
    x'' = (res_moe[0] * x' + res_moe[1]) + (res_moe[2] * f + res_moe[3])

then logits = rms(x, final_norm) W_head (W_head is the embedding,
transposed: tied).

Assumed (the catalog's config fixes the widths, the kernels' sizes, the
rotated share, top-1 and the router's width, not these; each is also in
the cell's `cell.json`): conv 1 grouped by head; the q-k mean's form
under GQA; which KV head the shifted half fills; the L2 norm to sqrt(d)
with a temperature a KV head on the keys; the router's depth, biases,
RMS norm, exact (erf) GELU, gamma a channel and none at layer 0, the
bias for the choice only; that the router reads the normed stream; the
residual scaling's four vectors; no mixture-of-depths output.
Weights are INPUTS, stored [in, out] (x @ W): a caller comparing an
int8-served model passes the dequantized weights.

params: {"embed", "final_norm", "lm_head", "layers": per-layer dicts}.
config: a mapping with `rms_norm_eps`, `num_attention_heads`,
`num_key_value_heads`, `head_dim`, `partial_rotary_factor`,
`rope_theta`, `num_experts_per_tok`, and a tool's switches, each of
which must FAIL a comparison with the model (chip_compare.py):
`int8_activations` (round every matmul's input to 8 bits per row);
`drop_conv_taps` (both convolutions lose their t-1 tap);
`no_value_shift` (the second half of the values reads this token);
`full_rotary` (the whole head rotated); `renormalise_top1` (the
expert's weight 1); `no_router_state` (no gamma term in any layer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
NEG = -1e30

_INT8_ACT = False


def mm(x, w):
    if _INT8_ACT:
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
        x = jnp.round(x / s) * s
    return jnp.dot(x, w, precision=lax.Precision.HIGHEST,
                   preferred_element_type=F32)


def rms(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def shifted(x):
    """x [S, ...] one token later: row t holds x_{t-1}, row 0 zeros."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def rope(x, theta, rotated: int):
    """x [S, heads, d] at positions 0..S-1: dims [0, rotated) rotated in
    pairs (i, i + rotated / 2), the rest passed through."""
    S = x.shape[0]
    half = rotated // 2
    inv = 1.0 / theta ** (jnp.arange(0, rotated, 2, dtype=F32) / rotated)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotated]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotated:]], axis=-1)


def cca_qkv(lp, u, config):
    """u [S, D] (normed) -> q [S, H, d], k, v [S, K, d] as the cache
    would hold them (after conv, mean, shift, norm and RoPE)."""
    H, K, d = (config["num_attention_heads"], config["num_key_value_heads"],
               config["head_dim"])
    G = H // K
    S = u.shape[0]
    taps = 0.0 if config.get("drop_conv_taps") else 1.0
    qc, kc = mm(u, lp["wq"]), mm(u, lp["wk"])
    c = jnp.concatenate([qc, kc], axis=-1)
    a = taps * lp["conv0_w"][0] * shifted(c) + lp["conv0_w"][1] * c \
        + lp["conv0_b"]
    ag = a.reshape(S, H + K, d)
    b = (taps * jnp.einsum("sgi,gio->sgo", shifted(ag), lp["conv1_w"][:, 0],
                           precision=lax.Precision.HIGHEST)
         + jnp.einsum("sgi,gio->sgo", ag, lp["conv1_w"][:, 1],
                      precision=lax.Precision.HIGHEST)
         + lp["conv1_b"].reshape(H + K, d))
    qc, kc = qc.reshape(S, K, G, d), kc.reshape(S, K, d)
    q = b[:, :H].reshape(S, K, G, d) + 0.5 * (qc + kc[:, :, None])
    k = b[:, H:] + 0.5 * (jnp.mean(qc, axis=2) + kc)
    q = q.reshape(S, H, d)
    v1, v2 = mm(u, lp["wv1"]), mm(u, lp["wv2"])
    v = jnp.concatenate(
        [v1, v2 if config.get("no_value_shift") else shifted(v2)],
        axis=-1).reshape(S, K, d)
    q = np.sqrt(d) * q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = (lp["k_temp"][None, :, None] * np.sqrt(d) * k
         / jnp.linalg.norm(k, axis=-1, keepdims=True))
    rotated = d if config.get("full_rotary") else \
        int(d * config["partial_rotary_factor"])
    return (rope(q, config["rope_theta"], rotated),
            rope(k, config["rope_theta"], rotated), v)


def attention(lp, u, config, keys=None):
    """The CCA sublayer's y [S, D] from the normed stream u. keys: a
    list that receives (k [S, K d], v [S, K d])."""
    H, K, d = (config["num_attention_heads"], config["num_key_value_heads"],
               config["head_dim"])
    S = u.shape[0]
    q, k, v = cca_qkv(lp, u, config)
    if keys is not None:
        keys.append((k.reshape(S, K * d), v.reshape(S, K * d)))
    qg = q.reshape(S, K, H // K, d)
    scores = jnp.einsum("tjgd,sjd->jgts", qg, k,
                        precision=lax.Precision.HIGHEST) / np.sqrt(d)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, NEG), axis=-1)
    o = jnp.einsum("jgts,sjd->tjgd", probs, v,
                   precision=lax.Precision.HIGHEST)
    return mm(o.reshape(S, H * d), lp["wo"])


def router(lp, m, r_prev, config):
    """m [S, D] (normed), r_prev [S, R] or None at layer 0 ->
    (p [S, E] float32, the state r [S, R] the next layer reads)."""
    r = mm(m, lp["r_dn"]) + lp["r_dn_b"]
    if r_prev is not None and not config.get("no_router_state"):
        r = r + lp["r_gamma"] * r_prev
    s = rms(r, lp["r_norm"], config["rms_norm_eps"])
    hid = jax.nn.gelu(mm(s, lp["r_w1"]) + lp["r_b1"], approximate=False)
    hid = jax.nn.gelu(mm(hid, lp["r_w2"]) + lp["r_b2"], approximate=False)
    return jax.nn.softmax(mm(hid, lp["r_w3"]), axis=-1), r


def experts(lp, m, p, config, routing=None, forced=None):
    """The expert sublayer's f [S, D]. routing: a list that receives the
    reference's own choice [S, k]. forced [S, k]: experts to take
    instead of it (their weights are still the reference's p)."""
    k = config["num_experts_per_tok"]
    _, chosen = lax.top_k(p + lp["router_bias"], k)
    if routing is not None:
        routing.append(chosen)
    if forced is not None:
        chosen = jnp.asarray(forced).reshape(chosen.shape)
    weight = jnp.take_along_axis(p, chosen, axis=-1)
    if config.get("renormalise_top1"):
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    out = jnp.zeros_like(m)
    for e in range(lp["we_gate"].shape[0]):
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        hid = jax.nn.silu(mm(m, lp["we_gate"][e])) * mm(m, lp["we_up"][e])
        out = out + w_e[:, None] * mm(hid, lp["we_down"][e])
    return out


def scaled_sum(res, x, y):
    return (res[0] * x + res[1]) + (res[2] * y + res[3])


def layer(lp, x, r_prev, config, routing=None, forced=None, keys=None):
    """One layer on x [S, D] -> (x'', the router's state r)."""
    eps = config["rms_norm_eps"]
    y = attention(lp, rms(x, lp["attn_norm"], eps), config, keys)
    x = scaled_sum(lp["res_attn"], x, y)
    m = rms(x, lp["mlp_norm"], eps)
    p, r = router(lp, m, r_prev, config)
    f = experts(lp, m, p, config, routing, forced)
    return scaled_sum(lp["res_moe"], x, f), r


def forward(params, sequences, config, layers=None, routing=None,
            forced=None, keys=None):
    """sequences: a list of token arrays [S_i] -> a list of logits
    [S_i, V] float32, every position (one array in, one array out).

    layers: an iterable of per-layer dicts to walk instead of
    params["layers"] (a caller at published widths hands one layer's
    float32 weights at a time). routing: a list of one list per
    sequence, which receives each layer's own choice of experts [S_i, k];
    keys likewise each layer's (k, v) as the cache would hold them.
    forced: per sequence, a list of [S_i, k] per layer to route by."""
    global _INT8_ACT
    single = not isinstance(sequences, (list, tuple))
    if single:
        sequences = [sequences]
    _INT8_ACT = bool(config.get("int8_activations"))
    try:
        with jax.default_matmul_precision("highest"):
            embed = jnp.asarray(params["embed"], F32)
            xs = [embed[np.asarray(tokens)] for tokens in sequences]
            rs = [None] * len(xs)
            for n, lp in enumerate(layers if layers is not None
                                   else params["layers"]):
                lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
                for i, x in enumerate(xs):
                    xs[i], rs[i] = layer(
                        lp, x, rs[i], config,
                        routing=None if routing is None else routing[i],
                        forced=None if forced is None else forced[i][n],
                        keys=None if keys is None else keys[i])
            norm = jnp.asarray(params["final_norm"], F32)
            head = jnp.asarray(params["lm_head"], F32)
            out = [mm(rms(x, norm, config["rms_norm_eps"]), head)
                   for x in xs]
    finally:
        _INT8_ACT = False
    return out[0] if single else out
