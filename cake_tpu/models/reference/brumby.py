"""Plain float32 reference of the Brumby decoder (`model_type: brumby`):
Qwen3's dense block with the softmax replaced by power retention of
degree 2 in every layer.

Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, a Python loop over layers, the retention layer in its
QUADRATIC form (the weights (q . k)^2 under the decay, a [T, T] matrix
in blocks of queries): no feature map, no state, no cache, no kernels,
no batching, and no import from `cake_tpu.ops`, `cake_tpu.models.llama`
or `cake_tpu.models.moe`.

On x [S, D], `rms` with `rms_norm_eps`, H query heads over KV heads of
hd, head a in group j = a // (H / KV):

    x = E[ids]
    every layer:
        h   = rms(x, norm)
        q^a = rope_t(rms_hd(h W_q^a, q_norm))      k^j likewise;  v^j = h W_v^j
        log gamma_t^j = logsigmoid(h w_g^j + b_g^j)
        w_ts  = (q_t^a . k_s^j / sqrt(hd))^2 * prod_{r = s+1 .. t} gamma_r^j     s <= t
        y_t^a = sum_s w_ts v_s^j / (sum_s w_ts + 1e-6)
        x = x + concat_a(y_t^a) W_o
        u = rms(x, mlp_norm);   x = x + (silu(u W_gate) * (u W_up)) W_down
    logits = rms(x, final_norm) W_head                 (untied)

The same layer is a recurrence over a state a K/V head: with phi the
symmetric square of a head (entries a_i a_j for i <= j, the off-
diagonal ones times sqrt 2: phi(a) . phi(b) = (a . b)^2),

    S_t = gamma_t S_{t-1} + phi(k_t) v_t^T;   z_t = gamma_t z_{t-1} + phi(k_t)
    y_t^a = phi(q_t^a)^T S_t / (phi(q_t^a)^T z_t + hd * 1e-6)

which `form: "recurrent"` computes token by token (`lax.scan`; what the
tests hold the quadratic form and the served window form to, and what a
tool's `state_dtype` switch rounds).

Departures from the published description, and what is held from it and
NOT from config.json (which has no key for the mixer; each is in the
cell's `cell.json` under `assumed`): the degree is 2; the gate is one
scalar a K/V head and token, logsigmoid of a linear map of the layer's
normed input with a bias, in float32; the output is divided by the sum
of its weights + 1e-6 and meets no norm layer; Qwen3's RMS norm a head
and the rotation (rotate-half, the whole head, `rope_theta`) stay in
front of the mixer, the scale 1/sqrt(hd) inside the square. Nothing on
the machine this was written on holds the paper (arXiv:2507.04239) or
the `retention` package: a later change that has the published
modelling code corrects whichever differs. Weights are INPUTS, stored
[in, out] (x @ W): a caller comparing an int8-served model passes the
dequantized weights.

params: {"embed", "final_norm", "lm_head", "layers": per-layer dicts
with "norm", "wq", "wk", "wv", "q_norm", "k_norm", "w_g", "b_g", "wo",
"mlp_norm", "w_gate", "w_up", "w_down"}. config: a mapping with
`rms_norm_eps`, `num_attention_heads`, `num_key_value_heads`,
`head_dim`, `rope_theta`, and a tool's switches, each of which must FAIL
a comparison with the model (tests/test_brumby.py, chip_compare.py):
`form` "recurrent" with `state_dtype` "bfloat16" (round the carried
state every token) or with `read_dtype` "bfloat16" (the state stays
float32 and is READ at the matrix unit's one-pass precision: phi(q), S
and z are rounded to bfloat16 as operands of the two products, which
add up in float32; the carried state's read and, since S_t holds the
window's own keys too, a window's products at default precision);
`gate` False (gamma = 1); `normaliser` False (no division); `rope`
False (no rotation); `degree` 1 (the weight q . k / sqrt(hd) itself;
the quadratic form's alone).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 512
EPS = 1e-6


def mm(x, w):
    return jnp.dot(x, w, precision=lax.Precision.HIGHEST,
                   preferred_element_type=F32)


def rms(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, theta, positions):
    """Rotate-half RoPE on x [S, heads, hd] at `positions` [S]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.asarray(positions, F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def phi(x):
    """The symmetric square of x [..., hd] -> [..., hd (hd + 1) / 2]."""
    i, j = np.triu_indices(x.shape[-1])
    return x[..., i] * x[..., j] * np.where(i == j, 1.0, np.sqrt(2.0)).astype(
        np.float32)


def project(lp, h, config):
    """h [S, D] -> q [S, H, hd], k, v [S, KV, hd], log gamma [S, KV]."""
    S = h.shape[0]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    eps = config["rms_norm_eps"]
    q = rms(mm(h, lp["wq"]).reshape(S, H, -1), lp["q_norm"], eps)
    k = rms(mm(h, lp["wk"]).reshape(S, KV, -1), lp["k_norm"], eps)
    v = mm(h, lp["wv"]).reshape(S, KV, -1)
    if config.get("rope", True):
        at = jnp.arange(S)
        q = rope(q, config["rope_theta"], at)
        k = rope(k, config["rope_theta"], at)
    lg = jax.nn.log_sigmoid(mm(h, lp["w_g"]) + lp["b_g"][None, :])
    if not config.get("gate", True):
        lg = jnp.zeros_like(lg)
    return q, k, v, lg


def weigh(scores, config):
    """q . k / sqrt(hd) -> the weight before the decay."""
    return scores * scores if config.get("degree", 2) == 2 else scores


def normalise(num, den, config):
    return num / (den + EPS) if config.get("normaliser", True) else num


def quadratic(q, k, v, lg, config, before=None):
    """y [S, H, hd]. before: (k, v, log gamma) of the tokens that the
    state held when the sequence began (None: nothing)."""
    S, H, hd = q.shape
    R = H // k.shape[1]
    if before is not None:
        k, v, lg = (jnp.concatenate([a, b], 0)
                    for a, b in zip(before, (k, v, lg)))
    P = k.shape[0] - S
    cum = jnp.repeat(jnp.cumsum(lg, axis=0), R, axis=1)       # [P + S, H]
    k, v = jnp.repeat(k, R, axis=1), jnp.repeat(v, R, axis=1)
    out = []
    for t0 in range(0, S, QUERY_BLOCK):
        t1 = min(S, t0 + QUERY_BLOCK)
        s = jnp.einsum("thd,shd->hts", q[t0:t1], k[:P + t1],
                       precision=lax.Precision.HIGHEST) / np.sqrt(hd)
        mask = (jnp.arange(P + t1)[None, :]
                <= P + jnp.arange(t0, t1)[:, None])
        decay = cum[P + t0:P + t1].T[:, :, None] - cum[:P + t1].T[:, None, :]
        w = jnp.where(mask[None], weigh(s, config)
                      * jnp.exp(jnp.where(mask[None], decay, 0.0)), 0.0)
        num = jnp.einsum("hts,shd->thd", w, v[:P + t1],
                         precision=lax.Precision.HIGHEST)
        out.append(normalise(num, jnp.sum(w, axis=2).T[:, :, None], config))
    return jnp.concatenate(out, 0)


def recurrent(q, k, v, lg, config, state=None):
    """The same layer (degree 2) token by token -> (y [S, H, hd], (S_T,
    z_T)). state: (S [KV, hd (hd + 1) / 2, hd], z [KV, hd (hd + 1) / 2])
    to start from (None: zeros)."""
    S, H, hd = q.shape
    KV = k.shape[1]
    dtype = jnp.dtype(config.get("state_dtype", "float32"))
    read = jnp.dtype(config.get("read_dtype", "float32"))
    D = hd * (hd + 1) // 2
    if state is None:
        state = (jnp.zeros((KV, D, hd), F32), jnp.zeros((KV, D), F32))

    def token(carry, x):
        St, zt = carry
        qt, kt, vt, lgt = x
        g, pk = jnp.exp(lgt), phi(kt)
        St = g[:, None, None] * St + pk[:, :, None] * vt[:, None, :]
        zt = g[:, None] * zt + pk
        St, zt = (a.astype(dtype).astype(F32) for a in (St, zt))
        pq, Sr, zr = (a.astype(read).astype(F32) for a in (
            phi(qt).reshape(KV, H // KV, -1), St, zt))
        num = jnp.einsum("grd,gdv->grv", pq, Sr,
                         precision=lax.Precision.HIGHEST)
        den = jnp.einsum("grd,gd->gr", pq, zr,
                         precision=lax.Precision.HIGHEST)
        return (St, zt), normalise(num / hd, den[..., None] / hd,
                                   config).reshape(H, hd)

    state, y = lax.scan(token, state, (q, k, v, lg))
    return y, state


def layer(lp, x, config, before=None, kept=None):
    """One layer on x [S, D]. before: what `quadratic` takes (the
    altered reference whose request inherits another's state); kept: a
    list that receives the layer's (k, v, log gamma), and under `form`
    "recurrent" the state it leaves as a fourth entry."""
    eps = config["rms_norm_eps"]
    q, k, v, lg = project(lp, rms(x, lp["norm"], eps), config)
    if config.get("form", "quadratic") == "recurrent":
        y, state = recurrent(q, k, v, lg, config)
        if kept is not None:
            kept.append((k, v, lg, state))
    else:
        y = quadratic(q, k, v, lg, config, before)
        if kept is not None:
            kept.append((k, v, lg))
    x = x + mm(y.reshape(x.shape[0], -1), lp["wo"])
    u = rms(x, lp["mlp_norm"], eps)
    return x + mm(jax.nn.silu(mm(u, lp["w_gate"])) * mm(u, lp["w_up"]),
                  lp["w_down"])


def forward(params, sequences, config, layers=None, kept=None, before=None,
            keep=None):
    """sequences: a list of token arrays [S_i] -> a list of logits
    [S_i, V] float32, every position (one array in, one array out).

    layers: an iterable of per-layer dicts to walk instead of
    params["layers"]. kept: a list of one list per sequence, which
    receives what `layer` hands it, a layer an entry. before: per
    sequence, a list of (k, v, log gamma) a layer (another sequence's
    `kept`), or None. keep: per sequence, the positions whose logits are
    returned (None: every position)."""
    single = not isinstance(sequences, (list, tuple))
    if single:
        sequences = [sequences]
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], F32)
        xs = [embed[np.asarray(tokens)] for tokens in sequences]
        for n, lp in enumerate(layers if layers is not None
                               else params["layers"]):
            lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
            for i, x in enumerate(xs):
                xs[i] = layer(
                    lp, x, config,
                    None if before is None or before[i] is None
                    else before[i][n][:3],
                    None if kept is None else kept[i])
        if keep is not None:
            xs = [x[np.asarray(at)] for x, at in zip(xs, keep)]
        norm = jnp.asarray(params["final_norm"], F32)
        head = jnp.asarray(params["lm_head"], F32)
        out = [mm(rms(x, norm, config["rms_norm_eps"]), head) for x in xs]
    return out[0] if single else out
