"""Plain float32 reference of the Granite-4.0-H decoder (`model_type:
granitemoehybrid`, its dense members): every layer a mixer (Mamba-2, or
GQA attention without a positional embedding) AND a dense SwiGLU, each
branch scaled by `residual_multiplier`.

Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, a Python loop over layers, the Mamba recurrence TOKEN BY
TOKEN (`lax.scan` over time; no chunked form), whole-sequence attention
in blocks of queries; no cache, no kernels, no batching, and no import
from `cake_tpu.ops`, `cake_tpu.models.llama` or `cake_tpu.models.moe`.

On x [S, D], `rms` with `rms_norm_eps`:

    x = E[ids] * embedding_multiplier
    every layer:
        x = x + residual_multiplier * mixer(rms(x, norm))
        x = x + residual_multiplier * mlp(rms(x, mlp_norm))
    mlp    [g | u] = h W_mlp_in;  out = (silu(g) * u) W_mlp_out
    mamba  (H heads of P, G groups, state N, d_inner = H P)
           [z d_inner | xBC d_inner + 2 G N | dt H] = h W_in
           xBC_t  = silu(sum_j w_conv[:, j] xBC_{t-K+1+j} + b_conv)   causal, depthwise;
                    the K-1 inputs before t = 0 are zeros
           [xs (H, P) | B (G, N) | C (G, N)] = xBC_t
           dt_t   = softplus(dt_t + dt_bias);  A = -exp(A_log)
           S_t[h] = exp(dt_t A)[h] S_{t-1}[h] + dt_t[h] xs_t[h] (x) B_t[g(h)]   S_{-1} = 0, g(h) = h // (H/G)
           y_t[h] = S_t[h] C_t[g(h)] + D[h] xs_t[h]
           y_t    = rms_group(y_t * silu(z_t), w_norm)    the gate BEFORE the norm; RMS over each of the G groups
           out    = y_t W_out
    attention  q = h W_q -> H_a x hd;  k, v = h W_k, h W_v -> KV x hd;  NO positional embedding
           a[t] = softmax_{s <= t}(q.k[s] * attention_multiplier) v[s], heads H_a/KV to a KV head
           out = concat(a) W_o
    logits = rms(x, final_norm) W_head / logits_scaling      W_head = E^T (tied)

Assumed, each also in the cell's `cell.json`: the gate before the norm
and the norm over each group (one group at the published sizes); no
upper clamp on dt; zeros before a sequence's first token. Weights are
INPUTS, stored [in, out] (x @ W): a caller comparing an int8-served
model passes the dequantized weights.

params: {"embed", "final_norm", "lm_head", "layers": per-layer dicts,
each with "kind" in "mamba", "attention", "norm", "mlp_norm",
"w_mlp_in", "w_mlp_out" and the mixer's leaves}. config: a mapping with
`rms_norm_eps`, `mamba_n_heads`, `mamba_d_head`, `mamba_n_groups`,
`mamba_d_state`, `num_attention_heads`, `num_key_value_heads`,
`embedding_multiplier`, `attention_multiplier`, `residual_multiplier`,
`logits_scaling`, and a tool's switches, each of which must FAIL a
comparison with the model (tests/test_granite_hybrid.py,
chip_compare.py): `ssm_state_dtype` "bfloat16" (round the carried state
every token); `conv_window` W (drop the conv's tail at every multiple of
W); `gate_after_norm` (rms(y) * silu(z)); `attn_rope_theta` (rotate q
and k); a multiplier handed over as 1, or `attention_multiplier` as
1/sqrt(hd), is the altered model too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 512
NEG = -1e30


def mm(x, w):
    return jnp.dot(x, w, precision=lax.Precision.HIGHEST,
                   preferred_element_type=F32)


def rms(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def mamba(lp, h, config, state=None, tail=None):
    """One Mamba-2 mixer on h [S, D] -> (out, S_last, the last K-1 conv
    inputs). state [H, P, N] / tail [K-1, conv]: what the sequence
    starts from (None = zeros)."""
    H, P = config["mamba_n_heads"], config["mamba_d_head"]
    G, N = config["mamba_n_groups"], config["mamba_d_state"]
    S = h.shape[0]
    di = H * P
    K = lp["conv_w"].shape[1]
    zxd = mm(h, lp["w_in"])
    z, xBC, dt = zxd[:, :di], zxd[:, di:di + di + 2 * G * N], zxd[:, -H:]
    if tail is None:
        tail = jnp.zeros((K - 1, xBC.shape[1]), F32)
    padded = jnp.concatenate([tail, xBC], 0)
    window = config.get("conv_window")
    conv = lp["conv_b"][None, :]
    for j in range(K):
        xj = padded[j:j + S]
        if window:
            # a served path that dropped the tail at window edges would
            # see zeros before each multiple of the window
            t = jnp.arange(S)[:, None]
            xj = jnp.where((t % window) >= K - 1 - j, xj, 0.0)
        conv = conv + lp["conv_w"][:, j][None, :] * xj
    conv = jax.nn.silu(conv)
    xs = conv[:, :di].reshape(S, H, P)
    Bm = jnp.repeat(conv[:, di:di + G * N].reshape(S, G, N), H // G, axis=1)
    Cm = jnp.repeat(conv[:, di + G * N:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"][None, :])             # [S, H]
    dA = jnp.exp(dt * -jnp.exp(lp["A_log"])[None, :])
    # (reduce_precision: a convert pair to bfloat16 and back is removed
    # by a compiler that allows excess precision)
    bf16_state = config.get("ssm_state_dtype", "float32") == "bfloat16"

    def step(S_prev, inp):
        dA_t, dt_t, x_t, B_t, C_t = inp
        S_t = (dA_t[:, None, None] * S_prev
               + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        if bf16_state:
            S_t = lax.reduce_precision(S_t, exponent_bits=8, mantissa_bits=7)
        return S_t, jnp.einsum("hpn,hn->hp", S_t, C_t,
                               precision=lax.Precision.HIGHEST)

    S0 = jnp.zeros((H, P, N), F32) if state is None else state
    S_last, y = lax.scan(step, S0, (dA, dt, xs, Bm, Cm))
    y = (y + lp["D"][None, :, None] * xs).reshape(S, di)
    gate = jax.nn.silu(z)

    def norm(v):
        v = v.reshape(S, G, di // G)
        return (v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                              + config["rms_norm_eps"])).reshape(S, di)

    y = norm(y) * gate if config.get("gate_after_norm") else norm(y * gate)
    return (mm(y * lp["ssm_norm"][None, :], lp["w_out"]), S_last,
            padded[-(K - 1):])


def _rope(x, theta):
    """Rotate-half RoPE at positions 0..S-1 on x [S, heads, hd] (only
    for the altered reference that must fail)."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def attention(lp, h, config):
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    S = h.shape[0]
    q = mm(h, lp["wq"]).reshape(S, H, -1)
    k = mm(h, lp["wk"]).reshape(S, KV, -1)
    v = mm(h, lp["wv"]).reshape(S, KV, -1)
    theta = config.get("attn_rope_theta")
    if theta:
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    out = []
    for t0 in range(0, S, QUERY_BLOCK):
        t1 = min(S, t0 + QUERY_BLOCK)
        s = jnp.einsum("thd,shd->hts", q[t0:t1], k[:t1],
                       precision=lax.Precision.HIGHEST
                       ) * config["attention_multiplier"]
        mask = jnp.arange(t1)[None, :] <= jnp.arange(t0, t1)[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, NEG), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", p, v[:t1],
                              precision=lax.Precision.HIGHEST))
    return mm(jnp.concatenate(out, 0).reshape(S, -1), lp["wo"])


def mlp(h, w_in, w_out):
    """The dense SwiGLU: w_in [D, 2 F] fused [gate | up]."""
    gu = mm(h, w_in)
    F = gu.shape[1] // 2
    return mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], w_out)


def layer(lp, x, config, start=None, states=None):
    """One layer on x [S, D]. start: (state, tail) a Mamba mixer starts
    from (None = zeros); states: a list that receives a Mamba mixer's
    (final state, final conv tail)."""
    eps, r = config["rms_norm_eps"], config["residual_multiplier"]
    h = rms(x, lp["norm"], eps)
    if lp["kind"] == "mamba":
        out, S_last, tail = mamba(lp, h, config, *(start or (None, None)))
        if states is not None:
            states.append((S_last, tail))
    else:
        out = attention(lp, h, config)
    x = x + r * out
    return x + r * mlp(rms(x, lp["mlp_norm"], eps), lp["w_mlp_in"],
                       lp["w_mlp_out"])


def forward(params, sequences, config, layers=None, states=None,
            starts=None):
    """sequences: a list of token arrays [S_i] -> a list of logits
    [S_i, V] float32, every position (one array in, one array out).

    layers: an iterable of per-layer dicts to walk instead of
    params["layers"]. states: a list of one list per sequence, which
    receives each Mamba mixer's (final state, final conv tail). starts:
    per sequence, a list of (state, tail) per Mamba mixer to start from
    (the altered reference whose request inherits another's state)."""
    single = not isinstance(sequences, (list, tuple))
    if single:
        sequences = [sequences]
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], F32)
        xs = [embed[np.asarray(tokens)] * config["embedding_multiplier"]
              for tokens in sequences]
        n_m = 0
        for lp in (layers if layers is not None else params["layers"]):
            lp = {k: (v if k == "kind" else jnp.asarray(v, F32))
                  for k, v in lp.items()}
            is_mamba = lp["kind"] == "mamba"
            for i, x in enumerate(xs):
                start = None
                if is_mamba and starts is not None and starts[i] is not None:
                    start = starts[i][n_m]
                xs[i] = layer(lp, x, config, start,
                              None if states is None else states[i])
            n_m += is_mamba
        norm = jnp.asarray(params["final_norm"], F32)
        head = jnp.asarray(params["lm_head"], F32)
        out = [mm(rms(x, norm, config["rms_norm_eps"]), head)
               / config["logits_scaling"] for x in xs]
    return out[0] if single else out
