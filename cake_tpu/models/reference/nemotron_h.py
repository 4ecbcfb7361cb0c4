"""Plain float32 reference of the Nemotron-3 decoder (`model_type:
nemotron_h`): Mamba-2 blocks, LatentMoE blocks, GQA attention blocks
without a positional embedding, one mixer a block.

Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, a Python loop over blocks and over experts, the Mamba
recurrence TOKEN BY TOKEN (`lax.scan` over time; no chunked form),
whole-sequence attention in blocks of queries; no cache, no kernels, no
batching, and no import from `cake_tpu.ops`, `cake_tpu.models.llama` or
`cake_tpu.models.moe`.

On x [S, D], `rms` with `rms_norm_eps`, every block
`x = x + mixer(rms(x, norm))`:

    M  (Mamba-2; H heads of P, G groups, state N, d_inner = H P)
       [z d_inner | xBC d_inner + 2 G N | dt H] = h W_in
       xBC_t  = silu(sum_j w_conv[:, j] xBC_{t-K+1+j} + b_conv)   causal, depthwise;
                the K-1 inputs before t = 0 are zeros
       [xs (H, P) | B (G, N) | C (G, N)] = xBC_t
       dt_t   = softplus(dt_t + dt_bias);  A = -exp(A_log)
       S_t[h] = exp(dt_t A)[h] S_{t-1}[h] + dt_t[h] xs_t[h] (x) B_t[g(h)]   S_{-1} = 0, g(h) = h // (H/G)
       y_t[h] = S_t[h] C_t[g(h)] + D[h] xs_t[h]
       y_t    = rms_group(y_t * silu(z_t), w_norm)            RMS over each of the G groups
       out    = y_t W_out
    *  q = h W_q -> H_a x hd;  k, v = h W_k, h W_v -> KV x hd;  NO positional embedding
       a[t] = softmax_{s <= t}(q.k[s] / sqrt(hd)) v[s], heads H_a/KV to a KV head
       out = concat(a) W_o
    E  s = sigmoid(h W_r)   float32, all the router's experts
       chosen = top-k of (s + router_bias), ties to the lower index
       g = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
       u = h W_fc1   [latent];  E_i(u) = relu(u W_up_i)^2 W_down_i
       out = (sum_i g_i E_chosen_i(u)) W_fc2 + relu(h Ws_up)^2 Ws_down

then logits = rms(x, final_norm) W_head.

THE SHARE. `held = (first, count)` gives the reference one chip's share
of a block's routed experts: the router keeps its width and its k, the
experts `first .. first+count-1` are computed for the tokens routed to
them, what the absent experts would add is left out (in a deployment
the partial sums meet in the latent, before W_fc2). `shared=False`
leaves the shared expert out, `latent_out=False` returns the share's
sum in the LATENT (before W_fc2): what the test that adds the shares up
sums.

Assumed, each also in the cell's `cell.json`: the router reads the full
hidden state and the experts the latent; no positional embedding in the
attention blocks; ties in the top-k to the lower index. Weights are
INPUTS, stored [in, out] (x @ W): a caller comparing an int8-served
model passes the dequantized weights.

params: {"embed", "final_norm", "lm_head", "layers": per-block dicts,
each with "kind" in "M", "E", "*" and "norm"}. config: a mapping with
`rms_norm_eps`, `mamba_num_heads`, `mamba_head_dim`, `n_groups`,
`ssm_state_size`, `num_attention_heads`, `num_key_value_heads`,
`num_experts_per_tok`, `norm_topk_prob`, `routed_scaling_factor`, and a
tool's switches, each of which must FAIL a comparison with the model
(chip_compare.py): `scoring_func` "softmax"; `expert_act` "swiglu"
(silu(a) * a in place of relu(a)^2); `attn_rope_theta` (rotate q and
k); `ssm_state_dtype` "bfloat16" (round the carried state every token);
`conv_window` W (drop the conv's tail at every multiple of W);
`int8_activations` (round every matmul's input to 8 bits per row).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 512
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


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba(lp, h, config, state=None, tail=None, final=None):
    """One Mamba-2 mixer on h [S, D]. state [H, P, N] / tail [K-1, conv]:
    what the sequence starts from (None = zeros). final: a list that
    receives (S_last, the last K-1 conv inputs)."""
    out, S_last, last = mamba_core(lp, h, config, state, tail)
    if final is not None:
        final.append((S_last, last))
    return out


def mamba_core(lp, h, config, state, tail):
    """mamba without its side effect -> (out, S_last, the last K-1 conv
    inputs): what a tool may put under jit."""
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
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
    y = y + lp["D"][None, :, None] * xs
    y = (y.reshape(S, di) * jax.nn.silu(z)).reshape(S, G, di // G)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                      + config["rms_norm_eps"])
    return (mm(y.reshape(S, di) * lp["ssm_norm"][None, :], lp["w_out"]),
            S_last, padded[-(K - 1):])


def _rope(x, theta):
    """Rotate-half RoPE at positions 0..S-1 on x [S, heads, hd] (only
    for the altered reference that must fail)."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def attention(lp, h, config, keys=None):
    """keys: a list that receives the block's keys [S, KV * hd] as
    attended (rotated, in the altered reference that rotates)."""
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    S = h.shape[0]
    q = mm(h, lp["wq"]).reshape(S, H, -1)
    k = mm(h, lp["wk"]).reshape(S, KV, -1)
    v = mm(h, lp["wv"]).reshape(S, KV, -1)
    hd = q.shape[-1]
    theta = config.get("attn_rope_theta")
    if theta:
        q, k = _rope(q, theta), _rope(k, theta)
    if keys is not None:
        keys.append(k.reshape(S, -1))
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    out = []
    for t0 in range(0, S, QUERY_BLOCK):
        t1 = min(S, t0 + QUERY_BLOCK)
        s = jnp.einsum("thd,shd->hts", q[t0:t1], k[:t1],
                       precision=lax.Precision.HIGHEST) / np.sqrt(hd)
        mask = jnp.arange(t1)[None, :] <= jnp.arange(t0, t1)[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, NEG), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", p, v[:t1],
                              precision=lax.Precision.HIGHEST))
    return mm(jnp.concatenate(out, 0).reshape(S, -1), lp["wo"])


def router(lp, h, config):
    """(weights [S, k], experts [S, k]) over ALL the router's experts."""
    k = config["num_experts_per_tok"]
    logits = mm(h, lp["router"]) if not _INT8_ACT else jnp.dot(
        h, lp["router"], precision=lax.Precision.HIGHEST)
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


def expert(u, w_up, w_down, config):
    a = mm(u, w_up)
    if config.get("expert_act", "relu2") == "swiglu":
        return mm(jax.nn.silu(a) * a, w_down)
    return mm(relu2(a), w_down)


def latent_moe(lp, h, config, held=None, shared=True, latent_out=True,
               routing=None):
    """The held experts on the tokens routed to them, in the latent,
    then W_fc2, plus the shared expert on h itself."""
    weights, experts = router(lp, h, config)
    if routing is not None:
        routing.append(np.asarray(experts))
    first = 0 if held is None else held[0]
    u = mm(h, lp["w_fc1"])
    acc = jnp.zeros_like(u)
    for e in range(lp["we_up"].shape[0]):
        if not bool(jnp.any(experts == first + e)):
            continue
        w = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=1)
        acc = acc + w[:, None] * expert(u, lp["we_up"][e], lp["we_down"][e],
                                        config)
    if not latent_out:
        return acc
    out = mm(acc, lp["w_fc2"])
    if shared:
        out = out + expert(h, lp["ws_up"], lp["ws_down"], config)
    return out


def block(lp, x, config, held=None, routing=None, states=None, start=None,
          keys=None):
    """One block on x [S, D]. start: (state, tail) a Mamba block starts
    from (None = zeros)."""
    h = rms(x, lp["norm"], config["rms_norm_eps"])
    kind = lp["kind"]
    if kind == "M":
        state, tail = start if start is not None else (None, None)
        return x + mamba(lp, h, config, state, tail, states)
    if kind == "*":
        return x + attention(lp, h, config, keys)
    return x + latent_moe(lp, h, config, held, routing=routing)


def forward(params, sequences, config, layers=None, held=None,
            routing=None, states=None, starts=None, keys=None):
    """sequences: a list of token arrays [S_i] -> a list of logits
    [S_i, V] float32, every position (one array in, one array out).

    layers: an iterable of per-block dicts to walk instead of
    params["layers"]. held: (first, count) of the routed experts the
    `we_*` leaves hold. routing / states: lists of one list per
    sequence, which receive each E block's expert indices [S_i, k] and
    each Mamba block's (final state, final conv tail); keys likewise
    each attention block's keys [S_i, KV * hd]. starts: per
    sequence, a list of (state, tail) per Mamba block to start from
    (the altered reference whose second request inherits the first's
    state)."""
    global _INT8_ACT
    single = not isinstance(sequences, (list, tuple))
    if single:
        sequences = [sequences]
    _INT8_ACT = bool(config.get("int8_activations"))
    try:
        with jax.default_matmul_precision("highest"):
            embed = jnp.asarray(params["embed"], F32)
            xs = [embed[np.asarray(tokens)] for tokens in sequences]
            n_m = 0
            for lp in (layers if layers is not None else params["layers"]):
                lp = {k: (v if k == "kind" else jnp.asarray(v, F32))
                      for k, v in lp.items()}
                for i, x in enumerate(xs):
                    start = None
                    if lp["kind"] == "M" and starts is not None \
                            and starts[i] is not None:
                        start = starts[i][n_m]
                    xs[i] = block(
                        lp, x, config, held=held,
                        routing=None if routing is None else routing[i],
                        states=None if states is None else states[i],
                        start=start,
                        keys=None if keys is None else keys[i])
                n_m += lp["kind"] == "M"
            norm = jnp.asarray(params["final_norm"], F32)
            head = jnp.asarray(params["lm_head"], F32)
            out = [mm(rms(x, norm, config["rms_norm_eps"]), head)
                   for x in xs]
    finally:
        _INT8_ACT = False
    return out[0] if single else out
