"""Plain float32 reference of Keye-VL-2.0's LANGUAGE MODEL (`model_type:
KeyeVL2`): GQA with an RMSNorm a head on q and k, a learned sparse
indexer that chooses the keys a query attends, M-RoPE over three
position streams, softmax-routed experts with none shared.

Straightforward `jax.numpy`, float32, `default_matmul_precision
("highest")`, a Python loop over layers and over experts, whole-sequence
index scores and a top-k per query computed in blocks of queries (so
that 33k positions fit), attention under the selection as a mask; no
cache, no kernels, no batching, and no import from `cake_tpu.ops`,
`cake_tpu.models.llama` or `cake_tpu.models.moe`.

One layer, on x [S, D] (`rms` with `rms_norm_eps`; every layer alike):

    h   = rms(x, attn_norm)
    qI  = rot_I(h W_qI) -> 16 heads of 64;  kI = rot_I(LayerNorm(h W_kI)) -> [64]
    w   = (h W_w) * 16^-0.5 * 64^-0.5                                 [16]
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])                  s <= t
    S_t = the `topk` largest of I[t, 0..t], ties to the lower index
          (every visible key while t + 1 <= topk)
    q   = rot(rms(h W_q, q_norm)) -> 32 heads of 128                  a head, over head_dim
    k   = rot(rms(h W_k, k_norm)), v = h W_v -> 4 heads of 128
    a[t] = softmax_{s in S_t}(q[t].k[s] / sqrt(hd)) v[s]
    (query head i reads K/V head i // (heads / kv_heads))
    x   = x + concat_heads(a) W_o
    h   = rms(x, mlp_norm)
    p   = softmax(h W_r)                                              float32, all experts
    chosen = the num_experts_per_tok largest of p, ties to the lower index
    g   = p[chosen] (/ their sum if norm_topk_prob)
    x   = x + sum_i g_i E_chosen_i(h)

then logits = rms(x, final_norm) W_head.

rot: M-RoPE. A token has THREE positions (temporal, height, width:
`positions` [3, S]); the head_dim / 2 frequencies theta^(-2i / head_dim)
of a head, on the pairs (i, i + head_dim / 2), are dealt to the streams
by `mrope_section` ([16, 24, 24]: frequencies 0-15 turn by the temporal
position, 16-39 by the height, 40-63 by the width). For text the three
streams are equal (0, 1, 2, ... each) and rot is the ordinary rotation
at every frequency: the default here, and what the served path computes.
rot_I: the indexer's heads of 64 rotated whole, 32 frequencies of their
own width at the same theta, by the temporal stream.

ASSUMED (the catalog fixes widths, counts and switches, not these; the
cell's `cell.json` lists them with where each comes from): the indexer
is the DeepSeek-V3.2 one (LayerNorm with weight and bias, eps 1e-6, on
the key; `w` scaled by heads^-0.5 * dim^-0.5; ties to the lower index),
its query projected from the normed hidden state; the RMSNorm a head on
q and k BEFORE the rotation; pre-norm residual blocks; half-split pairs.

Weights are INPUTS, stored [in, out] (x @ W): a caller comparing an
int8-served model passes the dequantized weights.

params: {"embed", "final_norm", "lm_head", "layers": per-layer dicts}.
config: a mapping with `num_attention_heads`, `num_key_value_heads`,
`head_dim`, `rope_theta`, `rms_norm_eps`, `mrope_section`,
`indexer_num_heads`, `indexer_head_dim`, `topk`, `num_experts_per_tok`,
`norm_topk_prob`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NEG = -1e30
QUERY_BLOCK = 256


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


def text_positions(n: int) -> np.ndarray:
    """[3, n]: the three streams of n text tokens, equal."""
    return np.broadcast_to(np.arange(n), (3, n))


def angles(positions, dim: int, theta: float, section=None) -> np.ndarray:
    """[S, dim / 2] float64: frequency i's angle at each token, by the
    stream `section` deals it to (None: the temporal stream throughout)."""
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    positions = np.asarray(positions, np.float64)
    stream = (np.zeros(dim // 2, int) if section is None
              else np.repeat(np.arange(len(section)), section))
    assert stream.shape[0] == dim // 2, "mrope_section must sum to dim / 2"
    return positions[stream].T * inv[None, :]


def rope(x, ang):
    """x [S, heads, d] rotated by ang [S, d / 2] on the pairs
    (i, i + d / 2)."""
    half = x.shape[-1] // 2
    cos = jnp.asarray(np.cos(ang), F32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_inputs(lp, h, positions, config):
    """(qI [S, nI, dI], kI [S, dI], w [S, nI]) of the whole sequence."""
    S = h.shape[0]
    nI, dI = config["indexer_num_heads"], config["indexer_head_dim"]
    ang = angles(positions, dI, config["rope_theta"])
    qI = rope(mm(h, lp["wi_q"]).reshape(S, nI, dI), ang)
    kI = rope(layernorm(mm(h, lp["wi_k"]), lp["wi_k_norm"],
                        lp["wi_k_bias"])[:, None, :], ang)[:, 0]
    return qI, kI, mm(h, lp["wi_w"]) * (nI ** -0.5) * (dI ** -0.5)


def score_block(qI, kI, w):
    """I [T, S] of a block of queries against every key."""
    dots = jnp.einsum("tjd,sd->tjs", qI, kI)
    return jnp.einsum("tjs,tj->ts", jax.nn.relu(dots), w)


def select_block(scores, lo: int, topk: int):
    """The sets of queries lo .. lo + T - 1 as a mask [T, S]: the topk
    largest of scores[t, 0..t], ties to the lower index; every visible
    key while there are no more than topk."""
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


def sets_as_mask(sets, S: int):
    """Index lists [T, K] (entries outside 0 .. S - 1 are padding) as a
    mask [T, S]."""
    sets = jnp.asarray(sets, jnp.int32)
    inside = (sets >= 0) & (sets < S)
    return jnp.zeros((sets.shape[0], S + 1), bool).at[
        jnp.arange(sets.shape[0])[:, None],
        jnp.where(inside, sets, S)].set(True)[:, :S]


def attend_block(q, k, v, mask):
    """q [T, KV, G, hd] over the keys `mask` [T, S] leaves each."""
    scores = jnp.einsum("tkgd,skd->kgts", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(mask[None, None], scores, NEG)
    return jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, axis=-1), v)


def attention(lp, h, positions, config, forced_sets=None, seen=None,
              keep=None):
    """The layer's attention -> its output [S, D] before the residual.
    forced_sets: index lists [S, K] to attend instead of the indexer's
    choice (a tool compares along another path's sets, so that one
    flipped key does not move every later layer). seen receives the
    indexer's OWN scores and sets, each [n, S], at the positions `keep`
    (None: every position)."""
    S = h.shape[0]
    H, KV, hd = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    eps, topk = config["rms_norm_eps"], config["topk"]
    ang = angles(positions, hd, config["rope_theta"],
                 config["mrope_section"])
    q = rope(rms(mm(h, lp["wq"]).reshape(S, H, hd), lp["q_norm"], eps), ang)
    k = rope(rms(mm(h, lp["wk"]).reshape(S, KV, hd), lp["k_norm"], eps), ang)
    v = mm(h, lp["wv"]).reshape(S, KV, hd)
    q = q.reshape(S, KV, H // KV, hd)
    qI, kI, w = index_inputs(lp, h, positions, config)
    keep = np.arange(S) if keep is None else np.asarray(keep)
    out, scores_seen, sets_seen = [], [], []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(S, lo + QUERY_BLOCK)
        wanted = keep[(keep >= lo) & (keep < hi)] - lo
        mask = None
        if forced_sets is None or (seen is not None and len(wanted)):
            scores = score_block(qI[lo:hi], kI, w[lo:hi])
            mask = select_block(scores, lo, topk)
            if seen is not None and len(wanted):
                scores_seen.append(np.asarray(scores[wanted]))
                sets_seen.append(np.asarray(mask[wanted]))
        if forced_sets is not None:
            mask = sets_as_mask(forced_sets[lo:hi], S)
        out.append(attend_block(q[lo:hi], k, v, mask))
    if seen is not None:
        seen.append({"positions": keep,
                     "scores": np.concatenate(scores_seen),
                     "sets": np.concatenate(sets_seen)})
    return mm(jnp.concatenate(out, 0).reshape(S, H * hd), lp["wo"])


def top_k_stable(scores, k: int):
    """The indices of the k largest of each row, best first, ties to the
    lower index."""
    return jnp.argsort(-scores, axis=-1, stable=True)[:, :k]


def router(lp, h, config, forced=None):
    """(weights [S, k], the experts computed [S, k], this router's own
    choice [S, k]). forced: experts [S, k] to compute instead of the
    router's choice, weighed by THIS router's probabilities of them."""
    probs = jax.nn.softmax(mm(h, lp["router"]), axis=-1)
    order = top_k_stable(probs, config["num_experts_per_tok"])
    chosen = order if forced is None else jnp.asarray(forced)
    weights = jnp.take_along_axis(probs, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, chosen, order


def swiglu(h, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def moe_ffn(lp, h, config, routing=None, forced=None):
    """Every expert on the tokens routed to it (by a weight of zero
    elsewhere). routing receives the router's OWN choice, whatever
    `forced` made it compute."""
    weights, experts, own = router(lp, h, config, forced)
    if routing is not None:
        routing.append(np.asarray(own))
    out = jnp.zeros_like(h)
    for e in range(lp["we_gate"].shape[0]):
        if not bool(jnp.any(experts == e)):
            continue
        w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        out = out + w[:, None] * swiglu(h, lp["we_gate"][e], lp["we_up"][e],
                                        lp["we_down"][e])
    return out


def layer(lp, x, positions, config, routing=None, forced=None,
          forced_sets=None, seen=None, keep=None):
    eps = config["rms_norm_eps"]
    x = x + attention(lp, rms(x, lp["attn_norm"], eps), positions, config,
                      forced_sets, seen, keep)
    return x + moe_ffn(lp, rms(x, lp["mlp_norm"], eps), config, routing,
                       forced)


def forward(params, sequences, config, layers=None, positions=None,
            routing=None, forced=None, selections=None, forced_sets=None,
            keep=None):
    """sequences: a list of token arrays [S_i] -> a list of logits
    [S_i, V] float32, every position (one array in, one array out).

    layers: an iterable of per-layer dicts to walk instead of
    params["layers"] (a generator lets a caller hold one layer's float32
    weights at a time). positions: one [3, S_i] a sequence (None: text).
    routing / selections: lists of one list per sequence, which receive
    each layer's expert indices [S_i, k] (the router's own choice) and
    the indexer's own scores and sets at the positions keep[i]
    (`attention`). forced / forced_sets: one list per sequence of each
    layer's experts [S_i, k] / key sets [S_i, K] to compute instead.
    keep: one array of positions a sequence; given, a sequence's logits
    come back at those positions alone, [len(keep[i]), V] (at 33k
    positions of 152k logits each, all of them would not fit)."""
    single = not isinstance(sequences, (list, tuple))
    if single:
        sequences = [sequences]
        positions = None if positions is None else [positions]
        keep = None if keep is None else [keep]
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], F32)
        xs = [embed[np.asarray(tokens)] for tokens in sequences]
        at = [text_positions(len(s)) if positions is None else positions[i]
              for i, s in enumerate(sequences)]
        for j, lp in enumerate(layers if layers is not None
                               else params["layers"]):
            lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
            for i, x in enumerate(xs):
                xs[i] = layer(
                    lp, x, at[i], config,
                    routing=None if routing is None else routing[i],
                    forced=None if forced is None else forced[i][j],
                    forced_sets=(None if forced_sets is None
                                 else forced_sets[i][j]),
                    seen=None if selections is None else selections[i],
                    keep=None if keep is None else keep[i])
        norm = jnp.asarray(params["final_norm"], F32)
        head = jnp.asarray(params["lm_head"], F32)
        if keep is not None:
            xs = [x[np.asarray(keep[i])] for i, x in enumerate(xs)]
        out = [mm(rms(x, norm, config["rms_norm_eps"]), head) for x in xs]
    return out[0] if single else out
