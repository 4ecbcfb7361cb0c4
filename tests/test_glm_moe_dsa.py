"""GLM-5.2 (`glm_moe_dsa`) at a tiny size on seeded weights: the served
path (mixed-step prefill in windows, decode through the latent page
pool, decode rows beside prefilling ones) against the plain float32
reference's full forward; the pieces one by one (absorbed against
up-projected attention, the shared layers' key sets, the shares of a
sparse layer, the routing rule); and the engine around them.

`index_topk` is 8 and the contexts run to 60 tokens, so every query
past the first eight drops most of its keys."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import (
    PagedKVCache, mixed_token_buckets, pack_plan,
)
from cake_tpu.models.moe import glm_dsa
from cake_tpu.models.moe.config import GlmMoeDsaConfig
from cake_tpu.models.moe.params import init_params
from cake_tpu.models.reference import glm_moe_dsa as ref
from cake_tpu.ops import mla_attention as mla
from cake_tpu.ops import moe as moe_ops
from cake_tpu.ops.quant import QTensor

B, C, PAGE, MAX_SEQ = 4, 8, 8, 64
REF_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rms_norm_eps", "rope_theta", "index_n_heads",
            "index_head_dim", "index_topk", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "scoring_func")


def ref_config(c, **over):
    return dict({k: getattr(c, k) for k in REF_KEYS}, **over)


def dequantized(leaf):
    if isinstance(leaf, QTensor):
        return (leaf.q.astype(jnp.float32)
                * jnp.expand_dims(leaf.scale, leaf.q.ndim - 2))
    return jnp.asarray(leaf, jnp.float32)


def ref_layers(params, c):
    """The per-layer float32 dicts the reference walks."""
    out = []
    for i in range(c.num_hidden_layers):
        lp = glm_dsa.layer_leaves(params["blocks"], c, i)
        out.append({
            k: dequantized(jax.tree.map(lambda a: a[int(v.layer)], v.stacked)
                           if isinstance(v, moe_ops.LayerOf) else v)
            for k, v in lp.items()})
    return out


def ref_params(params, c):
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": dequantized(params["lm_head"]),
            "layers": ref_layers(params, c)}


@pytest.fixture(scope="module")
def model():
    c = GlmMoeDsaConfig.tiny_glm()
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    return c, params, RopeTables.create(c, MAX_SEQ)


def fresh_cache(c):
    cache = PagedKVCache.create(c, B, 1 + B * (MAX_SEQ // PAGE), PAGE,
                                MAX_SEQ, dtype=jnp.float32)
    table = np.stack([1 + b * (MAX_SEQ // PAGE) + np.arange(MAX_SEQ // PAGE)
                      for b in range(B)]).astype(np.int32)
    return cache._replace(table=jnp.asarray(table))


def serve(model, sequences, prompts, attn="fold", company=True):
    """Every sequence through the step programs: prompts in C-wide
    windows, one window a dispatch, the rows that finished their prompt
    riding the other rows' mixed steps as one-token rows (when
    `company`), then the decode program. Returns per sequence
    {position: logits}, and the selections [L_full][position] -> set."""
    c, params, rope = model
    T = mixed_token_buckets(B, C, (1,))[-1]
    cache = fresh_cache(c)
    off = [0] * len(sequences)
    got = [dict() for _ in sequences]
    sets = [dict() for _ in sequences]

    def keep(b, position, x, out, col=None):
        """col: the token's index in the dispatch's window, or None for
        a row's single token."""
        got[b][position] = np.asarray(x)
        if col is None:
            n = int(out.n_selected[b])
            sets[b][position] = [
                set(np.asarray(out.selected[f, b, :n]).tolist())
                for f in range(out.selected.shape[0])]
        else:
            sets[b][position] = [
                set(np.flatnonzero(out.selected_window[f, col]).tolist())
                for f in range(out.selected_window.shape[0])]

    head = params["lm_head"]
    while any(off[b] < prompts[b] for b in range(len(sequences))):
        b0 = next(b for b in range(len(sequences)) if off[b] < prompts[b])
        toks = np.zeros((B, C), np.int32)
        pos = np.zeros(B, np.int32)
        qlen = np.zeros(B, np.int32)
        for b, seq in enumerate(sequences):
            if b == b0:
                n = min(C, prompts[b] - off[b])
            elif company and prompts[b] <= off[b] < len(seq):
                n = 1
            else:
                continue
            toks[b, :n], pos[b], qlen[b] = seq[off[b]:off[b] + n], off[b], n
        active = qlen > 0
        out, plan = jax.jit(
            glm_dsa.mixed_trunk, static_argnames=("config", "attn",
                                                  "n_tokens"))(
            params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(qlen),
            jnp.asarray(active), cache, rope, config=c, attn=attn,
            n_tokens=T)
        cache = out.cache
        logits = out.x @ head
        for b in np.flatnonzero(qlen):
            for j in range(qlen[b]):
                keep(b, off[b] + j, logits[int(plan.start[b]) + j], out,
                     j if qlen[b] > 1 else None)
            off[b] += int(qlen[b])
    while any(off[b] < len(s) for b, s in enumerate(sequences)):
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        for b, seq in enumerate(sequences):
            if off[b] < len(seq):
                toks[b, 0], pos[b], active[b] = seq[off[b]], off[b], True
        out = jax.jit(glm_dsa.decode_trunk,
                      static_argnames=("config", "attn"))(
            params, jnp.asarray(toks), cache, jnp.asarray(pos),
            jnp.asarray(active), rope, config=c, attn=attn)
        cache = out.cache
        logits = out.x @ head
        for b in np.flatnonzero(active):
            keep(b, off[b], logits[b], out)
            off[b] += 1
    return got, sets


@pytest.fixture(scope="module")
def traffic(model):
    c = model[0]
    rng = np.random.default_rng(0)
    prompts = (37, 9, 52)
    sequences = [rng.integers(0, c.vocab_size, p + 8) for p in prompts]
    return sequences, prompts


@pytest.fixture(scope="module")
def reference_run(model, traffic):
    c, params, _ = model
    sequences, _ = traffic
    selections = [[] for _ in sequences]
    routing = [[] for _ in sequences]
    logits = ref.forward(ref_params(params, c), sequences, ref_config(c),
                         selections=selections, routing=routing)
    return [np.asarray(x) for x in logits], selections


@pytest.fixture(scope="module")
def served_run(model, traffic):
    return serve(model, *traffic)


@pytest.mark.parametrize("row", [0, 1, 2])
def test_served_path_matches_the_reference_forward(
        served_run, reference_run, traffic, row):
    """Prefill in windows, then decode through the cache, decode rows
    beside prefilling ones: every position's logits."""
    got, want = served_run[0][row], reference_run[0][row]
    assert sorted(got) == list(range(len(traffic[0][row])))
    for position, logits in got.items():
        np.testing.assert_allclose(logits, want[position], atol=2e-5,
                                   err_msg=f"position {position}")


@pytest.mark.parametrize("full_layer", [0, 1])
def test_selected_key_sets_are_the_references(
        served_run, reference_run, traffic, full_layer):
    """The indexer's exact top-k, per query: the served path's list is
    the reference's mask, where contexts pass index_topk several times
    over; the shared layers got no other (the logits above)."""
    c = GlmMoeDsaConfig.tiny_glm()
    for row, seq in enumerate(traffic[0]):
        # the reference records every layer's attended sets: a shared
        # layer's are its full layer's
        masks = reference_run[1][row]
        mask = masks[c.full_layers[full_layer]]
        assert all(np.array_equal(masks[i], mask)
                   for i in range(c.full_layers[full_layer] + 1, (
                       c.full_layers + (c.num_hidden_layers,))[
                           full_layer + 1]))
        for position in range(len(seq)):
            want = set(np.flatnonzero(mask[position]).tolist())
            assert served_run[1][row][position][full_layer] == want
            assert len(want) == min(position + 1, 8)


def test_a_shared_layer_with_its_own_indexer_is_another_model(
        model, traffic, reference_run):
    """What IndexShare means: give a shared layer indexer weights of
    its own and the logits move."""
    c, params, _ = model
    layers = ref_layers(params, c)
    donor = layers[c.full_layers[0]]
    fresh = jax.random.normal(jax.random.PRNGKey(9), donor["wi_q"].shape)
    layers[1] = dict(layers[1], **{k: donor[k] for k in (
        "wi_k", "wi_k_norm", "wi_k_bias", "wi_w")}, wi_q=fresh * 0.2)
    seq = traffic[0][0]
    moved = np.asarray(ref.forward(
        dict(ref_params(params, c), layers=layers), seq, ref_config(c)))
    assert np.abs(moved - reference_run[0][0])[16:].max() > 1e-3


@pytest.mark.parametrize("quant", [None, 8])
def test_absorbed_attention_is_the_up_projected_one(quant):
    """q_nope W_kvb^K against c_kv and the attended latent through
    W_kvb^V (the served path, here over ALL keys) against per-head keys
    and values up-projected from the latent (the reference)."""
    c = GlmMoeDsaConfig.tiny_glm()
    params = init_params(c, jax.random.PRNGKey(1), jnp.float32, bits=quant)
    lp = glm_dsa.layer_leaves(params["blocks"], c, 0)
    S = 24
    h = jax.random.normal(jax.random.PRNGKey(2), (S, c.hidden_size))
    rope = RopeTables.create(c, 64)
    cos, sin = rope.cos[:S], rope.sin[:S]
    pool = jnp.zeros((1, 4, 8, c.latent_row), jnp.float32)
    table = jnp.arange(4, dtype=jnp.int32)[None]
    slot = jnp.zeros(S, jnp.int32)
    position = jnp.arange(S, dtype=jnp.int32)
    q_cat, pool, _ = glm_dsa.project_latent(
        lp, h, cos, sin, slot, position, jnp.ones(S, bool), pool, 0, table,
        c)
    idx = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (S, S))
    # every token as a row of its own over the one page list
    o_lat = glm_dsa.attend(
        q_cat, pool, 0, jnp.broadcast_to(table, (S, 4)), slot, position,
        glm_dsa.Selection(idx, position + 1, None), c, "fold", None)
    # (first = position: token t is row t's single token)
    o = glm_dsa.unabsorb_value(o_lat, lp["wkv_b_v"])
    from cake_tpu.ops.quant import qmatmul
    got = qmatmul(o.reshape(S, -1), lp["wo"])
    flat = {k: dequantized(v) for k, v in lp.items()
            if not isinstance(v, moe_ops.LayerOf)}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.attention(flat, h, ref_config(c), None)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_rope_on_interleaved_pairs_is_the_references():
    x = jax.random.normal(jax.random.PRNGKey(3), (12, 3, 8))
    cos, sin = RopeTables.create(GlmMoeDsaConfig.tiny_glm(), 64)
    np.testing.assert_allclose(
        glm_dsa.rope_pairs(x, cos[5:17], sin[5:17]),
        ref.rope(x, np.arange(5, 17), 10000.0), atol=1e-6)


@pytest.mark.parametrize("n_valid", [(16, 16, 16), (1, 5, 16)])
def test_attention_kernel_is_the_fold(n_valid):
    """cake_mla_attn (interpreted) against the XLA fold."""
    T, H, K, W, R = 3, 4, 16, 24, 16
    q = jax.random.normal(jax.random.PRNGKey(4), (T, H, W))
    kv = jax.random.normal(jax.random.PRNGKey(5), (T, K, W))
    n = jnp.asarray(n_valid, jnp.int32)
    want = mla.attend_selected(q, kv, n, R, 0.2, impl="fold")
    got = mla.attend_selected(q, kv, n, R, 0.2, impl="pallas",
                              interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("C_,last", [(24, 23), (32, 60), (32, 95)],
                         ids=["tq8_first_window", "tq16_mid_block",
                              "tq16_table_end"])
def test_window_kernel_is_the_fold_under_a_selection(C_, last):
    """cake_mla_window_attn (interpreted) against the XLA fold under a
    selection's bias: 0 on the keys a query selected among those it
    sees, NEG_INF elsewhere, added on a token's every head. A query
    that selected nothing gets zeros, whole blocks of a query's keys
    are unselected, and what the bias allows past the walk (here every
    key of the table, for the last query) is still not attended."""
    rng = np.random.default_rng(C_ + last)
    L, N, P, W, R, H, pages = 2, 16, 8, 24, 16, 4, 12
    pool = jnp.asarray(rng.standard_normal((L, N, P, W)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((C_, H, W)), jnp.float32)
    table = jnp.asarray(rng.permutation(N)[:pages], jnp.int32)
    positions = max(last - C_ + 1, 0) + np.arange(C_)
    picked = ((np.arange(pages * P)[None, :] <= positions[:, None])
              & (rng.random((C_, pages * P)) < 0.2))
    picked[3] = False                          # a query with no key
    # one whose first block of four pages (first page, in the first
    # window) is all masked, and who selected its own key
    picked[C_ - 2, :4 * P if last >= 5 * P else P] = False
    picked[C_ - 2, last - 1] = True
    picked[C_ - 1] = True
    bias = jnp.where(picked, 0.0, mla.NEG_INF).astype(jnp.float32)
    args = (q, pool, 1, table, bias, jnp.int32(last), R, 0.2)
    want = np.asarray(mla.attend_window(*args, impl="fold"))
    got = np.asarray(mla.attend_window(*args, impl="pallas", interpret=True))
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert not got[3].any() and np.abs(got[C_ - 2]).max() > 1e-3
    # the last query over exactly the walked keys, by hand
    keys = np.asarray(pool[1, np.asarray(table)]).reshape(-1, W)[
        :(last // P + 1) * P]
    s = np.asarray(q[C_ - 1]) @ keys.T * 0.2
    p = np.exp(s - s.max(-1, keepdims=True))
    np.testing.assert_allclose(got[C_ - 1],
                               (p / p.sum(-1, keepdims=True)) @ keys[:, :R],
                               atol=1e-5)


def test_window_scores_are_the_rows_scores():
    """The window's score kernel and the one-query-a-row pass are one
    function of (query, key); blocks past the window's end are skipped
    (zeros nobody may select)."""
    C_, J, d, S = 5, 2, 16, 48
    qI = jax.random.normal(jax.random.PRNGKey(6), (C_, J, d))
    kI = jax.random.normal(jax.random.PRNGKey(7), (S, d))
    w = jax.random.normal(jax.random.PRNGKey(8), (C_, J))
    # two blocks of 24 keys: the second starts past position 19
    assert mla.index_tiles(C_, J, d, S) == (C_, 24)
    win = mla.index_scores_window(qI, kI, w, jnp.int32(19))
    rows = mla.index_scores_rows(qI, jnp.broadcast_to(kI, (C_, S, d)), w)
    np.testing.assert_allclose(win[:, :24], rows[:, :24], rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(win[:, 24:]).any()


# -- the expert layer ----------------------------------------------------------


def _old_route(x, router_w, k, norm_topk_prob):
    """ops/moe.route as it was before the rule became data."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


@pytest.mark.parametrize("family,norm,k,E", [("olmoe", False, 8, 64),
                                             ("mixtral", True, 2, 8)])
def test_softmax_routing_is_bit_for_bit_what_it_was(family, norm, k, E):
    x = jax.random.normal(jax.random.PRNGKey(10), (33, 48), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(11), (48, E), jnp.bfloat16)
    new = jax.jit(lambda x, w: moe_ops.route(x, w, k, norm))(x, w)
    old = jax.jit(lambda x, w: _old_route(x, w, k, norm))(x, w)
    for a, b in zip(new, old):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_sigmoid_routing_bias_changes_the_choice_not_the_weights():
    rule = dict(norm_topk_prob=True, scoring="sigmoid", scale=2.5)
    x = jax.random.normal(jax.random.PRNGKey(12), (40, 32))
    w = jax.random.normal(jax.random.PRNGKey(13), (32, 16)) * 0.3
    bias = jnp.zeros(16).at[5].set(10.0)
    plain_w, plain_e = moe_ops.route(x, w, 2, **rule)
    w_b, e_b = moe_ops.route(x, w, 2, bias=bias, **rule)
    assert (np.asarray(e_b) == 5).any(axis=1).all()        # forced in
    assert not (np.asarray(plain_e) == 5).any(axis=1).all()
    scores = np.asarray(jax.nn.sigmoid(x @ w))
    picked = np.take_along_axis(scores, np.asarray(e_b), axis=1)
    want = picked / (picked.sum(1, keepdims=True) + 1e-20) * 2.5
    np.testing.assert_allclose(w_b, want, rtol=1e-5)
    # and where the bias leaves the choice alone, the weights are equal
    same = (np.sort(plain_e, 1) == np.sort(e_b, 1)).all(1)
    assert np.allclose(np.sort(plain_w, 1)[same], np.sort(w_b, 1)[same])
    np.testing.assert_allclose(np.asarray(w_b).sum(1), 2.5, rtol=1e-5)


@pytest.mark.parametrize("side", ["reference", "served"])
def test_sixteen_shares_and_one_shared_expert_are_the_uncut_layer(side):
    """Each of 16 chips holds one of a layer's 16 routed experts and
    routes over all of them; their parts, with the shared expert counted
    once, add up to what the uncut reference gives for the layer."""
    c = GlmMoeDsaConfig.tiny_glm(num_local_experts=16,
                                 n_routed_experts_total=16)
    params = init_params(c, jax.random.PRNGKey(14), jnp.float32)
    lp = ref_layers(params, c)[1]
    h = jax.random.normal(jax.random.PRNGKey(15), (21, c.hidden_size))
    cfg = ref_config(c)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_ffn(lp, h, cfg)
        total = ref.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    for e in range(16):
        share = {k: (v[e:e + 1] if k.startswith("we_") else v)
                 for k, v in lp.items()}
        if side == "reference":
            with jax.default_matmul_precision("highest"):
                part = ref.moe_ffn(share, h, cfg, held=(e, 1), shared=False)
        else:
            routed = {k: v for k, v in share.items()
                      if not k.startswith("ws_")}
            part, stats = moe_ops.moe_mlp(
                routed, h[None], 2, c.norm_topk_prob, first_expert=e,
                scoring=c.scoring_func, scale=c.routed_scaling_factor)
            part = part[0]
            assert float(stats.rows_routed) == 21 * 2
            assert float(stats.rows) == float(
                jnp.sum(stats.experts == e))
        total = total + part
    np.testing.assert_allclose(total, whole, atol=2e-5)


# -- the pool, the sizes, the config -------------------------------------------


def test_latent_pool_rows():
    c = GlmMoeDsaConfig.tiny_glm()
    cache = PagedKVCache.create(c, 4, 10, 8, 64, dtype=jnp.bfloat16)
    assert cache.k.shape == (5, 10, 8, 16 + 8)       # one latent row
    assert cache.v.shape == (2, 10, 8, 16)           # full layers only
    assert cache.table.shape == (4, 8) and cache.page_size == 8


def test_one_window_a_dispatch():
    assert mixed_token_buckets(8, 512, (1,)) == (528,)
    assert mixed_token_buckets(16, 128) == (144, 272)     # as it was
    plan = pack_plan(jnp.asarray([1, 6, 0, 1]),
                     jnp.asarray([True, True, False, True]), 16, 8)
    w = glm_dsa.window_of(plan, jnp.asarray([9, 16, 0, 3]),
                          jnp.asarray([1, 6, 0, 1]),
                          jnp.asarray([True, True, False, True]))
    assert int(w.row) == 1 and int(w.start) == 1 and int(w.last_pos) == 21
    assert np.asarray(w.real).tolist() == [True] * 6 + [False] * 2
    assert np.asarray(w.positions).tolist() == list(range(16, 24))
    assert np.asarray(w.member).tolist() == [False] + [True] * 6 + [False] * 9


def test_published_config_parses():
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "glm-5.2-int8-share16", "config.json")
    with open(path) as f:
        raw = json.load(f)
    from cake_tpu.models.llama.config import load_config_dict
    c = load_config_dict(raw)
    assert isinstance(c, GlmMoeDsaConfig)
    assert (c.num_hidden_layers, c.hidden_size, c.latent_width) == (
        9, 6144, 576)
    assert c.full_layers == (0, 4, 8) and c.sparse_layers == tuple(
        range(1, 9))
    assert (c.num_local_experts, c.n_routed_experts_total,
            c.num_experts_per_tok) == (16, 256, 8)
    assert c.rope_theta == 8e6 and c.rope_dim == 64
    assert c.routed_scaling_factor == 2.5 and c.scoring_func == "sigmoid"


@pytest.mark.parametrize("key,value", [("n_group", 8), ("topk_group", 4),
                                       ("num_nextn_predict_layers", 1),
                                       ("first_routed_expert", 250)])
def test_what_is_not_implemented_is_refused(key, value):
    from cake_tpu.models.llama.config import load_config_dict
    raw = dict(
        model_type="glm_moe_dsa", vocab_size=64, hidden_size=32,
        intermediate_size=64, num_hidden_layers=2, num_attention_heads=2,
        q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, index_n_heads=2,
        index_head_dim=8, index_topk=4, moe_intermediate_size=16,
        n_routed_experts=16, n_routed_experts_total=256,
        num_experts_per_tok=2, first_k_dense_replace=1)
    load_config_dict(raw)
    with pytest.raises(ValueError):
        load_config_dict(dict(raw, **{key: value}))


# -- the engine ----------------------------------------------------------------


def make_engine(**kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    c = GlmMoeDsaConfig.tiny_glm(vocab_size=300, eos_token_ids=(300,))
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    opts = dict(max_slots=4, max_seq_len=128, cache_dtype=jnp.float32,
                sampling=SamplingConfig(temperature=0.0,
                                        repeat_penalty=1.0),
                kv_pages=64, kv_page_size=8, prefill_chunk=16)
    opts.update(kw)
    return c, params, InferenceEngine(c, params, ByteTokenizer(c.vocab_size),
                                      **opts)


@pytest.fixture(scope="module")
def engine_run():
    c, params, eng = make_engine()
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(3, 250, n)))
               for n in (40, 7, 70, 21, 33)]
    from cake_tpu.obs import steps as obs_steps
    before = {k: s.value for k, s in obs_steps.DSA_COUNTERS}
    with eng:
        handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for h in handles:
            assert h.wait(180)
        records = eng.flight.dump()
    after = {k: s.value for k, s in obs_steps.DSA_COUNTERS}
    return (c, params, prompts, [h.token_ids for h in handles], records,
            {k: after[k] - before[k] for k in after}, eng)


@pytest.mark.parametrize("request_index", range(4))
def test_engine_serves_the_references_greedy_tokens(engine_run,
                                                    request_index):
    """Through submit -> _do_mixed -> the in-flight decode step: four
    requests over four rows and a fifth behind them, prompts of 1 to 5
    windows. Teacher-forced: the reference's forward over the prompt and
    the tokens the engine gave must choose each of them."""
    c, params, prompts, tokens, *_ = engine_run
    prompt, out = prompts[request_index], tokens[request_index]
    assert len(out) == 10
    logits = np.asarray(ref.forward(
        ref_params(params, c), np.asarray(prompt + out), ref_config(c)))
    for i, tok in enumerate(out):
        at = logits[len(prompt) - 1 + i]
        top2 = np.sort(at)[-2:]
        if top2[1] - top2[0] > 1e-3:        # a near-tie may fall either way
            assert tok == int(np.argmax(at)), i


def test_step_records_name_the_attention_and_carry_the_counters(engine_run):
    *_, records, moved, eng = engine_run
    kinds = {r["kind"]: r for r in records}
    assert set(kinds) >= {"mixed", "decode"}
    for r in records:
        assert r["impl"] == "paged-dsa-fold"
    counted = [r for r in records if "dsa_keys_visible" in r]
    assert counted
    for r in counted:
        assert 0 < r["dsa_keys_selected"] <= r["dsa_keys_visible"]
        assert r["dsa_rows_distinct"] <= r["dsa_keys_selected"]
        assert r["dsa_index_reused"] == 1.5 * r["dsa_index_layers"]
        assert r["moe_rows_routed"] >= r["moe_rows"]
        # the windows' selections: the mixed dispatches' alone
        assert (r["dsa_select_keys_walked"] > 0) == (r["kind"] == "mixed")
        assert r["dsa_select_keys_walked"] <= r["dsa_select_keys_table"]
        # ... and their score passes'
        assert (r["dsa_index_keys_scored"] > 0) == (r["kind"] == "mixed")
        assert r["dsa_index_keys_scored"] <= r["dsa_select_keys_table"]
    assert any(r.get("chained") for r in records if r["kind"] == "decode")
    assert all(v > 0 for v in moved.values()), moved
    assert eng._mixed_buckets == (32,) and not eng._prefix_capable


def test_mixed_records_count_the_window_kernels_walk(engine_run):
    """window_pages / window_folds of a mixed step: every dispatch's
    window (one a prefilling row, so a step of k of them sums k), its
    row's live pages in each of the model's layers, 4 a fold."""
    c, *_, records, _moved, eng = engine_run
    L = c.num_hidden_layers
    mixed = [r for r in records if r["kind"] == "mixed"]
    assert mixed and all("window_pages" not in r for r in records
                         if r["kind"] != "mixed")
    for r in mixed:
        assert r["window_pages"] % L == 0 and r["window_folds"] % L == 0
        assert (r["window_pages"] / 4 <= r["window_folds"]
                <= r["window_pages"])
    assert eng._window_walk(0) == (L, L)
    assert eng._window_walk(69) == (L * 9, L * 3)
    assert eng._window_walk(10**6) == (L * 16, L * 4)      # the table's end


def test_counters_count_what_the_reference_attends():
    """One prompt of 20 tokens in windows of 8, index_topk 8, 5 layers:
    visible 1+..+20 = 210 a layer, selected 36 + 12 * 8 = 132 a layer."""
    c = GlmMoeDsaConfig.tiny_glm()
    model = (c, init_params(c, jax.random.PRNGKey(0), jnp.float32),
             RopeTables.create(c, MAX_SEQ))
    cache = fresh_cache(c)
    seq = np.arange(20) % 200
    total = np.zeros(glm_dsa.N_COUNTERS)
    for off in (0, 8, 16):
        n = min(8, 20 - off)
        toks = np.zeros((B, C), np.int32)
        toks[2, :n] = seq[off:off + n]
        qlen = np.zeros(B, np.int32)
        qlen[2] = n
        pos = np.zeros(B, np.int32)
        pos[2] = off
        _, cache, counters = glm_dsa.mixed_step_latent(
            model[1], jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(qlen),
            jnp.asarray(qlen > 0), cache, model[2], c, n_tokens=16)
        total += np.asarray(counters)
    assert total[6] == 5 * 210 and total[7] == 5 * 132
    assert total[9] == 3 * 2 and total[10] == 3 * 3
    # distinct rows: at most what was selected, at least a window's
    # largest set, in each of 5 layers
    assert 5 * 8 * 3 - 5 * (8 - 1 - 0) <= total[8] <= total[7]
    assert total[5] == 20 * 2 * 4 == total[0]           # all experts held


@pytest.mark.parametrize("refused", [
    dict(kv_pages=None), dict(kv_dtype="int8"), dict(kv_host_pages=8),
    dict(auto_prefix_system=True), dict(disagg="prefill")])
def test_engine_refuses_what_the_latent_pool_does_not_serve(refused):
    with pytest.raises(ValueError, match="glm_moe_dsa"):
        make_engine(**refused)


def test_prefix_registration_is_refused_by_name():
    *_, eng = make_engine()
    with pytest.raises(ValueError, match="latent page pool"):
        eng.register_prefix([5, 6, 7, 8, 9, 10, 11, 12, 13])


@pytest.mark.parametrize("kind", ["window", "single_token"])
def test_a_rows_bits_do_not_depend_on_its_company(model, traffic, kind):
    """Served alone or beside three other rows, the same program gives
    a window's last logits and a row's single token the same bits."""
    sequences, prompts = traffic
    alone, _ = serve(model, sequences[:1], prompts[:1], company=False)
    amid, _ = serve(model, sequences, prompts)
    positions = ([7, 15, 31, 36] if kind == "window"
                 else list(range(37, 45)))
    for position in positions:
        assert np.array_equal(alone[0][position], amid[0][position])
