"""ZAYA1 (`zaya`) at a tiny size on seeded float32 weights: the served
path (mixed-step prefill in windows of 8 on the packed axis, several
rows prefilling in one dispatch, decode through the rows' conv tail,
decode rows beside prefilling ones) against the plain float32
reference's full forward; the pieces one by one (a window boundary at
every offset, position 0, the q-k mean under a group of 4, half-head
RoPE, the router's state, the choice's bias, the top-1 weight); the
tail's lifecycle (a slot reused, rows that hold no token, recompute
preemption); and the engine around them.

4 layers, hidden 64, 4 query heads over 2 key heads of 16, 4 experts of
32 of which a token takes one, a 16-wide router, vocabulary 512."""

import json
import os
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import load_config_dict
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import HybridPagedCache, PagedKVCache
from cake_tpu.models.moe import zaya
from cake_tpu.models.moe.config import ZayaConfig
from cake_tpu.models.moe.params import init_params
from cake_tpu.models.reference import zaya as ref
from cake_tpu.obs import steps as obs_steps
from cake_tpu.ops import moe as moe_ops
from cake_tpu.ops import rope as rope_ops
from cake_tpu.ops.quant import QTensor, qmatmul

B, C, PAGE, MAX_SEQ, T = 4, 8, 8, 64, 24
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUTER_LEAVES = ("r_dn", "r_dn_b", "r_gamma", "r_norm", "r_w1", "r_b1",
                 "r_w2", "r_b2", "r_w3")
PUBLISHED = os.path.join(ROOT, "benchmarks", "configs", "zaya1-8b-int8",
                         "config.json")


def ref_params(params, c):
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": zaya.dequantized(params["lm_head"]),
            "layers": list(zaya.reference_layers(params["blocks"], c))}


def reference(model, sequences, **switches):
    c, params = model
    return [np.asarray(x) for x in ref.forward(
        ref_params(params, c), list(sequences),
        dict(zaya.reference_config(c), **switches))]


@pytest.fixture(scope="module")
def model():
    c = ZayaConfig.tiny_zaya()
    return c, init_params(c, jax.random.PRNGKey(0), jnp.float32)


def fresh_cache(c):
    cache = PagedKVCache.create(c, B, 1 + B * (MAX_SEQ // PAGE), PAGE,
                                MAX_SEQ, dtype=jnp.float32)
    table = np.stack([1 + b * (MAX_SEQ // PAGE) + np.arange(MAX_SEQ // PAGE)
                      for b in range(B)]).astype(np.int32)
    return cache._replace(table=jnp.asarray(table))


@partial(jax.jit, static_argnames=("c", "n_tokens"))
def _mixed(params, toks, pos, qlen, cache, c, n_tokens):
    out, plan = zaya.mixed_trunk(params, toks, pos, qlen, qlen > 0, cache,
                                 RopeTables.create(c, MAX_SEQ), c, "fold",
                                 n_tokens)
    return qmatmul(out.x, params["lm_head"]), out, plan


@partial(jax.jit, static_argnames=("c",))
def _decode(params, toks, pos, active, cache, c):
    out = zaya.decode_trunk(params, toks, cache, pos, active,
                            RopeTables.create(c, MAX_SEQ), c, "fold")
    return qmatmul(out.x, params["lm_head"]), out


def mixed(model, cache, toks, pos, qlen, n_tokens=T):
    """One mixed dispatch with the head at every packed position ->
    (logits by (row, column), cache, TrunkOut)."""
    c, params = model
    logits, out, plan = _mixed(params, jnp.asarray(toks), jnp.asarray(pos),
                               jnp.asarray(qlen), cache, c, n_tokens)
    logits = np.asarray(logits)
    row, col, real = (np.asarray(x) for x in (plan.row, plan.col, plan.real))
    at = {(int(row[t]), int(col[t])): logits[t]
          for t in range(n_tokens) if real[t]}
    return at, out.cache, out


def decode(model, cache, toks, pos, active):
    c, params = model
    logits, out = _decode(params, jnp.asarray(toks), jnp.asarray(pos),
                          jnp.asarray(active), cache, c)
    return np.asarray(logits), out.cache, out


def serve(model, sequences, prompts, cache=None, rows=None, windows=None):
    """Each sequence in its row: the prompt through mixed windows (every
    row that still prefills in the one dispatch, rows that have finished
    decoding beside them as one-token rows), then the decode program.
    windows: per sequence, the q_len of its successive windows (default
    C each). -> (logits [S_i, V] per sequence, cache)."""
    c, _ = model
    cache = fresh_cache(c) if cache is None else cache
    rows = list(range(len(sequences))) if rows is None else rows
    got = [np.zeros((len(s), c.vocab_size), np.float32) for s in sequences]
    off = [0] * len(sequences)
    turn = [0] * len(sequences)
    while any(off[i] < len(s) for i, s in enumerate(sequences)):
        qlen, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for i, s in enumerate(sequences):
            if off[i] == len(s):
                continue
            left = prompts[i] - off[i]
            want = C if windows is None else (
                windows[i][turn[i]] if turn[i] < len(windows[i]) else C)
            qlen[rows[i]] = min(want, left) if left > 0 else 1
            pos[rows[i]] = off[i]
        if (qlen > 1).any():
            toks = np.zeros((B, C), np.int32)
            for i, s in enumerate(sequences):
                n = qlen[rows[i]]
                toks[rows[i], :n] = s[off[i]:off[i] + n]
            at, cache, _ = mixed(model, cache, toks, pos, qlen)
            for i in range(len(sequences)):
                for j in range(qlen[rows[i]]):
                    got[i][off[i] + j] = at[(rows[i], j)]
        else:
            toks = np.zeros((B, 1), np.int32)
            for i, s in enumerate(sequences):
                if qlen[rows[i]]:
                    toks[rows[i], 0] = s[off[i]]
            logits, cache, _ = decode(model, cache, toks, pos, qlen > 0)
            for i in range(len(sequences)):
                if qlen[rows[i]]:
                    got[i][off[i]] = logits[rows[i]]
        for i in range(len(sequences)):
            off[i] += int(qlen[rows[i]])
            turn[i] += 1
    return got, cache


@pytest.fixture(scope="module")
def traffic(model):
    c, _ = model
    rng = np.random.default_rng(0)
    prompts = [21, 13, 6]
    return [rng.integers(0, c.vocab_size, p + 5) for p in prompts], prompts


@pytest.fixture(scope="module")
def served_run(model, traffic):
    return serve(model, *traffic)


@pytest.fixture(scope="module")
def reference_run(model, traffic):
    return reference(model, traffic[0])


# -- the served path is the reference ------------------------------------------


@pytest.mark.parametrize("row", [0, 1, 2])
def test_served_path_matches_the_reference_forward(served_run, reference_run,
                                                   row):
    """Prefill through windows of 8 (three rows prefilling in one
    dispatch, then decode rows beside a prefilling one), then decode
    through the cache: logits at every position."""
    got, want = served_run[0][row], reference_run[row]
    assert np.abs(got - want).max() < 2e-4


@pytest.mark.parametrize("position", [0, 1, 7, 8, 9, 16])
def test_position_0_and_a_windows_first_token_read_what_they_should(
        served_run, reference_run, position):
    """Position 0's conv taps and shifted values are zeros; positions 8
    and 16 read the row's stored tail."""
    got, want = served_run[0][0], reference_run[0]
    assert np.abs(got[position] - want[position]).max() < 2e-4


@pytest.mark.parametrize("offset", [0, 1, 2, 3])
def test_a_window_boundary_at_every_offset_gives_one_long_windows_bits(
        model, offset):
    c, _ = model
    seq = np.random.default_rng(3).integers(0, c.vocab_size, C)
    whole, _ = serve(model, [seq], [C])
    split, _ = serve(model, [seq], [C], windows=[[2 + offset, C]])
    np.testing.assert_array_equal(split[0], whole[0])


@pytest.mark.parametrize("altered", [
    "int8_activations", "drop_conv_taps", "no_value_shift", "full_rotary",
    "renormalise_top1", "no_router_state"])
def test_an_altered_reference_is_another_model(model, traffic, served_run,
                                               altered):
    """What chip_compare.py shows failing on the chip, at float32: each
    switch moves the logits by far more than the served path's 2e-4."""
    other = reference(model, traffic[0][:1], **{altered: True})[0]
    assert np.abs(served_run[0][0] - other).max() > 5e-3


# -- the tail's lifecycle ------------------------------------------------------


@pytest.mark.parametrize("prompt", [5, 12, 30])
def test_a_reused_slot_gives_the_request_what_it_gets_alone(model, traffic,
                                                            prompt):
    c, _ = model
    sequences, prompts = traffic
    _, used = serve(model, sequences[:1], prompts[:1], rows=[1])
    nxt = np.random.default_rng(prompt).integers(0, c.vocab_size, prompt + 3)
    alone, _ = serve(model, [nxt], [prompt], rows=[1])
    after, _ = serve(model, [nxt], [prompt], cache=used, rows=[1])
    np.testing.assert_array_equal(after[0], alone[0])


@pytest.mark.parametrize("kind", ["mixed", "decode"])
def test_a_row_with_no_token_keeps_its_tail(model, served_run, kind):
    c, _ = model
    cache = served_run[1]
    before = np.asarray(cache.conv)
    qlen = np.array([0, 3, 0, 0], np.int32)
    pos = np.array([0, 18, 0, 0], np.int32)
    if kind == "mixed":
        _, after, _ = mixed(model, jax.tree.map(jnp.copy, cache),
                            np.ones((B, C), np.int32), pos, qlen)
    else:
        _, after, _ = decode(model, jax.tree.map(jnp.copy, cache),
                             np.ones((B, 1), np.int32), pos, qlen > 0)
    after = np.asarray(after.conv)
    for row in (0, 2, 3):
        np.testing.assert_array_equal(after[:, row], before[:, row])
    assert np.abs(after[:, 1] - before[:, 1]).max() > 0


@pytest.mark.parametrize("kind", ["window", "single_token", "two_windows"])
def test_a_rows_bits_do_not_depend_on_its_company(model, traffic, kind):
    """A row alone, and beside rows that decode and rows that prefill
    (one or two of them in its dispatch)."""
    sequences, prompts = traffic
    alone, _ = serve(model, sequences[:1], prompts[:1], rows=[2])
    if kind == "two_windows":
        together, _ = serve(model, sequences, prompts, rows=[2, 0, 3])
    else:
        n = 2 if kind == "window" else 3
        together, _ = serve(model, [sequences[0], sequences[n - 1]],
                            [prompts[0], prompts[n - 1]], rows=[2, 0])
    np.testing.assert_array_equal(together[0], alone[0])


def test_the_cache_is_the_hybrid_one_without_a_recurrent_leaf(model):
    c, _ = model
    cache = fresh_cache(c)
    assert isinstance(cache, HybridPagedCache) and cache.ssm is None
    assert cache.k.shape[0] == cache.conv.shape[0] == c.num_hidden_layers
    assert cache.conv.shape[1:] == (B, 1, c.cca_tail_width)
    assert c.cca_tail_width == 2 * (4 + 2) * 16 + 16
    assert cache.state_bytes() == cache.conv.nbytes
    assert len(jax.tree.leaves(cache)) == 4


# -- the pieces ----------------------------------------------------------------


@pytest.mark.parametrize("side", ["reference", "served"])
def test_the_q_k_mean_under_a_group_of_four(side):
    """With conv 1 zeroed, one token at position 0 (no taps, no
    rotation): q[h] is the direction of qc[h] + kc[h // 4], k[j] that
    of mean over its 4 heads of qc + kc[j], at the published group."""
    c = ZayaConfig.tiny_zaya(num_attention_heads=8)
    H, K, d = 8, 2, 16
    params = init_params(c, jax.random.PRNGKey(1), jnp.float32)
    lp = next(zaya.reference_layers(params["blocks"], c))
    lp = dict(lp, conv1_w=jnp.zeros_like(lp["conv1_w"]),
              conv1_b=jnp.zeros_like(lp["conv1_b"]))
    u = jax.random.normal(jax.random.PRNGKey(2), (1, c.hidden_size))
    qc = np.asarray(u @ lp["wq"]).reshape(K, 4, d)
    kc = np.asarray(u @ lp["wk"]).reshape(K, d)
    q_want = (qc + kc[:, None]).reshape(H, d)
    k_want = qc.mean(axis=1) + kc
    tau = np.asarray(lp["k_temp"])
    if side == "reference":
        q, k, _ = ref.cca_qkv(lp, u, zaya.reference_config(c))
    else:
        proj = jnp.concatenate([u @ lp[n] for n in
                                ("wq", "wk", "wv1", "wv2")], axis=-1)
        rows = zaya.Rows(*(jnp.array([x], jnp.int32) for x in (0, 1, 0)))
        q, k, _, _ = zaya.cca_mix(
            lp, proj, jnp.zeros((1, c.cca_tail_width)),
            jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32), rows, c)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(q)[0], 4.0 * unit(q_want),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(k)[0],
                               4.0 * tau[:, None] * unit(k_want), atol=1e-5)


def _rope_as_it_was(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[None, :, None, :].astype(jnp.float32)
    s = sin[None, :, None, :].astype(jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@pytest.mark.parametrize("case", ["factor_1_is_todays_bits",
                                  "half_differs_from_full",
                                  "half_is_the_references"])
def test_half_head_rope(case):
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 3, 16))
    full = rope_ops.precompute_rope(16, 12, 5e6)
    half = rope_ops.precompute_rope(8, 12, 5e6)
    if case == "factor_1_is_todays_bits":
        np.testing.assert_array_equal(
            np.asarray(rope_ops.apply_rope(x, *full)),
            np.asarray(_rope_as_it_was(x, *full)))
        return
    got = np.asarray(rope_ops.apply_rope(x, *half))
    if case == "half_differs_from_full":
        assert np.abs(got - np.asarray(
            rope_ops.apply_rope(x, *full))).max() > 0.1
        np.testing.assert_array_equal(got[..., 8:], np.asarray(x)[..., 8:])
    else:
        np.testing.assert_allclose(got[0], np.asarray(ref.rope(x[0], 5e6, 8)),
                                   atol=1e-5)


@pytest.mark.parametrize("case", ["from_its_own_token", "none_at_layer_0"])
def test_the_routers_state_runs_down_the_layers(model, case):
    c, params = model
    def leaves(layer):
        return {k: params["blocks"][k][layer] for k in ROUTER_LEAVES}

    lp = leaves(1)
    m = jax.random.normal(jax.random.PRNGKey(0), (5, c.hidden_size))
    r_prev = jax.random.normal(jax.random.PRNGKey(1),
                               (5, c.router_hidden_size))
    base, r = zaya.router_logits(lp, m, r_prev, c.rms_norm_eps)
    if case == "from_its_own_token":
        # the state of token 2 at the layer above moves token 2's
        # logits and no neighbour's
        moved, _ = zaya.router_logits(lp, m, r_prev.at[2].add(1.0),
                                      c.rms_norm_eps)
        delta = np.abs(np.asarray(moved - base)).max(axis=-1)
        assert delta[2] > 1e-3 and np.all(delta[[0, 1, 3, 4]] == 0)
        np.testing.assert_allclose(
            np.asarray(r), np.asarray(
                m @ lp["r_dn"] + lp["r_dn_b"] + lp["r_gamma"] * r_prev),
            atol=1e-5)
    else:
        # layer 0 starts from zeros: the reference's `None`
        p_ref, r_ref = ref.router(leaves(0), m, None,
                                  zaya.reference_config(c))
        logits, r0 = zaya.router_logits(leaves(0), m, jnp.zeros_like(r_prev),
                                        c.rms_norm_eps)
        np.testing.assert_allclose(np.asarray(jax.nn.softmax(logits)),
                                   np.asarray(p_ref), atol=1e-6)
        np.testing.assert_allclose(np.asarray(r0), np.asarray(r_ref),
                                   atol=1e-5)


@pytest.mark.parametrize("case", ["bias_moves_the_choice_not_the_weight",
                                  "top1_weight_is_p",
                                  "renormalised_would_be_1"])
def test_the_choice_of_one_expert(case):
    logits = jnp.log(jnp.array([[0.5, 0.3, 0.2], [0.1, 0.2, 0.7]]))
    if case == "bias_moves_the_choice_not_the_weight":
        bias = jnp.array([0.0, 0.3, 0.0])
        w, e = moe_ops.choose(logits, 1, False, bias=bias)
        assert e[:, 0].tolist() == [1, 2]
        np.testing.assert_allclose(np.asarray(w[:, 0]), [0.3, 0.7],
                                   rtol=1e-6)
    elif case == "top1_weight_is_p":
        w, e = moe_ops.choose(logits, 1, False)
        assert e[:, 0].tolist() == [0, 2]
        np.testing.assert_allclose(np.asarray(w[:, 0]), [0.5, 0.7],
                                   rtol=1e-6)
    else:
        w, _ = moe_ops.choose(logits, 1, True)
        np.testing.assert_allclose(np.asarray(w[:, 0]), [1.0, 1.0],
                                   rtol=1e-6)


def test_moe_mlp_takes_the_familys_logits(model):
    """With `logits`, the layer needs no `router` leaf, and routes by
    them: the linear router's own logits give the linear router's bits."""
    from cake_tpu.models.moe.config import MoEConfig
    c = MoEConfig.tiny_olmoe()
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    lp = {k: v[0] for k, v in params["blocks"].items()}
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 6, c.hidden_size))
    want, s_want = moe_ops.moe_mlp(lp, h, 2, False)
    logits = moe_ops.router_logits(h[0], lp["router"])
    got, s_got = moe_ops.moe_mlp(
        {k: v for k, v in lp.items() if k != "router"}, h, 2, False,
        logits=logits[None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(s_got.experts),
                                  np.asarray(s_want.experts))


def test_the_counters_count(model, traffic):
    """One mixed dispatch: a tail a row with tokens a layer; the bias
    changes some choices with the seeded bias and none without it."""
    c, params = model
    sequences, _ = traffic
    toks = np.zeros((B, C), np.int32)
    toks[0], toks[2, :3] = sequences[0][:C], sequences[1][:3]
    qlen = np.array([C, 0, 3, 0], np.int32)
    _, _, out = mixed(model, fresh_cache(c), toks, np.zeros(B, np.int32),
                      qlen)
    counters = np.asarray(out.counters)
    assert len(counters) == len(zaya.COUNTERS)
    L = c.num_hidden_layers
    assert counters[0] == L * (C + 3)            # one row a token a layer
    assert counters[5] == L * 2
    assert 0 < counters[6] <= L * (C + 3)
    flat = dict(params, blocks=dict(
        params["blocks"],
        router_bias=jnp.zeros_like(params["blocks"]["router_bias"])))
    _, _, out = mixed((c, flat), fresh_cache(c), toks,
                      np.zeros(B, np.int32), qlen)
    assert np.asarray(out.counters)[6] == 0


def test_int8_draws_the_matmul_leaves_and_keeps_the_small_ones_float():
    c = ZayaConfig.tiny_zaya()
    params = init_params(c, jax.random.PRNGKey(0), jnp.bfloat16, bits=8)
    quantized = {k for k, v in params["blocks"].items()
                 if isinstance(v, QTensor)}
    assert quantized == {"w_cca", "wo", "we_gate", "we_up", "we_down"}
    assert isinstance(params["lm_head"], QTensor)
    # the head is the embedding: tied, one draw
    head = np.asarray(zaya.dequantized(params["lm_head"]))
    table = np.asarray(params["embed"].astype(jnp.float32)).T
    assert np.abs(head - table).max() <= np.abs(table).max() / 127 + 1e-6


# -- the config ----------------------------------------------------------------


with open(PUBLISHED) as _f:
    RAW = json.load(_f)


def test_published_config_parses():
    c = load_config_dict(RAW)
    assert isinstance(c, ZayaConfig)
    assert (c.num_hidden_layers, c.hidden_size, c.num_attention_heads,
            c.num_key_value_heads, c.head_dim) == (40, 2048, 8, 2, 128)
    assert (c.num_local_experts, c.num_experts_per_tok, c.intermediate_size,
            c.router_hidden_size) == (16, 1, 2048, 256)
    assert (c.rope_dim, c.rope_theta, c.vocab_size) == (64, 5e6, 262272)
    assert c.cca_channels == 1280 and c.cca_tail_width == 2688
    assert c.tie_word_embeddings and not c.norm_topk_prob
    assert c.sliding_window is None and c.eos_token_ids == (262272,)
    assert c.is_moe and hash(c) is not None


@pytest.mark.parametrize("key,value,named", [
    ("sliding_window", 4096, "sliding_window"),
    ("layer_types", ["hybrid"] * 39 + ["hybrid_sliding"], "layer_types"),
    ("cca_time0", 4, "cca_time0"),
    ("cca_time1", 3, "cca_time1"),
    ("hidden_act", "gelu", "hidden_act"),
    ("attention_bias", True, "attention_bias"),
    ("partial_rotary_factor", 0.51, "partial_rotary_factor"),
    ("num_experts", None, "num_experts"),
    ("model_type", "zaya2", "unknown model_type")])
def test_what_is_not_served_is_refused_by_key(key, value, named):
    raw = dict(RAW, **{key: value})
    if value is None:
        del raw[key]
    if key == "partial_rotary_factor":
        raw["rope_parameters"] = {"hybrid": {"partial_rotary_factor": value}}
    with pytest.raises(ValueError, match=named):
        load_config_dict(raw)


def test_a_checkpoint_is_refused_with_the_familys_name():
    from cake_tpu.models.moe.params import load_params_from_hf
    with pytest.raises(NotImplementedError, match="zaya"):
        load_params_from_hf("/nonexistent", ZayaConfig.tiny_zaya())


# -- the engine ----------------------------------------------------------------


def make_engine(**kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    c = ZayaConfig.tiny_zaya()
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    opts = dict(max_slots=4, max_seq_len=120, cache_dtype=jnp.float32,
                sampling=SamplingConfig(temperature=0.0,
                                        repeat_penalty=1.0),
                kv_pages=64, kv_page_size=8, prefill_chunk=8)
    opts.update(kw)
    return c, params, InferenceEngine(c, params, ByteTokenizer(c.vocab_size),
                                      **opts)


@pytest.fixture(scope="module")
def engine_run():
    c, params, eng = make_engine()
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(3, 500, n)))
               for n in (40, 7, 70, 21, 33, 12)]
    before = {k: s.value for k, s in obs_steps.CCA_COUNTERS}
    with eng:
        handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for h in handles:
            assert h.wait(180)
        records = eng.flight.dump()
    after = {k: s.value for k, s in obs_steps.CCA_COUNTERS}
    return (c, params, prompts, [h.token_ids for h in handles], records,
            {k: after[k] - before[k] for k in after}, eng)


@pytest.mark.parametrize("request_index", range(6))
def test_engine_serves_the_references_greedy_tokens(engine_run,
                                                    request_index):
    """Through submit -> _do_mixed -> the in-flight decode step: four
    requests over four rows and two behind them in REUSED slots, prompts
    of 1 to 9 windows, two prefilling rows a dispatch. Teacher-forced:
    the reference's forward over the prompt and the tokens the engine
    gave must choose each of them."""
    c, params, prompts, tokens, *_ = engine_run
    prompt, out = prompts[request_index], tokens[request_index]
    assert len(out) == 10
    logits = reference((c, params), [np.asarray(prompt + out)])[0]
    for i, tok in enumerate(out):
        at = logits[len(prompt) - 1 + i]
        top2 = np.sort(at)[-2:]
        if top2[1] - top2[0] > 1e-3:        # a near-tie may fall either way
            assert tok == int(np.argmax(at)), i


def test_step_records_name_the_attention_and_carry_the_counters(engine_run):
    c, _, prompts, _, records, moved, eng = engine_run
    assert {r["kind"] for r in records} >= {"mixed", "decode"}
    for r in records:
        assert r["impl"] == "paged-cca-fold"
    counted = [r for r in records if "cca_tail_rows" in r]
    assert counted and all("ssm_state_rows" not in r
                           and "moe_rows_routed" not in r for r in records)
    for r in counted:
        assert r["cca_tail_rows"] % c.num_hidden_layers == 0
        assert r["moe_rows"] > 0 and r["moe_experts_touched"] > 0
        assert "router_choice_by_bias" in r
    assert any(r.get("chained") for r in records if r["kind"] == "decode")
    assert any(r.get("chained") for r in records if r["kind"] == "mixed")
    # every token of every request reads its row's tail in every layer
    assert moved["cca_tail_rows"] >= c.num_hidden_layers * len(prompts)
    assert moved["router_choice_by_bias"] > 0
    # ONE packed size, the two-window one, and no prefix pages
    assert eng._mixed_buckets == (32,) and not eng._prefix_capable


def test_metrics_carry_the_tails_bytes(engine_run):
    *_, eng = engine_run
    assert obs_steps.CCA_TAIL_BYTES.value == eng.cache.state_bytes() > 0
    assert zaya.COUNTERS[5:] == tuple(k for k, _ in obs_steps.CCA_COUNTERS)
    assert zaya.COUNTERS[5:] == ("cca_tail_rows", "router_choice_by_bias")
    assert eng.flight._counters == eng.config.family.counters
    assert eng.config.family.counters == zaya.COUNTERS


def test_recompute_preemption_gives_the_same_tokens():
    """A batch request preempted mid-decode by an interactive arrival on
    a 1-slot engine, then resumed by recomputing its prompt and its
    tokens so far: the tail is rebuilt with the pages."""
    from cake_tpu.sched import SchedConfig
    prompt, gen = [5] * 9, 24

    def run(preempt: bool):
        _, _, eng = make_engine(
            max_slots=1, priority_classes=True, preemption=preempt,
            sched_config=SchedConfig(preempt_budget=8))
        with eng:
            hb = eng.submit(prompt, max_new_tokens=gen, priority="batch")
            if preempt:
                t0 = time.perf_counter()
                while (len(hb._req.out_tokens) < 4
                       and time.perf_counter() - t0 < 120):
                    time.sleep(0.002)
                hi = eng.submit([2, 9, 4, 7, 3], max_new_tokens=4,
                                priority="interactive")
                assert hi.wait(300)
            assert hb.wait(300)
            assert (eng.stats.preemptions >= 1) == preempt
            return list(hb._req.out_tokens)

    assert run(True) == run(False)


@pytest.mark.parametrize("refused,named", [
    (dict(kv_pages=None), "--kv-pages"),
    (dict(kv_dtype="int8"), "--kv-dtype"),
    (dict(kv_host_pages=8), "--kv-host-pages"),
    (dict(auto_prefix_system=True), "--auto-prefix"),
    (dict(disagg="prefill"), "--disagg")])
def test_engine_refuses_by_name_what_a_tail_does_not_serve(refused, named):
    with pytest.raises(ValueError) as e:
        make_engine(**refused)
    assert "zaya" in str(e.value) and named in str(e.value)


def test_speculation_is_refused_by_name():
    c = ZayaConfig.tiny_zaya()
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(ValueError, match="zaya.*--spec-draft"):
        make_engine(spec_draft_params=params, spec_draft_config=c,
                    spec_gamma=2)


def test_prefix_registration_is_refused_by_name():
    *_, eng = make_engine()
    with pytest.raises(ValueError, match="conv tail"):
        eng.register_prefix([5, 6, 7, 8, 9, 10, 11, 12, 13])


def test_a_live_switch_is_refused_by_name():
    *_, eng = make_engine()
    assert not eng._reconfig_supported()
    assert "conv tail" in eng._reconfig_refusal()
