"""LongCat-Flash (`longcat_flash`) at a tiny size on seeded weights: the
served path (mixed-step prefill in windows, decode through the latent
page pool, decode rows beside prefilling ones) against the plain float32
reference's full forward; the zero experts and the shares of a layer one
by one; the config class's refusals; and the engine around them.

2 layers = 4 sublayers, 4 heads, 16 routed experts + 8 zero experts, 3 a
token. ONE served run and one reference run are shared by the logits,
the routers' choices and the altered references (which recompute the
reference alone)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import load_config_dict
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import PagedKVCache, mixed_token_buckets
from cake_tpu.models.moe import glm_dsa
from cake_tpu.models.moe.config import LongcatFlashConfig
from cake_tpu.models.moe.params import init_params
from cake_tpu.models.reference import longcat_flash as ref
from cake_tpu.ops import moe as moe_ops
from cake_tpu.ops.quant import QTensor

ROOT = os.path.join(os.path.dirname(__file__), "..")
CONFIG_DIR = os.path.join(ROOT, "benchmarks", "configs",
                          "longcat-flash-int8-share32")
B, C, PAGE, MAX_SEQ = 4, 8, 8, 64
REF_KEYS = ("num_attention_heads", "hidden_size", "q_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rms_norm_eps", "rope_theta", "mla_scale_q_lora",
            "mla_scale_kv_lora", "routed_scaling_factor")
# the altered references: each is another model
SWITCHES = {
    "bf16_softmax": dict(softmax_dtype="bfloat16"),
    "int8_activations": dict(int8_activations=True),
    "zero_experts_dropped": dict(zero_experts=False),
    "renormalised": dict(norm_topk_prob=True),
    "no_q_scale": dict(mla_scale_q_lora=False),
    "no_kv_scale": dict(mla_scale_kv_lora=False),
    "tapped_from_the_second_sublayer": dict(tap=1),
    "returned_before_the_second_sublayer": dict(back=0),
    "bias_in_the_weight": dict(bias_in_weight=True),
}


def ref_config(c, **over):
    return dict({k: getattr(c, k) for k in REF_KEYS},
                n_routed_experts=c.n_routed_experts_total,
                moe_topk=c.num_experts_per_tok, **over)


def dequantized(leaf):
    if isinstance(leaf, QTensor):
        return (leaf.q.astype(jnp.float32)
                * jnp.expand_dims(leaf.scale, leaf.q.ndim - 2))
    return jnp.asarray(leaf, jnp.float32)


def ref_layers(params, c):
    """The per-sublayer float32 dicts the reference walks."""
    def plain(lp):
        return {k: plain(v) if isinstance(v, dict) else dequantized(
                    jax.tree.map(lambda a: a[int(v.layer)], v.stacked)
                    if isinstance(v, moe_ops.LayerOf) else v)
                for k, v in lp.items()}

    return [plain(glm_dsa.layer_leaves(params["blocks"], c, i))
            for i in range(c.num_hidden_layers)]


def ref_params(params, c):
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": dequantized(params["lm_head"]),
            "layers": ref_layers(params, c)}


@pytest.fixture(scope="module")
def model():
    c = LongcatFlashConfig.tiny_longcat()
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    return c, params, RopeTables.create(c, MAX_SEQ)


def fresh_cache(c):
    cache = PagedKVCache.create(c, B, 1 + B * (MAX_SEQ // PAGE), PAGE,
                                MAX_SEQ, dtype=jnp.float32)
    table = np.stack([1 + b * (MAX_SEQ // PAGE) + np.arange(MAX_SEQ // PAGE)
                      for b in range(B)]).astype(np.int32)
    return cache._replace(table=jnp.asarray(table))


def serve(model, sequences, prompts, attn="fold"):
    """Every sequence through the step programs: prompts in C-wide
    windows, one window a dispatch, the rows that finished their prompt
    riding the other rows' mixed steps as one-token rows, then the
    decode program. Returns per sequence {position: logits}, the
    counters' sum and per sequence the routers' choices [L, S, k]."""
    c, params, rope = model
    T = mixed_token_buckets(B, C, (1,))[-1]
    cache = fresh_cache(c)
    off = [0] * len(sequences)
    got = [dict() for _ in sequences]
    routed = [np.zeros((c.num_layers, len(s), c.num_experts_per_tok),
                       np.int32) for s in sequences]
    total = np.zeros(len(glm_dsa.SHORTCUT_COUNTERS))
    head = params["lm_head"]
    mixed = jax.jit(glm_dsa.mixed_trunk,
                    static_argnames=("config", "attn", "n_tokens"))
    decode = jax.jit(glm_dsa.decode_trunk, static_argnames=("config", "attn"))
    while any(off[b] < prompts[b] for b in range(len(sequences))):
        b0 = next(b for b in range(len(sequences)) if off[b] < prompts[b])
        toks = np.zeros((B, C), np.int32)
        pos = np.zeros(B, np.int32)
        qlen = np.zeros(B, np.int32)
        for b, seq in enumerate(sequences):
            if b == b0:
                n = min(C, prompts[b] - off[b])
            elif prompts[b] <= off[b] < len(seq):
                n = 1
            else:
                continue
            toks[b, :n], pos[b], qlen[b] = seq[off[b]:off[b] + n], off[b], n
        out, plan = mixed(params, jnp.asarray(toks), jnp.asarray(pos),
                          jnp.asarray(qlen), jnp.asarray(qlen > 0), cache,
                          rope, config=c, attn=attn, n_tokens=T)
        cache = out.cache
        total += np.asarray(out.counters)
        logits, experts = np.asarray(out.x @ head), np.asarray(out.experts)
        for b in np.flatnonzero(qlen):
            at = int(plan.start[b])
            for j in range(qlen[b]):
                got[b][off[b] + j] = logits[at + j]
            routed[b][:, off[b]:off[b] + qlen[b]] = experts[
                :, at:at + qlen[b]]
            off[b] += int(qlen[b])
    while any(off[b] < len(s) for b, s in enumerate(sequences)):
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        for b, seq in enumerate(sequences):
            if off[b] < len(seq):
                toks[b, 0], pos[b], active[b] = seq[off[b]], off[b], True
        out = decode(params, jnp.asarray(toks), cache, jnp.asarray(pos),
                     jnp.asarray(active), rope, config=c, attn=attn)
        cache = out.cache
        total += np.asarray(out.counters)
        logits, experts = np.asarray(out.x @ head), np.asarray(out.experts)
        for b in np.flatnonzero(active):
            got[b][off[b]] = logits[b]
            routed[b][:, off[b]] = experts[:, b]
            off[b] += 1
    return got, total, routed


@pytest.fixture(scope="module")
def traffic(model):
    c = model[0]
    rng = np.random.default_rng(0)
    prompts = (29, 9, 18)
    sequences = [rng.integers(0, c.vocab_size, p + 6) for p in prompts]
    return sequences, prompts


@pytest.fixture(scope="module")
def reference_run(model, traffic):
    c, params, _ = model
    routing = [[] for _ in traffic[0]]
    logits = ref.forward(ref_params(params, c), traffic[0], ref_config(c),
                         routing=routing)
    return [np.asarray(x) for x in logits], routing


@pytest.fixture(scope="module")
def served_run(model, traffic):
    return serve(model, *traffic)


@pytest.mark.parametrize("row", [0, 1, 2])
def test_served_path_matches_the_reference_forward(
        served_run, reference_run, traffic, row):
    """Prefill in windows, then decode through the pages, decode rows
    beside prefilling ones: every position's logits, and every layer's
    router's choice."""
    got, want = served_run[0][row], reference_run[0][row]
    assert sorted(got) == list(range(len(traffic[0][row])))
    for position, logits in got.items():
        np.testing.assert_allclose(logits, want[position], atol=3e-5,
                                   err_msg=f"position {position}")
    for layer, choice in enumerate(reference_run[1][row]):
        assert np.array_equal(np.sort(choice, -1),
                              np.sort(served_run[2][row][layer], -1)), layer


def test_the_kernels_serve_what_the_folds_serve(model, traffic, served_run):
    """attn="pallas" (both kernels interpreted) against attn="fold": a
    prompt that crosses a page and a window, decode past the next
    page."""
    sequences, prompts = traffic
    got, _, _ = serve(model, sequences[1:2], prompts[1:2], attn="pallas")
    for position, logits in got[0].items():
        np.testing.assert_allclose(logits, served_run[0][1][position],
                                   atol=2e-5)


@pytest.mark.parametrize("name", SWITCHES)
def test_every_part_of_the_layer_moves_the_logits(model, traffic,
                                                  reference_run, name):
    """Each switch of the reference is another model: the logits move
    by far more than the served path's distance from the reference."""
    c, params, _ = model
    moved = np.asarray(ref.forward(ref_params(params, c), traffic[0][2],
                                   ref_config(c, **SWITCHES[name])))
    assert np.abs(moved - reference_run[0][2])[8:].max() > 1e-3


def test_counters_count_the_keys_and_the_zero_pairs(served_run, traffic,
                                                    reference_run, model):
    """mla_keys_attended: position + 1 over the single-token rows x 4
    latent layers; moe_rows_routed: every real token x 3 experts x 2
    layers; moe_pairs_zero: the pairs whose expert is past the 16 routed
    ones, as the reference's routers chose them; the rest reach the
    grouped matmul (all 16 held)."""
    sequences, prompts = traffic
    _, total, _ = served_run
    total = dict(zip(glm_dsa.SHORTCUT_COUNTERS, total))
    tokens = sum(len(s) for s in sequences)
    # (a prompt's last window of ONE token is a single-token row too)
    single = sum(sum(range(p + 1, len(s) + 1)) + (p if p % C == 1 else 0)
                 for s, p in zip(sequences, prompts))
    zero = sum(int((choice >= 16).sum())
               for row in reference_run[1] for choice in row)
    assert total["mla_keys_attended"] == 4 * single
    assert total["moe_rows_routed"] == tokens * 3 * 2
    assert 0 < zero == total["moe_pairs_zero"] < tokens * 3 * 2
    assert total["moe_rows"] == tokens * 3 * 2 - zero


# -- the zero experts, the shares -------------------------------------------


def moe_case(seed=3, **over):
    c = LongcatFlashConfig.tiny_longcat(**over)
    params = init_params(c, jax.random.PRNGKey(seed), jnp.float32)
    lp = ref_layers(params, c)[0]["shortcut"]
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (21, c.hidden_size))
    return c, lp, h


def served_moe(c, lp, h, **kw):
    return moe_ops.moe_mlp(lp, h[None], c.num_experts_per_tok, False,
                           scoring="softmax", scale=c.routed_scaling_factor,
                           zero_from=c.n_routed_experts_total, **kw)


@pytest.mark.parametrize("side", ["reference", "served"])
def test_four_shares_and_the_identity_once_are_the_uncut_layer(side):
    """Each of 4 chips holds four of a layer's 16 routed experts and
    routes over the router's whole width; their routed parts, with the
    identity part counted ONCE, add up to what the uncut reference gives
    for the layer."""
    c, lp, h = moe_case()
    cfg = ref_config(c)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_ffn(lp, h, cfg)
        weights, experts, _ = ref.router(lp, h, cfg)
    zero = np.asarray(experts) >= 16
    assert zero.any() and not zero.all()
    identity = jnp.sum(jnp.where(zero, weights, 0.0), 1)[:, None] * h
    total = identity
    for g in range(4):
        share = {k: (v[4 * g:4 * g + 4] if k.startswith("we_") else v)
                 for k, v in lp.items()}
        if side == "reference":
            with jax.default_matmul_precision("highest"):
                part = ref.moe_ffn(share, h, cfg, held=(4 * g, 4),
                                   identity=False)
        else:
            part, stats = served_moe(c, share, h, first_expert=4 * g)
            # every share computes the identity part alike: take it off
            part = part[0] - identity
            assert float(stats.rows_routed) == 21 * 3
            assert float(stats.pairs_zero) == zero.sum()
            assert float(stats.rows) == float(
                jnp.sum(np.asarray(experts) // 4 == g))
        total = total + part
    np.testing.assert_allclose(total, whole, atol=3e-5)


def test_a_token_of_zero_experts_alone_touches_no_expert():
    """A bias that lifts the zero experts over every routed one: each
    token's three choices are zero experts, the layer returns (the sum
    of their weights) x its input, and nothing reaches the grouped
    matmul."""
    c, lp, h = moe_case()
    bias = jnp.where(jnp.arange(24) >= 16, 10.0, 0.0)
    out, stats = served_moe(c, dict(lp, router_bias=bias), h)
    scores = jax.nn.softmax(moe_ops.router_logits(h, lp["router"]), -1)
    assert np.asarray(stats.experts >= 16).all()
    w = jnp.take_along_axis(scores, stats.experts, -1).sum(-1) * 6.0
    np.testing.assert_allclose(out[0], w[:, None] * h, rtol=1e-6, atol=1e-6)
    assert (float(stats.rows), float(stats.touched), float(stats.rows_padded)
            ) == (0, 0, 0)
    assert float(stats.pairs_zero) == float(stats.rows_routed) == 21 * 3


def test_a_token_of_routed_experts_alone_is_the_plain_layer():
    """A bias that sinks the zero experts: no token chooses one, and the
    layer is ops/moe.moe_mlp without zero experts on the same router."""
    c, lp, h = moe_case()
    sunk = dict(lp, router_bias=jnp.where(jnp.arange(24) >= 16, -10.0,
                                          lp["router_bias"]))
    out, stats = served_moe(c, sunk, h)
    plain, plain_stats = moe_ops.moe_mlp(
        sunk, h[None], 3, False, scoring="softmax", scale=6.0)
    assert np.array_equal(out, plain)
    assert float(stats.pairs_zero) == 0 and plain_stats.pairs_zero is None
    assert float(stats.rows) == float(plain_stats.rows) == 21 * 3


def test_a_masked_token_has_no_identity_part():
    c, lp, h = moe_case()
    mask = jnp.arange(21) % 2 == 0
    out, stats = served_moe(c, lp, h, token_mask=mask[None])
    whole, _ = served_moe(c, lp, h)
    assert not np.asarray(out[0][1::2]).any()
    np.testing.assert_allclose(out[0][::2], whole[0][::2], atol=1e-6)
    assert float(stats.rows_routed) == 11 * 3


def test_the_choice_bias_moves_the_choice_and_not_the_weight():
    c, lp, h = moe_case()
    with jax.default_matmul_precision("highest"):
        weights, experts, _ = ref.router(lp, h, ref_config(c))
        scores = jax.nn.softmax(h @ lp["router"], -1)
        unbiased = ref.top_k_stable(scores, 3)
    assert not np.array_equal(np.asarray(experts), np.asarray(unbiased))
    np.testing.assert_allclose(
        weights, jnp.take_along_axis(scores, experts, -1) * 6.0, rtol=1e-6)
    served = moe_ops.choose(moe_ops.router_logits(h, lp["router"]), 3, False,
                            "softmax", 6.0, lp["router_bias"])
    assert np.array_equal(np.asarray(served[1]), np.asarray(experts))


# -- the pool, the config ---------------------------------------------------


def test_the_pool_and_the_tree_have_a_layer_a_sublayer():
    c = LongcatFlashConfig.tiny_longcat()
    cache = PagedKVCache.create(c, 4, 10, 8, 64, dtype=jnp.bfloat16)
    assert cache.k.shape == (4, 10, 8, 16 + 8)       # a row a sublayer
    assert cache.v.size == 0 and cache.v.shape[0] == 0
    blocks = init_params(c, jax.random.PRNGKey(0), jnp.bfloat16)["blocks"]
    assert blocks["wq_a"].shape[0] == blocks["w_gate"].shape[0] == 4
    assert blocks["router"].shape == (2, 64, 24)
    assert blocks["router"].dtype == jnp.float32
    assert blocks["router_bias"].shape == (2, 24)
    assert blocks["we_up"].shape == (2, 16, 64, 32)
    assert not any(k.startswith(("wi_", "ws_")) for k in blocks)
    geo = c.geometry(0)
    assert (geo.q_scale, geo.kv_scale) == (2 ** 0.5, 2.0)
    assert c.family.name == "longcat_flash"
    assert "4 latent layers of 2" in c.family.says(c)
    assert "(16 of 16) + 8 zero experts" in c.family.says(c)


def test_published_config_parses():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        raw = json.load(f)
    c = load_config_dict(raw)
    assert isinstance(c, LongcatFlashConfig)
    assert (c.num_layers, c.num_hidden_layers, c.hidden_size,
            c.latent_width, c.latent_row) == (7, 14, 6144, 576, 640)
    assert c.indexer_types == ("dense",) * 14
    assert c.mlp_layer_types == ("shortcut", "dense") * 7
    assert c.shortcut_layers == tuple(range(0, 14, 2))
    assert (c.num_local_experts, c.n_routed_experts_total, c.zero_expert_num,
            c.first_routed_expert, c.num_experts_per_tok) == (
                16, 512, 256, 0, 12)
    assert (c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank,
            c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (
                64, 1536, 512, 128, 64, 128)
    assert (c.intermediate_size, c.moe_intermediate_size) == (12288, 2048)
    geo = c.geometry(0)
    assert geo.q_scale == 2.0 and abs(geo.kv_scale - 12 ** 0.5) < 1e-12
    assert c.routed_scaling_factor == 6 and c.norm_topk_prob is False
    assert c.rope_theta == 1e7 and c.rms_norm_eps == 1e-5
    assert c.vocab_size == 16384 and c.eos_token_ids == (16384,)
    said = c.family.says(c)
    assert "14 latent layers of 7" in said
    assert "(16 of 512) + 256 zero experts" in said


RAW = dict(
    model_type="longcat_flash", vocab_size=64, hidden_size=32,
    ffn_hidden_size=64, expert_ffn_hidden_size=16, num_layers=2,
    num_attention_heads=2, q_lora_rank=16, kv_lora_rank=8,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, n_routed_experts=4,
    n_routed_experts_total=16, zero_expert_num=8,
    zero_expert_type="identity", moe_topk=3, routed_scaling_factor=6.0,
    attention_method="MLA", attention_bias=False)


@pytest.mark.parametrize("key,value", [
    ("zero_expert_type", "copy"), ("attention_method", "MHA"),
    ("attention_bias", True), ("router_bias", True),
    ("norm_topk_prob", True), ("q_lora_rank", None),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("first_routed_expert", 14), ("moe_topk", 25), ("mtp_num_layers", 1),
    ("hidden_act", "gelu")])
def test_what_is_not_served_is_refused_by_its_name(key, value):
    c = load_config_dict(RAW)
    assert (c.num_hidden_layers, c.num_local_experts, c.zero_expert_num
            ) == (4, 4, 8)
    with pytest.raises(ValueError, match=key):
        load_config_dict(dict(RAW, **{key: value}))


def test_a_scale_that_the_config_leaves_out_is_left_out():
    c = load_config_dict(dict(RAW, mla_scale_kv_lora=False))
    assert (c.geometry(0).q_scale, c.geometry(0).kv_scale) == (2 ** 0.5, 1.0)


# -- the engine -------------------------------------------------------------


def make_engine(**kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    # four of the sixteen routed experts held: the chip's share of four
    c = LongcatFlashConfig.tiny_longcat(num_local_experts=4,
                                        first_routed_expert=4)
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
    from cake_tpu.obs import steps as obs_steps
    c, params, eng = make_engine()
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(3, 250, n))) for n in (40, 7, 21)]
    series = obs_steps.COUNTER_SERIES["moe_pairs_zero"]
    before = series.value
    with eng:
        handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
        for h in handles:
            assert h.wait(180)
        records = eng.flight.dump()
    return (c, params, prompts, [h.token_ids for h in handles], records,
            series.value - before, eng)


@pytest.mark.parametrize("request_index", range(3))
def test_engine_serves_the_references_greedy_tokens(engine_run,
                                                    request_index):
    """Through submit -> _do_mixed -> the in-flight decode step, four of
    sixteen experts held. Teacher-forced: the reference's forward (the
    same share) over the prompt and the tokens the engine gave must
    choose each of them."""
    c, params, prompts, tokens, *_ = engine_run
    prompt, out = prompts[request_index], tokens[request_index]
    assert len(out) == 8
    logits = np.asarray(ref.forward(
        ref_params(params, c), np.asarray(prompt + out), ref_config(c),
        held=(4, 4)))
    for i, tok in enumerate(out):
        at = logits[len(prompt) - 1 + i]
        top2 = np.sort(at)[-2:]
        if top2[1] - top2[0] > 1e-3:        # a near-tie may fall either way
            assert tok == int(np.argmax(at)), i


def test_the_zero_pairs_reach_the_records_and_the_series(engine_run):
    from cake_tpu.obs import metrics as obs_metrics
    *_, records, moved, eng = engine_run
    assert {r["kind"] for r in records} >= {"mixed", "decode"}
    assert all(r["impl"] == "paged-mla-fold" for r in records)
    counted = [r for r in records if "moe_pairs_zero" in r]
    assert counted and {r["kind"] for r in counted} == {"mixed", "decode"}
    for r in counted:
        assert 0 <= r["moe_pairs_zero"] <= r["moe_rows_routed"]
        # a zero pair never reaches the grouped matmul
        assert r["moe_rows"] <= r["moe_rows_routed"] - r["moe_pairs_zero"]
        assert "moe_tokens_group_held" not in r
    assert moved == sum(r["moe_pairs_zero"] for r in counted) > 0
    assert "cake_moe_pairs_zero_total" in obs_metrics.REGISTRY.render()
    decode = [r for r in records if r["kind"] == "decode"]
    # 4 latent layers walk their pages; a window a step
    assert all(r["mla_decode_pages"] == 4 * r["attn_pages"] for r in decode)
    assert eng._mixed_buckets == (32,) and not eng._prefix_capable


@pytest.mark.parametrize("refused,option", [
    (dict(kv_pages=None), "--kv-pages"), (dict(kv_dtype="int8"), "--kv-dtype")])
def test_engine_refuses_by_option_what_latent_rows_cannot_move(refused,
                                                               option):
    with pytest.raises(ValueError) as err:
        make_engine(**refused)
    said = str(err.value)
    assert "model_type longcat_flash" in said and option in said
    assert "latent row" in said and "index key" not in said
