"""Granite-4.0-H (`granitemoehybrid`) at a tiny size on seeded weights:
the served path (mixed-step prefill in windows whose edges fall inside
and across the scan's chunks, decode through the rows' recurrent state,
rows admitted and retired mid-run, slots reused) against the plain
float32 reference's full forward, logits and carried state; four altered
references that must fail; the softmax scale through both folds and both
kernels; the config's refusals; the engine around them; and Nemotron's
step programs, which this family's pieces were lifted out of, lowering
as they did.

Layers `mamba mamba attention mamba mamba`: 8 Mamba heads of 16 in ONE
group, state 16, chunk 8, windows of 12 (so a window's edge falls inside
a chunk and a chunk's inside a window), 4 query heads over 2 of 16 at a
softmax scale of 1/16 (1/sqrt(hd) is 1/4), multipliers 12, 0.22 and 8.
"""

import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import load_config_dict
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import (
    HybridPagedCache, PagedKVCache, mixed_token_buckets, paged_attention,
    paged_attention_mixed,
)
from cake_tpu.models.moe import granite_hybrid as gh
from cake_tpu.models.moe import nemotron_h as nh
from cake_tpu.models.moe.config import GraniteHybridConfig, NemotronHConfig
from cake_tpu.models.moe.params import init_params
from cake_tpu.models.reference import granite_hybrid as ref
from cake_tpu.ops.attention import gqa_attention

B, C, PAGE, MAX_SEQ = 4, 12, 8, 64
REF_KEYS = ("rms_norm_eps", "mamba_n_heads", "mamba_d_head",
            "mamba_n_groups", "mamba_d_state", "num_attention_heads",
            "num_key_value_heads", "embedding_multiplier",
            "attention_multiplier", "residual_multiplier", "logits_scaling")
# float32 on both sides at `highest` matmul precision; the two differ in
# the ORDER of sums alone (chunks of 8 against token by token, pages of 8
# against whole rows), a few 1e-7 on logits of ~1 (read: 3e-7). The
# altered references below must leave it TENFOLD; the nearest, a bfloat16
# state, reads 1.9e-4 at this size
ATOL = 5e-6


def ref_config(c, **over):
    return dict({k: getattr(c, k) for k in REF_KEYS}, **over)


def ref_params(params, c):
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": nh.dequantized(params["lm_head"]),
            "layers": list(gh.reference_layers(params["blocks"], c))}


@pytest.fixture(scope="module")
def model():
    c = GraniteHybridConfig.tiny_granite()
    return c, init_params(c, jax.random.PRNGKey(0), jnp.float32)


def fresh_cache(c):
    cache = PagedKVCache.create(c, B, 1 + B * (MAX_SEQ // PAGE), PAGE,
                                MAX_SEQ, dtype=jnp.float32)
    table = np.stack([1 + b * (MAX_SEQ // PAGE) + np.arange(MAX_SEQ // PAGE)
                      for b in range(B)]).astype(np.int32)
    return cache._replace(table=jnp.asarray(table))


def mixed(model, cache, toks, pos, qlen, attn="fold"):
    c, params = model
    return jax.jit(gh.mixed_trunk, static_argnames=(
        "config", "attn", "n_tokens"))(
        params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(qlen),
        jnp.asarray(qlen > 0), cache, config=c, attn=attn,
        n_tokens=mixed_token_buckets(B, C, (1,))[-1])


def decode(model, cache, toks, pos, active, attn="fold"):
    c, params = model
    return jax.jit(gh.decode_trunk, static_argnames=("config", "attn"))(
        params, jnp.asarray(toks), cache, jnp.asarray(pos),
        jnp.asarray(active), config=c, attn=attn)


def serve(model, sequences, prompts, cache=None, attn="fold"):
    """Every sequence through the step programs as an engine would run
    them, teacher-forced: a sequence takes the lowest free slot in
    order; ONE window a step, the prompts mid-prefill in admission
    order, the rows past their prompt riding it as one-token rows; the
    decode program where no prompt is open; a finished sequence leaves
    its slot to the next. Returns (per sequence {position: logits},
    cache, the slot each took, per sequence the state it left behind
    (ssm [L_M, ...], conv) read when it finished)."""
    c, params = model
    cache = fresh_cache(c) if cache is None else cache
    waiting = list(range(len(sequences)))
    slot_of, off = {}, {}
    took = [None] * len(sequences)
    left = [None] * len(sequences)
    got = [dict() for _ in sequences]
    while waiting or slot_of:
        free = sorted(set(range(B)) - set(slot_of.values()))
        while waiting and free:
            i = waiting.pop(0)
            slot_of[i], off[i] = free.pop(0), 0
            took[i] = slot_of[i]
        open_ = [i for i in slot_of if off[i] < prompts[i]]
        width = C if open_ else 1
        toks = np.zeros((B, width), np.int32)
        pos = np.zeros(B, np.int32)
        qlen = np.zeros(B, np.int32)
        for i, b in slot_of.items():
            n = (min(C, prompts[i] - off[i]) if open_ and i == open_[0]
                 else 0 if off[i] < prompts[i] else 1)
            toks[b, :n] = sequences[i][off[i]:off[i] + n]
            pos[b], qlen[b] = off[i], n
        if open_:
            out, plan = mixed(model, cache, toks, pos, qlen, attn)
            start = np.asarray(plan.start)
        else:
            out = decode(model, cache, toks, pos, qlen > 0, attn)
            start = np.arange(B)
        cache = out.cache
        logits = np.asarray(gh.logits_of(out.x, params, c))
        for i, b in list(slot_of.items()):
            for j in range(qlen[b]):
                got[i][off[i] + j] = logits[start[b] + j]
            off[i] += int(qlen[b])
            if off[i] == len(sequences[i]):
                left[i] = (np.asarray(cache.ssm[:, b]),
                           np.asarray(cache.conv[:, b]))
                del slot_of[i]
    return got, cache, took, left


@pytest.fixture(scope="module")
def traffic(model):
    """Seven requests over four slots: three take reused slots."""
    rng = np.random.default_rng(0)
    prompts = (37, 9, 52, 12, 5, 30, 24)
    outs = (8, 3, 6, 11, 14, 4, 7)
    return [rng.integers(0, model[0].vocab_size, p + o)
            for p, o in zip(prompts, outs)], prompts


@pytest.fixture(scope="module")
def reference_run(model, traffic):
    c, params = model
    states = [[] for _ in traffic[0]]
    logits = ref.forward(ref_params(params, c), traffic[0], ref_config(c),
                         states=states)
    return [np.asarray(x) for x in logits], states


@pytest.fixture(scope="module")
def served_run(model, traffic):
    return serve(model, *traffic)


# -- the served path against the reference -------------------------------------


@pytest.mark.parametrize("request_index", range(7))
def test_served_path_matches_the_reference_forward(
        served_run, reference_run, traffic, request_index):
    """Prefill in windows of 12 over chunks of 8, then decode through
    the state, beside rows that come and go: every position's logits
    (ATOL: its reason is beside it)."""
    got, want = served_run[0][request_index], reference_run[0][request_index]
    assert sorted(got) == list(range(len(traffic[0][request_index])))
    for position, logits in got.items():
        np.testing.assert_allclose(logits, want[position], atol=ATOL,
                                   err_msg=f"position {position}")


def test_rows_were_admitted_mid_run_into_reused_slots(served_run):
    """The first four fill the slots; the three behind them each wait
    for a request to finish and take the slot it left."""
    took = served_run[2]
    assert took[:4] == [0, 1, 2, 3]
    assert len(took[4:]) == 3 and set(took[4:]) <= set(range(B))


@pytest.mark.parametrize("request_index", range(7))
def test_the_carried_state_is_the_references(served_run, reference_run,
                                             request_index):
    """Each Mamba mixer's state and conv tail after the request's last
    token, read when it left its slot. The state is float32 on both
    sides and sums up to 66 tokens of products of ~1: 2e-5 is a hundred
    roundings."""
    ssm, conv = served_run[3][request_index]
    for j, (S, tail) in enumerate(reference_run[1][request_index]):
        np.testing.assert_allclose(ssm[j], S, atol=2e-5)
        np.testing.assert_allclose(conv[j], tail, atol=2e-5)


def test_a_reused_slot_gives_the_request_what_it_gets_alone(model, traffic,
                                                            served_run):
    """Request 4 ran in a slot whose state another left behind: position
    0 zeroes it inside the step program, and with ONE packed size a
    row's bits do not depend on its company."""
    sequences, prompts = traffic
    alone, *_ = serve(model, [sequences[4]], [prompts[4]])
    for position, logits in alone[0].items():
        assert np.array_equal(logits, served_run[0][4][position])


@pytest.mark.parametrize("altered,why", [
    (dict(embedding_multiplier=1.0), "the embedding's multiplier dropped"),
    (dict(residual_multiplier=1.0), "the residual's multiplier dropped"),
    (dict(logits_scaling=1.0), "the logits' scaling dropped"),
    (dict(attention_multiplier=0.25), "a softmax scale of 1/sqrt(hd)"),
    (dict(ssm_state_dtype="bfloat16"), "a bfloat16 state"),
    (dict(gate_after_norm=True), "the gate after the norm"),
    (dict(conv_window=C), "the conv's tail dropped at a window's edge"),
    (dict(attn_rope_theta=10000.0), "rotated queries and keys"),
    ("state_not_zeroed", "a slot's state inherited")])
def test_an_altered_reference_is_another_model(model, traffic, served_run,
                                               reference_run, altered, why):
    """What chip_compare.py holds to fail on the chip, here at float32
    where nothing hides it: each altered reference leaves the served
    path's tolerance tenfold or more."""
    c, params = model
    seq = traffic[0][0]
    if altered == "state_not_zeroed":
        kw = dict(config=ref_config(c), starts=[list(reference_run[1][2])])
    else:
        kw = dict(config=ref_config(c, **altered))
    logits = np.asarray(ref.forward(ref_params(params, c), [seq], **kw)[0])
    apart = max(float(np.abs(logits[p] - got).max())
                for p, got in served_run[0][0].items())
    assert apart > 10 * ATOL, (why, apart)


@pytest.mark.parametrize("kind", ["mixed", "decode"])
def test_a_row_with_no_token_keeps_its_state(model, served_run, kind):
    cache = served_run[1]
    before = (np.asarray(cache.ssm), np.asarray(cache.conv))
    toks = np.ones((B, C if kind == "mixed" else 1), np.int32)
    pos = np.asarray([45, 17, 60, 0], np.int32)
    if kind == "mixed":
        qlen = np.asarray([1, 0, 0, 7], np.int32)
        out, _ = mixed(model, cache, toks, pos, qlen)
    else:
        out = decode(model, cache, toks, pos,
                     np.asarray([True, False, False, True]))
    for stored, was in zip((out.cache.ssm, out.cache.conv), before):
        stored = np.asarray(stored)
        assert np.array_equal(stored[:, 1:3], was[:, 1:3])
        assert not np.array_equal(stored[:, 0], was[:, 0])
        assert not np.array_equal(stored[:, 3], was[:, 3])
    # rows x 4 Mamba mixers; scanned; stepped; row 3 starts at 0
    assert list(np.asarray(out.counters)) == (
        [8, 28, 4, 1] if kind == "mixed" else [8, 0, 8, 1])


def test_the_cache_is_nemotrons_and_one_window_a_dispatch(model):
    c, _ = model
    assert mixed_token_buckets(64, 512, (1,)) == (576,)
    cache = PagedKVCache.create(c, 4, 10, 8, 64, dtype=jnp.bfloat16)
    assert isinstance(cache, HybridPagedCache)
    assert cache.k.shape == (1, 10, 8, 2 * 16)        # attention layers only
    assert cache.ssm.shape == (4, 4, 8, 16, 16)
    assert cache.ssm.dtype == jnp.float32 and cache.conv.dtype == jnp.bfloat16
    assert cache.conv.shape == (4, 4, 3, 128 + 2 * 16)
    assert gh.FAMILY.create_cache is nh.create_cache
    assert gh.FAMILY.counters == nh.COUNTERS[-4:]


# -- the softmax scale through both folds and both kernels --------------------


@pytest.fixture(scope="module")
def pages():
    """A row of 37 keys over pages of 8 at heads of 64: 8 query heads
    over 2 (the pool row 128 lanes)."""
    rng = np.random.default_rng(3)
    H, KV, hd, P, n_pages = 8, 2, 64, 8, 6
    k = jnp.asarray(rng.normal(size=(2, n_pages + 1, P, KV * hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, n_pages + 1, P, KV * hd)), jnp.float32)
    table = jnp.asarray([[3, 1, 6, 2, 5, -1]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(1, 12, H, hd)), jnp.float32)
    flat = lambda pool: pool[1][jnp.asarray([3, 1, 6, 2, 5])].reshape(
        1, 5 * P, KV, hd)
    return q, k, v, table, flat(k), flat(v)


@pytest.mark.parametrize("scale", [1.0 / 64, None, 0.3])
@pytest.mark.parametrize("impl", ["fold", "pallas"])
@pytest.mark.parametrize("kind", ["decode", "mixed"])
def test_scale_reaches_the_fold_and_the_kernel(pages, kind, impl, scale):
    """Against ops/attention.gqa_attention at the same scale; None is
    1/sqrt(hd), as it was. (pallas: the kernel, interpreted.)"""
    q, k, v, table, fk, fv = pages
    layer = jnp.int32(1)
    if kind == "decode":
        pos = jnp.asarray([36], jnp.int32)
        got = paged_attention(q[:, :1], k, v, layer, table, pos, impl=impl,
                              scale=scale)
        mask = (jnp.arange(40) <= 36)[None, None, :]
        want = gqa_attention(q[:, :1], fk, fv, mask=mask, scale=scale)
    else:
        pos, n = jnp.asarray([25], jnp.int32), jnp.asarray([12], jnp.int32)
        got = paged_attention_mixed(q, k, v, layer, table, pos, n,
                                    impl=impl, scale=scale)
        mask = (jnp.arange(40)[None, :]
                <= (25 + jnp.arange(12))[:, None])[None]
        want = gqa_attention(q, fk, fv, mask=mask, scale=scale)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if scale is not None:
        other = gqa_attention(q[:, :got.shape[1]], fk, fv, mask=mask)
        assert float(jnp.abs(other - want).max()) > 1e-2


@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_both_impls_serve_the_same_model(model, traffic, served_run, attn):
    """The step programs through the kernels (interpreted) give the
    fold's logits: request 2, five windows and six single tokens."""
    got, *_ = serve(model, [traffic[0][2]], [traffic[1][2]], attn=attn)
    alone, *_ = serve(model, [traffic[0][2]], [traffic[1][2]])
    for position, logits in got[0].items():
        np.testing.assert_allclose(logits, alone[0][position], atol=ATOL)


def test_the_subwindow_is_the_kernels_own_count():
    """128 queries at the published heads (256 ask for 16.9 MiB of the
    kernel's 16: the compiler's own refusal, tests/test_step_hlo.py),
    the whole window at a test's sizes."""
    c = GraniteHybridConfig.tiny_granite(
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
        mamba_n_heads=64, mamba_d_head=64)
    assert c.head_dim == 64
    assert gh.subwindow(c, 512, 128, 2, 2) == 128
    assert gh.subwindow(GraniteHybridConfig.tiny_granite(), 12, 8, 4, 4) == 12


# -- the config ----------------------------------------------------------------


def published():
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "granite-4.0-h-micro-int8", "config.json")
    with open(path) as f:
        return json.load(f)


def test_published_config_parses():
    c = load_config_dict(published())
    assert isinstance(c, GraniteHybridConfig) and c.is_moe
    assert c.family is gh.FAMILY
    assert (len(c.layer_types), c.attn_layers) == (40, (5, 15, 25, 35))
    assert len(c.mamba_layers) == 36
    assert (c.hidden_size, c.d_inner, c.conv_dim, c.in_proj_dim) == (
        2048, 4096, 4352, 8512)
    assert (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
            c.ssm_state_size, c.conv_kernel, c.chunk_size) == (
        64, 64, 1, 128, 4, 256)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        32, 8, 64)
    assert (c.embedding_multiplier, c.attention_multiplier,
            c.residual_multiplier, c.logits_scaling) == (
        12.0, 0.015625, 0.22, 8.0)
    assert c.shared_intermediate_size == 8192 and c.tie_word_embeddings
    assert c.vocab_size == 100352 and c.eos_token_ids == (100352,)
    assert c.chat_template == "chatml"


def test_published_config_has_no_alias_key():
    """config.json is its source but for eos_token_id: none of the
    names models/moe/nemotron_h.py reads is smuggled in beside the
    published ones."""
    raw = published()
    assert not {"mamba_num_heads", "mamba_head_dim", "n_groups",
                "ssm_state_size", "chunk_size", "conv_kernel",
                "hybrid_override_pattern"} & set(raw)


RAW = dict(
    model_type="granitemoehybrid", vocab_size=64, hidden_size=32,
    num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
    num_attention_heads=2, num_key_value_heads=1, intermediate_size=48,
    shared_intermediate_size=48, mamba_n_heads=4, mamba_d_head=16,
    mamba_n_groups=1, mamba_d_state=8, mamba_d_conv=4, mamba_expand=2,
    num_local_experts=0, num_experts_per_tok=0,
    position_embedding_type="nope", tie_word_embeddings=True,
    embedding_multiplier=12, attention_multiplier=0.0625,
    residual_multiplier=0.22, logits_scaling=8)


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 64), ("position_embedding_type", "rope"),
    ("mamba_proj_bias", True), ("tie_word_embeddings", False),
    ("layer_types", ["mamba", "attention", "linear"]),
    ("layer_types", ["mamba", "attention"]), ("attention_bias", True),
    ("mamba_conv_bias", False), ("hidden_act", "gelu"),
    ("normalization_function", "layernorm"), ("mamba_expand", 4),
    ("mamba_n_groups", 3), ("rope_scaling", {"rope_type": "yarn"})])
def test_what_is_not_served_is_refused_by_its_key(key, value):
    assert isinstance(load_config_dict(RAW), GraniteHybridConfig)
    with pytest.raises(ValueError, match=key):
        load_config_dict(dict(RAW, **{key: value}))


# -- the engine ----------------------------------------------------------------


def make_engine(**kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    c = GraniteHybridConfig.tiny_granite(vocab_size=300,
                                         eos_token_ids=(300,))
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    opts = dict(max_slots=4, max_seq_len=120, cache_dtype=jnp.float32,
                sampling=SamplingConfig(temperature=0.0,
                                        repeat_penalty=1.0),
                kv_pages=64, kv_page_size=8, prefill_chunk=12)
    opts.update(kw)
    return c, params, InferenceEngine(c, params, ByteTokenizer(c.vocab_size),
                                      **opts)


@pytest.fixture(scope="module")
def engine_run():
    c, params, eng = make_engine()
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(3, 250, n)))
               for n in (40, 7, 70, 21, 33, 12)]
    from cake_tpu.obs import steps as obs_steps
    before = {k: s.value for k, s in obs_steps.SSM_COUNTERS}
    with eng:
        handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for h in handles:
            assert h.wait(180)
        records = eng.flight.dump()
    after = {k: s.value for k, s in obs_steps.SSM_COUNTERS}
    return (c, params, prompts, [h.token_ids for h in handles], records,
            {k: after[k] - before[k] for k in after}, eng)


@pytest.mark.parametrize("request_index", range(6))
def test_engine_serves_the_references_greedy_tokens(engine_run,
                                                    request_index):
    """Through submit -> _do_mixed -> the in-flight decode step: four
    requests over four rows and two behind them in REUSED slots.
    Teacher-forced: the reference's forward over the prompt and the
    tokens the engine gave must choose each of them."""
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


def test_step_records_and_metrics_carry_the_state(engine_run):
    from cake_tpu.obs import steps as obs_steps
    c, _, prompts, _, records, moved, eng = engine_run
    assert {r["kind"] for r in records} >= {"mixed", "decode"}
    assert {r["impl"] for r in records} == {"paged-ssm-fold"}
    counted = [r for r in records if "ssm_state_rows" in r]
    assert counted and all("moe_rows" not in r for r in records)
    assert all(r["ssm_state_rows"] % 4 == 0 for r in counted)
    assert any(r.get("chained") for r in records if r["kind"] == "decode")
    assert any(r.get("chained") for r in records if r["kind"] == "mixed")
    assert moved["ssm_state_resets"] == len(prompts)
    assert moved["ssm_tokens_scanned"] == 4 * sum(map(len, prompts))
    assert eng._mixed_buckets == (16,) and not eng._prefix_capable
    assert obs_steps.SSM_STATE_BYTES.value == eng.cache.state_bytes() > 0
    assert eng.flight._counters == gh.COUNTERS == tuple(
        k for k, _ in obs_steps.SSM_COUNTERS)


@pytest.mark.parametrize("refused,named", [
    (dict(kv_pages=None), "--kv-pages"),
    (dict(kv_dtype="int8"), "--kv-dtype"),
    (dict(kv_host_pages=8), "--kv-host-pages"),
    (dict(auto_prefix_system=True), "--auto-prefix"),
    (dict(disagg="prefill"), "--disagg")])
def test_engine_refuses_by_name_what_a_state_does_not_serve(refused, named):
    with pytest.raises(ValueError) as e:
        make_engine(**refused)
    assert "granitemoehybrid" in str(e.value) and named in str(e.value)


def test_prefix_registration_is_refused_by_name():
    *_, eng = make_engine()
    with pytest.raises(ValueError, match="recurrent state"):
        eng.register_prefix([5, 6, 7, 8, 9, 10, 11, 12, 13])


# -- Nemotron's step programs lower as they did ---------------------------------

# sha256 of `.lower(...).as_text()` at NemotronHConfig.tiny_nemotron(),
# taken on the commit BEFORE this family called nemotron_h's blocks and
# threaded `scale=` through models/llama/paged.py (01e49cf). A change
# that means to move Nemotron's programs re-pins them; one that does
# not, and fails here, has moved them. PR 62 re-pinned ("mixed",
# "pallas"): `cake_mixed_attn` walks its rows' pages itself.
NEMOTRON_LOWERED = {
    ("decode", "fold"):
        "7dd6972f8076d6c9d50f9f4b4829cecfcdee79f89e4fe46be582dc862f811a7b",
    ("mixed", "fold"):
        "2a07595c028cf5aa77f1c6191e82cd127f3269f4b3813472516e7b2c6a40bc15",
    ("decode", "pallas"):
        "bb880d48c71d26bba75e01d83f4e73d3476e74f3010325969e128054f6c14181",
    ("mixed", "pallas"):
        "063e8e1b8f69688b1b96afb835bc7e34d619349ae747eea80bc8515c79ed8588",
}


@pytest.mark.parametrize("kind,attn", list(NEMOTRON_LOWERED))
def test_nemotrons_step_programs_lower_as_before(kind, attn):
    c = NemotronHConfig.tiny_nemotron()
    f = c.family
    S, W, SEQ = 4, 8, 64
    cache = jax.eval_shape(lambda: PagedKVCache.create(
        c, S, 16, 4, SEQ, dtype=jnp.float32, width=W))
    params = jax.eval_shape(partial(init_params, c, jax.random.PRNGKey(0),
                                    jnp.float32))
    rope = jax.eval_shape(lambda: RopeTables.create(c, SEQ))
    row = jax.ShapeDtypeStruct((S,), jnp.int32)
    live = jax.ShapeDtypeStruct((S,), bool)
    if kind == "decode":
        lowered = f.decode_step.lower(
            params, jax.ShapeDtypeStruct((S, 1), jnp.int32), row, live,
            cache, rope, config=c, attn=attn)
    else:
        lowered = f.mixed_step.lower(
            params, jax.ShapeDtypeStruct((S, W), jnp.int32), row, row, live,
            cache, rope, config=c, attn=attn,
            n_tokens=mixed_token_buckets(S, W, f.prefill_rows)[-1])
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    assert digest == NEMOTRON_LOWERED[kind, attn]
