"""Ling-3.0 (`bailing_hybrid`) at a tiny size on seeded weights: the
served path (mixed-step prefill in windows whose edges fall inside and
across the delta rule's chunks, decode through the rows' matrix state
and the latent pages, decode rows beside prefilling ones) against the
plain float32 reference's full forward; the pieces one by one (the two
forms of the delta rule against the recurrence, the bounded gate, the
group rule against a NumPy spelling, the shares of a sparse layer); the
state's lifecycle (a slot reused, rows that hold no token); what the
config class refuses; and the engine around them.

Layers `K K M K K M`, 4 heads of 8, windows of 12 over chunks of 16 and
of 8 (so a window's edge falls inside a chunk and a chunk's inside a
window), 16 routed experts in 4 groups of which 2 are taken, 3 a
token."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import load_config_dict
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import (
    HybridPagedCache, PagedKVCache, mixed_token_buckets,
)
from cake_tpu.models.moe import bailing_hybrid as bh
from cake_tpu.models.moe.config import BailingHybridConfig
from cake_tpu.models.moe.params import init_params
from cake_tpu.models.reference import bailing_hybrid as ref
from cake_tpu.ops import moe as moe_ops

ROOT = os.path.join(os.path.dirname(__file__), "..")
CONFIG_DIR = os.path.join(ROOT, "benchmarks", "configs",
                          "ling-3.0-flash-int8-share4")
B, C, PAGE, MAX_SEQ = 4, 12, 8, 64
# float32 on both sides; the chunked rule's products of e^G and e^-G and
# six layers of sums in another order leave a few 1e-5 of logits that
# span ~3
ATOL = 5e-5


def ref_config(c, **over):
    return dict(
        num_attention_heads=c.num_attention_heads, head_dim=c.kda_head_dim,
        kda_lower_bound=c.kda_lower_bound,
        qk_nope_head_dim=c.qk_nope_head_dim,
        qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
        rms_norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
        n_group=c.n_group, topk_group=c.topk_group,
        num_experts_per_tok=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor, **over)


def ref_params(params, c):
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": bh.dequantized(params["lm_head"]),
            "layers": list(bh.reference_layers(params["blocks"], c))}


def seeded(c, key=0):
    """The seeded tree with a choice bias that is not zero (the draw's
    is, as an untrained balancer's), so that a test sees it."""
    params = init_params(c, jax.random.PRNGKey(key), jnp.float32)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(key + 100),
                                    params["blocks"]["router_bias"].shape)
    params["blocks"]["router_bias"] = bias
    return params


@pytest.fixture(scope="module")
def model():
    c = BailingHybridConfig.tiny_ling()
    return c, seeded(c), RopeTables.create(c, MAX_SEQ)


def fresh_cache(c):
    cache = PagedKVCache.create(c, B, 1 + B * (MAX_SEQ // PAGE), PAGE,
                                MAX_SEQ, dtype=jnp.float32)
    table = np.stack([1 + b * (MAX_SEQ // PAGE) + np.arange(MAX_SEQ // PAGE)
                      for b in range(B)]).astype(np.int32)
    return cache._replace(table=jnp.asarray(table))


def mixed(model, cache, toks, pos, qlen, attn="fold"):
    c, params, rope = model
    return jax.jit(bh.mixed_trunk, static_argnames=(
        "config", "attn", "n_tokens"))(
        params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(qlen),
        jnp.asarray(qlen > 0), cache, rope, config=c, attn=attn,
        n_tokens=mixed_token_buckets(B, C, (1,))[-1])


def decode(model, cache, toks, pos, active, attn="fold"):
    c, params, rope = model
    return jax.jit(bh.decode_trunk, static_argnames=("config", "attn"))(
        params, jnp.asarray(toks), cache, jnp.asarray(pos),
        jnp.asarray(active), rope, config=c, attn=attn)


def serve(model, sequences, prompts, company=True, cache=None, rows=None,
          attn="fold"):
    """Every sequence through the step programs: prompts in C-wide
    windows, one window a dispatch, the rows that finished their prompt
    riding the other rows' mixed steps as one-token rows (when
    `company`), then the decode program. rows: the slot of each
    sequence. Returns (per sequence {position: logits}, cache, the
    counters' sum)."""
    c, params, _ = model
    cache = fresh_cache(c) if cache is None else cache
    rows = list(range(len(sequences))) if rows is None else rows
    off = [0] * len(sequences)
    got = [dict() for _ in sequences]
    total = np.zeros(len(bh.COUNTERS))
    head = bh.dequantized(params["lm_head"])
    while any(off[i] < prompts[i] for i in range(len(sequences))):
        i0 = next(i for i in range(len(sequences)) if off[i] < prompts[i])
        toks = np.zeros((B, C), np.int32)
        pos = np.zeros(B, np.int32)
        qlen = np.zeros(B, np.int32)
        for i, seq in enumerate(sequences):
            if i == i0:
                n = min(C, prompts[i] - off[i])
            elif company and prompts[i] <= off[i] < len(seq):
                n = 1
            else:
                continue
            b = rows[i]
            toks[b, :n], pos[b], qlen[b] = seq[off[i]:off[i] + n], off[i], n
        out, plan = mixed(model, cache, toks, pos, qlen, attn)
        cache = out.cache
        total += np.asarray(out.counters)
        logits = out.x @ head
        for i in range(len(sequences)):
            for j in range(qlen[rows[i]]):
                got[i][off[i] + j] = np.asarray(
                    logits[int(plan.start[rows[i]]) + j])
            off[i] += int(qlen[rows[i]])
    while any(off[i] < len(s) for i, s in enumerate(sequences)):
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        for i, seq in enumerate(sequences):
            if off[i] < len(seq):
                b = rows[i]
                toks[b, 0], pos[b], active[b] = seq[off[i]], off[i], True
        out = decode(model, cache, toks, pos, active, attn)
        cache = out.cache
        total += np.asarray(out.counters)
        logits = out.x @ head
        for i in range(len(sequences)):
            if active[rows[i]]:
                got[i][off[i]] = np.asarray(logits[rows[i]])
                off[i] += 1
    return got, cache, total


@pytest.fixture(scope="module")
def traffic(model):
    # (seed 0 draws a token whose 3rd and 4th biased scores in layer 3
    # lie 2e-7 apart: the two paths take one each, and the KDA layers
    # above carry that token's difference to every later position)
    rng = np.random.default_rng(1)
    prompts = (37, 9, 52)
    return [rng.integers(0, model[0].vocab_size, p + 8)
            for p in prompts], prompts


@pytest.fixture(scope="module")
def reference_run(model, traffic):
    c, params, _ = model
    states = [[] for _ in traffic[0]]
    routing = [[] for _ in traffic[0]]
    logits = ref.forward(ref_params(params, c), traffic[0], ref_config(c),
                         states=states, routing=routing)
    return [np.asarray(x) for x in logits], states, routing


@pytest.fixture(scope="module")
def served_run(model, traffic):
    return serve(model, *traffic)


# -- the served path against the reference -------------------------------------


@pytest.mark.parametrize("row", [0, 1, 2])
def test_served_path_matches_the_reference_forward(
        served_run, reference_run, traffic, row):
    """Prefill in windows of 12 over chunks of 16, then decode through
    the state and the latent pages, decode rows beside prefilling ones:
    every position's logits."""
    got, want = served_run[0][row], reference_run[0][row]
    assert sorted(got) == list(range(len(traffic[0][row])))
    for position, logits in got.items():
        np.testing.assert_allclose(logits, want[position], atol=ATOL,
                                   err_msg=f"position {position}")


@pytest.mark.parametrize("layer", [0, 3])
def test_state_and_tails_at_the_end_are_the_references(
        served_run, reference_run, layer):
    """A row's stored matrix state and conv tails after its last token:
    the reference's final state of that KDA layer."""
    cache = served_run[1]
    for row in range(3):
        S, tails = reference_run[1][row][layer]
        np.testing.assert_allclose(cache.ssm[layer, row], S, atol=2e-5)
        np.testing.assert_allclose(cache.conv[layer, row],
                                   jnp.concatenate(tails, -1), atol=2e-5)


def test_the_kernels_serve_what_the_folds_serve(model, traffic, served_run):
    """attn="pallas" (the MLA layers' two kernels interpreted) against
    attn="fold"."""
    sequences, prompts = traffic
    got, *_ = serve(model, sequences[:2], prompts[:2], attn="pallas")
    fold, *_ = serve(model, sequences[:2], prompts[:2])
    for row in range(2):
        for position, logits in got[row].items():
            np.testing.assert_allclose(logits, fold[row][position],
                                       atol=2e-5)


@pytest.mark.parametrize("altered", [
    dict(kda_state_dtype="bfloat16"), dict(kda_decay_dtype="bfloat16"),
    dict(kda_gate="softplus"), dict(group_top=1), dict(head_gate=False),
    "state_not_zeroed"])
def test_an_altered_reference_is_another_model(model, traffic, served_run,
                                               reference_run, altered):
    """What chip_compare.py holds to fail on the chip, here at float32
    where nothing hides it: each altered reference leaves the served
    path's tolerance tenfold or more."""
    c, params, _ = model
    seq = traffic[0][2]
    if altered == "state_not_zeroed":
        # the sequence starts from the state another left behind
        kw = dict(config=ref_config(c), starts=[list(reference_run[1][0])])
    else:
        kw = dict(config=ref_config(c, **altered))
    logits = np.asarray(ref.forward(ref_params(params, c), [seq], **kw)[0])
    apart = max(float(np.abs(logits[p] - got).max())
                for p, got in served_run[0][2].items())
    assert apart > 10 * ATOL, apart


def test_counters_count_the_two_forms_and_the_keys(served_run, traffic):
    """kda_tokens_chunked + kda_tokens_stepped: every token x 4 KDA
    layers, a prompt's windows chunked (but a last window of ONE token);
    mla_keys_attended: position + 1 over the single-token rows x 2 MLA
    layers; all experts held, so moe_rows == moe_rows_routed."""
    sequences, prompts = traffic
    total = dict(zip(bh.COUNTERS, served_run[2]))
    tokens = sum(len(s) for s in sequences)
    lone = sum(1 for p in prompts if p % C == 1)
    assert total["kda_tokens_chunked"] == 4 * (sum(prompts) - lone)
    assert total["kda_tokens_stepped"] == 4 * (tokens - sum(prompts) + lone)
    single = sum(sum(range(p + 1, len(s) + 1)) + (p if p % C == 1 else 0)
                 for s, p in zip(sequences, prompts))
    assert total["mla_keys_attended"] == 2 * single
    assert total["moe_rows_routed"] == tokens * 3 * 5 == total["moe_rows"]
    assert total["moe_tokens_group_held"] == 0            # no share
    assert total["kda_state_rows"] >= 4 * 3


# -- the delta rule in its two forms -------------------------------------------


def recurrence(S0, q, k, v, g, beta):
    """The delta rule token by token, float64."""
    S, out = np.asarray(S0, np.float64), []
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    for t in range(q.shape[0]):
        S = np.exp(g[t])[:, :, None] * S
        u = beta[t][:, None] * (v[t] - np.einsum("hk,hkv->hv", k[t], S))
        S = S + k[t][:, :, None] * u[:, None, :]
        out.append(np.einsum("hk,hkv->hv", q[t], S))
    return S, np.stack(out)


def delta_case(n, seed, lower=-5.0):
    H, dk = 4, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    S0 = jax.random.normal(ks[0], (H, dk, dk))
    q = bh.l2_normed(jax.random.normal(ks[1], (n, H, dk))) * dk ** -0.5
    k = bh.l2_normed(jax.random.normal(ks[2], (n, H, dk)))
    v = jax.random.normal(ks[3], (n, H, dk))
    # log-decays over the whole of (lower, 0): the fastest channels lose
    # e^-5 a token, e^-80 a chunk
    g = lower * jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (n, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (n, H)))
    return S0, q, k, v, g, beta


@pytest.mark.parametrize("n,chunk", [(16, 16), (12, 16), (5, 16), (40, 16),
                                     (24, 8), (64, 16)])
def test_chunked_is_one_step_is_the_recurrence(n, chunk):
    """From a NON-ZERO state: the chunked form over n tokens, n one-step
    updates, and the recurrence in float64."""
    S0, q, k, v, g, beta = delta_case(n, n * 31 + chunk)
    S_c, o_c = bh.kda_chunked(S0, q, k, v, g, beta, chunk)
    S, os_ = S0[None], []
    for t in range(n):
        S, o = bh.kda_step(S, q[t][None], k[t][None], v[t][None],
                           g[t][None], beta[t][None])
        os_.append(o[0])
    S_r, o_r = recurrence(S0, q, k, v, g, beta)
    np.testing.assert_allclose(S_c, S_r, atol=2e-5)
    np.testing.assert_allclose(S[0], S_r, atol=2e-5)
    np.testing.assert_allclose(o_c, o_r, atol=2e-5)
    np.testing.assert_allclose(jnp.stack(os_), o_r, atol=2e-5)


def test_chunked_stays_finite_at_the_bound():
    """Every channel at the bound itself, -5 a token: e^-G of a 16-token
    chunk is e^80, finite in float32, and the chunked form is still the
    recurrence."""
    S0, q, k, v, g, beta = delta_case(32, 7)
    g = jnp.full_like(g, -5.0)
    S_c, o_c = bh.kda_chunked(S0, q, k, v, g, beta)
    S_r, o_r = recurrence(S0, q, k, v, g, beta)
    assert np.isfinite(np.asarray(o_c)).all()
    np.testing.assert_allclose(o_c, o_r, atol=2e-5)
    np.testing.assert_allclose(S_c, S_r, atol=2e-5)


def test_tokens_past_a_windows_own_leave_the_state():
    """g = 0 and beta = 0 (what kda_layer hands the chunked form past
    the window's real tokens): the state passes through unchanged."""
    S0, q, k, v, g, beta = delta_case(24, 3)
    own = jnp.arange(24) < 13
    S_c, _ = bh.kda_chunked(S0, q, k, v, jnp.where(own[:, None, None], g, 0),
                            jnp.where(own[:, None], beta, 0))
    S_r, _ = recurrence(S0, q[:13], k[:13], v[:13], g[:13], beta[:13])
    np.testing.assert_allclose(S_c, S_r, atol=2e-5)


def test_the_bounded_gate_stays_inside_its_bound():
    """g in (lower_bound, 0) whatever the projection gives, float32; at
    a zero input the seeded draw places each channel's half-life in
    1 .. 1,000 tokens."""
    c = BailingHybridConfig.tiny_ling()
    blocks = init_params(c, jax.random.PRNGKey(3), jnp.bfloat16)["blocks"]
    A_log, dt_bias = blocks["A_log"][0], blocks["dt_bias"][0]
    assert A_log.dtype == dt_bias.dtype == jnp.float32
    a = 40.0 * jax.random.normal(jax.random.PRNGKey(4), (64, 4, 8))
    g = bh.kda_gate(a, dt_bias, A_log, c.kda_lower_bound)
    assert g.dtype == jnp.float32
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    assert float(g.min()) < -4.9 and float(g.max()) > -0.1
    rest = bh.kda_gate(jnp.zeros((1, 4, 8)), dt_bias, A_log, -5.0)
    half_life = np.log(2.0) / -np.asarray(rest)
    assert half_life.min() >= 0.999 and half_life.max() <= 1001.0
    assert half_life.max() / half_life.min() > 20


# -- the state's lifecycle -----------------------------------------------------


@pytest.mark.parametrize("prompt", [5, 12, 30])
def test_a_reused_slot_gives_the_request_what_it_gets_alone(model, traffic,
                                                            prompt):
    """A second request in a slot whose state the first left behind:
    position 0 zeroes the state and the tails inside the step program."""
    rng = np.random.default_rng(prompt)
    second = rng.integers(0, 256, prompt + 4)
    alone, *_ = serve(model, [second], [prompt], rows=[1])
    _, used, _ = serve(model, [traffic[0][2]], [traffic[1][2]], rows=[1])
    assert float(jnp.abs(used.ssm[:, 1]).max()) > 0
    after, *_ = serve(model, [second], [prompt], rows=[1], cache=used)
    for position in alone[0]:
        assert np.array_equal(alone[0][position], after[0][position])


@pytest.mark.parametrize("kind", ["mixed", "decode"])
def test_a_row_with_no_token_keeps_its_state(model, served_run, kind):
    """Idle, frozen and out-of-budget rows are rows that are not active
    or hold no token in the dispatch: their state's bits stay."""
    cache = served_run[1]
    before = (np.asarray(cache.ssm), np.asarray(cache.conv))
    toks = np.ones((B, C if kind == "mixed" else 1), np.int32)
    pos = np.asarray([45, 17, 60, 0], np.int32)
    if kind == "mixed":
        # row 0 decodes, row 3 starts a prompt; row 1 is active with no
        # token (out of budget), row 2 is not in the dispatch
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
    counters = dict(zip(bh.COUNTERS, np.asarray(out.counters)))
    assert counters["kda_state_rows"] == 4 * 2        # KDA layers x rows
    assert counters["kda_tokens_chunked"] == (4 * 7 if kind == "mixed" else 0)
    assert counters["kda_tokens_stepped"] == (4 if kind == "mixed" else 8)


@pytest.mark.parametrize("kind", ["window", "single_token"])
def test_a_rows_bits_do_not_depend_on_its_company(model, traffic, kind):
    """Served alone or beside two other rows, the same program gives a
    window's logits and a row's single token the same bits."""
    sequences, prompts = traffic
    alone, *_ = serve(model, sequences[:1], prompts[:1], company=False)
    amid, *_ = serve(model, sequences, prompts)
    positions = ([7, 11, 12, 31, 36] if kind == "window"
                 else list(range(37, 45)))
    for position in positions:
        assert np.array_equal(alone[0][position], amid[0][position])


def test_the_cache_is_the_hybrid_one_as_it_is():
    """`k` the latent pool of the MLA layers alone, `v` empty, the KDA
    state float32 a row and head, the three tails as one."""
    assert mixed_token_buckets(32, 512, (1,)) == (544,)
    c = BailingHybridConfig.tiny_ling()
    cache = PagedKVCache.create(c, 4, 10, 8, 64, dtype=jnp.bfloat16)
    assert isinstance(cache, HybridPagedCache)
    assert cache.k.shape == (2, 10, 8, 16 + 8) and cache.v.size == 0
    assert cache.ssm.shape == (4, 4, 4, 8, 8)
    assert cache.ssm.dtype == jnp.float32
    assert cache.conv.shape == (4, 4, 3, 3 * 32)
    assert cache.conv.dtype == jnp.bfloat16
    assert cache.state_bytes() == cache.ssm.nbytes + cache.conv.nbytes
    assert cache.memory_bytes() == cache.k.nbytes


# -- the group rule ------------------------------------------------------------


def numpy_rule(logits, bias, k, n_group, topk_group, scale):
    """Ling's rule spelled token by token: sigmoid scores; a group's
    score the sum of its two best BIASED scores; the best groups, ties
    to the lower index; the k best biased scores inside them; weights
    the UNBIASED scores of the chosen over their sum, times scale."""
    scores = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    N, E = scores.shape
    per = E // n_group
    experts, weights, groups = [], [], []
    for t in range(N):
        c = scores[t] + bias
        group_score = [np.sort(c[g * per:(g + 1) * per])[-2:].sum()
                       for g in range(n_group)]
        taken = sorted(range(n_group),
                       key=lambda g: (-group_score[g], g))[:topk_group]
        allowed = [e for e in range(E) if e // per in taken]
        chosen = sorted(allowed, key=lambda e: (-c[e], e))[:k]
        w = scores[t][chosen]
        experts.append(chosen)
        groups.append(taken)
        weights.append(w / (w.sum() + 1e-20) * scale)
    return np.asarray(weights), np.asarray(experts), np.asarray(groups)


@pytest.mark.parametrize("case", ["seeded", "ties", "bias_decides"])
def test_the_group_rule_is_its_numpy_spelling(case):
    """Two-best sum, the bias for the choice only, ties to the lower
    index; served (ops/moe.choose_in_groups) and reference alike."""
    rng = np.random.default_rng(11)
    N, E, k, G, tg = 40, 32, 4, 8, 3
    logits = rng.standard_normal((N, E)).astype(np.float32)
    bias = (0.3 * rng.standard_normal(E)).astype(np.float32)
    if case == "ties":
        # whole groups alike, and experts alike inside them
        logits = np.round(logits)
        logits[:, 8:16] = logits[:, 0:8]
        bias = np.zeros(E, np.float32)
    if case == "bias_decides":
        # a bias that lifts group 7 over every other, below 0 elsewhere
        bias = np.full(E, -2.0, np.float32)
        bias[28:] = 3.0
    want_w, want_e, want_g = numpy_rule(logits, bias, k, G, tg, 2.5)
    w, e, g = moe_ops.choose_in_groups(
        jnp.asarray(logits), k, True, "sigmoid", 2.5, jnp.asarray(bias), G,
        tg, 2)
    assert np.array_equal(np.asarray(g), want_g)
    assert np.array_equal(np.asarray(e), want_e)
    np.testing.assert_allclose(w, want_w, rtol=2e-6)
    # the reference's router on the same logits (an identity "router")
    lp = {"router": jnp.eye(E), "router_bias": jnp.asarray(bias)}
    cfg = dict(num_experts_per_tok=k, n_group=G, topk_group=tg,
               routed_scaling_factor=2.5)
    rw, re_, _, rg = ref.router(lp, jnp.asarray(logits), cfg)
    assert np.array_equal(np.asarray(re_), want_e)
    assert np.array_equal(np.asarray(rg), want_g)
    np.testing.assert_allclose(rw, want_w, rtol=2e-6)
    if case == "bias_decides":
        assert (want_g[:, 0] == 7).all()
        # a group not taken is never chosen, though every taken score
        # but group 7's is below 0
        assert all(ex // 4 in set(gr) for ex_row, gr in zip(want_e, want_g)
                   for ex in ex_row)


def test_the_best_alone_is_another_rule():
    """group_top 1 (DeepSeek-V2's) and 2 choose other groups where a
    group has one high score and another two fair ones."""
    logits = jnp.asarray([[4.0, -9, -9, -9, 2.0, 2.0, -9, -9]])
    by_best = moe_ops.top_groups(jax.nn.sigmoid(logits), 2, 1, 1)
    by_two = moe_ops.top_groups(jax.nn.sigmoid(logits), 2, 1, 2)
    assert int(by_best[0, 0]) == 0 and int(by_two[0, 0]) == 1


@pytest.mark.parametrize("side", ["reference", "served"])
def test_four_shares_are_the_uncut_layer(side):
    """Each of 4 chips holds one of a layer's 4 groups (4 of 16 routed
    experts) and routes over all of them; the four shares' routed parts,
    with the mixer and the shared expert counted once, are the uncut
    reference's layer."""
    c = BailingHybridConfig.tiny_ling()
    params = seeded(c, 14)
    lp = list(bh.reference_layers(params["blocks"], c))[1]
    assert lp["kind"] == "kda" and "router" in lp
    x = jax.random.normal(jax.random.PRNGKey(15), (21, c.hidden_size))
    cfg = ref_config(c)
    with jax.default_matmul_precision("highest"):
        whole = ref.layer(lp, x, cfg)
        # the mixer once, and the shared expert once
        mixed_in = ref.layer({k: v for k, v in lp.items()
                              if not k.startswith(("we_", "ws_", "router"))}
                             | {"w_gate": jnp.zeros((c.hidden_size, 1)),
                                "w_up": jnp.zeros((c.hidden_size, 1)),
                                "w_down": jnp.zeros((1, c.hidden_size))},
                             x, cfg)
        h = ref.rms(mixed_in, lp["mlp_norm"], c.rms_norm_eps)
        total = mixed_in + ref.swiglu(h, lp["ws_gate"], lp["ws_up"],
                                      lp["ws_down"])
    held_tokens = 0
    for first in range(0, 16, 4):
        share = {k: (v[first:first + 4] if k.startswith("we_") else v)
                 for k, v in lp.items()}
        if side == "reference":
            with jax.default_matmul_precision("highest"):
                part = ref.moe_ffn(share, h, cfg, held=(first, 4),
                                   shared=False)
        else:
            routed = {k: v for k, v in share.items()
                      if k in ("router", "router_bias", "we_gate", "we_up",
                               "we_down")}
            part, stats = moe_ops.moe_mlp(
                routed, h[None], 3, True, first_expert=first,
                scoring="sigmoid", scale=2.5, n_group=4, topk_group=2,
                group_top=2)
            part = part[0]
            here = (stats.experts >= first) & (stats.experts < first + 4)
            assert float(stats.rows_routed) == 21 * 3
            assert float(stats.rows) == float(jnp.sum(here))
            held_tokens += float(stats.group_held)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=3e-5)
    if side == "served":
        # every token takes 2 of the 4 groups: each is held by one share
        assert held_tokens == 21 * 2


def test_two_held_groups_count_a_token_once():
    """A share of two neighbouring groups (the cell's: 128 of 512 in
    groups 0 and 1): moe_tokens_group_held counts the tokens whose
    groups include EITHER."""
    c = BailingHybridConfig.tiny_ling()
    lp = list(bh.reference_layers(seeded(c, 2)["blocks"], c))[1]
    h = jax.random.normal(jax.random.PRNGKey(5), (30, c.hidden_size))
    routed = {k: (v[:8] if k.startswith("we_") else v)
              for k, v in lp.items()
              if k in ("router", "router_bias", "we_gate", "we_up",
                       "we_down")}
    _, stats = moe_ops.moe_mlp(routed, h[None], 3, True, first_expert=0,
                               scoring="sigmoid", scale=2.5, n_group=4,
                               topk_group=2, group_top=2)
    _, _, _, groups = ref.router(lp, h, ref_config(c))
    assert float(stats.group_held) == float(
        jnp.sum(jnp.any(groups <= 1, axis=-1)))


# -- the config class ----------------------------------------------------------


def test_published_config_parses():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        raw = json.load(f)
    c = load_config_dict(raw)
    assert isinstance(c, BailingHybridConfig)
    assert c.indexer_types == ("kda",) * 5 + ("dense",) + ("kda",) * 5 + (
        "dense",)
    assert c.kda_layers == (0, 1, 2, 3, 4, 6, 7, 8, 9, 10)
    assert c.latent_layers == (5, 11) and c.sparse_layers == tuple(
        range(2, 12))
    assert (c.hidden_size, c.num_attention_heads, c.kda_head_dim,
            c.kda_width, c.conv_kernel) == (2560, 32, 128, 4096, 4)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.latent_row) == (
        None, 512, 128, 64, 128, 640)
    assert (c.num_local_experts, c.n_routed_experts_total,
            c.num_experts_per_tok, c.n_group, c.topk_group, c.group_top) == (
        128, 512, 8, 8, 4, 2)
    assert (c.moe_intermediate_size, c.intermediate_size,
            c.n_shared_experts) == (768, 6144, 1)
    assert c.routed_scaling_factor == 2.5 and c.kda_lower_bound == -5.0
    assert c.rope_theta == 6e6 and c.rope_dim == 64
    assert c.geometry(5).gated and c.geometry(5).q_lora_rank is None
    assert c.vocab_size == 39296 and c.eos_token_ids == (39296,)
    assert c.chat_template == "chatml" and c.family.name == "bailing_hybrid"


RAW = dict(
    model_type="bailing_hybrid", vocab_size=64, hidden_size=32,
    intermediate_size=48, num_hidden_layers=4, layer_group_size=2,
    first_k_dense_replace=1, num_attention_heads=2, head_dim=8,
    q_lora_rank=None, kv_lora_rank=8, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, moe_intermediate_size=16,
    num_experts=4, num_experts_total=16, num_experts_per_tok=2, n_group=4,
    topk_group=2, routed_scaling_factor=2.5,
    expert_swiglu_limit_list=[0, 0, 0, 0, 4],
    share_expert_swiglu_limit_list=[0, 0, 0, 0, 5])


@pytest.mark.parametrize("key,value", [
    ("expert_swiglu_limit_list", [0, 0, 0, 4, 4]),
    ("share_expert_swiglu_limit_list", [0, 5, 0, 0]),
    ("expert_swiglu_limit_list", [0, 0]),
    ("num_nextn_predict_layers", 1), ("first_routed_expert", 14),
    ("kda_safe_gate", False), ("no_kda_lora", False),
    ("use_kda_lora", True), ("linear_silu", False),
    ("gated_attention_proj_granularity_type", "element_wise"),
    ("topk_method", "group_limited_greedy"), ("score_function", "softmax"),
    ("rope_scaling", {"type": "yarn", "factor": 4}), ("use_bias", True),
    ("use_qk_norm", False), ("rope_interleave", False),
    ("group_norm_size", 4), ("rotary_dim", 8), ("qk_head_dim", 16),
    ("n_group", 3), ("num_key_value_heads", 1)])
def test_what_is_not_implemented_is_refused(key, value):
    load_config_dict(RAW)
    named = ("not among the router" if key == "first_routed_expert"
             else key)
    with pytest.raises(ValueError, match=named):
        load_config_dict(dict(RAW, **{key: value}))


def test_a_limit_past_the_served_layers_is_not_read():
    """The published lists clamp from layer 34 on: a cut to the layers
    below serves, a cut that reaches one is refused by the key."""
    c = load_config_dict(RAW)
    assert c.num_hidden_layers == 4
    with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
        load_config_dict(dict(RAW, num_hidden_layers=5))


# -- the engine ----------------------------------------------------------------


def make_engine(**kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    c = BailingHybridConfig.tiny_ling(vocab_size=300, eos_token_ids=(300,))
    params = seeded(c)
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
    before = {k: s.value for k, s in obs_steps.KDA_COUNTERS}
    with eng:
        handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for h in handles:
            assert h.wait(180)
        records = eng.flight.dump()
    after = {k: s.value for k, s in obs_steps.KDA_COUNTERS}
    return (c, params, prompts, [h.token_ids for h in handles], records,
            {k: after[k] - before[k] for k in after}, eng)


@pytest.mark.parametrize("request_index", range(6))
def test_engine_serves_the_references_greedy_tokens(engine_run,
                                                    request_index):
    """Through submit -> _do_mixed -> the in-flight decode step: four
    requests over four rows and two behind them in REUSED slots, prompts
    of 1 to 6 windows. Teacher-forced: the reference's forward over the
    prompt and the tokens the engine gave must choose each of them."""
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
    c, _, prompts, _, records, moved, eng = engine_run
    assert {r["kind"] for r in records} >= {"mixed", "decode"}
    for r in records:
        assert r["impl"] == "paged-kda-fold"
    counted = [r for r in records if "kda_state_rows" in r]
    assert counted and all("ssm_state_rows" not in r for r in records)
    for r in counted:
        assert r["kda_state_rows"] % 4 == 0           # x 4 KDA layers
        assert r["moe_rows_routed"] >= r["moe_rows"]
    # every prompt token but a one-token last window's is chunked
    assert moved["kda_tokens_chunked"] == 4 * sum(map(len, prompts))
    assert moved["kda_tokens_stepped"] >= 4 * 6 * 9
    assert eng._mixed_buckets == (16,) and not eng._prefix_capable


def test_records_count_the_two_latent_layers_page_walk(engine_run):
    """mla_decode_pages / mla_decode_folds (cake_mla_decode_attn): a
    decode record's rows over the TWO latent layers of six, the GQA
    decode kernel's count of a layer's pages twice; the mixed records'
    single-token rows likewise."""
    *_, records, _moved, eng = engine_run
    decode = [r for r in records if r["kind"] == "decode"]
    assert decode
    for r in decode:
        assert r["mla_decode_pages"] == 2 * r["attn_pages"]
        assert 0 < r["mla_decode_folds"] <= r["mla_decode_pages"]
    assert any(r.get("mla_decode_pages")
               for r in records if r["kind"] == "mixed")
    # a table of 15 pages: blocks of 4 would pad it by one page in 15
    assert eng.cache.max_pages == 15
    assert eng._mla_decode_pages([40, 119]) == {
        "mla_decode_pages": 2 * (6 + 15), "mla_decode_folds": 2 * (2 + 4)}


def test_metrics_carry_the_state(engine_run):
    from cake_tpu.obs import steps as obs_steps
    *_, eng = engine_run
    assert obs_steps.KDA_STATE_BYTES.value == eng.cache.state_bytes() > 0
    assert bh.COUNTERS[-3:] == tuple(k for k, _ in obs_steps.KDA_COUNTERS)
    assert eng.flight._counters == bh.COUNTERS


@pytest.mark.parametrize("refused,named", [
    (dict(kv_pages=None), "--kv-pages"),
    (dict(step_fns=(print, print)), "topology"),
    (dict(kv_dtype="int8"), "--kv-dtype"),
    (dict(kv_host_pages=8), "--kv-host-pages"),
    (dict(auto_prefix_system=True), "--auto-prefix"),
    (dict(disagg="prefill"), "--disagg")])
def test_engine_refuses_by_name_what_a_state_does_not_serve(refused, named):
    with pytest.raises(ValueError) as e:
        make_engine(**refused)
    assert "bailing_hybrid" in str(e.value) and named in str(e.value)
    assert "KDA state" in str(e.value)


def test_prefix_registration_is_refused_by_name():
    *_, eng = make_engine()
    with pytest.raises(ValueError, match="KDA state"):
        eng.register_prefix([5, 6, 7, 8, 9, 10, 11, 12, 13])
