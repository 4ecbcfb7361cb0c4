"""Nemotron-3 (`nemotron_h`) at a tiny size on seeded weights: the served
path (mixed-step prefill in windows whose edges fall inside and across
the scan's chunks, decode through the rows' recurrent state, decode rows
beside prefilling ones) against the plain float32 reference's full
forward; the pieces one by one (the two forms of the recurrence, the
conv across a window's edge, the shares of an E block, relu² experts,
attention without rotation, the older families' expert path bit for
bit); the state's lifecycle (a slot reused, rows that hold no token);
and the engine around them.

Pattern `EM*EM`: every kind of block, 4 Mamba heads of 8 in 2 groups,
state 16, chunk 8, windows of 12 (so a window's edge falls inside a
chunk and a chunk's inside a window), 16 routed experts of which 4 are
held, 3 a token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.paged import (
    HybridPagedCache, PagedKVCache, mixed_token_buckets,
)
from cake_tpu.models.moe import nemotron_h as nh
from cake_tpu.models.moe.config import MoEConfig, NemotronHConfig
from cake_tpu.models.moe.params import init_params
from cake_tpu.models.reference import nemotron_h as ref
from cake_tpu.ops import moe as moe_ops

B, C, PAGE, MAX_SEQ = 4, 12, 8, 64
REF_KEYS = ("rms_norm_eps", "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "num_attention_heads", "num_key_value_heads",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "scoring_func")
HELD = (0, 4)


def ref_config(c, **over):
    return dict({k: getattr(c, k) for k in REF_KEYS}, **over)


def ref_params(params, c):
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": nh.dequantized(params["lm_head"]),
            "layers": list(nh.reference_blocks(params["blocks"], c))}


@pytest.fixture(scope="module")
def model():
    c = NemotronHConfig.tiny_nemotron()
    return c, init_params(c, jax.random.PRNGKey(0), jnp.float32)


def fresh_cache(c):
    cache = PagedKVCache.create(c, B, 1 + B * (MAX_SEQ // PAGE), PAGE,
                                MAX_SEQ, dtype=jnp.float32)
    table = np.stack([1 + b * (MAX_SEQ // PAGE) + np.arange(MAX_SEQ // PAGE)
                      for b in range(B)]).astype(np.int32)
    return cache._replace(table=jnp.asarray(table))


def mixed(model, cache, toks, pos, qlen, attn="fold"):
    c, params = model
    out, plan = jax.jit(nh.mixed_trunk, static_argnames=(
        "config", "attn", "n_tokens"))(
        params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(qlen),
        jnp.asarray(qlen > 0), cache, config=c, attn=attn,
        n_tokens=mixed_token_buckets(B, C, (1,))[-1])
    return out, plan


def decode(model, cache, toks, pos, active, attn="fold"):
    c, params = model
    return jax.jit(nh.decode_trunk, static_argnames=("config", "attn"))(
        params, jnp.asarray(toks), cache, jnp.asarray(pos),
        jnp.asarray(active), config=c, attn=attn)


def serve(model, sequences, prompts, company=True, cache=None, rows=None):
    """Every sequence through the step programs: prompts in C-wide
    windows, one window a dispatch, the rows that finished their prompt
    riding the other rows' mixed steps as one-token rows (when
    `company`), then the decode program. rows: the slot of each
    sequence. Returns (per sequence {position: logits}, cache)."""
    c, params = model
    cache = fresh_cache(c) if cache is None else cache
    rows = list(range(len(sequences))) if rows is None else rows
    off = [0] * len(sequences)
    got = [dict() for _ in sequences]
    head = nh.dequantized(params["lm_head"])
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
        out, plan = mixed(model, cache, toks, pos, qlen)
        cache = out.cache
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
        out = decode(model, cache, toks, pos, active)
        cache = out.cache
        logits = out.x @ head
        for i in range(len(sequences)):
            if active[rows[i]]:
                got[i][off[i]] = np.asarray(logits[rows[i]])
                off[i] += 1
    return got, cache


@pytest.fixture(scope="module")
def traffic(model):
    rng = np.random.default_rng(0)
    prompts = (37, 9, 52)
    return [rng.integers(0, model[0].vocab_size, p + 8)
            for p in prompts], prompts


@pytest.fixture(scope="module")
def reference_run(model, traffic):
    c, params = model
    states = [[] for _ in traffic[0]]
    logits = ref.forward(ref_params(params, c), traffic[0], ref_config(c),
                         held=HELD, states=states)
    return [np.asarray(x) for x in logits], states


@pytest.fixture(scope="module")
def served_run(model, traffic):
    return serve(model, *traffic)


# -- the served path against the reference -------------------------------------


@pytest.mark.parametrize("row", [0, 1, 2])
def test_served_path_matches_the_reference_forward(
        served_run, reference_run, traffic, row):
    """Prefill in windows of 12 over chunks of 8, then decode through
    the state, decode rows beside prefilling ones: every position."""
    got, want = served_run[0][row], reference_run[0][row]
    assert sorted(got) == list(range(len(traffic[0][row])))
    for position, logits in got.items():
        np.testing.assert_allclose(logits, want[position], atol=3e-5,
                                   err_msg=f"position {position}")


@pytest.mark.parametrize("position", [0, 1, 2, 11, 12, 13, 14, 24, 36, 37])
def test_conv_at_a_prompts_start_and_across_a_windows_edge(
        served_run, reference_run, position):
    """The first three tokens read zeros before them; tokens 12-14 and
    24 read their window's stored tail; 37 is the first one-step
    token and reads the last window's."""
    np.testing.assert_allclose(served_run[0][0][position],
                               reference_run[0][0][position], atol=3e-5)


@pytest.mark.parametrize("block", [0, 1])
def test_state_at_the_end_is_the_references(served_run, reference_run,
                                            traffic, block):
    """Each Mamba block's state and conv tail after the last token."""
    cache = served_run[1]
    for row in range(3):
        S, tail = reference_run[1][row][block]
        np.testing.assert_allclose(cache.ssm[block, row], S, atol=2e-5)
        np.testing.assert_allclose(cache.conv[block, row], tail, atol=2e-5)


@pytest.mark.parametrize("n,chunk", [(8, 8), (12, 8), (5, 8), (24, 8),
                                     (16, 4)])
def test_chunked_scan_is_the_one_step_recurrence_is_the_references(n, chunk):
    """From a NON-ZERO state: the chunked form over n tokens, n one-step
    updates, and the reference's token-by-token recurrence."""
    H, P, G, N = 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(n * 31 + chunk), 7)
    S0 = jax.random.normal(ks[0], (H, P, N))
    x = jax.random.normal(ks[1], (n, H, P))
    Bm = jax.random.normal(ks[2], (n, G, N))
    Cm = jax.random.normal(ks[3], (n, G, N))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (n, H)))
    a = -dt * jnp.exp(jax.random.normal(ks[5], (H,)))[None]
    D = jax.random.normal(ks[6], (H,))
    S_scan, y_scan = nh.ssm_scan(S0, x, Bm, Cm, dt, a, D, chunk)
    S, ys = S0[None], []
    for t in range(n):
        S, y = nh.ssm_step(S, x[t][None], Bm[t][None], Cm[t][None],
                           dt[t][None], a[t][None], D)
        ys.append(y[0])
    # the reference's: S_t = exp(a_t) S_{t-1} + dt_t x_t (x) B_t
    Sr, yr = np.asarray(S0, np.float64), []
    for t in range(n):
        Bh, Ch = (np.repeat(np.asarray(m[t], np.float64), H // G, 0)
                  for m in (Bm, Cm))
        Sr = (np.exp(np.asarray(a[t], np.float64))[:, None, None] * Sr
              + (np.asarray(dt[t])[:, None] * np.asarray(x[t]))[:, :, None]
              * Bh[:, None, :])
        yr.append(np.einsum("hpn,hn->hp", Sr, Ch)
                  + np.asarray(D)[:, None] * np.asarray(x[t]))
    np.testing.assert_allclose(S_scan, Sr, atol=1e-4)
    np.testing.assert_allclose(S[0], Sr, atol=1e-4)
    np.testing.assert_allclose(y_scan, np.stack(yr), atol=1e-4)
    np.testing.assert_allclose(jnp.stack(ys), np.stack(yr), atol=1e-4)


@pytest.mark.parametrize("altered", [
    dict(ssm_state_dtype="bfloat16"), dict(conv_window=C),
    dict(attn_rope_theta=10000.0), dict(expert_act="swiglu"),
    dict(scoring_func="softmax"), dict(int8_activations=True),
    "state_not_zeroed"])
def test_an_altered_reference_is_another_model(model, traffic, served_run,
                                               reference_run, altered):
    """What chip_compare.py holds to fail on the chip, here at float32
    where nothing hides it: each altered reference leaves the served
    path's tolerance (3e-5) tenfold or more. A bfloat16 state
    among them: on the chip its effect lies under the floor that the
    choice of experts sets (PERF.md section 6, PR 33)."""
    c, params = model
    seq = traffic[0][0]
    if altered == "state_not_zeroed":
        # the sequence starts from the state another left behind
        kw = dict(config=ref_config(c),
                  starts=[list(reference_run[1][2])])
    else:
        kw = dict(config=ref_config(c, **altered))
    logits = np.asarray(ref.forward(ref_params(params, c), [seq],
                                    held=HELD, **kw)[0])
    apart = max(float(np.abs(logits[p] - got).max())
                for p, got in served_run[0][0].items())
    assert apart > 3e-4, apart


# -- the state's lifecycle -----------------------------------------------------


@pytest.mark.parametrize("prompt", [5, 12, 30])
def test_a_reused_slot_gives_the_request_what_it_gets_alone(model, traffic,
                                                            prompt):
    """A second request in a slot whose state the first left behind:
    position 0 zeroes the state inside the step program."""
    rng = np.random.default_rng(prompt)
    second = rng.integers(0, 256, prompt + 4)
    alone, _ = serve(model, [second], [prompt], rows=[1])
    _, used = serve(model, [traffic[0][2]], [traffic[1][2]], rows=[1])
    assert float(jnp.abs(used.ssm[:, 1]).max()) > 0
    after, _ = serve(model, [second], [prompt], rows=[1], cache=used)
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
    counters = np.asarray(out.counters)
    assert counters[6] == 2 * 2                       # rows x Mamba blocks
    assert counters[7] == (2 * 7 if kind == "mixed" else 0)
    assert counters[8] == (2 * 1 if kind == "mixed" else 2 * 2)
    assert counters[9] == 1                           # row 3, position 0


@pytest.mark.parametrize("kind", ["window", "single_token"])
def test_a_rows_bits_do_not_depend_on_its_company(model, traffic, kind):
    """Served alone or beside two other rows, the same program gives a
    window's logits and a row's single token the same bits."""
    sequences, prompts = traffic
    alone, _ = serve(model, sequences[:1], prompts[:1], company=False)
    amid, _ = serve(model, sequences, prompts)
    positions = ([7, 11, 12, 31, 36] if kind == "window"
                 else list(range(37, 45)))
    for position in positions:
        assert np.array_equal(alone[0][position], amid[0][position])


def test_one_window_a_dispatch():
    assert mixed_token_buckets(32, 512, (1,)) == (544,)
    cache = PagedKVCache.create(NemotronHConfig.tiny_nemotron(), 4, 10, 8,
                                64, dtype=jnp.bfloat16)
    assert isinstance(cache, HybridPagedCache)
    assert cache.k.shape == (1, 10, 8, 2 * 16)        # attention blocks only
    assert cache.ssm.shape == (2, 4, 4, 8, 16)
    assert cache.ssm.dtype == jnp.float32
    assert cache.conv.shape == (2, 4, 3, 32 + 2 * 2 * 16)
    assert cache.state_bytes() == cache.ssm.nbytes + cache.conv.nbytes


# -- the blocks, one by one ----------------------------------------------------


@pytest.mark.parametrize("side", ["reference", "served"])
def test_four_shares_are_the_uncut_block(side):
    """Each of 4 chips holds 4 of a block's 16 routed experts and routes
    over all of them; their sums in the latent, the latent projections
    and the shared expert counted once, are the uncut reference's
    block."""
    c = NemotronHConfig.tiny_nemotron(num_local_experts=16)
    params = init_params(c, jax.random.PRNGKey(14), jnp.float32)
    lp = list(nh.reference_blocks(params["blocks"], c))[0]
    assert lp["kind"] == "E"
    h = jax.random.normal(jax.random.PRNGKey(15), (21, c.hidden_size))
    cfg = ref_config(c)
    with jax.default_matmul_precision("highest"):
        whole = ref.latent_moe(lp, h, cfg)
        latent = jnp.zeros((21, c.moe_latent_size))
    for first in range(0, 16, 4):
        share = {k: (v[first:first + 4] if k.startswith("we_") else v)
                 for k, v in lp.items()}
        if side == "reference":
            with jax.default_matmul_precision("highest"):
                part = ref.latent_moe(share, h, cfg, held=(first, 4),
                                      latent_out=False)
        else:
            # the served share: routed over all 16, its 4 experts'
            # sum left in the latent (no w_fc2), no shared expert
            routed = {k: v for k, v in share.items()
                      if k in ("router", "router_bias", "w_fc1", "we_up",
                               "we_down")}
            part, stats = moe_ops.moe_mlp(
                routed, h[None], 3, True, first_expert=first,
                scoring="sigmoid", scale=5.0, act="relu2")
            part = part[0]
            here = (stats.experts >= first) & (stats.experts < first + 4)
            assert float(stats.rows_routed) == 21 * 3
            assert float(stats.rows) == float(jnp.sum(here))
        latent = latent + part
    with jax.default_matmul_precision("highest"):
        total = (latent @ lp["w_fc2"]
                 + ref.expert(h, lp["ws_up"], lp["ws_down"], cfg))
    np.testing.assert_allclose(total, whole, atol=3e-5)


@pytest.mark.parametrize("held", [(0, 16), (4, 4)])
def test_latent_relu2_experts_without_a_gate(held):
    """moe_mlp with the latent projections, relu² and no gate, the
    shared expert on h itself, against the reference's block; and the
    gated SwiGLU reading of the same leaves is another function."""
    first, count = held
    c = NemotronHConfig.tiny_nemotron(num_local_experts=count,
                                      first_routed_expert=first)
    params = init_params(c, jax.random.PRNGKey(3), jnp.float32)
    lp = list(nh.reference_blocks(params["blocks"], c))[0]
    served = {k: v for k, v in lp.items() if k not in ("kind", "norm")}
    h = jax.random.normal(jax.random.PRNGKey(4), (17, c.hidden_size))
    got, stats = moe_ops.moe_mlp(
        served, h[None], 3, True, scoring="sigmoid", scale=5.0,
        first_expert=None if count == 16 else first, act="relu2")
    want = ref.latent_moe(lp, h, ref_config(c), held=held)
    np.testing.assert_allclose(got[0], want, atol=3e-5)
    assert float(stats.rows_routed) == 17 * 3
    swiglu = ref.latent_moe(lp, h, ref_config(c, expert_act="swiglu"),
                            held=held)
    assert float(jnp.abs(swiglu - want).max()) > 1e-2


def test_attention_has_no_rotation(model, served_run, reference_run):
    """The served attention block against the reference's without a
    positional embedding (the whole-model comparison above), and the
    reference WITH RoPE is another model."""
    c, params = model
    rng = np.random.default_rng(5)
    toks = rng.integers(0, 256, 20)
    p = ref_params(params, c)
    plain = ref.forward(p, toks, ref_config(c), held=HELD)
    rotated = ref.forward(p, toks, ref_config(c, attn_rope_theta=10000.0),
                          held=HELD)
    got, _ = serve(model, [toks], [12])
    for position in (0, 11, 12, 19):
        np.testing.assert_allclose(got[0][position], plain[position],
                                   atol=3e-5)
    assert float(jnp.abs(plain[1:] - rotated[1:]).max()) > 1e-2


def _swiglu_as_it_was(lp, h, k, norm_topk_prob):
    """ops/moe.moe_mlp's gated path as PR 30 left it, call for call."""
    N, D = h.shape
    weights, experts = moe_ops.route(h, lp["router"], k, norm_topk_prob)
    stacks = [jax.tree.map(lambda a: a[None], lp[n])
              for n in ("we_gate", "we_up", "we_down")]
    plan = moe_ops.dispatch_plan(experts, stacks[0].shape[1], None)
    xs = jnp.take(h, plan.src_token, axis=0)
    walk = (plan.visit_tile, plan.visit_expert, plan.visit_lo,
            plan.visit_hi)
    layer = jnp.int32(0)
    gate = moe_ops.grouped_matmul(xs, stacks[0], layer, *walk, tm=plan.tm)
    up = moe_ops.grouped_matmul(xs, stacks[1], layer, *walk, tm=plan.tm)
    ys = moe_ops.grouped_matmul(jax.nn.silu(gate) * up, stacks[2], layer,
                                *walk, tm=plan.tm)
    picked = jnp.take(ys, plan.slot_of.reshape(N * k), axis=0)
    wk = jnp.where(plan.valid, weights, 0.0)
    picked = jnp.where(plan.valid.reshape(N * k, 1), picked, 0)
    return jnp.einsum("nkd,nk->nd",
                      picked.reshape(N, k, D).astype(jnp.float32), wk)


@pytest.mark.parametrize("family", ["mixtral", "olmoe", "glm"])
def test_gated_expert_path_is_bit_for_bit_what_it_was(family):
    from cake_tpu.models.moe.config import GlmMoeDsaConfig
    if family == "glm":
        c = GlmMoeDsaConfig.tiny_glm()
        blocks = init_params(c, jax.random.PRNGKey(2), jnp.float32)["blocks"]
        names = ("router", "we_gate", "we_up", "we_down")
        lp = {n: jax.tree.map(lambda a: a[0], blocks[n]) for n in names}
        k, norm = c.num_experts_per_tok, True
    else:
        c = MoEConfig.tiny() if family == "mixtral" else MoEConfig.tiny_olmoe()
        blocks = init_params(c, jax.random.PRNGKey(2), jnp.float32)["blocks"]
        lp = {n: jax.tree.map(lambda a: a[0], blocks[n])
              for n in ("router", "we_gate", "we_up", "we_down")}
        k, norm = c.num_experts_per_tok, c.norm_topk_prob
    h = jax.random.normal(jax.random.PRNGKey(6), (19, c.hidden_size))
    got, _ = moe_ops.moe_mlp(lp, h[None], k, norm)
    assert np.array_equal(np.asarray(got[0]),
                          np.asarray(_swiglu_as_it_was(lp, h, k, norm)))


# -- the config ----------------------------------------------------------------


def test_published_config_parses():
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "nemotron3-super-int8-share4",
                        "config.json")
    with open(path) as f:
        raw = json.load(f)
    from cake_tpu.models.llama.config import load_config_dict
    c = load_config_dict(raw)
    assert isinstance(c, NemotronHConfig)
    assert "".join(c.pattern) == "EMEMEMEMEM*" * 2
    assert (len(c.mamba_layers), len(c.sparse_layers),
            len(c.attn_layers)) == (10, 10, 2)
    assert (c.hidden_size, c.d_inner, c.conv_dim, c.in_proj_dim) == (
        4096, 8192, 10240, 18560)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        32, 2, 128)
    assert (c.num_local_experts, c.n_routed_experts_total,
            c.num_experts_per_tok) == (128, 512, 22)
    assert (c.moe_latent_size, c.moe_intermediate_size,
            c.moe_shared_expert_intermediate_size) == (1024, 2688, 5376)
    assert c.routed_scaling_factor == 5 and c.chat_template == "chatml"
    assert c.vocab_size == 32768 and c.eos_token_ids == (32768,)


RAW = dict(
    model_type="nemotron_h", vocab_size=64, hidden_size=32,
    num_hidden_layers=3, hybrid_override_pattern="EM*",
    num_attention_heads=2, num_key_value_heads=1, mamba_num_heads=4,
    mamba_head_dim=8, n_groups=2, ssm_state_size=8, moe_intermediate_size=16,
    moe_latent_size=16, moe_shared_expert_intermediate_size=32,
    n_routed_experts=4, n_routed_experts_total=16, num_experts_per_tok=2)


@pytest.mark.parametrize("key,value", [
    ("n_group", 8), ("topk_group", 4), ("num_nextn_predict_layers", 1),
    ("first_routed_expert", 14), ("hybrid_override_pattern", "EM"),
    ("hybrid_override_pattern", "EMX"), ("mlp_hidden_act", "silu"),
    ("mamba_proj_bias", True), ("use_conv_bias", False), ("head_dim", 24),
    ("n_groups", 3)])
def test_what_is_not_implemented_is_refused(key, value):
    from cake_tpu.models.llama.config import load_config_dict
    load_config_dict(RAW)
    named = ("not among the router" if key == "first_routed_expert"
             else key)
    with pytest.raises(ValueError, match=named):
        load_config_dict(dict(RAW, **{key: value}))


# -- the engine ----------------------------------------------------------------


def make_engine(**kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    c = NemotronHConfig.tiny_nemotron(vocab_size=300, eos_token_ids=(300,))
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
    requests over four rows and two behind them in REUSED slots, prompts
    of 1 to 6 windows. Teacher-forced: the reference's forward over the
    prompt and the tokens the engine gave must choose each of them."""
    c, params, prompts, tokens, *_ = engine_run
    prompt, out = prompts[request_index], tokens[request_index]
    assert len(out) == 10
    logits = np.asarray(ref.forward(
        ref_params(params, c), np.asarray(prompt + out), ref_config(c),
        held=HELD))
    for i, tok in enumerate(out):
        at = logits[len(prompt) - 1 + i]
        top2 = np.sort(at)[-2:]
        if top2[1] - top2[0] > 1e-3:        # a near-tie may fall either way
            assert tok == int(np.argmax(at)), i


def test_step_records_name_the_attention_and_carry_the_counters(engine_run):
    c, _, prompts, _, records, moved, eng = engine_run
    assert {r["kind"] for r in records} >= {"mixed", "decode"}
    for r in records:
        assert r["impl"] == "paged-ssm-fold"
    counted = [r for r in records if "ssm_state_rows" in r]
    assert counted and all("dsa_keys_visible" not in r for r in records)
    for r in counted:
        assert r["ssm_state_rows"] % 2 == 0           # x 2 Mamba blocks
        assert r["moe_rows_routed"] >= r["moe_rows"]
    assert any(r.get("chained") for r in records if r["kind"] == "decode")
    assert any(r.get("chained") for r in records if r["kind"] == "mixed")
    # every prompt token but a one-token last window's is scanned; every
    # request resets its row once
    assert moved["ssm_state_resets"] == len(prompts)
    assert moved["ssm_tokens_scanned"] + moved["ssm_tokens_stepped"] >= (
        2 * (sum(map(len, prompts)) + 6 * 9))
    assert moved["ssm_tokens_scanned"] == 2 * sum(map(len, prompts))
    assert eng._mixed_buckets == (16,) and not eng._prefix_capable


def test_metrics_carry_the_state(engine_run):
    from cake_tpu.obs import steps as obs_steps
    *_, eng = engine_run
    assert obs_steps.SSM_STATE_BYTES.value == eng.cache.state_bytes() > 0
    assert nh.COUNTERS[5:] == (
        "moe_rows_routed", "ssm_state_rows", "ssm_tokens_scanned",
        "ssm_tokens_stepped", "ssm_state_resets")
    assert nh.COUNTERS[6:] == tuple(k for k, _ in obs_steps.SSM_COUNTERS)
    assert eng.flight._counters == nh.COUNTERS


@pytest.mark.parametrize("refused,named", [
    (dict(kv_pages=None), "--kv-pages"),
    (dict(kv_dtype="int8"), "--kv-dtype"),
    (dict(kv_host_pages=8), "--kv-host-pages"),
    (dict(auto_prefix_system=True), "--auto-prefix"),
    (dict(disagg="prefill"), "--disagg"),
    (dict(prefill_chunk=200, max_seq_len=400), "--prefill-chunk")])
def test_engine_refuses_by_name_what_a_state_does_not_serve(refused, named):
    with pytest.raises(ValueError) as e:
        make_engine(**refused)
    assert "nemotron_h" in str(e.value) and named in str(e.value)


def test_speculation_is_refused_by_name():
    c = NemotronHConfig.tiny_nemotron(vocab_size=300, eos_token_ids=(300,))
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(ValueError, match="nemotron_h.*--spec-draft"):
        make_engine(spec_draft_params=params, spec_draft_config=c,
                    spec_gamma=2)


def test_prefix_registration_is_refused_by_name():
    *_, eng = make_engine()
    with pytest.raises(ValueError, match="recurrent state"):
        eng.register_prefix([5, 6, 7, 8, 9, 10, 11, 12, 13])
