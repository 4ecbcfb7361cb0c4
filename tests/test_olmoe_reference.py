"""OLMoE on the served path against its plain float32 reference.

Logits, not tokens (with random weights the largest logit changes on
rounding). The reference (cake_tpu/models/reference/olmoe.py) is
whole-sequence float32 `jax.numpy` at `highest` matmul precision, with
no cache, kernels or batching; the system runs its own blocks, routing,
sorted dispatch, grouped-matmul kernel (interpreted on the CPU) and, in
the paged cases, the page pool with mixed and decode step programs.

Tolerances, relative to the range (max - min) of the reference logits,
the largest over a position's vocabulary:

  F32_TOL 2e-4 — a float32 system and the float32 reference differ only
      by summation order (the kernel's f32 accumulator, the combine's
      sum over k, the fold attention's running softmax): a few float32
      roundings through 3 layers, measured 3e-7 .. 7e-7 here. The bound
      leaves room for other seeds and is still 50 times under what a
      bfloat16 run shows.
  BF16_TOL 2e-2 at nine positions in ten, BF16_FLIP_TOL 0.15 at the
      rest — a bfloat16 system (8 mantissa bits, 4e-3 a rounding,
      through 3 layers of matmuls, norms and a softmax) against the
      float32 reference over the same bf16-rounded weights: measured
      3e-3 .. 1e-2 where the routing agrees. bfloat16 router inputs
      flip a near-tie at a position in fifteen or so, which swaps a
      whole (random) expert: measured 0.05 .. 0.09 there. The float32
      run must be at least 10x closer than the bfloat16 one, so that
      serving in a lower precision than stated fails the float32 cases.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import load_config_dict
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import (
    PagedKVCache, decode_step_ragged_paged, mixed_step_paged,
)
from cake_tpu.models.moe import MoEConfig, init_params
from cake_tpu.models.reference import olmoe as ref
from cake_tpu.ops.moe import dispatch_plan, moe_mlp, route
from cake_tpu.ops.quant import QTensor, quantize_params

F32_TOL = 2e-4
BF16_TOL = 2e-2
BF16_FLIP_TOL = 0.15

CFG = MoEConfig.tiny_olmoe()
REF_CFG = {"num_attention_heads": CFG.num_attention_heads,
           "num_key_value_heads": CFG.num_key_value_heads,
           "rms_norm_eps": CFG.rms_norm_eps, "rope_theta": CFG.rope_theta,
           "num_experts_per_tok": CFG.num_experts_per_tok,
           "norm_topk_prob": CFG.norm_topk_prob}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def dequantized(leaf):
    """A leaf as the float32 array the reference is fed: an int8
    QTensor's q * scale (scale broadcast over the contracted dim)."""
    if isinstance(leaf, QTensor):
        return (leaf.q.astype(jnp.float32)
                * jnp.expand_dims(leaf.scale, leaf.q.ndim - 2))
    return jnp.asarray(leaf, jnp.float32)


def reference_params(params):
    blocks = {k: dequantized(v) for k, v in params["blocks"].items()}
    return {"embed": dequantized(params["embed"]),
            "final_norm": dequantized(params["final_norm"]),
            "lm_head": dequantized(params["lm_head"]),
            "layers": ref.layers_of(blocks)}


def rel_errs(got, want):
    """Per position: the largest |got - want| over the vocabulary,
    relative to the range of `want`."""
    want = np.asarray(want, np.float32)
    diff = np.abs(np.asarray(got, np.float32) - want)
    return diff.reshape(-1, want.shape[-1]).max(axis=1) / (
        want.max() - want.min())


def rel_err(got, want):
    return float(rel_errs(got, want).max())


def assert_bf16_close(errs):
    errs = np.asarray(errs)
    assert np.quantile(errs, 0.9) < BF16_TOL, np.sort(errs)
    assert errs.max() < BF16_FLIP_TOL, np.sort(errs)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(7), dtype=jnp.float32)


def layer0(params):
    return jax.tree.map(lambda a: a[0], params["blocks"])


# -- (a) routing ---------------------------------------------------------------


@pytest.mark.parametrize("norm", [False, True])
def test_routing_matches_reference(params, norm):
    lp = layer0(params)
    h = jax.random.normal(jax.random.PRNGKey(1), (40, CFG.hidden_size))
    w, e = route(h, lp["router"], CFG.num_experts_per_tok, norm)
    w_ref, e_ref = ref.router(
        {"router": lp["router"]}, h,
        dict(REF_CFG, norm_topk_prob=norm))
    # a seed with no near-ties: the two top-k orders agree exactly
    np.testing.assert_array_equal(np.asarray(e), np.asarray(e_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), atol=1e-6)
    sums = np.asarray(w).sum(axis=1)
    if norm:
        np.testing.assert_allclose(sums, 1.0, atol=1e-6)
    else:
        # 2 of 8 experts never hold all the mass: renormalising the
        # published weights would fail here
        assert sums.max() < 0.9


def test_renormalised_weights_are_mixtrals_softmax_over_top_k(params):
    """norm_topk_prob=True through the shared function IS Mixtral's
    softmax over the top-k logits."""
    lp = layer0(params)
    h = jax.random.normal(jax.random.PRNGKey(2), (16, CFG.hidden_size))
    w, e = route(h, lp["router"], 2, True)
    logits = np.asarray(h @ lp["router"])
    top = np.take_along_axis(logits, np.asarray(e), axis=1)
    want = np.exp(top - top.max(axis=1, keepdims=True))
    want /= want.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), want, atol=1e-6)


# -- (b) grouped dispatch ------------------------------------------------------


def test_dispatch_with_skewed_groups_matches_expert_loop(params):
    """One expert takes every token, one takes none."""
    lp = dict(layer0(params))
    D, E = lp["router"].shape
    lp["router"] = lp["router"].at[:, 0].set(4.0).at[:, E - 1].set(-4.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (2, 19, D)))
    out, stats = moe_mlp(lp, h, CFG.num_experts_per_tok, False)
    _, experts = route(h.reshape(-1, D), lp["router"], 2, False)
    counts = np.bincount(np.asarray(experts).ravel(), minlength=E)
    assert counts[0] == 38 and counts[E - 1] == 0
    assert float(stats.load_max) == 38 and float(stats.rows) == 76
    want = ref.moe_ffn({k: jnp.asarray(v, jnp.float32)
                        for k, v in lp.items()}, h.reshape(-1, D), REF_CFG)
    assert rel_err(out.reshape(-1, D), want) < F32_TOL


def test_dispatch_plan_walks_every_row_once():
    rng = np.random.default_rng(0)
    experts = jnp.asarray(rng.integers(0, 5, size=(37, 3)), jnp.int32)
    plan = dispatch_plan(experts, 5, tm=16)
    covered = np.zeros(plan.src_token.shape[0], int)
    flat = np.sort(np.asarray(experts).ravel())
    for t, e, lo, hi in zip(*(np.asarray(a) for a in (
            plan.visit_tile, plan.visit_expert, plan.visit_lo,
            plan.visit_hi))):
        if hi > lo:
            assert t * 16 <= lo and hi <= (t + 1) * 16
            assert (flat[lo:hi] == e).all()
            covered[lo:hi] += 1
    assert (covered[:flat.size] == 1).all() and covered[flat.size:].sum() == 0


# -- (c) one full forward ------------------------------------------------------


def system_logits_all(params, tokens, dtype):
    from cake_tpu.models.llama.cache import KVCache
    from cake_tpu.models.llama.model import forward_logits_all

    S = len(tokens)
    cache = KVCache.create(CFG, 1, S, dtype=dtype)
    logits, _ = forward_logits_all(
        params, jnp.asarray(tokens, jnp.int32)[None], cache, jnp.int32(0),
        RopeTables.create(CFG, S), CFG)
    return logits[0]


def test_full_forward_matches_reference(params):
    tokens = np.random.default_rng(4).integers(0, CFG.vocab_size, 33)
    want = ref.forward(reference_params(params), tokens, REF_CFG)
    assert rel_err(system_logits_all(params, tokens, jnp.float32),
                   want) < F32_TOL


def test_bf16_forward_within_its_tolerance_and_f32_ten_times_closer(params):
    tokens = np.random.default_rng(5).integers(0, CFG.vocab_size, 33)
    bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    # the reference over the SAME (bf16-rounded) weights, in float32
    rounded = jax.tree.map(lambda a: a.astype(jnp.float32), bf16)
    want = ref.forward(reference_params(rounded), tokens, REF_CFG)
    errs_bf16 = rel_errs(system_logits_all(bf16, tokens, jnp.bfloat16), want)
    err_f32 = rel_err(system_logits_all(rounded, tokens, jnp.float32), want)
    assert_bf16_close(errs_bf16)
    assert err_f32 < F32_TOL and err_f32 * 10 < np.median(errs_bf16)


# -- (d), (e) the paged engine's step programs ---------------------------------

PAGE, WIDTH, SLOTS, T = 8, 16, 3, 96
N_DECODE = 24


def paged_logits(params, sequences, prompt_lens, dtype=jnp.float32,
                 n_tokens=None):
    """Prefill each row's prompt through mixed steps of WIDTH (the
    other rows busy in the same launches; n_tokens: the packed size
    they run at, None = the windows), then N_DECODE decode steps
    through the page pool, teacher-forced. Returns {row: {position:
    logits}} for every window's last token and every decode step, and
    the counters of the first mixed step."""
    cache = PagedKVCache.create(CFG, SLOTS, SLOTS * T // PAGE + 1, PAGE, T,
                                dtype=dtype)
    per = T // PAGE
    table = np.full((SLOTS, per), -1, np.int32)
    for b in range(len(sequences)):
        table[b] = 1 + b * per + np.arange(per)
    cache = cache._replace(table=jnp.asarray(table))
    rope = RopeTables.create(CFG, T)
    out = {b: {} for b in range(len(sequences))}
    off = [0] * len(sequences)
    first_stats = None
    while any(off[b] < prompt_lens[b] for b in range(len(sequences))):
        toks = np.zeros((SLOTS, WIDTH), np.int32)
        pos = np.zeros(SLOTS, np.int32)
        qlen = np.zeros(SLOTS, np.int32)
        active = np.zeros(SLOTS, bool)
        for b, seq in enumerate(sequences):
            n = min(WIDTH, prompt_lens[b] - off[b])
            if n <= 0:
                continue
            toks[b, :n] = seq[off[b]:off[b] + n]
            pos[b], qlen[b], active[b] = off[b], n, True
        logits, cache, stats = mixed_step_paged(
            params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(qlen),
            jnp.asarray(active), cache, rope, config=CFG, attn="fold",
            n_tokens=n_tokens)
        if first_stats is None:
            first_stats = (np.asarray(stats), int(qlen.sum()))
        for b in range(len(sequences)):
            if active[b]:
                off[b] += int(qlen[b])
                out[b][off[b] - 1] = np.asarray(logits[b])
    for i in range(N_DECODE):
        toks = np.zeros((SLOTS, 1), np.int32)
        pos = np.zeros(SLOTS, np.int32)
        active = np.zeros(SLOTS, bool)
        for b, seq in enumerate(sequences):
            toks[b, 0], pos[b], active[b] = (seq[prompt_lens[b] + i],
                                             prompt_lens[b] + i, True)
        logits, cache, _ = decode_step_ragged_paged(
            params, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(active), cache, rope, config=CFG, attn="fold")
        for b in range(len(sequences)):
            out[b][int(pos[b])] = np.asarray(logits[b])
    return out, first_stats


def paged_case(params, dtype=jnp.float32, n_tokens=None):
    rng = np.random.default_rng(6)
    # three windows of 16 for the first row, two and a bit for the other
    prompt_lens = [3 * WIDTH, 2 * WIDTH + 5]
    sequences = [rng.integers(0, CFG.vocab_size, n + N_DECODE)
                 for n in prompt_lens]
    got, stats = paged_logits(params, sequences, prompt_lens, dtype,
                              n_tokens)
    return sequences, prompt_lens, got, stats


def paged_errors(params, ref_params, dtype=jnp.float32, n_tokens=None):
    """rel_errs of every compared position: each window's last token
    and every decode step of both rows."""
    sequences, prompt_lens, got, _ = paged_case(params, dtype, n_tokens)
    errs = []
    for b, seq in enumerate(sequences):
        want = np.asarray(ref.forward(ref_params, seq, REF_CFG))
        scale = want.max() - want.min()
        errs += [np.abs(logits - want[position]).max() / scale
                 for position, logits in got[b].items()]
    assert len(errs) == 3 + N_DECODE + 3 + N_DECODE
    return np.asarray(errs)


# the packed sizes the mixed steps run at: the windows (48 positions),
# and 32, which the first steps' two full windows fill exactly
@pytest.mark.parametrize("n_tokens", [None, 2 * WIDTH])
def test_paged_prefill_then_decode_matches_reference(params, n_tokens):
    assert paged_errors(params, reference_params(params),
                        n_tokens=n_tokens).max() < F32_TOL


def test_paged_int8_weights_match_reference_on_dequantized(params):
    """int8 per-channel weights, consumed as stored by the grouped
    matmul, against the reference fed q * scale: the same numbers in
    another order, so the float32 tolerance holds."""
    q = quantize_params(params, bits=8)
    assert isinstance(q["blocks"]["we_gate"], QTensor)
    assert paged_errors(q, reference_params(q)).max() < F32_TOL


def test_paged_bf16_within_its_tolerance(params):
    bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    rounded = jax.tree.map(lambda a: a.astype(jnp.float32), bf16)
    errs = paged_errors(bf16, reference_params(rounded), jnp.bfloat16)
    assert_bf16_close(errs)
    f32 = paged_errors(rounded, reference_params(rounded)).max()
    assert f32 < F32_TOL and f32 * 10 < np.median(errs)


# -- (g) padded rows -----------------------------------------------------------


@pytest.mark.parametrize("n_tokens", [None, 2 * WIDTH])
def test_padded_mixed_rows_take_no_expert_slot(params, n_tokens):
    _, _, _, (stats, real_tokens) = paged_case(params, n_tokens=n_tokens)
    rows, rows_padded, load_max, load_mean, touched = stats
    L, k, E = (CFG.num_hidden_layers, CFG.num_experts_per_tok,
               CFG.num_local_experts)
    # SLOTS * WIDTH = 48 positions went in, 32 of them real
    assert real_tokens == 2 * WIDTH < SLOTS * WIDTH
    assert rows == real_tokens * k * L
    assert rows <= rows_padded <= rows + (E - 1 + 1) * 16 * L
    assert load_mean == pytest.approx(real_tokens * k / E)
    assert load_max >= load_mean
    assert 1 <= touched <= E * L


def test_masked_tokens_come_back_zero(params):
    lp = layer0(params)
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 6, CFG.hidden_size))
    mask = jnp.asarray([[True] * 4 + [False] * 2, [False] * 6])
    out, stats = moe_mlp(lp, h, 2, False, token_mask=mask)
    full, _ = moe_mlp(lp, h, 2, False)
    assert float(stats.rows) == 4 * 2
    np.testing.assert_array_equal(np.asarray(out)[~np.asarray(mask)], 0.0)
    np.testing.assert_allclose(np.asarray(out)[0, :4],
                               np.asarray(full)[0, :4], atol=1e-6)


@pytest.mark.parametrize("real", [5, 12, 13, 40])
def test_packed_tokens_and_masked_windows_agree(params, real):
    """What the packed mixed step hands the layer: the real tokens of
    48 masked window positions, gathered to the front of a shorter
    axis. Each token's output, the rows computed and the busiest expert
    are the same; the shorter axis never walks more tiles."""
    lp = layer0(params)
    h = jax.random.normal(jax.random.PRNGKey(9), (3, 16, CFG.hidden_size))
    mask = np.zeros(48, bool)
    mask[np.random.default_rng(real).permutation(48)[:real]] = True
    want, want_stats = moe_mlp(lp, h, 2, False,
                               token_mask=jnp.asarray(mask.reshape(3, 16)))
    size = -(-real // 8) * 8
    where = np.flatnonzero(mask)
    front = np.zeros(size, np.int64)
    front[:real] = where
    got, stats = jax.jit(
        lambda lp, h, m: moe_mlp(lp, h, 2, False, token_mask=m))(
            lp, h.reshape(48, -1)[front][None],
            jnp.arange(size)[None] < real)
    np.testing.assert_allclose(np.asarray(got)[0, :real],
                               np.asarray(want).reshape(48, -1)[where],
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got)[0, real:], 0.0)
    assert float(stats.rows) == float(want_stats.rows) == real * 2
    assert float(stats.load_max) == float(want_stats.load_max)
    assert float(stats.rows_padded) <= float(want_stats.rows_padded)
    np.testing.assert_array_equal(
        np.asarray(stats.experts)[:real],
        np.asarray(want_stats.experts)[where])


def test_step_programs_hold_no_all_experts_intermediate(params):
    """Work follows N*k: no value in the mixed step program is an
    all-experts-on-all-tokens intermediate or a float copy of a
    layer's int8 expert weights."""
    q = quantize_params(params, bits=8)
    cache = PagedKVCache.create(CFG, SLOTS, 40, PAGE, T, dtype=jnp.float32)
    args = (jnp.zeros((SLOTS, WIDTH), jnp.int32), jnp.zeros(SLOTS, jnp.int32),
            jnp.full(SLOTS, WIDTH, jnp.int32), jnp.ones(SLOTS, bool))
    jaxpr = jax.make_jaxpr(
        lambda p, c: mixed_step_paged.__wrapped__(
            p, *args, c, RopeTables.create(CFG, T), CFG, attn="fold"))(
                q, cache)
    N, E = SLOTS * WIDTH, CFG.num_local_experts
    D, F = CFG.hidden_size, CFG.intermediate_size
    seen = []

    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                seen.append((eqn.primitive.name, v.aval))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert any(name == "pallas_call" for name, _ in seen)
    M = N * CFG.num_experts_per_tok
    for name, aval in seen:
        dims = set(getattr(aval, "shape", ()))
        # no [N or N*k, E, F or D] intermediate ...
        assert not (dims & {N, M} and E in dims and dims & {D, F}), (
            name, aval)
        # ... and no float copy of a layer's [E, D, F] expert weights
        if jnp.issubdtype(aval.dtype, jnp.floating):
            assert not {E, D, F} <= dims, (name, aval)


# -- (h) model_type, the int8 draw, the benchmark's copy ----------------------


@pytest.mark.parametrize("model_type", ["llama", "mistral", "qwen2",
                                        "mixtral", "olmoe", None])
def test_known_model_types_accepted(model_type):
    raw = {"vocab_size": 64, "hidden_size": 32, "intermediate_size": 16,
           "num_hidden_layers": 1, "num_attention_heads": 2}
    if model_type:
        raw["model_type"] = model_type
    cfg = load_config_dict(raw)
    assert cfg.is_moe == (model_type in ("mixtral", "olmoe"))
    if model_type == "olmoe":
        assert (cfg.num_local_experts, cfg.num_experts_per_tok,
                cfg.norm_topk_prob, cfg.qk_norm, cfg.chat_template) == (
                    64, 8, False, True, "tulu")
    if model_type == "mixtral":
        assert cfg.norm_topk_prob and not cfg.qk_norm


def test_unknown_model_type_refused_with_the_known_ones():
    with pytest.raises(ValueError) as e:
        load_config_dict({"model_type": "gpt_neox"})
    for name in ("llama", "mistral", "qwen2", "mixtral", "olmoe"):
        assert name in str(e.value)


def test_published_config_resolves_to_olmoe():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "olmoe-1b-7b-int8", "config.json")) as f:
        cfg = load_config_dict(json.load(f))
    want = MoEConfig.olmoe_1b_7b()
    for field in ("hidden_size", "intermediate_size", "num_hidden_layers",
                  "num_attention_heads", "num_key_value_heads",
                  "num_local_experts", "num_experts_per_tok",
                  "norm_topk_prob", "qk_norm", "vocab_size", "rope_theta"):
        assert getattr(cfg, field) == getattr(want, field), field


def test_int8_draw_has_the_tree_quantize_params_leaves():
    drawn = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32, bits=8)
    after = quantize_params(
        init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32), bits=8)
    assert jax.tree.structure(drawn) == jax.tree.structure(after)
    for a, b in zip(jax.tree.leaves(drawn), jax.tree.leaves(after)):
        assert a.shape == b.shape and a.dtype == b.dtype
    # dequantized, the draw has the float draw's spread
    w = dequantized(drawn["blocks"]["we_gate"])
    assert float(jnp.std(w)) == pytest.approx(
        1 / np.sqrt(CFG.hidden_size), rel=0.05)


def test_benchmark_copy_of_the_reference_is_identical():
    with open(ref.__file__, "rb") as a, open(os.path.join(
            ROOT, "benchmarks", "configs", "olmoe-1b-7b-int8",
            "reference.py"), "rb") as b:
        assert a.read() == b.read()


def test_reference_is_independent_of_the_served_code():
    with open(ref.__file__) as f:
        src = f.read()
    code = [ln for ln in src.splitlines()
            if ln.startswith(("import ", "from "))]
    assert not any("cake_tpu" in ln for ln in code)
    assert 'default_matmul_precision("highest")' in src


def test_tulu_chat_template():
    from cake_tpu.models.chat import History, Message

    h = History("tulu")
    h.add_message(Message.user(" hello "))
    assert h.render() == "<|endoftext|><|user|>\nhello\n<|assistant|>\n"


# -- the engine and the CLI ----------------------------------------------------


def test_paged_engine_serves_olmoe_and_records_the_expert_counters(params):
    """The continuous-batching engine over the page pool, mixed and
    decode steps: the sequential generator's greedy tokens, and the
    step programs' expert counters on the step records and /metrics."""
    from cake_tpu.models.llama.generator import ByteTokenizer, LlamaGenerator
    from cake_tpu.obs import steps as obs_steps
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    greedy = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
    prompt = list(np.random.default_rng(11).integers(3, 200, 21))
    before = obs_steps.MOE_COUNTERS[0][1].value
    engine = InferenceEngine(CFG, params, ByteTokenizer(CFG.vocab_size),
                             max_slots=2, max_seq_len=64, sampling=greedy,
                             cache_dtype=jnp.float32, kv_pages=20,
                             kv_page_size=8, prefill_chunk=16)
    with engine:
        h = engine.submit(prompt, max_new_tokens=6)
        assert h.wait(timeout=300)
        records = engine.flight.dump()
    got = h._req.out_tokens[:6]
    gen = LlamaGenerator(CFG, params, ByteTokenizer(CFG.vocab_size),
                         max_seq_len=64, sampling=greedy,
                         cache_dtype=jnp.float32)
    want = gen.generate_on_device(
        np.asarray([prompt], np.int32),
        np.asarray([len(prompt)], np.int32), 6)[0].tolist()
    eos_at = next((i for i, t in enumerate(want)
                   if t in CFG.eos_token_ids), 6)
    assert got[:eos_at + 1] == want[:eos_at + 1][:len(got)]
    kinds = {r["kind"] for r in records}
    assert "mixed" in kinds
    L, k = CFG.num_hidden_layers, CFG.num_experts_per_tok
    routed = sum(r.get("moe_rows", 0) for r in records)
    # every prompt token and every generated token but the last went
    # through every layer's experts once
    assert routed == (len(prompt) + len(got) - 1) * k * L
    assert obs_steps.MOE_COUNTERS[0][1].value - before == routed
    with_counters = [r for r in records if "moe_rows" in r]
    assert all(r["moe_rows_padded"] >= r["moe_rows"] > 0
               and r["moe_experts_touched"] >= L for r in with_counters)


def test_cli_refuses_a_directory_of_another_family(tmp_path, capsys,
                                                   monkeypatch):
    from cake_tpu import cli

    # (in this process cli.main must not turn JAX's persistent cache on
    # for the tests that follow: tests/test_serving_topology.py)
    monkeypatch.setattr("cake_tpu.utils.compile_cache.enable_compile_cache",
                        lambda: "off")

    (tmp_path / "config.json").write_text(json.dumps(
        {"model_type": "llama", "vocab_size": 64, "hidden_size": 32,
         "intermediate_size": 16, "num_hidden_layers": 1,
         "num_attention_heads": 2}))
    rc = cli.main(["--model", str(tmp_path), "--require-model-type",
                   "olmoe"])
    assert rc == 2
    assert "resolves to model_type 'llama'" in capsys.readouterr().err


def test_load_params_from_hf_olmoe_layout(tmp_path):
    """Synthetic OLMoE-layout safetensors (mlp.gate, mlp.experts.N.
    {gate,up,down}_proj, self_attn.{q,k}_norm) round-trip into the tree,
    int8 leaf by leaf when asked."""
    from cake_tpu.models import load_text_params
    from cake_tpu.utils.loading import save_safetensors

    c = MoEConfig.tiny_olmoe(num_hidden_layers=1, num_local_experts=2)
    rng = np.random.default_rng(3)
    D, F, E = c.hidden_size, c.intermediate_size, c.num_local_experts
    hd, H, KV = c.head_dim, c.num_attention_heads, c.num_key_value_heads
    pre = "model.layers.0"
    tensors = {
        "model.embed_tokens.weight": rng.normal(size=(c.vocab_size, D)),
        "model.norm.weight": rng.normal(size=(D,)),
        "lm_head.weight": rng.normal(size=(c.vocab_size, D)),
        f"{pre}.input_layernorm.weight": rng.normal(size=(D,)),
        f"{pre}.post_attention_layernorm.weight": rng.normal(size=(D,)),
        f"{pre}.self_attn.q_proj.weight": rng.normal(size=(H * hd, D)),
        f"{pre}.self_attn.k_proj.weight": rng.normal(size=(KV * hd, D)),
        f"{pre}.self_attn.v_proj.weight": rng.normal(size=(KV * hd, D)),
        f"{pre}.self_attn.o_proj.weight": rng.normal(size=(D, H * hd)),
        f"{pre}.self_attn.q_norm.weight": rng.normal(size=(H * hd,)),
        f"{pre}.self_attn.k_norm.weight": rng.normal(size=(KV * hd,)),
        f"{pre}.mlp.gate.weight": rng.normal(size=(E, D)),
    }
    for e in range(E):
        base = f"{pre}.mlp.experts.{e}"
        tensors[f"{base}.gate_proj.weight"] = rng.normal(size=(F, D))
        tensors[f"{base}.up_proj.weight"] = rng.normal(size=(F, D))
        tensors[f"{base}.down_proj.weight"] = rng.normal(size=(D, F))
    tensors = {k: v.astype(np.float32) for k, v in tensors.items()}
    save_safetensors(str(tmp_path / "model.safetensors"), tensors)

    params = load_text_params(c, str(tmp_path), jnp.float32)
    blocks = params["blocks"]
    assert blocks["q_norm"].shape == (1, H * hd)
    np.testing.assert_allclose(
        np.asarray(blocks["k_norm"][0]),
        tensors[f"{pre}.self_attn.k_norm.weight"])
    np.testing.assert_allclose(
        np.asarray(blocks["we_up"][0, 1]),
        tensors[f"{pre}.mlp.experts.1.up_proj.weight"].T)
    np.testing.assert_allclose(
        np.asarray(blocks["router"][0]), tensors[f"{pre}.mlp.gate.weight"].T)
    q = load_text_params(c, str(tmp_path), jnp.float32, quant="int8")
    assert isinstance(q["blocks"]["we_down"], QTensor)
    assert q["blocks"]["we_down"].q.shape == (1, E, F, D)
    np.testing.assert_allclose(
        np.asarray(dequantized(q["blocks"]["we_down"])[0, 0]),
        tensors[f"{pre}.mlp.experts.0.down_proj.weight"].T, atol=0.05)
