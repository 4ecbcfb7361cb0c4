"""Keye-VL-2.0's language model (`KeyeVL2`) at a tiny size on seeded
weights: the served path (mixed-step prefill in windows, decode through
the three pools, decode rows beside prefilling ones, rows under and over
`topk` in one step) against the plain float32 reference's full forward,
logits and selected key sets; below `topk` the same bits as the
unselected kernels; the selection's mask in the mixed kernel against
exact attention; the reference's three-stream rotation; what the config
class and the family refuse; the cell's files.

3 layers, 4 heads of 16 over 2 K/V heads, an indexer of 2 heads of 8
that selects 48 keys, pages of 8, windows of 16: a prompt of 100 selects
48 of up to 108 keys, one of 30 attends all it sees."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import MODEL_TYPES, load_config_dict
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import (
    PagedKVCache, mixed_token_buckets, paged_attention,
    paged_attention_mixed,
)
from cake_tpu.models.moe import keye_vl2 as kv2
from cake_tpu.models.moe.config import KeyeVL2Config
from cake_tpu.models.moe.params import init_params
from cake_tpu.models.reference import keye_vl2 as ref

ROOT = os.path.join(os.path.dirname(__file__), "..")
CONFIG_DIR = os.path.join(ROOT, "benchmarks", "configs",
                          "keye-vl-2.0-lm-int8-8of48")
B, C, PAGE, MAX_SEQ = 4, 16, 8, 128
# float32 on both sides; three layers of sums in another order leave a
# few 1e-5 of logits that span ~3 (the other families' limit is 2e-4 at
# most)
ATOL = 5e-5


def ref_params(params, c):
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": kv2.dequantized(params["lm_head"]),
            "layers": list(kv2.reference_layers(params["blocks"], c))}


@pytest.fixture(scope="module")
def model():
    c = KeyeVL2Config.tiny_keye()
    return (c, init_params(c, jax.random.PRNGKey(0), jnp.float32),
            RopeTables.create(c, MAX_SEQ))


def fresh_cache(c):
    cache = PagedKVCache.create(c, B, 1 + B * (MAX_SEQ // PAGE), PAGE,
                                MAX_SEQ, dtype=jnp.float32, width=C)
    table = np.stack([1 + b * (MAX_SEQ // PAGE) + np.arange(MAX_SEQ // PAGE)
                      for b in range(B)]).astype(np.int32)
    return cache._replace(table=jnp.asarray(table))


def mixed(model, cache, toks, pos, qlen, attn="fold"):
    c, params, rope = model
    return jax.jit(kv2.mixed_trunk, static_argnames=(
        "config", "attn", "n_tokens", "probe"))(
        params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(qlen),
        jnp.asarray(qlen > 0), cache, rope, config=c, attn=attn,
        n_tokens=mixed_token_buckets(B, C, (1,))[-1], probe=True)


def decode(model, cache, toks, pos, active, attn="fold"):
    c, params, rope = model
    return jax.jit(kv2.decode_trunk, static_argnames=(
        "config", "attn", "probe"))(
        params, jnp.asarray(toks), cache, jnp.asarray(pos),
        jnp.asarray(active), rope, config=c, attn=attn, probe=True)


def serve(model, sequences, prompts, attn="fold"):
    """Every sequence through the step programs: prompts in C-wide
    windows, one window a dispatch, the rows that finished their prompt
    riding the other rows' mixed steps as one-token rows, then the
    decode program. Returns (per sequence {position: logits}, per
    sequence {position: [L] key sets}, cache, the counters' sum)."""
    c, params, _ = model
    cache = fresh_cache(c)
    off = [0] * len(sequences)
    got = [dict() for _ in sequences]
    sets = [dict() for _ in sequences]
    total = np.zeros(len(kv2.COUNTERS))
    head = kv2.dequantized(params["lm_head"])
    L = c.num_hidden_layers

    def keep(i, position, logits, out, window_col=None):
        got[i][position] = np.asarray(logits)
        if window_col is None:
            sets[i][position] = [
                set(np.flatnonzero(np.asarray(out.selected[j, i])).tolist())
                for j in range(L)]
            assert all(len(s) == int(out.n_selected[i])
                       for s in sets[i][position])
        else:
            sets[i][position] = [
                set(np.flatnonzero(np.asarray(
                    out.selected_window[j, window_col])).tolist())
                for j in range(L)]

    while any(off[i] < prompts[i] for i in range(len(sequences))):
        i0 = next(i for i in range(len(sequences)) if off[i] < prompts[i])
        toks = np.zeros((B, C), np.int32)
        pos = np.zeros(B, np.int32)
        qlen = np.zeros(B, np.int32)
        for i, seq in enumerate(sequences):
            if i == i0:
                n = min(C, prompts[i] - off[i])
            elif prompts[i] <= off[i] < len(seq):
                n = 1
            else:
                continue
            toks[i, :n], pos[i], qlen[i] = seq[off[i]:off[i] + n], off[i], n
        out, plan = mixed(model, cache, toks, pos, qlen, attn)
        cache = out.cache
        total += np.asarray(out.counters)
        logits = out.x @ head
        for i in range(len(sequences)):
            for j in range(qlen[i]):
                keep(i, off[i] + j, logits[int(plan.start[i]) + j], out,
                     j if qlen[i] > 1 else None)
            off[i] += int(qlen[i])
    while any(off[i] < len(s) for i, s in enumerate(sequences)):
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        for i, seq in enumerate(sequences):
            if off[i] < len(seq):
                toks[i, 0], pos[i], active[i] = seq[off[i]], off[i], True
        out = decode(model, cache, toks, pos, active, attn)
        cache = out.cache
        total += np.asarray(out.counters)
        logits = out.x @ head
        for i in range(len(sequences)):
            if active[i]:
                keep(i, off[i], logits[i], out)
                off[i] += 1
    return got, sets, cache, total


@pytest.fixture(scope="module")
def traffic(model):
    rng = np.random.default_rng(1)
    # 100 + 8 selects 48 of up to 108; 30 + 8 stays under topk; 49 ends
    # its prompt a key past it; 17 is a window and one token
    prompts = (100, 30, 49, 17)
    return [rng.integers(0, model[0].vocab_size, p + 8)
            for p in prompts], prompts


@pytest.fixture(scope="module")
def reference_run(model, traffic):
    c, params, _ = model
    routing = [[] for _ in traffic[0]]
    selections = [[] for _ in traffic[0]]
    logits = ref.forward(ref_params(params, c), traffic[0],
                         kv2.reference_config(c), routing=routing,
                         selections=selections)
    return [np.asarray(x) for x in logits], routing, selections


@pytest.fixture(scope="module")
def served_run(model, traffic):
    return serve(model, *traffic)


# -- the served path against the reference -------------------------------------


@pytest.mark.parametrize("row", [0, 1, 2, 3])
def test_served_path_matches_the_reference_forward(
        served_run, reference_run, traffic, row):
    """Prefill in windows of 16, then decode through the pools, rows of
    unequal length in one step: every position's logits."""
    got, want = served_run[0][row], reference_run[0][row]
    assert sorted(got) == list(range(len(traffic[0][row])))
    for position, logits in got.items():
        np.testing.assert_allclose(logits, want[position], atol=ATOL,
                                   err_msg=f"position {position}")


@pytest.mark.parametrize("row", [0, 1, 2, 3])
def test_served_path_selects_the_references_keys(
        model, served_run, reference_run, traffic, row):
    """Every layer's set at every position: all that is visible while
    that is no more than topk, the reference's 48 beyond."""
    c = model[0]
    sets, want = served_run[1][row], reference_run[2][row]
    for position in range(len(traffic[0][row])):
        for layer in range(c.num_hidden_layers):
            theirs = set(np.flatnonzero(
                want[layer]["sets"][position]).tolist())
            assert len(theirs) == min(position + 1, c.index_topk)
            assert sets[position][layer] == theirs, (position, layer)


def test_counters_count_what_was_selected(model, served_run, traffic):
    c = model[0]
    total = dict(zip(kv2.COUNTERS, served_run[3]))
    L, K = c.num_hidden_layers, c.index_topk
    lengths = [len(s) for s in traffic[0]]
    assert total["dsa_keys_visible"] == L * sum(
        n * (n + 1) // 2 for n in lengths)
    assert total["dsa_keys_selected"] == L * sum(
        min(t + 1, K) for n in lengths for t in range(n))
    assert total["dsa_keys_single"] <= total["dsa_keys_scanned_single"]
    assert total["dsa_keys_single"] <= total["dsa_keys_selected"]
    assert total["gqa_rows_single"] > 0
    assert total["moe_rows"] == total["moe_rows_routed"] == (
        L * c.num_experts_per_tok * sum(lengths))


def test_counters_count_the_single_token_rows_walk(model, served_run,
                                                   traffic):
    """Every single-token row attends by the walk under its mask: the
    rows that took it are the rows that held one token, and its pages
    each such token's live pages (position // page + 1) a layer. The
    tokens that were single: the eight past a prompt, and a prompt's
    last window where that holds one token (17 = 16 + 1)."""
    c = model[0]
    total = dict(zip(kv2.COUNTERS, served_run[3]))
    seqs, prompts = traffic
    single = [t for seq, p in zip(seqs, prompts)
              for t in range(len(seq))
              if t >= p or (t == p - 1 and p % C == 1)]
    assert total["dsa_walk_rows_single"] == total["gqa_rows_single"] == len(
        single)
    assert total["dsa_walk_pages_single"] == c.num_hidden_layers * sum(
        t // PAGE + 1 for t in single)
    assert total["dsa_keys_single"] == c.num_hidden_layers * sum(
        min(t + 1, c.index_topk) for t in single)


def test_pallas_kernels_serve_the_same_logits(model, traffic):
    """Both kernels interpreted, the window's mask streamed beside the
    pages: the fold's logits to float32 rounding, and its sets."""
    seqs, prompts = [traffic[0][0][:70], traffic[0][1]], (62, 30)
    got, sets, _, _ = serve(model, seqs, prompts, attn="pallas")
    fold, fold_sets, _, _ = serve(model, seqs, prompts, attn="fold")
    for row in range(2):
        assert sorted(got[row]) == list(range(len(seqs[row])))
        for position in fold[row]:
            np.testing.assert_allclose(got[row][position],
                                       fold[row][position], atol=ATOL)
            assert sets[row][position] == fold_sets[row][position]


# -- below topk: the unselected kernels' bits ----------------------------------


def seeded_pools(c, n_keys: int, seed: int = 3):
    """One row's K and V pages holding n_keys drawn keys, and queries."""
    rng = np.random.default_rng(seed)
    KV, hd, H = c.num_key_value_heads, c.head_dim, c.num_attention_heads
    pages = MAX_SEQ // PAGE
    k = jnp.asarray(rng.standard_normal((1, 1 + pages, PAGE, KV * hd)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal(k.shape), jnp.float32)
    table = jnp.asarray(1 + np.arange(pages, dtype=np.int32))[None]
    q = jnp.asarray(rng.standard_normal((C, H, hd)), jnp.float32)
    return k, v, table, q


@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_below_topk_a_single_token_is_the_unselected_kernel_bit_for_bit(
        model, attn):
    """A row of 40 keys, all of them selected (topk 48): the mask
    changes no bit of cake_decode_attn's result."""
    c = model[0]
    k, v, table, q = seeded_pools(c, 40)
    pos = jnp.asarray([39], jnp.int32)
    plain = paged_attention(q[:1, None], k, v, jnp.int32(0), table, pos,
                            impl=attn)[:, 0]
    picked = kv2.attend_rows(q[:1], k, v, jnp.int32(0), table, pos,
                             jnp.arange(MAX_SEQ)[None, :] <= pos[:, None],
                             attn)
    assert np.array_equal(np.asarray(plain), np.asarray(picked))


@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_a_rows_selection_is_exact_attention_over_the_chosen_keys(
        model, attn):
    """Above topk: three rows over one table at positions 39, 21 and
    none (-1), each over ITS 12 keys where they lie in its own pages,
    against exact softmax attention over those keys alone; the row
    without a token gives zeros."""
    c = model[0]
    k, v, table, q = seeded_pools(c, 40)
    KV, hd, H = c.num_key_value_heads, c.head_dim, c.num_attention_heads
    rng = np.random.default_rng(6)
    pos = np.asarray([39, 21, -1], np.int32)
    own = np.zeros((3, MAX_SEQ), bool)
    for b in range(2):
        own[b, rng.choice(pos[b] + 1, 12, replace=False)] = True
    own[2, :8] = True       # marked, and no position to see it from
    got = np.asarray(kv2.attend_rows(
        q[:3], k, v, jnp.int32(0), jnp.broadcast_to(table, (3, MAX_SEQ // PAGE)),
        jnp.asarray(pos), jnp.asarray(own), attn))
    keys = np.asarray(k[0, 1:]).reshape(MAX_SEQ, KV, hd)
    vals = np.asarray(v[0, 1:]).reshape(MAX_SEQ, KV, hd)
    qn = np.asarray(q[:2]).reshape(2, KV, H // KV, hd)
    s = np.einsum("tkgd,skd->tkgs", qn, keys) / np.sqrt(hd)
    s = np.where(own[:2, None, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("tkgs,skd->tkgd", p / p.sum(-1, keepdims=True), vals)
    np.testing.assert_allclose(got[:2].reshape(want.shape), want, atol=2e-5)
    assert not got[2].any()


@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_below_topk_a_window_is_the_unselected_kernel_bit_for_bit(
        model, attn):
    """A window at positions 24 .. 39 whose every visible key is
    selected: the mask changes no bit of cake_mixed_attn's result."""
    c = model[0]
    k, v, table, q = seeded_pools(c, 40)
    first, n = jnp.int32(24), jnp.int32(C)
    plain = paged_attention_mixed(
        q[None], k, v, jnp.int32(0), table, first[None], n[None], impl=attn)
    visible = (jnp.arange(MAX_SEQ)[None, :]
               <= (first + jnp.arange(C))[:, None])
    picked = kv2.attend_window(q, k, v, jnp.int32(0), table[0], first, n,
                               visible, attn)
    assert np.array_equal(np.asarray(plain[0]), np.asarray(picked))


@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_a_windows_selection_is_exact_attention_over_the_chosen_keys(
        model, attn):
    """Above topk: each query over ITS 12 keys of 40, against exact
    softmax attention over those keys alone."""
    c = model[0]
    k, v, table, q = seeded_pools(c, 40)
    KV, hd, H = c.num_key_value_heads, c.head_dim, c.num_attention_heads
    rng = np.random.default_rng(5)
    first = 24
    picked = np.zeros((C, MAX_SEQ), bool)
    for i in range(C):
        picked[i, rng.choice(first + i + 1, 12, replace=False)] = True
    got = kv2.attend_window(q, k, v, jnp.int32(0), table[0],
                            jnp.int32(first), jnp.int32(C),
                            jnp.asarray(picked), attn)
    keys = np.asarray(k[0, 1:]).reshape(MAX_SEQ, KV, hd)
    vals = np.asarray(v[0, 1:]).reshape(MAX_SEQ, KV, hd)
    qn = np.asarray(q).reshape(C, KV, H // KV, hd)
    s = np.einsum("tkgd,skd->tkgs", qn, keys) / np.sqrt(hd)
    s = np.where(picked[:, None, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("tkgs,skd->tkgd", p / p.sum(-1, keepdims=True), vals)
    np.testing.assert_allclose(np.asarray(got).reshape(want.shape), want,
                               atol=2e-5)


def test_the_bench_tool_rehearses_and_checks_both_forms(capsys):
    """tools/decode_selected_bench.py at tiny shapes: one JSON line, the
    gather form it keeps (what this trunk served until PR 66) and the
    mask form select the same sets and attend to the same output, every
    form timed at every length."""
    import importlib.util

    path = os.path.join(ROOT, "tools", "decode_selected_bench.py")
    spec = importlib.util.spec_from_file_location("decode_selected_bench",
                                                  path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearse", "--calls", "2", "--lengths",
                      "20,100"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [case["keys"] for case in line["cases"]] == [20, 100]
    for case in line["cases"]:
        assert case["same_sets"] and case["max_abs_diff"] < 1e-4
        assert {"gather_us", "mask_us", "mask_select_us", "mask_attend_us",
                "walk_us"} <= set(case)
    assert "crossover_keys" in line


# -- the rotation --------------------------------------------------------------


def test_three_equal_streams_are_the_ordinary_rotation():
    """What "text" means: with the three streams equal, M-RoPE by
    mrope_section is the rotation by one position at every frequency."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((9, 2, 16)), jnp.float32)
    text = ref.text_positions(9)
    dealt = ref.rope(x, ref.angles(text, 16, 1e7, [2, 3, 3]))
    plain = ref.rope(x, ref.angles(text, 16, 1e7))
    assert np.array_equal(np.asarray(dealt), np.asarray(plain))
    from cake_tpu.ops.rope import apply_rope, precompute_rope
    cos, sin = precompute_rope(16, 9, 1e7)
    np.testing.assert_allclose(np.asarray(apply_rope(x[None], cos, sin)[0]),
                               np.asarray(plain), atol=1e-6)


def test_streams_that_differ_turn_their_own_frequencies():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((9, 2, 16)), jnp.float32)
    image = np.stack([np.arange(9), np.arange(9) // 3, np.arange(9) % 3])
    dealt = np.asarray(ref.rope(x, ref.angles(image, 16, 1e7, [2, 3, 3])))
    plain = np.asarray(ref.rope(x, ref.angles(image, 16, 1e7)))
    # frequencies 0-1 (pairs (0, 8), (1, 9)) turn by the temporal stream
    # in both; 2-7 by height and width in the dealt one alone
    assert np.array_equal(dealt[..., [0, 1, 8, 9]], plain[..., [0, 1, 8, 9]])
    assert not np.allclose(dealt[3:, :, 2:8], plain[3:, :, 2:8])
    with pytest.raises(AssertionError, match="mrope_section"):
        ref.angles(image, 16, 1e7, [2, 3, 4])


def test_the_reference_forward_moves_with_the_streams(model, traffic):
    c, params, _ = model
    seq = traffic[0][3]
    n = len(seq)
    image = np.stack([np.arange(n), np.arange(n) // 5, np.arange(n) % 5])
    rp, rc = ref_params(params, c), kv2.reference_config(c)
    text = np.asarray(ref.forward(rp, seq, rc))
    same = np.asarray(ref.forward(rp, seq, rc,
                                  positions=ref.text_positions(n)))
    moved = np.asarray(ref.forward(rp, seq, rc, positions=image))
    assert np.array_equal(text, same)
    assert np.abs(moved - text).max() > 1e-3


# -- the config class ----------------------------------------------------------


def published():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return json.load(f)


def test_the_cells_config_loads_under_its_published_keys():
    c = load_config_dict(published())
    assert isinstance(c, KeyeVL2Config) and "KeyeVL2" in MODEL_TYPES
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        32, 4, 128)
    assert (c.index_n_heads, c.index_head_dim, c.index_topk) == (16, 64, 2048)
    assert (c.num_local_experts, c.num_experts_per_tok,
            c.moe_intermediate_size) == (128, 8, 768)
    assert c.mrope_section == (16, 24, 24) and c.rope_theta == 1e7
    assert c.num_hidden_layers == 8 and c.vocab_size == 151936
    assert c.family.name == "KeyeVL2" and c.family.impl == "paged-dsa-gqa-"


@pytest.mark.parametrize("change, named", [
    ({"vision_config": {"depth": 27}}, "vision_config"),
    ({"audio_config": {}}, "audio_config"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                    "indexer_num_kv_heads": 2, "topk": 2048}},
     "indexer_num_kv_heads"),
    ({"sa_config": None}, "sa_config"),
    ({"rope_scaling": {"mrope_section": [16, 24, 23],
                       "rope_type": "default"}}, "mrope_section"),
    ({"rope_scaling": {"mrope_section": [16, 24, 24],
                       "rope_type": "yarn"}}, "rope_type"),
    ({"num_local_experts": 16}, "num_local_experts"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_the_config_class_refuses_by_key(change, named):
    with pytest.raises(ValueError, match=named):
        load_config_dict(dict(published(), **change))


def test_the_family_refuses_what_an_index_key_pool_cannot_move():
    family = KeyeVL2Config.tiny_keye().family
    for option in ("--auto-prefix", "--kv-host-pages", "--spec-draft",
                   "topology", "--kv-dtype", "--disagg", "--kv-pages"):
        assert not family.moves(option)
        assert "index-key pool" in family.refuses[option]
    assert "index-key pool" in family.refuses["register_prefix"]
    said = family.refusal({"--spec-draft": True, "--kv-pages": False})
    assert "KeyeVL2" in said and "--spec-draft" in said


def test_the_cache_is_three_pools_over_one_table(model):
    c = model[0]
    cache = fresh_cache(c)
    assert type(cache) is PagedKVCache
    assert cache.k.shape == cache.v.shape == (3, 1 + B * 16, PAGE, 2 * 16)
    assert cache.idx.shape == (3, 1 + B * 16, PAGE, 8)
    assert cache.memory_bytes() == (cache.k.nbytes + cache.v.nbytes
                                    + cache.idx.nbytes)
    # every other family's cache has no such leaf
    from cake_tpu.models.llama.config import LlamaConfig
    plain = PagedKVCache.create(LlamaConfig.tiny(), 2, 8, 8, 32)
    assert plain.idx is None and len(jax.tree.leaves(plain)) == 3


# -- the cell's files ----------------------------------------------------------


def test_the_cells_reference_is_the_repos_byte_for_byte():
    with open(os.path.join(CONFIG_DIR, "reference.py"), "rb") as f:
        copy = f.read()
    with open(os.path.join(ROOT, "cake_tpu", "models", "reference",
                           "keye_vl2.py"), "rb") as f:
        assert copy == f.read()


def test_the_rehearsal_config_is_the_tests_toy():
    with open(os.path.join(CONFIG_DIR, "cell.json")) as f:
        cell = json.load(f)
    toy = load_config_dict(dict(published(), **cell["rehearse"]["config"]))
    tiny = KeyeVL2Config.tiny_keye()
    for name in ("hidden_size", "num_hidden_layers", "num_attention_heads",
                 "num_key_value_heads", "head_dim", "index_n_heads",
                 "index_head_dim", "index_topk", "num_local_experts",
                 "num_experts_per_tok", "moe_intermediate_size",
                 "mrope_section", "vocab_size"):
        assert getattr(toy, name) == getattr(tiny, name), name


# -- the engine ----------------------------------------------------------------


def make_engine(**kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    c = KeyeVL2Config.tiny_keye(vocab_size=300, eos_token_ids=(300,))
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
    # 90 + 10 selects 48 of up to 100; two requests wait for a slot
    prompts = [list(map(int, rng.integers(3, 250, n)))
               for n in (90, 7, 60, 21, 33, 12)]
    with eng:
        handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for h in handles:
            assert h.wait(180)
        records = eng.flight.dump()
    return c, params, prompts, [h.token_ids for h in handles], records, eng


@pytest.mark.parametrize("request_index", range(6))
def test_engine_serves_the_references_greedy_tokens(engine_run,
                                                    request_index):
    """Through submit -> the mixed step -> the in-flight decode step:
    four requests over four rows and two behind them in REUSED slots,
    prompts of 1 to 6 windows. Teacher-forced: the reference's forward
    over the prompt and the tokens the engine gave must choose each."""
    c, params, prompts, tokens, *_ = engine_run
    prompt, out = prompts[request_index], tokens[request_index]
    assert len(out) == 10
    logits = np.asarray(ref.forward(
        ref_params(params, c), np.asarray(prompt + out),
        kv2.reference_config(c)))
    for i, tok in enumerate(out):
        at = logits[len(prompt) - 1 + i]
        top2 = np.sort(at)[-2:]
        if top2[1] - top2[0] > 1e-3:        # a near-tie may fall either way
            assert tok == int(np.argmax(at)), i


def test_step_records_name_the_attention_and_carry_the_counters(engine_run):
    from cake_tpu.obs import steps as obs_steps
    c, _, _, _, records, eng = engine_run
    assert {r["kind"] for r in records} >= {"mixed", "decode"}
    assert {r["impl"] for r in records} == {"paged-dsa-gqa-fold"}
    counted = [r for r in records if "dsa_keys_single" in r]
    assert counted
    for r in counted:
        assert r["dsa_keys_selected"] <= r["dsa_keys_visible"]
        assert r["dsa_keys_single"] <= r["dsa_keys_scanned_single"]
        # every single-token row walks its pages under its mask
        assert r["dsa_walk_rows_single"] == r["gqa_rows_single"]
        assert (r["dsa_walk_pages_single"] * eng.cache.page_size
                >= r["dsa_keys_scanned_single"])
        # (a step of k prompts is k dispatches: Windows.DISPATCH)
        assert r["dsa_index_layers"] % c.num_hidden_layers == 0
        # the window's selection: a mixed dispatch's alone, and at this
        # table (one block) the whole width
        walked, table = r["dsa_select_keys_walked"], r["dsa_select_keys_table"]
        assert walked == table == (
            r["dsa_index_layers"] * eng.cache.table.shape[1]
            * eng.cache.page_size
            if r["kind"] == "mixed" else 0)
        # ... and its score pass (`cake_dsa_index`) the same
        assert r["dsa_index_keys_scored"] == walked
    assert any(r["dsa_keys_selected"] < r["dsa_keys_visible"]
               for r in counted)
    assert eng.flight._counters == kv2.COUNTERS
    assert all(k in obs_steps.COUNTER_SERIES for k in kv2.COUNTERS)
    assert eng._mixed_buckets == (32,) and not eng._prefix_capable
    assert eng.cache.idx.shape == (3, 64, 8, 8)


def test_mixed_records_count_the_windows_walk_of_its_pages(engine_run):
    """mixed_attn_pages / _table / _folds on Keye's mixed records: a
    dispatch's one window as `attend_window` hands it over (entries of
    `query_tile` queries over the row's table), each entry walking from
    page 0 to its last real query's, an entry past the window's tokens
    and a dispatch of single tokens none; no decode record has them."""
    from cake_tpu.ops import ragged_paged_attention as rpa
    c, _, _, _, records, eng = engine_run
    pages = eng.cache.table.shape[1]
    walk = kv2.mixed_attn_walk(c, eng.cache, 16)
    tile = kv2.query_tile(16, c.num_attention_heads, c.num_key_value_heads,
                          c.head_dim, 8, 4, 4)
    block = rpa.mixed_block(8, c.num_attention_heads, c.num_key_value_heads,
                            c.head_dim, tile, pages, 4, 4, selecting=True)
    # a full window at position 32 of pages of 8: every entry to its end
    ends = [(32 + start + tile - 1) // 8 + 1 for start in range(0, 16, tile)]
    assert walk(32, 16) == (sum(ends), 16 // tile * pages,
                            sum(-(-e // block) for e in ends))
    # five tokens: the entries past them are idle; none: no walk at all
    assert walk(32, 5)[0] == sum(
        (32 + start + min(5 - start, tile) - 1) // 8 + 1
        for start in range(0, 16, tile) if start < 5)
    assert walk(32, 0) == (0, 16 // tile * pages, 0)
    mixed = [r for r in records if r["kind"] == "mixed"]
    assert mixed and all("mixed_attn_pages" in r for r in mixed)
    for r in mixed:
        assert 0 <= r["mixed_attn_pages"] < r["mixed_attn_pages_table"]
        assert r["mixed_attn_pages_table"] % (16 // tile * pages) == 0
        assert (r["mixed_attn_pages"] / block <= r["mixed_attn_folds"]
                <= r["mixed_attn_pages"])
    assert any(r["mixed_attn_pages"] for r in mixed)
    assert not [r for r in records
                if r["kind"] == "decode" and "mixed_attn_pages" in r]


@pytest.mark.parametrize("refused,named", [
    (dict(kv_pages=None), "--kv-pages"),
    (dict(step_fns=(print, print)), "topology"),
    (dict(kv_dtype="int8"), "--kv-dtype"),
    (dict(kv_host_pages=8), "--kv-host-pages"),
    (dict(auto_prefix_system=True), "--auto-prefix"),
    (dict(disagg="prefill"), "--disagg")])
def test_engine_refuses_by_name_what_an_index_key_pool_does_not_serve(
        refused, named):
    with pytest.raises(ValueError) as e:
        make_engine(**refused)
    assert "KeyeVL2" in str(e.value) and named in str(e.value)
    assert "index-key pool" in str(e.value)


def test_prefix_registration_is_refused_by_name():
    *_, eng = make_engine()
    with pytest.raises(ValueError, match="index-key pool"):
        eng.register_prefix([5, 6, 7, 8, 9, 10, 11, 12, 13])


# -- every other family's programs are the parent's ----------------------------

# sha256 of `.lower(...).as_text()` at tests/test_family.TINY's configs,
# taken on the commit BEFORE the mixed kernel took `selected=`,
# PagedKVCache its `idx` leaf and RopeTables.create its third form
# (368216f): no selected-set operand, leaf or table reaches a program
# of a family without an indexer over K/V pages. A change that means to
# move these programs re-pins them (the same calls in the parent's
# tree); one that does not, and fails here, has moved them. PR 61
# re-pinned glm_moe_dsa's and dots3_note's eight: their mixed programs
# call `cake_dsa_select` where `select_mask` stood, and all eight return
# a counter vector two keys longer (`dsa_select_keys_walked` /
# `_table`: two constant zeros in a decode program, nothing else of it
# moves); the other twelve are as they were. PR 62 re-pinned the three
# mixed programs that call `cake_mixed_attn` (olmoe, mistral, exaone_moe:
# the kernel walks its rows' pages itself, grid (rows,)); every decode
# program, `walk_live_pages`' two earlier callers among them, and the
# latent families' mixed programs lower as they did. PR 68 re-pinned
# glm_moe_dsa's and dots3_note's eight again: their mixed programs call
# `cake_dsa_index` where the blocked `lax.map` stood, and all eight
# return a counter vector one key longer (`dsa_index_keys_scored`: a
# constant zero in a decode program, nothing else of it moves).
LOWERED_BEFORE = {
    ("glm_moe_dsa", "decode", "fold"):
        "d04abd22c0311991ba52db3bd62870920e7c39bbfb691eed74943eccbd13ee09",
    ("glm_moe_dsa", "decode", "pallas"):
        "4e4fa522c0167825c4b82a55159e7d7c5257152184ae519be441f3aa800061f2",
    ("glm_moe_dsa", "mixed", "fold"):
        "a09b496df07a99d280e43c38dbe9aa9c2e4e95a261afe19aa4f8c1982de5c555",
    ("glm_moe_dsa", "mixed", "pallas"):
        "1c7c4b2aa7b913514e4281becb61ca45287f0f729e6cc69017a23ba21ce5f20a",
    ("dots3_note", "decode", "fold"):
        "6e279d2b0759765fc477e2d89886449627a2ada6529f3a7fe5b9ee66719f4215",
    ("dots3_note", "decode", "pallas"):
        "b4e83d286936bfebc1bcaeb5f491d783fb6860653f18072d6305cd8683b4de39",
    ("dots3_note", "mixed", "fold"):
        "75653ec97a58230d21f51d190a02a4bb8a5541d4b84d0410f93695983c7dea57",
    ("dots3_note", "mixed", "pallas"):
        "fffdfb0f86663fa6b28f8cc992c3c1347c0b85527a081ad10cbce94afae6890d",
    ("exaone_moe", "decode", "fold"):
        "07419160964f7238f5651f50e8b883b0627df46bf064c797682ff624ffa9815c",
    ("exaone_moe", "decode", "pallas"):
        "d74a1b1c7941bde0578fbadffbfec9fa1fa887f1c3f7cb55efa5ccf402984313",
    ("exaone_moe", "mixed", "fold"):
        "ebaf614c91933bff09b2d03d5fad275dfa5ba8d441d5acf3154dcb5089727240",
    ("exaone_moe", "mixed", "pallas"):
        "23445f0f9880a42aa1e1b6e23df37c9124411e939a39bb5c2fccde35c3207ee0",
    ("olmoe", "decode", "fold"):
        "d167d15f7f4435124d3835fcf63e6099a80260db1c4993b5da23a9d645121784",
    ("olmoe", "decode", "pallas"):
        "46de525b88ec912a31e6d7344e105df15a31bb345dcee9e21a42e23c373f41e1",
    ("olmoe", "mixed", "fold"):
        "2ad674eac7dfcea6d6a4b13806c22d5495e2cc8eab0a28bf80822afc048c3b6c",
    ("olmoe", "mixed", "pallas"):
        "850ebea96af2939cad887a87e2d9edfa20caa61e2dffcc27f041bfb7b8c278ef",
    ("mistral", "decode", "fold"):
        "fbcbef1dea0981cad5b372f15ecbbaf5ebf3f46995bbf4c42b1d2ec5038b1fae",
    ("mistral", "decode", "pallas"):
        "2fae0860ebebff6947a1da24abfc066bc8986ec1b9c5cec819e6b9b7e96c3521",
    ("mistral", "mixed", "fold"):
        "ac61136edb0263ecde66c5f05812c1b952e1363297605f4dd1c428df3908d6ee",
    ("mistral", "mixed", "pallas"):
        "6d3f047daa027259c89add8a3519624363f056cc94a2e7927815c237052c2638",
    # the families whose shared code PR 63 (model_type brumby) touched,
    # taken on the commit before it (8e4a1c0): ops/kda._step_kernel grew
    # `outs` / `scratch` / `stay` for ops/retention.py, and
    # HybridPagedCache a user with a pool of no layers. (Nemotron's four
    # are pinned in tests/test_granite_hybrid.py.)
    ("granitemoehybrid", "decode", "fold"):
        "4d0b30fcb798ca1e210728e4844de83f67a5ec5e288ae576a071d106f0d33bdd",
    ("granitemoehybrid", "decode", "pallas"):
        "e95cb5ae513b83527192ef73752070b72b85d78920c5aba2e1e73a73bbeb6c7b",
    ("granitemoehybrid", "mixed", "fold"):
        "2b2ba9d6af4cb01ff2bca65a98b72e4eedb1f65327c9aeb855d40581eb367b68",
    ("granitemoehybrid", "mixed", "pallas"):
        "301c236cb11c4b166de4a3d4f4874d1a1442de2714a7c0f51fce5bdae8791843",
    ("bailing_hybrid", "decode", "fold"):
        "41209b0bd73b7a679d94731b52f785a93ca9e7d37f7474f429629e69170c0511",
    # (Ling's two pallas programs hold cake_mla_decode_attn, which PR 64
    # MEANT to move: a block of pages a softmax update. Taken on PR 64's
    # tree; its fold programs, and the thirty others, are the parent's.)
    ("bailing_hybrid", "decode", "pallas"):
        "65040a8411aed0443cff0b9ddcfbbee523bf36d4bad3a22b19113a9bdb73716b",
    ("bailing_hybrid", "mixed", "fold"):
        "e0954fcb46f5d0fdf9c5010bb6a47f0033479df54127c6abd45f6dd72e8783e9",
    ("bailing_hybrid", "mixed", "pallas"):
        "81164b167ddf422391557614f623b09b7a7e2a47e92e91711acbccea945b63ac",
    ("zaya", "decode", "fold"):
        "3c80cd2e9ef459be219508b6b178759f26358fdd3dd7ff814f63caed009d53cb",
    ("zaya", "decode", "pallas"):
        "177a0ba8eb7bfad696cb80b3f91fccea48c329954c348a50da8da4ffa6910376",
    ("zaya", "mixed", "fold"):
        "9931026bca1fdd9a69dfdd61bbe26300f3e30e8e222dca8e40bb93de76d63049",
    ("zaya", "mixed", "pallas"):
        "4b3d216473b5d6e37c4a31345bbf5b46733e1bb67df34a7b992fd0bd4263a15d",
}


@pytest.mark.parametrize("model_type,kind,attn", list(LOWERED_BEFORE))
def test_other_families_step_programs_lower_as_before(model_type, kind,
                                                      attn):
    import hashlib
    from functools import partial

    from test_family import init_params as family_init, tiny_config

    c = tiny_config(model_type)
    f = c.family
    S, W, SEQ = 4, 8, 64
    cache = jax.eval_shape(lambda: PagedKVCache.create(
        c, S, 16, 4, SEQ, dtype=jnp.float32, width=W))
    params = jax.eval_shape(partial(family_init, c))
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
    assert digest == LOWERED_BEFORE[model_type, kind, attn]
