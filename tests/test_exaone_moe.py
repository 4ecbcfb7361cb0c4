"""K-EXAONE (`exaone_moe`) at a tiny size on seeded weights: the served
path (mixed-step prefill in windows, decode through the pages, decode
rows beside prefilling ones, contexts that wrap the sliding layers' ring
several times, rows of unequal length in one step) against the plain
float32 reference's full forward; the banded kernels in interpret mode
against the fold and against exact attention, sliding and full, decode
and mixed, over a ring and over a whole table; the ring's inequality at
the cell's sizes; the shares of a sparse layer; what the config class
and the family refuse; and the engine around them.

Layers `S S S F` twice, 8 heads of 16 over 2 K/V heads, a window of 6
keys over pages of 4 (a band starts inside a page), windows of 12, so
R = 6 ring pages and a context of 70 wraps the ring three times."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import load_config_dict
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import (
    PagedKVCache, WindowedKVCache, mixed_token_buckets, paged_attention,
    paged_attention_mixed, ring_holds, write_token_rows,
)
from cake_tpu.models.moe import exaone_moe as ex
from cake_tpu.models.moe.config import ExaoneMoeConfig
from cake_tpu.models.moe.params import init_params
from cake_tpu.models.reference import exaone_moe as ref
from cake_tpu.ops import moe as moe_ops

ROOT = os.path.join(os.path.dirname(__file__), "..")
CONFIG_DIR = os.path.join(ROOT, "benchmarks", "configs",
                          "k-exaone-236b-int8-share8")
B, C, PAGE, MAX_SEQ = 4, 12, 4, 96
# float32 on both sides; eight layers of sums in another order leave a
# few 1e-5 of logits that span ~3
ATOL = 5e-5


def ref_config(c, **over):
    return dict(ex.reference_config(c), **over)


def ref_params(params, c):
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": ex.dequantized(params["lm_head"]),
            "layers": list(ex.reference_layers(params["blocks"], c))}


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
    c = ExaoneMoeConfig.tiny_exaone()
    return c, seeded(c), RopeTables.create(c, MAX_SEQ)


def fresh_cache(c):
    cache = PagedKVCache.create(c, B, 1 + B * (MAX_SEQ // PAGE), PAGE,
                                MAX_SEQ, dtype=jnp.float32, width=C)
    table = np.stack([1 + b * (MAX_SEQ // PAGE) + np.arange(MAX_SEQ // PAGE)
                      for b in range(B)]).astype(np.int32)
    return cache._replace(table=jnp.asarray(table))


def mixed(model, cache, toks, pos, qlen, attn="fold", trunk=None):
    c, params, rope = model
    return jax.jit(trunk or ex.mixed_trunk, static_argnames=(
        "config", "attn", "n_tokens"))(
        params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(qlen),
        jnp.asarray(qlen > 0), cache, rope, config=c, attn=attn,
        n_tokens=mixed_token_buckets(B, C, (1,))[-1])


def decode(model, cache, toks, pos, active, attn="fold"):
    c, params, rope = model
    return jax.jit(ex.decode_trunk, static_argnames=("config", "attn"))(
        params, jnp.asarray(toks), cache, jnp.asarray(pos),
        jnp.asarray(active), rope, config=c, attn=attn)


def serve(model, sequences, prompts, company=True, cache=None, rows=None,
          attn="fold", trunk=None):
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
    total = np.zeros(len(ex.COUNTERS))
    head = ex.dequantized(params["lm_head"])
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
        out, plan = mixed(model, cache, toks, pos, qlen, attn, trunk)
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
    rng = np.random.default_rng(1)
    # 70 + 8: the ring of 6 pages x 4 wraps three times; 9 is shorter
    # than a window and, decoding, rides the long rows' mixed steps; 5
    # is shorter than the sliding window itself
    prompts = (53, 9, 70, 5)
    return [rng.integers(0, model[0].vocab_size, p + 8)
            for p in prompts], prompts


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


# -- the served path against the reference -------------------------------------


@pytest.mark.parametrize("row", [0, 1, 2, 3])
def test_served_path_matches_the_reference_forward(
        served_run, reference_run, traffic, row):
    """Prefill in windows of 12, then decode through the pages and the
    ring, rows of unequal length in one step (decode rows beside a
    prefilling one): every position's logits."""
    got, want = served_run[0][row], reference_run[0][row]
    assert sorted(got) == list(range(len(traffic[0][row])))
    for position, logits in got.items():
        np.testing.assert_allclose(logits, want[position], atol=ATOL,
                                   err_msg=f"position {position}")


def test_the_ring_wrapped_and_holds_a_rows_last_pages(model, served_run):
    """After 78 tokens a row's ring of 6 pages holds logical pages
    14 .. 19 (19 = 77 // 4) and nothing older, and the full layers'
    pool every page."""
    c, *_ = model
    cache = served_run[1]
    assert isinstance(cache, WindowedKVCache) and cache.ring_pages == 6
    assert cache.wk.shape == (6, B * 6, PAGE, 2 * 16)
    assert cache.k.shape[0] == 2
    assert np.array_equal(np.asarray(cache.wtable),
                          np.arange(B * 6).reshape(B, 6))


def test_the_kernels_serve_what_the_folds_serve(model, traffic, served_run):
    """attn="pallas" (both kernels interpreted, banded in the sliding
    layers) against attn="fold"."""
    sequences, prompts = traffic
    got, *_ = serve(model, sequences[:2], prompts[:2], attn="pallas")
    for row in range(2):
        for position, logits in got[row].items():
            np.testing.assert_allclose(
                logits, served_run[0][row][position], atol=2e-5)


def test_a_window_in_entries_is_the_window(model, traffic, served_run,
                                           monkeypatch):
    """The window handed to the mixed kernel as 3 entries of 4 queries
    (what 64 heads of 128 force at 512: `query_tile`) serves what one
    entry of 12 serves."""
    monkeypatch.setattr(ex, "query_tile", lambda *a: 4)
    sequences, prompts = traffic

    def tiled(*a, **kw):
        return ex.mixed_trunk(*a, **kw)

    for attn in ("fold", "pallas"):
        got, *_ = serve(model, sequences[2:3], prompts[2:3], attn=attn,
                        trunk=tiled, rows=[2])
        for position, logits in got[0].items():
            np.testing.assert_allclose(
                logits, served_run[0][2][position], atol=2e-5)


def test_query_tile_at_the_published_heads():
    """64 heads of 128 over 128-token bf16 pages: 64 queries an entry
    (11.5 MiB by the kernel's own count; 128 asks 22), so a 512-token
    window is 8 entries; Mistral's 32 heads take 128."""
    assert ex.query_tile(512, 64, 8, 128, 128, 2, 2) == 64
    assert ex.query_tile(512, 32, 8, 128, 128, 2, 2) == 128
    assert ex.query_tile(12, 8, 2, 16, 4, 4, 4) == 12


@pytest.mark.parametrize("altered", [
    dict(sliding_window=5), dict(sliding_window=7), dict(rope_in_full=True),
    dict(qk_norm=False), dict(softmax_dtype="bfloat16"), "no_bias",
    "window_everywhere"])
def test_an_altered_reference_is_another_model(model, traffic, served_run,
                                               altered):
    """What chip_compare.py holds to fail on the chip, here at float32
    where nothing hides it: each altered reference leaves the served
    path's tolerance tenfold or more."""
    c, params, _ = model
    seq = traffic[0][2]
    p = ref_params(params, c)
    kw = {}
    if altered == "no_bias":
        p["layers"] = [dict(lp, router_bias=0 * lp["router_bias"])
                       if "router_bias" in lp else lp for lp in p["layers"]]
    elif altered == "window_everywhere":
        p["layers"] = [dict(lp, kind="sliding") for lp in p["layers"]]
    else:
        kw = altered
    logits = np.asarray(ref.forward(p, [seq], ref_config(c, **kw))[0])
    apart = max(float(np.abs(logits[q] - got).max())
                for q, got in served_run[0][2].items())
    assert apart > 10 * ATOL, apart


def test_counters_count_the_keys_and_the_pages(served_run, traffic):
    """Keys visible and attended by kind of layer, over every token;
    the pages a single-token row walks: the band's in six sliding
    layers, every live page in two full ones; all experts held, so
    moe_rows == moe_rows_routed."""
    sequences, prompts = traffic
    total = dict(zip(ex.COUNTERS, served_run[2]))
    tokens = sum(len(s) for s in sequences)
    visible = sum(sum(range(1, len(s) + 1)) for s in sequences)
    attended = sum(sum(min(t + 1, 6) for t in range(len(s)))
                   for s in sequences)
    assert total["swa_keys_visible"] == 6 * visible
    assert total["swa_keys_attended"] == 6 * attended
    assert total["gqa_full_keys_attended"] == 2 * visible
    # single-token rows: every decode position, and a prompt's last
    # window where that is one token
    single = [t for s, p in zip(sequences, prompts)
              for t in list(range(p, len(s))) + ([p - 1] if p % C == 1
                                                 else [])]
    assert total["gqa_rows_single"] == len(single)
    assert total["gqa_window_keys_single"] == 6 * sum(
        min(t + 1, 6) for t in single)
    assert total["gqa_full_keys_single"] == 2 * sum(t + 1 for t in single)
    assert total["gqa_full_pages_walked"] == 2 * sum(
        t // PAGE + 1 for t in single)
    assert total["gqa_window_pages_walked"] == 6 * sum(
        t // PAGE - max(t - 5, 0) // PAGE + 1 for t in single)
    assert total["gqa_ring_pages_live"] <= total["gqa_full_pages_live"]
    assert total["moe_rows_routed"] == tokens * 2 * 7 == total["moe_rows"]


@pytest.mark.parametrize("kind", ["window", "single_token"])
def test_a_rows_bits_do_not_depend_on_its_company(model, traffic, kind):
    """One packed size: a prompt's window, and a decoding row's token,
    give the same bits with the other rows busy or idle."""
    sequences, prompts = traffic
    if kind == "window":
        alone, *_ = serve(model, sequences[:1], prompts[:1])
        busy, *_ = serve(model, sequences[:3], prompts[:3])
    else:
        alone, *_ = serve(model, sequences[1:2], prompts[1:2], rows=[1])
        busy, *_ = serve(model, sequences[:3], prompts[:3])
        busy = busy[1:]
    for position, logits in alone[0].items():
        assert np.array_equal(logits, busy[0][position]), position


@pytest.mark.parametrize("prompt", [5, 12, 30])
def test_a_reused_slot_gives_the_request_what_it_gets_alone(model, traffic,
                                                            served_run,
                                                            prompt):
    """A request in a slot whose ring and pages another left full of
    its keys: the stale slots lie ahead of every query or outside its
    band, so nothing of them is read."""
    rng = np.random.default_rng(prompt)
    seq = rng.integers(0, model[0].vocab_size, prompt + 4)
    fresh, *_ = serve(model, [seq], [prompt], rows=[2])
    reused, *_ = serve(model, [seq], [prompt], rows=[2],
                       cache=served_run[1])
    for position, logits in fresh[0].items():
        assert np.array_equal(logits, reused[0][position]), position


# -- the banded kernels ---------------------------------------------------------


def exact_attention(q, keys, vals, pos0, window):
    """q [C, H, hd] at positions pos0 + i over keys / vals [S, KV, hd]
    in float64: softmax over the band (or every key <= the query)."""
    Cq, H, hd = q.shape
    G = H // keys.shape[1]
    out = np.zeros((Cq, H, hd))
    for i in range(Cq):
        t = pos0 + i
        lo = max(0, t - window + 1) if window else 0
        for h in range(H):
            k, v = keys[lo:t + 1, h // G], vals[lo:t + 1, h // G]
            s = k @ q[i, h] / np.sqrt(hd)
            p = np.exp(s - s.max())
            out[i, h] = (p / p.sum()) @ v
    return out


@pytest.mark.parametrize("window,ring", [
    (6, True), (8, True), (11, True), (16, True), (6, False), (11, False),
    (None, False)])
def test_banded_kernels_are_exact_attention(window, ring):
    """Both kernels (interpreted) and the fold, with a band that starts
    inside a page (6, 11 over pages of 8), one that is a whole page (8)
    and two (16), over a ring that wraps and over a whole table, rows
    shorter than the window, windows of unequal length in one call, and
    a decode call after every window; and with no band."""
    P, KV, hd, H, Cq, Bq, S = 8, 2, 16, 8, 12, 3, 70
    R = (-(-(window - 1 + Cq) // P) + 1) if ring else 10
    rng = np.random.default_rng(window or 0)
    keys = rng.normal(size=(Bq, S, KV, hd)).astype(np.float32)
    vals = rng.normal(size=(Bq, S, KV, hd)).astype(np.float32)
    table = jnp.arange(Bq * R, dtype=jnp.int32).reshape(Bq, R)
    pk = jnp.zeros((2, Bq * R, P, KV * hd))
    pv = jnp.zeros((2, Bq * R, P, KV * hd))
    layer, done = 1, np.zeros(Bq, int)
    lens = [[12, 12, 12, 5, 12, 12], [3, 12, 7, 12, 12, 12],
            [12, 1, 1, 12, 12, 12]]
    for w in range(6):
        qlen = np.array([lens[b][w] for b in range(Bq)])
        q = rng.normal(size=(Bq, Cq, H, hd)).astype(np.float32)
        for b in range(Bq):
            at = done[b] + np.arange(qlen[b])
            for pool, src in ((0, keys), (1, vals)):
                new = write_token_rows(
                    (pk, pv)[pool], layer,
                    jnp.asarray(src[b, at].reshape(len(at), -1)),
                    jnp.full((len(at),), b), jnp.asarray(at),
                    jnp.ones(len(at), bool), table, ring=ring)
                pk, pv = (new, pv) if pool == 0 else (pk, new)
        for impl in ("fold", "pallas"):
            out = paged_attention_mixed(
                jnp.asarray(q), pk, pv, layer, table,
                jnp.asarray(done, jnp.int32), jnp.asarray(qlen, jnp.int32),
                impl=impl, window=window)
            for b in range(Bq):
                want = exact_attention(q[b, :qlen[b]], keys[b], vals[b],
                                       done[b], window)
                np.testing.assert_allclose(out[b, :qlen[b]], want,
                                           atol=1e-5, err_msg=f"{impl} {w}")
        done += qlen
        qd = rng.normal(size=(Bq, 1, H, hd)).astype(np.float32)
        for impl in ("fold", "pallas"):
            out = paged_attention(jnp.asarray(qd), pk, pv, layer, table,
                                  jnp.asarray(done - 1, jnp.int32),
                                  impl=impl, window=window)
            for b in range(Bq):
                want = exact_attention(qd[b], keys[b], vals[b], done[b] - 1,
                                       window)
                np.testing.assert_allclose(out[b], want, atol=1e-5,
                                           err_msg=f"{impl} decode {w}")


def test_a_row_with_no_token_walks_no_page():
    """pos -1 (an idle row, the window's row in the decode kernel's
    call): zeros, banded or not, kernel and fold."""
    rng = np.random.default_rng(3)
    pk = jnp.asarray(rng.normal(size=(1, 6, 4, 32)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 1, 8, 16)), jnp.float32)
    table = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)
    for window in (None, 6):
        for impl in ("fold", "pallas"):
            out = paged_attention(q, pk, pk, 0, table,
                                  jnp.asarray([-1, 5], jnp.int32), impl=impl,
                                  window=window)
            assert not np.asarray(out[0]).any()
            assert np.asarray(out[1]).any()


# -- the ring -------------------------------------------------------------------


@pytest.mark.parametrize("window,width,page", [(128, 512, 128), (6, 12, 4),
                                               (128, 64, 128)])
def test_ring_holds_every_key_a_dispatch_needs(window, width, page):
    """R = ceil((window - 1 + width) / page) + 1 (6 at the cell's 128 /
    512 / 128) holds for every start and every n_written <= width."""
    c = ExaoneMoeConfig.tiny_exaone(sliding_window_size=window)
    R = c.window_ring_pages(page, width)
    assert R == -(-(window - 1 + width) // page) + 1
    if (window, width, page) == (128, 512, 128):
        assert R == 6
    starts = range(0, 4 * R * page, max(1, page // 4))
    assert all(ring_holds(page, R, window, s, n)
               for s in starts for n in (1, width // 2, width))
    # (the count is a bound: at the cell's sizes one page fewer fails,
    # two fewer fail at any)
    fewer = R - 1 if (window, width, page) == (128, 512, 128) else R - 2
    assert not all(ring_holds(page, fewer, window, s, width)
                   for s in range(0, 2 * R * page))


def test_the_cache_is_pools_by_kind_of_layer():
    c = ExaoneMoeConfig.tiny_exaone()
    cache = PagedKVCache.create(c, 3, 20, 4, 48, dtype=jnp.float32, width=12)
    assert isinstance(cache, WindowedKVCache)
    assert cache.k.shape == cache.v.shape == (2, 20, 4, 32)
    assert cache.wk.shape == cache.wv.shape == (6, 3 * 6, 4, 32)
    assert cache.table.shape == (3, 12) and cache.wtable.shape == (3, 6)
    assert (cache.page_size, cache.n_pages, cache.max_pages,
            cache.max_seq_len, cache.ring_pages) == (4, 20, 12, 48, 6)
    assert cache.memory_bytes() == 2 * 2 * 20 * 4 * 32 * 4
    assert cache.beside_bytes() == 2 * 6 * 18 * 4 * 32 * 4
    with pytest.raises(ValueError, match="width"):
        PagedKVCache.create(c, 3, 20, 4, 48)


# -- the shares of a sparse layer ------------------------------------------------


@pytest.mark.parametrize("side", ["reference", "served"])
def test_eight_shares_are_the_uncut_layer(side):
    """The cell's cut at a test's size: 8 shares of 2 of 16 experts
    each, the shared expert counted once, add up to what the uncut
    layer gives."""
    c = ExaoneMoeConfig.tiny_exaone(num_local_experts=16,
                                    n_routed_experts_total=16,
                                    num_experts_per_tok=4)
    lp = list(ex.reference_layers(seeded(c, 3)["blocks"], c))[1]
    h = jax.random.normal(jax.random.PRNGKey(9), (40, c.hidden_size))
    cfg = ref_config(c)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_ffn(lp, h, cfg)
        parts = ref.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        for share in range(8):
            held = {k: (v[2 * share:2 * share + 2] if k.startswith("we_")
                        else v) for k, v in lp.items()}
            if side == "reference":
                parts = parts + ref.moe_ffn(held, h, cfg,
                                            held=(2 * share, 2),
                                            shared=False)
            else:
                routed = {k: v for k, v in held.items()
                          if k in ("router", "router_bias", "we_gate",
                                   "we_up", "we_down")}
                out, stats = moe_ops.moe_mlp(
                    routed, h[None], 4, True, first_expert=2 * share,
                    scoring="sigmoid", scale=2.5)
                parts = parts + out[0]
                assert float(stats.rows_routed) == 40 * 4
    np.testing.assert_allclose(parts, whole, atol=2e-5)


# -- the config class ----------------------------------------------------------


def test_published_config_parses():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        raw = json.load(f)
    c = load_config_dict(raw)
    assert isinstance(c, ExaoneMoeConfig)
    assert c.indexer_types == ("sliding", "sliding", "sliding", "full") * 3
    assert c.full_layers == (3, 7, 11) and len(c.sliding_layers) == 9
    assert c.sparse_layers == tuple(range(1, 12))
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, c.rope_dim) == (6144, 64, 8, 128, 128)
    assert c.sliding_window_size == 128 and c.sliding_window is None
    assert c.window_ring_pages(128, 512) == 6
    assert (c.num_local_experts, c.n_routed_experts_total,
            c.num_experts_per_tok, c.n_group, c.topk_group) == (
        16, 128, 8, 1, 1)
    assert (c.moe_intermediate_size, c.intermediate_size,
            c.n_shared_experts) == (2048, 18432, 1)
    assert c.routed_scaling_factor == 2.5 and c.norm_topk_prob
    assert c.scoring_func == "sigmoid" and c.rope_theta == 1e6
    assert c.vocab_size == 19200 and c.eos_token_ids == (19200,)
    assert c.chat_template == "chatml" and c.family.name == "exaone_moe"


RAW = dict(
    model_type="exaone_moe", vocab_size=64, hidden_size=32,
    intermediate_size=48, num_hidden_layers=4, first_k_dense_replace=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    sliding_window=6, sliding_windows=[6, 6, 6, 0],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
    moe_intermediate_size=16, num_experts=4, num_experts_total=16,
    num_experts_per_tok=2, num_shared_experts=1, routed_scaling_factor=2.5,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"})


@pytest.mark.parametrize("key,value", [
    ("num_nextn_predict_layers", 1),
    ("layer_types", ["sliding_attention"] * 3 + ["chunked_attention"]),
    ("layer_types", ["sliding_attention"] * 3),
    ("sliding_windows", [6, 6, 6, 6]), ("sliding_windows", [6, 6, 4, 0]),
    ("mlp_layer_types", ["dense", "sparse", "latent", "sparse"]),
    ("first_routed_expert", 14), ("hidden_act", "gelu"),
    ("scoring_func", "softmax"), ("n_group", 4), ("topk_group", 2),
    ("attention_bias", True),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
    ("num_shared_experts", 0), ("sliding_window", 0)])
def test_what_is_not_implemented_is_refused(key, value):
    c = load_config_dict(RAW)
    assert c.head_dim == 16 and c.rope_theta == 1e6
    named = {"first_routed_expert": "not among the router",
             "rope_parameters": "rope_type"}.get(key, key)
    with pytest.raises(ValueError, match=named):
        load_config_dict(dict(RAW, **{key: value}))


def test_the_checkpoint_loader_says_what_it_cannot_name():
    from cake_tpu.models.moe.params import hf_layout
    with pytest.raises(NotImplementedError, match="exaone_moe"):
        hf_layout(ExaoneMoeConfig.tiny_exaone())


@pytest.mark.parametrize("asked,named", [
    ("--kv-pages", "--kv-pages"), ("topology", "topology"),
    ("--spec-draft", "--spec-draft"),
    ("--kv-dtype", "--kv-dtype"), ("--kv-host-pages", "--kv-host-pages"),
    ("--disagg", "--disagg"), ("--auto-prefix", "--auto-prefix")])
def test_family_refuses_by_name_beside_a_ring(asked, named):
    """Prefix pages, spill, speculation and the rest beside a K/V ring,
    through family.refusal: one sentence that names the option."""
    said = ex.FAMILY.refusal({asked: True})
    assert "exaone_moe" in said and named in said and "K/V ring" in said
    assert ex.FAMILY.refusal({asked: False}) is None
    assert not ex.FAMILY.moves("register_prefix")


# -- the engine ----------------------------------------------------------------


def make_engine(**kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    c = ExaoneMoeConfig.tiny_exaone(vocab_size=300, eos_token_ids=(300,))
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
    before = {k: s.value for k, s in obs_steps.GQA_WINDOW_COUNTERS}
    with eng:
        handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for h in handles:
            assert h.wait(180)
        records = eng.flight.dump()
    after = {k: s.value for k, s in obs_steps.GQA_WINDOW_COUNTERS}
    return (c, params, prompts, [h.token_ids for h in handles], records,
            {k: after[k] - before[k] for k in after}, eng)


@pytest.mark.parametrize("request_index", range(6))
def test_engine_serves_the_references_greedy_tokens(engine_run,
                                                    request_index):
    """Through submit -> _do_mixed -> the in-flight decode step: four
    requests over four rows and two behind them in REUSED slots (and
    reused rings), prompts of 1 to 6 windows. Teacher-forced: the
    reference's forward over the prompt and the tokens the engine gave
    must choose each of them."""
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
        assert r["impl"] == "paged-swa-fold"
    counted = [r for r in records if "gqa_rows_single" in r]
    assert counted and all("dsa_keys_visible" not in r for r in records)
    for r in counted:
        assert r["swa_layers"] % 6 == 0               # x 6 sliding layers
        assert r["swa_keys_attended"] <= r["swa_keys_visible"]
        assert r["gqa_ring_pages_live"] <= r["gqa_full_pages_live"]
    assert moved["gqa_rows_single"] >= 6 * 9
    assert eng._mixed_buckets == (16,) and not eng._prefix_capable
    # the decode records' pages are the FULL layers' walk, a layer
    decode = [r for r in records if r["kind"] == "decode"
              and "attn_pages" in r]
    assert decode


def test_metrics_carry_the_ring(engine_run):
    from cake_tpu.obs import steps as obs_steps
    *_, eng = engine_run
    assert (obs_steps.GQA_WINDOW_POOL_BYTES.value
            == eng.cache.window_bytes() > 0)
    assert ex.COUNTERS[-8:] == tuple(
        k for k, _ in obs_steps.GQA_WINDOW_COUNTERS)
    assert eng.flight._counters == ex.COUNTERS
    # 4 slots x R = ceil((6 - 1 + 12) / 8) + 1 = 4 ring pages
    assert eng.cache.ring_pages == 4 and eng.cache.wk.shape[1] == 16


@pytest.mark.parametrize("refused,named", [
    (dict(kv_pages=None), "--kv-pages"),
    (dict(step_fns=(print, print)), "topology"),
    (dict(kv_dtype="int8"), "--kv-dtype"),
    (dict(kv_host_pages=8), "--kv-host-pages"),
    (dict(auto_prefix_system=True), "--auto-prefix"),
    (dict(disagg="prefill"), "--disagg")])
def test_engine_refuses_by_name_what_a_ring_does_not_serve(refused, named):
    with pytest.raises(ValueError) as e:
        make_engine(**refused)
    assert "exaone_moe" in str(e.value) and named in str(e.value)
    assert "K/V ring" in str(e.value)


def test_prefix_registration_is_refused_by_name():
    *_, eng = make_engine()
    with pytest.raises(ValueError, match="K/V ring"):
        eng.register_prefix([5, 6, 7, 8, 9, 10, 11, 12, 13])
