"""DeepSeek-V2 (`deepseek_v2`) at a tiny size on seeded weights: the
served path (mixed-step prefill in windows, decode through the latent
page pool, decode rows beside prefilling ones) against the plain float32
reference's full forward; the pieces one by one (the page-walking
kernel against its fold, the group-limited rule against a loop, YaRN
against its formulas, the shares of a sparse layer); and the engine
around them.

The trained length is 16 and the contexts run to 60 tokens, so YaRN's
blend is several times past it; 16 experts in 4 groups of which 2 are
taken."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import load_config_dict
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import PagedKVCache, mixed_token_buckets
from cake_tpu.models.moe import glm_dsa
from cake_tpu.models.moe.config import DeepseekV2Config
from cake_tpu.models.moe.params import init_params
from cake_tpu.models.reference import deepseek_v2 as ref
from cake_tpu.ops import mla_attention as mla
from cake_tpu.ops import moe as moe_ops
from cake_tpu.ops import rope as rope_ops
from cake_tpu.ops.quant import QTensor

ROOT = os.path.join(os.path.dirname(__file__), "..")
CONFIG_DIR = os.path.join(ROOT, "benchmarks", "configs",
                          "deepseek-v2-int8-share8")
B, C, PAGE, MAX_SEQ = 4, 8, 8, 64
REF_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rms_norm_eps", "rope_theta", "n_group",
            "topk_group", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor")


def ref_config(c, **over):
    y = c.rope_scaling
    scaling = y and {
        "factor": y.factor, "original_max_position_embeddings": y.original,
        "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
        "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim}
    return dict({k: getattr(c, k) for k in REF_KEYS}, rope_scaling=scaling,
                **over)


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
    c = DeepseekV2Config.tiny_dsv2()
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
    {position: logits} and the counters' sum."""
    c, params, rope = model
    T = mixed_token_buckets(B, C, (1,))[-1]
    cache = fresh_cache(c)
    off = [0] * len(sequences)
    got = [dict() for _ in sequences]
    total = np.zeros(len(glm_dsa.DENSE_COUNTERS))
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
            elif company and prompts[b] <= off[b] < len(seq):
                n = 1
            else:
                continue
            toks[b, :n], pos[b], qlen[b] = seq[off[b]:off[b] + n], off[b], n
        out, plan = mixed(params, jnp.asarray(toks), jnp.asarray(pos),
                          jnp.asarray(qlen), jnp.asarray(qlen > 0), cache,
                          rope, config=c, attn=attn, n_tokens=T)
        cache = out.cache
        total += np.asarray(out.counters)
        logits = np.asarray(out.x @ head)
        for b in np.flatnonzero(qlen):
            for j in range(qlen[b]):
                got[b][off[b] + j] = logits[int(plan.start[b]) + j]
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
        logits = np.asarray(out.x @ head)
        for b in np.flatnonzero(active):
            got[b][off[b]] = logits[b]
            off[b] += 1
    return got, total


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
    beside prefilling ones: every position's logits."""
    got, want = served_run[0][row], reference_run[0][row]
    assert sorted(got) == list(range(len(traffic[0][row])))
    for position, logits in got.items():
        np.testing.assert_allclose(logits, want[position], atol=3e-5,
                                   err_msg=f"position {position}")


def test_the_kernels_serve_what_the_folds_serve(model, traffic, served_run):
    """attn="pallas" (both kernels interpreted) against attn="fold"."""
    sequences, prompts = traffic
    got, total = serve(model, sequences[:2], prompts[:2], attn="pallas")
    fold, _ = serve(model, sequences[:2], prompts[:2])
    for row in range(2):
        for position, logits in got[row].items():
            np.testing.assert_allclose(logits, fold[row][position],
                                       atol=2e-5)


@pytest.mark.parametrize("switch", [
    dict(softmax_dtype="bfloat16"), dict(mscale_in_scale=False),
    dict(yarn=False), dict(group_limited=False), dict(norm_topk_prob=True),
    dict(routed_scaling_factor=1.0)])
def test_every_part_of_the_layer_moves_the_logits(model, traffic,
                                                  reference_run, switch):
    """Each switch of the reference is another model: the logits move
    by far more than the served path's distance from the reference."""
    c, params, _ = model
    moved = np.asarray(ref.forward(ref_params(params, c), traffic[0][2],
                                   ref_config(c, **switch)))
    assert np.abs(moved - reference_run[0][2])[20:].max() > 1e-3


def test_counters_count_the_keys_and_the_groups(served_run, traffic,
                                                reference_run, model):
    """mla_keys_attended: position + 1 over the single-token rows x 4
    layers; moe_rows_routed: every real token x 3 experts x 3 sparse
    layers; all experts held, so every token's groups include the held
    ones and moe_rows == moe_rows_routed."""
    c = model[0]
    sequences, prompts = traffic
    _, total = served_run
    tokens = sum(len(s) for s in sequences)
    # (a prompt's last window of ONE token is a single-token row too)
    single = sum(sum(range(p + 1, len(s) + 1)) + (p if p % C == 1 else 0)
                 for s, p in zip(sequences, prompts))
    names = glm_dsa.DENSE_COUNTERS
    assert names[-1] == "mla_keys_attended" and len(total) == 8
    assert total[names.index("mla_keys_attended")] == 4 * single
    assert total[names.index("moe_rows_routed")] == tokens * 3 * 3
    assert total[names.index("moe_rows")] == tokens * 3 * 3
    assert total[names.index("moe_tokens_group_held")] == 0   # no share


@pytest.mark.parametrize("kind", ["window", "single_token"])
def test_a_rows_bits_do_not_depend_on_its_company(model, traffic, kind):
    """Served alone or beside two other rows, the same program gives a
    window's logits and a row's single token the same bits."""
    sequences, prompts = traffic
    alone, _ = serve(model, sequences[:1], prompts[:1], company=False)
    amid, _ = serve(model, sequences, prompts)
    positions = ([7, 15, 31, 36] if kind == "window"
                 else list(range(37, 45)))
    for position in positions:
        assert np.array_equal(alone[0][position], amid[0][position])


# -- the page-walking kernel ------------------------------------------------


def _pool_case(dtype=jnp.float32):
    rng = np.random.default_rng(3)
    L, N, P, W, r, rows, H = 2, 14, 8, 24, 16, 5, 4
    pool = jnp.asarray(rng.standard_normal((L, N, P, W)), dtype)
    q = jnp.asarray(rng.standard_normal((rows, H, W)), dtype)
    table = np.full((rows, 6), -1, np.int32)
    table[0, :3] = [1, 2, 3]                 # its last page cut mid-page
    table[1, :5] = [4, -1, 5, 6, 7]          # a hole inside the live range
    table[3, :2] = [8, 9]                    # its last page exactly full
    table[4, :6] = [10, 11, 12, 13, 1, 2]    # every page of the table
    pos = np.asarray([20, 36, -1, 15, 47], np.int32)     # row 2 idle
    return pool, q, table, pos, r


def _dense_attention(pool, q, table, pos, r, layer, scale):
    out = np.zeros((q.shape[0], q.shape[1], r), np.float32)
    P = pool.shape[2]
    for b in range(q.shape[0]):
        keys = [np.asarray(pool[layer, table[b, s // P], s % P], np.float32)
                for s in range(pos[b] + 1) if table[b, s // P] >= 0]
        if not keys:
            continue
        keys = np.stack(keys)
        s = np.asarray(q[b], np.float32) @ keys.T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (p / p.sum(-1, keepdims=True)) @ keys[:, :r]
    return out


@pytest.mark.parametrize("impl", ["fold", "pallas"])
def test_page_walk_attends_every_visible_key(impl):
    """Rows with a hole, an idle row, a last page cut mid-page, a full
    table: the kernel (interpreted) and its fold against plain softmax
    attention over the row's visible keys."""
    pool, q, table, pos, r = _pool_case()
    got = mla.attend_pages(q, pool, 1, jnp.asarray(table), jnp.asarray(pos),
                           r, 0.3, impl)
    want = _dense_attention(pool, q, table, pos, r, 1, 0.3)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert not np.asarray(got[2]).any()                   # the idle row


def test_page_walk_kernel_is_its_fold_in_bfloat16():
    pool, q, table, pos, r = _pool_case(jnp.bfloat16)
    args = (q, pool, 0, jnp.asarray(table), jnp.asarray(pos), r, 0.3)
    fold = mla.attend_pages(*args, "fold").astype(jnp.float32)
    kernel = mla.attend_pages(*args, "pallas").astype(jnp.float32)
    np.testing.assert_allclose(kernel, fold, atol=2e-2)


def test_both_decode_kernels_walk_their_pages_by_one_helper():
    """The latent kernel has no page walk of its own: the GQA decode
    kernel's (`rpa.walk_live_pages`), over a list of one pool."""
    import inspect

    from cake_tpu.ops import ragged_paged_attention as rpa
    for kernel in (rpa._decode_kernel, mla._pages_kernel):
        source = inspect.getsource(kernel)
        assert "walk_live_pages(" in source
        assert "next_live_row" not in source and "fori_loop" not in source


def test_page_walk_starts_no_trip_for_an_idle_call():
    pool, q, table, _, r = _pool_case()
    idle = jnp.full((5,), -1, jnp.int32)
    for impl in ("fold", "pallas"):
        out = mla.attend_pages(q, pool, 0, jnp.asarray(table), idle, r, 0.3,
                               impl)
        assert not np.asarray(out).any()


def test_ring_depth_keeps_a_mebibyte_ahead():
    """Slots of F pages: the one that folds and a MiB in flight."""
    assert mla.pages_ring_depth(128 * 640 * 2) == 8       # the cell's page
    assert mla.pages_ring_depth(4 * 128 * 640 * 2) == 3   # its trip of four
    assert mla.pages_ring_depth(2 * 128 * 640 * 2) == 5
    assert mla.pages_ring_depth(8 * 24 * 4) == 17         # a test's


def _walked_pages(block, q, pool, layer, table, pos, r, monkeypatch):
    """The interpreted kernel at `block` pages a fold (the rule
    replaced; the jitted wrapper caches on its static arguments, not on
    the module's globals, so the call goes under it)."""
    monkeypatch.setattr(mla, "decode_block", lambda *shapes: block)
    return mla._pages_pallas.__wrapped__(
        q, pool, jnp.int32(layer), jnp.asarray(table), jnp.asarray(pos),
        r=r, scale=0.3, interpret=True)


@pytest.mark.parametrize("block", [1, 2, 4])
def test_page_walk_folds_a_block_of_pages_an_update(block, monkeypatch):
    """_pool_case's rows at 1, 2 and 4 pages a fold, over a table of 6:
    a hole inside a block, an idle row, a last page cut mid-page, live
    counts no block divides (3, 5; at 4 a fold the full table's last
    trip passes the table's end), and two rows whose FIRST trip is
    holes alone (nothing visible yet: a probability of exactly 0, not
    exp(0)), one of them with nothing but holes."""
    pool, q, table, pos, r = _pool_case()
    table[2, :5] = [-1, -1, -1, -1, 3]
    table[3, :2] = -1
    pos[2] = 37
    got = _walked_pages(block, q, pool, 1, table, pos, r, monkeypatch)
    want = _dense_attention(pool, q, table, pos, r, 1, 0.3)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert np.asarray(got[2]).any() and not np.asarray(got[3]).any()
    idle = np.full(5, -1, np.int32)
    assert not np.asarray(_walked_pages(block, q, pool, 1, table, idle, r,
                                        monkeypatch)).any()


@pytest.mark.parametrize("block", [1, 2, 4])
def test_page_walk_kernel_is_its_fold_in_bfloat16_at_every_block(
        block, monkeypatch):
    pool, q, table, pos, r = _pool_case(jnp.bfloat16)
    kernel = _walked_pages(block, q, pool, 0, table, pos, r, monkeypatch)
    fold = mla.attend_pages(q, pool, 0, jnp.asarray(table), jnp.asarray(pos),
                            r, 0.3, "fold")
    np.testing.assert_allclose(kernel.astype(jnp.float32),
                               fold.astype(jnp.float32), atol=2e-2)


def test_decode_block_follows_bytes_not_names():
    """Pages a fold at the two cells' shapes (bfloat16, 128-token pages
    of a 640-wide row, 512-wide values): four, at a count well under
    what a kernel is granted unasked; fewer where whole blocks would pad
    a short table by more than an eighth, or where a trip's ring,
    scores and accumulator pass the plan."""
    from cake_tpu.ops import ragged_paged_attention as rpa
    for H, pages in ((128, 40), (32, 76)):      # DeepSeek-V2, Ling-3.0
        assert mla.decode_block(H, 640, 512, 128, pages, 2) == 4
        assert (mla.pages_vmem_bytes(H, 640, 512, 128, 2, 4)
                <= mla._PAGES_VMEM_PLAN == rpa._VMEM_SCOPED_LIMIT // 2)
    assert mla.pages_vmem_bytes(128, 640, 512, 128, 2, 4) == 3_735_552
    assert mla.decode_block(128, 640, 512, 128, 9, 2) == 2
    assert mla.decode_block(128, 640, 512, 128, 5, 2) == 1
    assert mla.decode_block(128, 640, 512, 128, 2, 2) == 2
    # pages of 512 tokens: a ring of two trips of four is 5 MiB and
    # their scores and probabilities 2.5, 8.6 MiB in all
    assert mla.decode_block(128, 640, 512, 512, 40, 2) == 2
    assert mla.decode_block(4, 24, 16, 8, 6, 4) == 2     # _pool_case's
    assert mla.pages_walk(4095, 128, 40, 4) == (32, 8)
    assert mla.pages_walk(4096, 128, 40, 4) == (33, 9)
    assert mla.pages_walk(9000, 128, 40, 4) == (40, 10)
    assert mla.pages_walk(-1, 128, 40, 4) == (0, 0)


@pytest.mark.parametrize("block", [1, 2, 4])
def test_host_counts_what_the_page_kernel_walks(monkeypatch, block):
    """pages_walk (what the engine writes into a record) against the
    interpreted kernel's own trips: every page copy it starts and every
    score product it runs, counted by callbacks from inside the
    kernel."""
    from jax.experimental.pallas import tpu as pltpu
    seen = {"copies": 0, "folds": 0}

    def tick(key):
        jax.debug.callback(lambda: seen.__setitem__(key, seen[key] + 1))

    class Copy:
        def __init__(self, *args):
            self.copy = make_copy(*args)

        def start(self):
            tick("copies")
            self.copy.start()

        def wait(self):
            self.copy.wait()

    def dot(a, b, *, trans_b):
        if trans_b:
            tick("folds")
        return rpa_dot(a, b, trans_b=trans_b)

    make_copy, rpa_dot = pltpu.make_async_copy, mla.rpa._dot
    monkeypatch.setattr(pltpu, "make_async_copy", Copy)
    monkeypatch.setattr(mla.rpa, "_dot", dot)
    pool, q, table, pos, r = _pool_case()
    table[1, 1] = 9              # the host knows no hole: none here
    _walked_pages(block, q, pool, 1, table, pos, r, monkeypatch)
    jax.effects_barrier()
    walked = [mla.pages_walk(int(p), 8, 6, block) for p in pos]
    assert [w[0] for w in walked] == [3, 5, 0, 2, 6]
    assert seen == {"copies": sum(w[0] for w in walked),
                    "folds": sum(w[1] for w in walked)}


def test_the_bench_tool_rehearses_and_reads_the_cells_shapes(capsys):
    """tools/mla_decode_attn_bench.py at tiny widths: one JSON line,
    the kernel at 1, 2 and 4 pages a fold against F = 1's result (that
    against the XLA fold) with what each walks, the rule left as it
    was; and the two cells' call shapes as it reads them from their
    files."""
    import importlib.util
    import pathlib

    path = pathlib.Path(ROOT).resolve() / "tools" / "mla_decode_attn_bench.py"
    spec = importlib.util.spec_from_file_location("mla_decode_attn_bench",
                                                  path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    rule = mla.decode_block
    assert tool.main(["--rehearse", "--calls", "2"]) == 0
    assert mla.decode_block is rule
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [c["block"] for c in line["cases"]] == [1, 2, 4]
    for case in line["cases"]:
        assert case["finite"] and case["err"] <= 1e-4
        assert case["pages"] == 19 and case["us"] > 0
    assert [c["folds"] for c in line["cases"]] == [19, 11, 7]
    shapes = {name: (c["H"], c["W"], c["r"], c["page"], c["table"],
                     len(c["pos"]), int((c["pos"] >= 0).sum()))
              for name, c in tool.cases(False).items()}
    assert shapes == {"dsv2": (128, 640, 512, 128, 40, 32, 30),
                      "ling3": (32, 640, 512, 128, 76, 32, 32)}


# -- the window kernel -------------------------------------------------------

WP, WPAGES = 8, 11           # a page, a table: at 4 pages a fold, 2 3/4 blocks


def _window_case(C_, last, hole=None, dtype=jnp.float32, seed=5):
    """A window of C_ queries that ends at `last` (the prompt's first
    window where it is shorter than C_: the tail of the queries is
    padding) over a table of WPAGES pages, `hole` unmapped."""
    rng = np.random.default_rng(seed)
    L, N, W, r, H = 2, 24, 24, 16, 4
    pool = jnp.asarray(rng.standard_normal((L, N, WP, W)), dtype)
    q = jnp.asarray(rng.standard_normal((C_, H, W)), dtype)
    table = rng.permutation(N)[:WPAGES].astype(np.int32)
    if hole is not None:
        table[hole] = -1
    positions = max(last - C_ + 1, 0) + np.arange(C_, dtype=np.int32)
    return pool, q, jnp.asarray(table), jnp.asarray(positions), r


def _window_pair(q, pool, table, bias, last, r, positions, scope="mla"):
    return [np.asarray(mla.attend_window(
        q, pool, 1, table, bias, jnp.int32(last), r, 0.3, impl=impl,
        scope=scope, positions=positions), np.float32)
        for impl in ("pallas", "fold")]


@pytest.mark.parametrize("masked_by", ["positions", "bias"])
@pytest.mark.parametrize("C_", [24, 32], ids=["tq8", "tq16"])
@pytest.mark.parametrize("last", [5, 15, 31, 32, 87], ids=[
    "inside_the_first_page", "a_pages_last_token", "a_blocks_last_token",
    "one_past_a_block", "the_tables_end"])
def test_window_kernel_is_its_fold(last, C_, masked_by):
    """cake_mla_window_attn (interpreted) against the XLA fold: the
    walk ends inside a page, on a page's end, on a block's end, one
    page into the next block and at the table's end (its last block is
    three pages of four), at both tile widths, causality read off the
    positions or handed as a bias array."""
    pool, q, table, positions, r = _window_case(C_, last)
    assert mla.window_tiles(C_, 4, 24, r, WP, WPAGES, 4, False) == (
        16 if C_ == 32 else 8, 4)
    bias = None
    if masked_by == "bias":
        bias = jnp.where(jnp.arange(WPAGES * WP)[None, :]
                         <= positions[:, None], 0.0, mla.NEG_INF)
        positions = None
    kernel, fold = _window_pair(q, pool, table, bias, last, r, positions)
    np.testing.assert_allclose(kernel, fold, atol=2e-6)
    assert np.abs(kernel).max() > 0.05


@pytest.mark.parametrize("masked_by", ["positions", "bias"])
def test_window_kernel_never_attends_an_unmapped_entry(masked_by):
    """A hole inside the live range: the kernel starts no copy for it
    and its columns are masked, whatever the caller's mask says of
    them; the queries whose every visible key lies in the hole (the
    hole is page 0 and they are the first page's) get zeros."""
    pool, q, table, positions, r = _window_case(24, 23, hole=0)
    bias = None
    if masked_by == "bias":
        bias, positions = jnp.zeros((24, WPAGES * WP), jnp.float32), None
    kernel, fold = _window_pair(q, pool, table, bias, 23, r, positions)
    np.testing.assert_allclose(kernel, fold, atol=2e-6)
    if masked_by == "positions":
        assert not kernel[:WP].any() and kernel[WP:].any(axis=(1, 2)).all()


def test_window_kernel_is_its_fold_in_bfloat16():
    pool, q, table, positions, r = _window_case(32, 70, dtype=jnp.bfloat16)
    kernel, fold = _window_pair(q, pool, table, None, 70, r, positions)
    np.testing.assert_allclose(kernel, fold, atol=2e-2)


def test_a_window_takes_a_bias_or_its_positions():
    pool, q, table, positions, r = _window_case(24, 40)
    with pytest.raises(ValueError, match="bias array or its positions"):
        mla.attend_window(q, pool, 1, table, None, jnp.int32(40), r, 0.3)


@pytest.mark.parametrize("last,C_", [(5, 24), (31, 24), (32, 32), (87, 32)])
def test_host_counts_what_the_window_kernel_walks(monkeypatch, last, C_):
    """window_walk (what the engine writes into a mixed record) against
    the interpreted kernel's own trips: every page copy it starts and
    every score product it runs, counted by callbacks from inside the
    kernel; and the pages it visits, read off the result (values that
    name their page, under a query of zeros)."""
    from jax.experimental.pallas import tpu as pltpu
    seen = {"copies": 0, "folds": 0}

    def tick(key):
        jax.debug.callback(lambda: seen.__setitem__(key, seen[key] + 1))

    class Copy:
        def __init__(self, *args):
            self.copy = make_copy(*args)

        def start(self):
            tick("copies")
            self.copy.start()

        def wait(self):
            self.copy.wait()

    def dot(a, b, *, trans_b):
        if trans_b:
            tick("folds")
        return rpa_dot(a, b, trans_b=trans_b)

    make_copy, rpa_dot = pltpu.make_async_copy, mla.rpa._dot
    monkeypatch.setattr(pltpu, "make_async_copy", Copy)
    monkeypatch.setattr(mla.rpa, "_dot", dot)
    pool, q, table, positions, r = _window_case(C_, last, seed=last)
    # page j of the row holds the value e_j
    named = np.zeros(pool.shape, np.float32)
    named[1, np.asarray(table), :, :r] = np.eye(r)[:WPAGES, None, :]
    out = np.asarray(mla._window_pallas.__wrapped__(
        jnp.zeros_like(q), jnp.asarray(named), jnp.int32(1), table, None,
        jnp.int32(last), positions, r=r, scale=0.3, interpret=True))
    jax.effects_barrier()
    tq, block = mla.window_tiles(C_, 4, 24, r, WP, WPAGES, 4, False)
    pages, folds = mla.window_walk(last, WP, WPAGES, block)
    tiles = C_ // tq
    assert seen == {"copies": tiles * pages, "folds": tiles * folds}
    # the last real query attends every key up to `last`, evenly
    at = int(np.flatnonzero(np.asarray(positions) == last)[0])
    want = np.zeros(r)
    want[:pages] = WP
    want[pages - 1] = last % WP + 1
    np.testing.assert_allclose(out[at, 0], want / (last + 1), atol=1e-6)


def test_window_tiles_follow_bytes_not_names():
    """tq and the pages a fold at the three cells' shapes (bfloat16,
    128-token pages, a 512-token window): four pages a fold under the
    plan the call hands the compiler, two where whole blocks of four
    would pad the table by more than an eighth (the 9-page ring)."""
    for H, W, r, pages, biased, tiles in (
            (128, 640, 512, 40, False, (8, 4)),
            (64, 640, 512, 100, True, (16, 4)),
            (128, 640, 512, 132, True, (8, 4)),
            (64, 1152, 1024, 9, True, (8, 2))):
        assert mla.window_tiles(512, H, W, r, 128, pages, 2, biased) == tiles
    assert mla.window_tiles(512, 128, 640, 512, 128, 2, 2, False) == (8, 2)
    assert mla.window_tiles(512, 128, 640, 512, 128, 5, 2, False) == (8, 1)
    # four 512-token pages of float32 scores and probabilities at
    # 1,024 rows are 20 MiB: past the plan beside the tile, so two
    assert mla.window_tiles(512, 128, 640, 512, 512, 40, 2, False) == (8, 2)
    assert mla.window_walk(4095, 128, 40, 4) == (32, 8)
    assert mla.window_walk(4096, 128, 40, 4) == (33, 9)
    assert mla.window_walk(9000, 128, 40, 4) == (40, 10)
    assert mla.window_walk(-1, 128, 40, 4) == (0, 0)


# -- the routing rule -------------------------------------------------------


def _loop_choice(probs, k, n_group, topk_group):
    """The published rule as a loop over tokens: group maxima, the
    best groups (ties to the lower index), zeros outside, the top k."""
    out = []
    for p in np.asarray(probs, np.float32):
        groups = p.reshape(n_group, -1).max(1)
        best = sorted(range(n_group), key=lambda g: (-groups[g], g))
        masked = np.where(np.isin(np.arange(p.size) // (p.size // n_group),
                                  best[:topk_group]), p, 0.0)
        out.append(sorted(range(p.size),
                          key=lambda e: (-masked[e], e))[:k])
    return np.asarray(out)


def test_group_limited_choice_is_the_published_loop():
    rng = np.random.default_rng(5)
    logits = rng.standard_normal((64, 160)).astype(np.float32)
    # ties: whole groups alike, and equal experts inside a group
    logits[:8, 20:40] = logits[:8, 0:20]
    logits[8:16, 5] = logits[8:16, 6] = 4.0
    logits[16:20] = 0.0
    weights, experts = moe_ops.choose(jnp.asarray(logits), 6, False,
                                      scale=16.0, n_group=8, topk_group=3)
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    want = _loop_choice(probs, 6, 8, 3)
    assert np.array_equal(np.asarray(experts), want)
    np.testing.assert_allclose(
        weights, 16.0 * np.take_along_axis(np.asarray(probs), want, 1),
        rtol=1e-6)
    # six experts out of at most three groups
    assert all(len(set(row // 20)) <= 3 for row in np.asarray(experts))
    # and the reference's router says the same
    _, _, own, groups = ref.router(
        {"router": jnp.eye(160)}, jnp.asarray(logits),
        dict(num_experts_per_tok=6, n_group=8, topk_group=3))
    assert np.array_equal(np.asarray(own), want)
    assert np.array_equal(
        np.asarray(groups),
        np.asarray(moe_ops.top_groups(probs, 8, 3)))


@pytest.mark.parametrize("n_group,topk_group", [(8, 3), (1, 1)])
def test_the_choice_says_which_groups_it_was_limited_to(n_group, topk_group):
    """What the held group's counter reads: the groups of the choice
    itself (None where the rule has no groups), and `choose` is the
    same choice without them."""
    logits = jax.random.normal(jax.random.PRNGKey(11), (48, 160))
    w, e, groups = moe_ops.choose_in_groups(
        logits, 6, False, scale=16.0, n_group=n_group, topk_group=topk_group)
    w2, e2 = moe_ops.choose(logits, 6, False, scale=16.0, n_group=n_group,
                            topk_group=topk_group)
    assert np.array_equal(np.asarray(e), np.asarray(e2))
    assert np.array_equal(np.asarray(w), np.asarray(w2))
    if n_group == 1:
        assert groups is None
        return
    assert np.array_equal(
        np.asarray(groups),
        np.asarray(moe_ops.top_groups(jax.nn.softmax(logits, axis=-1),
                                      n_group, topk_group)))
    # every chosen expert lies in one of its token's groups
    assert np.all(np.any(np.asarray(e)[:, :, None] // 20
                         == np.asarray(groups)[:, None, :], axis=-1))


@pytest.mark.parametrize("scoring,norm,bias", [
    ("softmax", False, False), ("softmax", True, False),
    ("sigmoid", True, True), ("sigmoid", False, False)])
def test_one_group_of_one_leaves_the_choice_as_it_was(scoring, norm, bias):
    """n_group 1 / topk_group 1 is every other family's rule: the same
    bits as a call that never names the groups."""
    logits = jax.random.normal(jax.random.PRNGKey(7), (40, 64))
    b = (0.05 * jax.random.normal(jax.random.PRNGKey(8), (64,))
         if bias else None)
    plain = jax.jit(lambda x: moe_ops.choose(x, 8, norm, scoring, 2.5, b))
    named = jax.jit(lambda x: moe_ops.choose(x, 8, norm, scoring, 2.5, b,
                                             n_group=1, topk_group=1))
    assert plain.lower(logits).as_text() == named.lower(logits).as_text()
    for a, w in zip(plain(logits), named(logits)):
        assert np.array_equal(np.asarray(a), np.asarray(w))


@pytest.mark.parametrize("side", ["reference", "served"])
def test_eight_groups_and_the_shared_experts_are_the_uncut_layer(side):
    """Each of 8 chips holds one GROUP of a layer's routed experts and
    routes over all of them; their parts, with the shared experts
    counted once, add up to what the uncut reference gives for the
    layer; and the tokens a chip's group is among are counted."""
    c = DeepseekV2Config.tiny_dsv2(n_group=8, topk_group=3)
    params = init_params(c, jax.random.PRNGKey(14), jnp.float32)
    lp = ref_layers(params, c)[1]
    h = jax.random.normal(jax.random.PRNGKey(15), (21, c.hidden_size))
    cfg = ref_config(c)
    rule = dict(scoring="softmax", scale=c.routed_scaling_factor,
                n_group=8, topk_group=3)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_ffn(lp, h, cfg)
        total = ref.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        groups = np.asarray(ref.router(lp, h, cfg)[3])
    assert lp["ws_gate"].shape[1] == 2 * c.moe_intermediate_size
    held_tokens = 0
    for g in range(8):
        share = {k: (v[2 * g:2 * g + 2] if k.startswith("we_") else v)
                 for k, v in lp.items()}
        if side == "reference":
            with jax.default_matmul_precision("highest"):
                part = ref.moe_ffn(share, h, cfg, held=(2 * g, 2),
                                   shared=False)
        else:
            routed = {k: v for k, v in share.items()
                      if not k.startswith("ws_")}
            part, stats = moe_ops.moe_mlp(
                routed, h[None], 3, c.norm_topk_prob, first_expert=2 * g,
                **rule)
            part = part[0]
            assert float(stats.rows_routed) == 21 * 3
            assert float(stats.rows) == float(
                jnp.sum(stats.experts // 2 == g))
            assert float(stats.group_held) == (groups == g).any(1).sum()
            held_tokens += float(stats.group_held)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=3e-5)
    if side == "served":
        assert held_tokens == 21 * 3             # three groups a token


# -- YaRN -------------------------------------------------------------------


def test_yarn_frequencies_and_scale_at_the_published_numbers():
    """factor 40, original 4,096, beta 32 / 1, mscale 0.707 both, dim
    64, theta 10,000: the formulas of ISSUE 45 written out here."""
    y = rope_ops.Yarn(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    f = 10000.0 ** (-np.arange(32) / 32.0)

    def c(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(1e4))

    low, high = max(math.floor(c(32)), 0), min(math.ceil(c(1)), 63)
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = (f / 40) * ramp + f * (1 - ramp)
    np.testing.assert_allclose(rope_ops.yarn_inv_freq(64, 1e4, y), want,
                               rtol=1e-12)
    np.testing.assert_allclose(ref.inv_freq(64, 1e4, {
        "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1}), want, rtol=1e-12)
    # the fast pairs are untouched, the slow ones divided by 40
    assert np.array_equal(want[:11], f[:11])
    np.testing.assert_allclose(want[23:], f[23:] / 40, rtol=1e-12)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert y.table_factor == 1.0
    assert abs(y.softmax_factor - m * m) < 1e-12
    assert abs(m * m - 1.5896) < 1e-4 and abs(192 ** -0.5 - 0.07217) < 1e-5
    with open(os.path.join(CONFIG_DIR, "config.json")) as fh:
        config = load_config_dict(json.load(fh))
    assert config.rope_scaling == y
    geo = config.geometry(3)
    assert abs(geo.softmax_scale - 192 ** -0.5 * m * m) < 1e-12
    tables = RopeTables.create(config, 5120)
    ang = np.arange(5120)[:, None] * want[None, :]
    np.testing.assert_allclose(tables.cos, np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(tables.sin[:4096], np.sin(ang)[:4096],
                               atol=1e-3)


def test_plain_rope_tables_are_what_they_were():
    cos, sin = rope_ops.precompute_rope(64, 128, 10000.0)
    inv = 1.0 / (10000.0 ** (jnp.arange(0, 64, 2, dtype=jnp.float32) / 64))
    freqs = jnp.outer(jnp.arange(128, dtype=jnp.float32), inv)
    assert np.array_equal(np.asarray(cos), np.asarray(jnp.cos(freqs)))
    assert np.array_equal(np.asarray(sin), np.asarray(jnp.sin(freqs)))


def test_a_glm_geometry_keeps_its_scale():
    from cake_tpu.models.moe.config import GlmMoeDsaConfig
    geo = GlmMoeDsaConfig.tiny_glm().geometry(0)
    assert geo.scale_factor == 1.0 and geo.softmax_scale == 24 ** -0.5


# -- the pool, the config ---------------------------------------------------


def test_latent_pool_has_no_index_key_pool():
    c = DeepseekV2Config.tiny_dsv2()
    cache = PagedKVCache.create(c, 4, 10, 8, 64, dtype=jnp.bfloat16)
    assert cache.k.shape == (4, 10, 8, 16 + 8)       # one latent row
    assert cache.v.size == 0 and cache.v.shape[0] == 0
    assert cache.table.shape == (4, 8) and cache.page_size == 8
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    assert not any(k.startswith("wi_") or k == "router_bias"
                   for k in params["blocks"])


def test_published_config_parses():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        raw = json.load(f)
    c = load_config_dict(raw)
    assert isinstance(c, DeepseekV2Config)
    assert c.family.name == "deepseek_v2"
    assert (c.num_hidden_layers, c.hidden_size, c.latent_width,
            c.latent_row) == (15, 5120, 576, 640)
    assert c.indexer_types == ("dense",) * 15 and c.full_layers == ()
    assert c.sparse_layers == tuple(range(1, 15))
    assert (c.num_local_experts, c.n_routed_experts_total,
            c.first_routed_expert, c.num_experts_per_tok) == (20, 160, 0, 6)
    assert (c.n_group, c.topk_group, c.n_shared_experts) == (8, 3, 2)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (c.intermediate_size, c.moe_intermediate_size) == (12288, 1536)
    assert c.routed_scaling_factor == 16 and c.scoring_func == "softmax"
    assert c.norm_topk_prob is False and c.rms_norm_eps == 1e-6
    assert c.vocab_size == 12800 and c.eos_token_ids == (12800,)


RAW = dict(
    model_type="deepseek_v2", vocab_size=64, hidden_size=32,
    intermediate_size=64, num_hidden_layers=2, num_attention_heads=2,
    q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, moe_intermediate_size=16, n_routed_experts=4,
    n_routed_experts_total=16, num_experts_per_tok=2, first_k_dense_replace=1,
    n_group=4, topk_group=2, n_shared_experts=2, routed_scaling_factor=16.0,
    topk_method="group_limited_greedy", scoring_func="softmax",
    norm_topk_prob=False,
    rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096})


@pytest.mark.parametrize("key,value", [
    ("index_topk", 2048), ("topk_method", "noaux_tc"),
    ("scoring_func", "sigmoid"), ("n_group", 3), ("topk_group", 5),
    ("q_lora_rank", None), ("attention_bias", True),
    ("num_nextn_predict_layers", 1), ("first_routed_expert", 14),
    ("n_shared_experts", 0), ("num_experts_per_tok", 9),
    ("rope_scaling", {"type": "linear", "factor": 4})])
def test_what_is_not_implemented_is_refused(key, value):
    c = load_config_dict(RAW)
    assert (c.n_group, c.topk_group, c.n_shared_experts) == (4, 2, 2)
    with pytest.raises(ValueError):
        load_config_dict(dict(RAW, **{key: value}))


@pytest.mark.parametrize("model_type", ["glm_moe_dsa", "dots3_note",
                                        "nemotron_h"])
def test_yarn_and_groups_stay_refused_where_they_are_not_served(model_type):
    """rope_scaling and n_group are accepted for deepseek_v2 alone: the
    three other families that could carry the keys refuse them by
    name."""
    from test_family import tiny_config   # noqa: F401 (the tiny configs)
    raws = {
        "glm_moe_dsa": dict(
            RAW, model_type="glm_moe_dsa", index_n_heads=2,
            index_head_dim=8, index_topk=4, n_shared_experts=1, n_group=1,
            topk_group=1, rope_scaling=None, scoring_func="sigmoid"),
        "nemotron_h": dict(
            model_type="nemotron_h", vocab_size=64, hidden_size=32,
            num_hidden_layers=2, hybrid_override_pattern="ME",
            num_attention_heads=2, num_key_value_heads=2,
            mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
            ssm_state_size=8, moe_intermediate_size=16, moe_latent_size=16,
            moe_shared_expert_intermediate_size=32, n_routed_experts=4,
            num_experts_per_tok=2),
    }
    raws["dots3_note"] = dict(
        raws["glm_moe_dsa"], model_type="dots3_note",
        layer_types=["full_attention", "sliding_attention"],
        attention_gate_type="headwise", swa_attention_gate_type="headwise",
        apply_mla_qkv_lora_rescale=True, swa_num_attention_heads=2,
        swa_q_lora_rank=8, swa_kv_lora_rank=8, swa_qk_nope_head_dim=8,
        swa_qk_rope_head_dim=4, swa_v_head_dim=8, sliding_window_size=4)
    raw = raws[model_type]
    load_config_dict(raw)
    with pytest.raises(ValueError, match="rope_scaling"):
        load_config_dict(dict(raw, rope_scaling=RAW["rope_scaling"]))
    with pytest.raises(ValueError, match="n_group"):
        load_config_dict(dict(raw, n_group=8))


# -- the engine -------------------------------------------------------------


def make_engine(**kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    # one group of four held: the chip's share of four
    c = DeepseekV2Config.tiny_dsv2(vocab_size=300, eos_token_ids=(300,),
                                   num_local_experts=4,
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
    c, params, eng = make_engine()
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(3, 250, n)))
               for n in (40, 7, 70, 21, 33)]
    from cake_tpu.obs import steps as obs_steps
    before = {k: s.value for k, s in obs_steps.MLA_DENSE_COUNTERS}
    with eng:
        handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for h in handles:
            assert h.wait(180)
        records = eng.flight.dump()
    after = {k: s.value for k, s in obs_steps.MLA_DENSE_COUNTERS}
    return (c, params, prompts, [h.token_ids for h in handles], records,
            {k: after[k] - before[k] for k in after}, eng)


@pytest.mark.parametrize("request_index", range(4))
def test_engine_serves_the_references_greedy_tokens(engine_run,
                                                    request_index):
    """Through submit -> _do_mixed -> the in-flight decode step: four
    requests over four rows and a fifth behind them, prompts of 1 to 5
    windows, one group of four held. Teacher-forced: the reference's
    forward (the same share) over the prompt and the tokens the engine
    gave must choose each of them."""
    c, params, prompts, tokens, *_ = engine_run
    prompt, out = prompts[request_index], tokens[request_index]
    assert len(out) == 10
    logits = np.asarray(ref.forward(
        ref_params(params, c), np.asarray(prompt + out), ref_config(c),
        held=(4, 4)))
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
        assert r["impl"] == "paged-mla-fold"
    counted = [r for r in records if "mla_keys_attended" in r]
    assert counted
    for r in counted:
        assert r["moe_rows_routed"] >= r["moe_rows"]
        # three of four groups a token: some tokens miss the held one
        assert r["moe_tokens_group_held"] * 3 <= r["moe_rows_routed"]
    decode = [r for r in records if r["kind"] == "decode"]
    assert all(r["mla_keys_attended"] > 0 for r in decode)
    # the host counts the pages the page-walking kernel walks
    assert all(0 < r["attn_pages"] <= r["attn_pages_table"] for r in decode)
    assert all(v > 0 for v in moved.values()), moved
    assert eng._mixed_buckets == (32,) and not eng._prefix_capable


def test_records_count_the_page_kernels_walk(engine_run):
    """mla_decode_pages / mla_decode_folds: a decode record's rows and
    a mixed record's single-token rows, position // page + 1 pages each
    over 4 layers, at the pages a fold the kernel takes for the
    engine's shapes; on /metrics as cake_mla_decode_pages_total /
    cake_mla_decode_folds_total."""
    from cake_tpu.obs import steps as obs_steps
    *_, records, _moved, eng = engine_run
    geo, pool = eng.config.geometry(0), eng.cache.k
    block = mla.decode_block(geo.heads, pool.shape[-1], geo.kv_lora_rank,
                             PAGE, eng.cache.max_pages, pool.dtype.itemsize)
    assert block == 4 and eng.cache.max_pages == 16
    decode = [r for r in records if r["kind"] == "decode"]
    for r in decode:
        # one layer's pages are the GQA decode kernel's count
        assert r["mla_decode_pages"] == 4 * r["attn_pages"]
        assert (r["mla_decode_pages"] >= r["mla_decode_folds"]
                >= r["mla_decode_pages"] / block)
    mixed = [r for r in records if r["kind"] == "mixed"]
    assert all("mla_decode_pages" in r for r in mixed)
    assert any(r["mla_decode_pages"] for r in mixed)     # rows beside
    assert eng._mla_decode_pages([40, 7, 127]) == {
        "mla_decode_pages": 4 * (6 + 1 + 16),
        "mla_decode_folds": 4 * (2 + 1 + 4)}
    assert eng._mla_decode_pages([]) == {"mla_decode_pages": 0,
                                         "mla_decode_folds": 0}
    before = (obs_steps._MLA_DECODE_PAGES.value,
              obs_steps._MLA_DECODE_FOLDS.value)
    eng.flight.record("decode", rows=1, tokens=1, wall_s=0.01,
                      **eng._mla_decode_pages([40]))
    assert (obs_steps._MLA_DECODE_PAGES.value - before[0],
            obs_steps._MLA_DECODE_FOLDS.value - before[1]) == (24, 8)


def test_mixed_records_count_the_window_kernels_walk(engine_run):
    """window_pages / window_folds: one window a step, 4 layers, the
    row's live pages up to the window's last position, 4 of them a
    fold; a decode record has neither."""
    *_, prompts, _tokens, records, _moved, eng = engine_run
    mixed = [r for r in records if r["kind"] == "mixed"]
    assert mixed and all("window_pages" not in r for r in records
                         if r["kind"] != "mixed")
    table = eng.cache.max_pages
    for r in mixed:
        pages = r["window_pages"] // 4
        assert r["window_pages"] == 4 * pages and 1 <= pages <= table
        assert r["window_folds"] == 4 * -(-pages // 4)
    # a prompt of 70 tokens ends in a window whose last position is 69
    assert max(r["window_pages"] for r in mixed) == 4 * (69 // PAGE + 1)
    assert eng._window_pages(
        np.asarray([0, 17, 0, 40]), np.asarray([1, 1, 0, 32]),
        [np.asarray([True, True, False, False]),
         np.asarray([False, False, False, True])]) == {
             # (a dispatch of single tokens: its first row, as argmax)
             "window_pages": 4 * (1 + 9), "window_folds": 4 * (1 + 3)}


@pytest.mark.parametrize("refused,option", [
    (dict(kv_pages=None), "--kv-pages"), (dict(kv_dtype="int8"), "--kv-dtype"),
    (dict(kv_host_pages=8), "--kv-host-pages"),
    (dict(auto_prefix_system=True), "--auto-prefix"),
    (dict(disagg="prefill"), "--disagg")])
def test_engine_refuses_by_option_what_latent_rows_cannot_move(refused,
                                                               option):
    with pytest.raises(ValueError) as err:
        make_engine(**refused)
    said = str(err.value)
    assert "model_type deepseek_v2" in said and option in said
    assert "latent row" in said and "index key" not in said


def test_prefix_registration_is_refused_by_name():
    *_, eng = make_engine()
    with pytest.raises(ValueError, match="latent page pool"):
        eng.register_prefix([5, 6, 7, 8, 9, 10, 11, 12, 13])
