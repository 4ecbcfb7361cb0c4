"""`cake_dsa_select` (ops/mla_attention.select_window), interpreted,
against `select_mask`, BIT FOR BIT: the window's top-k as one kernel
must pick the keys the XLA form picks, ties at the k-th value to the
lower index included, whatever the shapes, k against the visible count,
where the window ends, and what the scores hold. Then the families that
call it, end to end at their tiny configs, with `select_mask` put back
in the kernel's place: the same hidden states, the same sets, the same
counters.

The kernel's statement (select_window's docstring): every row equals
select_mask on the visibility `span <= min(positions, last_pos)`; for a
query at or before `last_pos` that is the seed's own call
(`span <= positions`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import mixed_token_buckets
from cake_tpu.models.moe import glm_dsa, keye_vl2
from cake_tpu.models.moe.params import init_params
from cake_tpu.ops import mla_attention as mla


def stated(scores, positions, last_pos, k):
    """What select_window states it returns, by select_mask."""
    span = jnp.arange(scores.shape[1])[None, :]
    at = jnp.minimum(jnp.asarray(positions), last_pos)
    return mla.select_mask(jnp.asarray(scores), span <= at[:, None], k)


def seeds_call(scores, positions, last_pos, k):
    """The call the step programs made before the kernel."""
    span = jnp.arange(scores.shape[1])[None, :]
    return mla.select_mask(jnp.asarray(scores),
                           span <= jnp.asarray(positions)[:, None], k)


def kernel(scores, positions, last_pos, k):
    return mla.select_window(jnp.asarray(scores), jnp.asarray(positions),
                             jnp.int32(last_pos), k, interpret=True)


def assert_same(scores, positions, last_pos, k):
    got = np.asarray(kernel(scores, positions, last_pos, k))
    want = np.asarray(stated(scores, positions, last_pos, k))
    assert got.dtype == np.bool_ and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    real = np.asarray(positions) <= last_pos
    np.testing.assert_array_equal(
        got[real],
        np.asarray(seeds_call(scores, positions, last_pos, k))[real])
    return got


def scores_of(C, S, seed=0):
    return np.random.default_rng(seed).standard_normal((C, S)).astype(
        np.float32)


# S 33,280 is Keye's table: 260 pages of 128, 20 blocks of 13 chunks
@pytest.mark.parametrize("S", [256, 1024, 33280])
@pytest.mark.parametrize("C", [8, 40, 128])
@pytest.mark.parametrize("k_is", ["below", "at", "above"])
def test_the_mask_is_select_masks(C, S, k_is):
    """A full window that ends three quarters into the table; k below
    every query's visible count, equal to the first query's, and above
    them all (a query that sees fewer than k keys selects them all)."""
    last = (3 * S) // 4
    first = last - C + 1
    k = {"below": 7, "at": first + 1, "above": min(S, last + 9)}[k_is]
    got = assert_same(scores_of(C, S, C + S), first + np.arange(C), last, k)
    counts = got.sum(axis=1)
    visible = first + 1 + np.arange(C)
    np.testing.assert_array_equal(counts, np.minimum(visible, k))


@pytest.mark.parametrize("where", ["first_block", "edge_below", "edge_at",
                                   "table_end"])
def test_the_bound_is_the_windows_last_position(where):
    """last_pos inside the first block, on a block's last key, on the
    next block's first, and at S - 1; nothing past its block is read
    (NaNs lie there) and that part of the mask is False."""
    C, S, k = 40, 33280, 64
    tq, chunk, block = mla.select_tiles(C, S)
    assert (tq, chunk, block) == (8, 128, 1664)
    last = {"first_block": 700, "edge_below": 3 * block - 1,
            "edge_at": 3 * block, "table_end": S - 1}[where]
    scores = scores_of(C, S, 3)
    walked = int(mla.select_walked(last, C, S))
    assert walked == (last // block + 1) * block
    poisoned = scores.copy()
    poisoned[:, walked:] = np.nan
    first = max(last - C + 1, 0)
    got = assert_same(scores, first + np.arange(C), last, k)
    np.testing.assert_array_equal(
        got, np.asarray(kernel(poisoned, first + np.arange(C), last, k)))
    assert not got[:, last + 1:].any()


@pytest.mark.parametrize("what", ["relu_floor", "straddle", "all_equal",
                                  "inf_and_zero"])
def test_ties_go_to_the_lower_index(what):
    """Half the scores exactly 0.0 (the relu's floor) with k reaching
    into them; a run of equal scores across a block's edge with room
    for part of it; every score equal; -inf, -0.0 and +0.0 among the
    scores (-0.0 sorts under +0.0, as `_sortable` has it)."""
    C, S, last = 16, 2 * 1664, 2 * 1664 - 1
    _, _, block = mla.select_tiles(C, S)
    assert block == 1664
    positions = last - C + 1 + np.arange(C)
    rng = np.random.default_rng(5)
    scores = np.abs(scores_of(C, S, 4)) + 1.0
    if what == "relu_floor":
        scores[rng.random((C, S)) < 0.5] = 0.0
        k = S // 2 + 300
    elif what == "straddle":
        # 40 keys tied at the top across the edge, k takes 25 of them
        scores[:, block - 20:block + 20] = 9.0
        k = 25
    elif what == "all_equal":
        scores[:] = 0.25
        k = 100
    else:
        scores[:, ::3] = -np.inf
        scores[:, 1::3] = -0.0
        scores[:, 2::3] = 0.0
        k = S // 3 + S // 6
    got = assert_same(scores, positions, last, k)
    if what == "straddle":
        assert got[:, block - 20:block + 5].all()
        assert not got[:, block + 5:].any()
    if what == "all_equal":
        assert got[:, :100].all() and not got[:, 100:].any()
    if what == "inf_and_zero":
        # every +0.0 (that all the queries see), then the lowest-index
        # -0.0s, and no -inf
        assert got[:, 2:positions[0]:3].all() and not got[:, ::3].any()
        assert got[:, 1:S // 4:3].all() and not got[:, S // 2 + 1::3].any()


def test_a_window_shorter_than_its_width():
    """n under C: the queries past the window's last position are
    padding; each gets what the last real one may see, by its own
    scores (finite, stated), and the real ones the seed's sets."""
    C, S, k, first, n = 40, 1024, 24, 300, 13
    positions = first + np.arange(C)
    got = assert_same(scores_of(C, S, 6), positions, first + n - 1, k)
    assert (got.sum(axis=1) == k).all()
    assert not got[:, first + n:].any()


def test_a_table_that_is_no_multiple_of_128_is_one_chunk():
    """A test's table (pages of 4 or 8): one chunk, one block."""
    for C, S in [(8, 48), (16, 96), (40, 2080)]:
        assert mla.select_tiles(C, S)[1:] == (S, S)
        assert_same(scores_of(C, S, 7), S // 2 + np.arange(C),
                    S // 2 + C - 1, 9)


def rows_top_k(scores, positions, k):
    """What a selecting model's single-token rows took before they went
    through the kernel: `lax.top_k` over the keys at or before each
    row's position, the first min(position + 1, k) of it, as sets."""
    S = scores.shape[1]
    seen = jnp.arange(S)[None, :] <= jnp.asarray(positions)[:, None]
    _, idx = jax.lax.top_k(jnp.where(seen, jnp.asarray(scores), -jnp.inf), k)
    sets = np.zeros(scores.shape, bool)
    for b, p in enumerate(positions):
        sets[b, np.asarray(idx[b, :min(int(p) + 1, k)])] = True
    return sets


# 2,080 keys: a test's table, one chunk; 33,280: Keye's
@pytest.mark.parametrize("S", [256, 2080, 33280])
@pytest.mark.parametrize("scores_are", ["distinct", "relu_floor"])
def test_one_tile_of_rows_is_each_rows_own_top_k(S, scores_are):
    """ONE tile of 8 queries that are eight ROWS: each with its own
    scores and its own position (one of them -1: a row with no single
    token), `last_pos` the greatest. Each row's set is `lax.top_k`'s
    over what it sees, ties at the k-th value (half the scores exactly
    0.0, the relu's floor) to the lower index in both; a row shorter
    than k selects all it sees, the -1 row nothing."""
    C = 8
    k = S // 8
    assert mla.select_tiles(C, S)[0] == C
    positions = np.asarray([S - 1, k - 1, k, -1, S // 2, 3, k // 2,
                            (3 * S) // 4], np.int32)
    scores = np.abs(scores_of(C, S, S)) + 0.5
    if scores_are == "relu_floor":
        scores[np.random.default_rng(1).random((C, S)) < 0.5] = 0.0
    got = assert_same(scores, positions, int(positions.max()), k)
    np.testing.assert_array_equal(got, rows_top_k(scores, positions, k))
    np.testing.assert_array_equal(got.sum(axis=1),
                                  np.minimum(positions + 1, k))


def test_a_tile_of_rows_with_no_single_token_selects_nothing():
    """Every position -1 (a dispatch that holds a window alone): the
    bound clips to the first block and nothing is seen."""
    got = kernel(scores_of(8, 256, 9), np.full(8, -1, np.int32), -1, 16)
    assert not np.asarray(got).any()


@pytest.mark.parametrize("C,S,tiles", [
    (512, 33280, (128, 128, 1664)),     # keyevl2.longctx-closed
    (512, 12800, (128, 128, 1280)),     # glm52.longdoc-closed
    (512, 16896, (128, 128, 1536)),     # dots3.longshort-closed
    (512, 65536, (64, 128, 2048)),      # a wider table: a narrower tile
    (40, 1024, (8, 128, 1024)),
    (12, 256, (12, 128, 256))])         # no tile divides: the window whole
def test_tiles_come_from_the_shapes(C, S, tiles):
    assert mla.select_tiles(C, S) == tiles
    tq, chunk, block = tiles
    assert C % tq == 0 and S % block == 0 and block % chunk == 0
    assert tq * S * 4 <= mla._SELECT_CODES_BYTES
    # the codes, the result's two buffers and the positions' fit
    assert tq * S * 6 + 2 * tq * 512 < mla._SELECT_VMEM_LIMIT


def test_walked_follows_the_context():
    C, S = 512, 33280
    walked = [int(mla.select_walked(p, C, S))
              for p in (0, 1663, 1664, 8191, 16383, 33279, 40000)]
    assert walked == [1664, 1664, 3328, 8320, 16640, 33280, 33280]


@pytest.mark.parametrize("what,match", [
    ("bf16", "float32 scores"), ("k0", "k >= 1"),
    ("positions", r"positions \[8\]"),
    ("lanes", "cannot run"), ("half_a_lane_tile", "cannot run"),
    ("codes", "cannot run")])
def test_shapes_the_tiling_cannot_take_are_refused_by_name(what, match):
    scores = jnp.zeros((8, 256), jnp.float32)
    positions = jnp.arange(8)
    args = {
        "bf16": (scores.astype(jnp.bfloat16), positions, 7, 4, True),
        "k0": (scores, positions, 7, 0, True),
        "positions": (jnp.zeros((4, 256)), positions, 7, 4, True),
        # on the chip: whole lane tiles of keys
        "lanes": (jnp.zeros((32, 200)), jnp.arange(32), 7, 4, False),
        "half_a_lane_tile": (scores[:, :64], positions, 7, 4, False),
        # a query's codes past the kernel's VMEM, anywhere
        "codes": (jax.ShapeDtypeStruct((4, 2**21), jnp.float32),
                  jnp.arange(4), 7, 4, True)}[what]
    with pytest.raises(ValueError, match="cake_dsa_select.*" + match):
        mla.select_window(*args[:4], interpret=args[4])


# -- the families, end to end ---------------------------------------------------


def _family(name):
    """(config, trunk(params, toks, pos, qlen, cache, rope), fresh
    cache, B, C, the counters' names) of a family's tiny config, from
    its own test module."""
    if name == "KeyeVL2":
        import test_keye_vl2 as t
        c = t.KeyeVL2Config.tiny_keye()

        def trunk(params, toks, pos, qlen, cache, rope, T):
            return keye_vl2.mixed_trunk(params, toks, pos, qlen, qlen > 0,
                                        cache, rope, c, "fold", T,
                                        probe=True)[0]
        names = keye_vl2.COUNTERS
    else:
        import test_dots3
        import test_glm_moe_dsa
        t = test_glm_moe_dsa if name == "glm_moe_dsa" else test_dots3
        c = (t.GlmMoeDsaConfig.tiny_glm() if name == "glm_moe_dsa"
             else t.Dots3NoteConfig.tiny_dots3())

        def trunk(params, toks, pos, qlen, cache, rope, T):
            return glm_dsa.mixed_trunk(params, toks, pos, qlen, qlen > 0,
                                       cache, rope, c, "fold", T)[0]
        names = c.family.counters
    return c, trunk, t.fresh_cache, t.B, t.C, t.MAX_SEQ, names


def _drive(name):
    """A prompt of 3 1/2 windows on row 1 while row 0 rides each
    dispatch with one token: per dispatch (x, the window's sets, the
    counters)."""
    c, trunk, fresh_cache, B, C, max_seq, names = _family(name)
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    rope = RopeTables.create(c, max_seq)
    T = mixed_token_buckets(B, C, (1,))[-1]
    # a new function a drive: nothing traced under another selection
    step = jax.jit(lambda toks, pos, qlen, cache: trunk(
        params, toks, pos, qlen, cache, rope, T))
    cache = fresh_cache(c)
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, 200, 3 * C + C // 2)
    out = []
    for w in range(4):
        toks = np.zeros((B, C), np.int32)
        n = min(C, len(prompt) - w * C)
        toks[1, :n] = prompt[w * C:w * C + n]
        toks[0, 0] = 7 + w
        got = step(jnp.asarray(toks), jnp.asarray([w, w * C, 0, 0]),
                   jnp.asarray([1, n, 0, 0]), cache)
        cache = got.cache
        out.append((np.asarray(got.x), np.asarray(got.selected_window), n,
                    dict(zip(names, np.asarray(got.counters).tolist()))))
    return out


@pytest.mark.parametrize("name", ["KeyeVL2", "glm_moe_dsa", "dots3_note"])
def test_a_family_serves_what_select_mask_served(name, monkeypatch):
    """The step program with the kernel, then with the seed's call in
    its place: the same hidden state at every token (so the same
    tokens), the same sets for the window's real queries, the same
    distinct rows."""
    with_kernel = _drive(name)
    monkeypatch.setattr(mla, "select_window", seeds_call)
    with_xla = _drive(name)
    for (x, sets, n, counters), (x0, sets0, _, counters0) in zip(
            with_kernel, with_xla):
        np.testing.assert_array_equal(x, x0)
        assert sets.shape == sets0.shape and sets.shape[1] >= n
        np.testing.assert_array_equal(sets[:, :n], sets0[:, :n])
        assert sets[:, :n].any()
        assert counters == counters0
        # a tiny table is one block: the selection walks it whole
        assert (counters["dsa_select_keys_walked"]
                == counters["dsa_select_keys_table"] > 0)
        assert counters["dsa_rows_distinct"] > 0


def test_the_bench_tool_rehearses_and_checks_the_kernel(capsys):
    """tools/dsa_select_bench.py at tiny widths: one JSON line, the
    kernel's mask compared bit for bit with XLA's form, every case
    timed with what it walks."""
    import importlib.util
    import json
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "dsa_select_bench.py"
    spec = importlib.util.spec_from_file_location("dsa_select_bench", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearse", "--calls", "2"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert len(line["cases"]) == 2
    for case in line["cases"]:
        assert case["same"] and case["walked"] <= case["S"]
        assert {"kernel_us", "xla_us", "bytes", "vector_passes"} <= set(case)
