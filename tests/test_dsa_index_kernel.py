"""`cake_dsa_index` (ops/mla_attention.index_scores_window), interpreted,
against `_weighted_relu` on the same operands: a window's index scores
as one kernel are the scores the einsums give, in [queries, keys] order,
up to the block that holds the window's last position; past it zeros,
and nothing of the keys there is read. Then the selection over them,
the tiles the three cells' shapes get, and the families that call it,
end to end at their tiny configs, with the blocked `lax.map` (the form
the step programs ran until PR 68, kept here and in
tools/dsa_index_bench.py alone) put back in the kernel's place: the
same sets, the same counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from cake_tpu.obs import steps
from cake_tpu.ops import mla_attention as mla


def operands(C, J, d, S, dtype=jnp.bfloat16, seed=0):
    kq, kk, kw = jax.random.split(jax.random.PRNGKey(seed + C + S), 3)
    return (jax.random.normal(kq, (C, J, d)).astype(dtype),
            jax.random.normal(kk, (S, d)).astype(dtype),
            jax.random.normal(kw, (C, J)) * (J * d) ** -0.5)


def kernel(qI, kI, w, last_pos):
    return mla.index_scores_window(qI, kI, w, jnp.int32(last_pos),
                                   interpret=True)


def blocked(qI, kI, w, last_pos, block=None):
    """The window's score pass as the step programs ran it before the
    kernel: a `lax.map` over key blocks with a `lax.cond` a block, the
    blocks stacked [blocks, queries, block] and transposed."""
    C, J, d = qI.shape
    S = kI.shape[0]
    block = block or mla.index_tiles(C, J, d, S)[1]
    blocks = kI.reshape(S // block, block, d)

    def one(args):
        i, kb = args
        return lax.cond(i * block <= last_pos,
                        lambda: mla._weighted_relu(qI, kb, w),
                        lambda: jnp.zeros((C, block), jnp.float32))

    out = lax.map(one, (jnp.arange(S // block), blocks))
    return jnp.transpose(out, (1, 0, 2)).reshape(C, S)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("C,J,d,S", [
    (8, 2, 8, 32),          # one tile, one block
    (16, 4, 16, 96),        # three blocks of 32
    (40, 3, 16, 256),       # a tile of 8 queries, 128-key chunks
    (24, 16, 64, 1024)])    # Keye's heads over two blocks of 512
def test_the_scores_are_the_weighted_relus(C, J, d, S, dtype):
    """The whole table live: every column is `_weighted_relu`'s on the
    same operands, float32, in [queries, keys] order."""
    qI, kI, w = operands(C, J, d, S, dtype)
    got = kernel(qI, kI, w, S - 1)
    assert got.dtype == jnp.float32 and got.shape == (C, S)
    np.testing.assert_allclose(got, mla._weighted_relu(qI, kI, w),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got, blocked(qI, kI, w, S - 1),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("where", ["zero", "edge_below", "edge_at",
                                   "table_end"])
def test_the_bound_is_the_windows_last_position(where):
    """last_pos at 0, on a block's last key, on the next block's first
    and at S - 1: the blocks that start at or before it are scored,
    the rest are zeros, and nothing past the last live block is read
    (NaNs lie there and the result stays finite)."""
    C, J, d, S = 16, 4, 16, 160
    tq, kb = mla.index_tiles(C, J, d, S)
    assert (tq, kb) == (16, 32)
    last = {"zero": 0, "edge_below": 3 * kb - 1, "edge_at": 3 * kb,
            "table_end": S - 1}[where]
    scored = int(mla.index_scored(last, S))
    assert scored == (last // kb + 1) * kb
    qI, kI, w = operands(C, J, d, S)
    got = np.asarray(kernel(qI, kI, w, last))
    want = np.asarray(mla._weighted_relu(qI, kI, w))
    np.testing.assert_allclose(got[:, :scored], want[:, :scored],
                               rtol=1e-5, atol=1e-5)
    assert want[:, :scored].any() and not got[:, scored:].any()
    poisoned = kI.at[scored:].set(jnp.nan)
    again = np.asarray(kernel(qI, poisoned, w, last))
    assert np.isfinite(again).all()
    np.testing.assert_array_equal(again, got)
    np.testing.assert_allclose(got, blocked(qI, kI, w, last), rtol=1e-5,
                               atol=1e-5)


def test_a_bound_outside_the_table_is_clipped():
    """A last position before 0 scores the first block, one past the
    table the whole of it (`select_window` clips the same way)."""
    C, J, d, S = 8, 2, 8, 96
    qI, kI, w = operands(C, J, d, S)
    kb = mla.index_tiles(C, J, d, S)[1]
    want = np.asarray(mla._weighted_relu(qI, kI, w))
    low = np.asarray(kernel(qI, kI, w, -1))
    np.testing.assert_allclose(low[:, :kb], want[:, :kb], rtol=1e-5,
                               atol=1e-5)
    assert not low[:, kb:].any()
    np.testing.assert_allclose(kernel(qI, kI, w, S + 40), want, rtol=1e-5,
                               atol=1e-5)
    assert [int(mla.index_scored(p, S)) for p in (-1, S + 40)] == [kb, S]


def test_the_selection_over_the_kernels_scores_is_the_references():
    """A seeded window with no near-ties among its live scores:
    `select_window` picks the same keys from the kernel's scores as
    from `_weighted_relu`'s."""
    C, J, d, S, k = 16, 4, 16, 256, 24
    qI, kI, w = operands(C, J, d, S, seed=5)
    # positive weights and a shift keep the relu's floor (exact ties
    # at 0.0, the same on both sides) out of the top k
    w = jnp.abs(w) + 0.1
    last = 200
    positions = last - C + 1 + jnp.arange(C)
    want = mla._weighted_relu(qI, kI, w)
    live = np.sort(np.asarray(want)[:, :last + 1], axis=1)
    assert np.min(np.diff(live[:, -2 * k:], axis=1)) > 1e-5
    got = kernel(qI, kI, w, last)
    picked = mla.select_window(got, positions, jnp.int32(last), k)
    np.testing.assert_array_equal(
        picked, mla.select_window(want, positions, jnp.int32(last), k))
    np.testing.assert_array_equal(np.asarray(picked).sum(axis=1), k)


@pytest.mark.parametrize("C,J,d,S,tiles", [
    (512, 16, 64, 33280, (512, 512)),     # keyevl2.longctx-closed
    (512, 32, 128, 12800, (512, 512)),    # glm52.longdoc-closed
    (512, 64, 128, 16896, (512, 512)),    # dots3.longshort-closed
    (512, 128, 128, 16896, (256, 512)),   # twice the heads: a narrower tile
    (528, 16, 64, 1280, (16, 256)),       # 10 chunks: two a block
    (12, 2, 16, 96, (12, 32))])           # no tile divides: the window whole
def test_tiles_come_from_the_shapes(C, J, d, S, tiles):
    assert mla.index_tiles(C, J, d, S) == tiles
    tq, kb = tiles
    assert C % tq == 0 and S % kb == 0
    need = mla.index_vmem_bytes(tq, J, d, kb, 2)
    assert need <= mla._INDEX_TILE_BYTES < mla._INDEX_VMEM_LIMIT
    # the count, by hand at Keye's tile: queries 2 x 1 MB, weights
    # 2 x 256 KB (16 heads take a whole vector of lanes), keys
    # 2 x 128 KB (64 wide: the same), four [512, 512] float32
    if (J, d, S) == (16, 64, 33280):
        assert need == 2 * 2**20 + 2 * 2**18 + 2 * 2**17 + 4 * 2**20


def test_scored_follows_the_context():
    scored = [int(mla.index_scored(p, 33280))
              for p in (0, 511, 512, 8191, 12287, 16383, 33279, 40000)]
    assert scored == [512, 512, 1024, 8192, 12288, 16384, 33280, 33280]


@pytest.mark.parametrize("what,match", [
    ("keys", r"keys \[S, d\] of their dtype"),
    ("dtype", r"keys \[S, d\] of their dtype"),
    ("weights", r"weights \[C, J\]"),
    ("lanes", "cannot run.*over 200 keys"),
    ("narrow_query", "cannot run.*2 heads of 16"),
    ("tile", "cannot run.*a tile of 4 queries")])
def test_shapes_the_tiling_cannot_take_are_refused_by_name(what, match):
    qI, kI, w = operands(8, 2, 16, 256)
    sds = jax.ShapeDtypeStruct
    args = {
        "keys": (qI, kI[:, :8], w, True),
        "dtype": (qI, kI.astype(jnp.float32), w, True),
        "weights": (qI, kI, w[:4], True),
        # on the chip: whole lane tiles of keys and of a query's heads
        "lanes": (jnp.zeros((8, 8, 16)), jnp.zeros((200, 16)),
                  jnp.zeros((8, 8)), False),
        "narrow_query": (qI, kI, w, False),
        # a query's heads past the kernel's VMEM, anywhere
        "tile": (sds((4, 2**14, 128), jnp.bfloat16),
                 sds((256, 128), jnp.bfloat16), sds((4, 2**14), jnp.float32),
                 True)}[what]
    with pytest.raises(ValueError, match="cake_dsa_index.*" + match):
        mla.index_scores_window(*args[:3], jnp.int32(7), interpret=args[3])


def test_the_series_is_on_the_metrics_page():
    series = dict(steps.DSA_COUNTERS)["dsa_index_keys_scored"]
    assert steps.COUNTER_SERIES["dsa_index_keys_scored"] is series
    assert series.name == "cake_dsa_index_keys_scored_total"


# -- the families, end to end ---------------------------------------------------


@pytest.mark.parametrize("name", ["KeyeVL2", "glm_moe_dsa", "dots3_note"])
def test_a_family_serves_what_the_blocked_map_served(name, monkeypatch):
    """The step program with the kernel, then with the blocked
    `lax.map` in its place: the same hidden state at every token to
    float32's rounding of a sum in another order, the same sets for
    the window's real queries, the same counters; the score pass
    visits what the context asks, never more than the table."""
    from test_dsa_select_kernel import _drive

    with_kernel = _drive(name)
    monkeypatch.setattr(mla, "index_scores_window", blocked)
    with_map = _drive(name)
    scored = []
    for (x, sets, n, counters), (x0, sets0, _, counters0) in zip(
            with_kernel, with_map):
        np.testing.assert_allclose(x, x0, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(sets[:, :n], sets0[:, :n])
        assert sets[:, :n].any()
        assert counters == counters0
        assert 0 < counters["dsa_index_keys_scored"] \
            <= counters["dsa_select_keys_table"]
        scored.append(counters["dsa_index_keys_scored"])
    # (a tiny table is a block or two: the bound's own tests are above)
    assert scored == sorted(scored)


def test_the_bench_tool_rehearses_and_checks_the_kernel(capsys):
    """tools/dsa_index_bench.py at tiny widths: one JSON line, the
    kernel's scores compared with the blocked map's, every case timed
    beside what it must move and multiply."""
    import importlib.util
    import json
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "dsa_index_bench.py"
    spec = importlib.util.spec_from_file_location("dsa_index_bench", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearse", "--calls", "2"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert len(line["cases"]) == 2
    for case in line["cases"]:
        assert case["same"] and case["scored"] <= case["S"]
        assert {"kernel_us", "xla_us", "bytes", "flops"} <= set(case)
    assert line["cases"][0]["scored"] < line["cases"][1]["scored"]
