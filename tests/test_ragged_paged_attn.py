"""Ragged paged-attention Pallas kernel vs the fold reference.

The fold (`models/llama/paged.py:paged_attention`) is the documented
reference semantics; the interpret-mode kernel must match it to f32
tolerance on every ragged shape the engine can produce, and a paged
engine running `paged_attn="pallas"` must emit token-identical streams
to `"fold"`. Cases stay tiny — tier-1 runs near its wall budget.
"""

import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.models.llama.paged import (
    paged_attention, paged_attention_mixed,
)
from cake_tpu.ops import ragged_paged_attention as rpa
from cake_tpu.ops.ragged_paged_attention import (
    MIXED_Q_TILE, mixed_q_tiles, ragged_paged_attention,
    ragged_paged_attention_mixed, ragged_paged_mixed_supported,
    ragged_paged_supported,
)

P = 8           # page size
N_PAGES = 12
MAX_PAGES = 5
# every pool is STACKED ([L, N_pages, page, KV*hd], as the engine holds
# it) with different data in every layer, and every parity helper
# compares at the first and the last layer: a kernel (or a fold) that
# ignored its layer index would read the wrong layer's pages
LAYERS = 3
CHECK_LAYERS = (0, LAYERS - 1)
LAYER = 1       # the layer of the direct kernel calls below


def _pool(rng, KV, hd, dtype=jnp.float32):
    k = jnp.asarray(rng.normal(size=(LAYERS, N_PAGES, P, KV * hd)), dtype)
    v = jnp.asarray(rng.normal(size=(LAYERS, N_PAGES, P, KV * hd)), dtype)
    return k, v


def _assert_parity(q, pk, pv, table, pos, atol=1e-5):
    for layer in CHECK_LAYERS:
        want = paged_attention(q, pk, pv, layer, table, pos)
        got = ragged_paged_attention(q, pk, pv, layer, table, pos,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=atol, rtol=atol)


def test_kernel_parity_ragged_pos():
    """Rows at different positions, partial last pages, one row mid-page
    and one on its first token."""
    rng = np.random.default_rng(0)
    pk, pv = _pool(rng, KV=2, hd=16)
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 16)), jnp.float32)
    table = jnp.asarray([[7, 2, 9, -1, -1],
                         [4, 11, -1, -1, -1],
                         [1, -1, -1, -1, -1]], jnp.int32)
    pos = jnp.asarray([2 * P + 5, P + 3, 0], jnp.int32)
    _assert_parity(q, pk, pv, table, pos)


def test_kernel_parity_page_boundaries():
    """pos exactly at page edges: last slot of a page, first of the
    next — the early-exit count must flip at precisely ceil((pos+1)/P)."""
    rng = np.random.default_rng(1)
    pk, pv = _pool(rng, KV=2, hd=16)
    q = jnp.asarray(rng.normal(size=(4, 1, 4, 16)), jnp.float32)
    table = jnp.asarray([[3, 6, 0, 10, 5]] * 4, jnp.int32)
    pos = jnp.asarray([P - 1, P, 2 * P - 1, 2 * P], jnp.int32)
    _assert_parity(q, pk, pv, table, pos)


def test_kernel_parity_unmapped_holes():
    """-1 holes INSIDE the live range (a dropped write's page) and a
    fully-unmapped row must both match the fold: holes masked, the dead
    row emitting zeros."""
    rng = np.random.default_rng(2)
    pk, pv = _pool(rng, KV=2, hd=16)
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 16)), jnp.float32)
    table = jnp.asarray([[4, -1, 11, 3, -1],       # hole at page 1
                         [-1, 2, 7, -1, -1],       # hole at page 0
                         [-1, -1, -1, -1, -1]],    # dead row
                        jnp.int32)
    pos = jnp.asarray([3 * P + 2, 2 * P + 1, P + 4], jnp.int32)
    _assert_parity(q, pk, pv, table, pos)
    dead = ragged_paged_attention(q, pk, pv, LAYER, table, pos,
                                  interpret=True)[2]
    np.testing.assert_array_equal(np.asarray(dead),
                                  np.zeros_like(np.asarray(dead)))


@pytest.mark.parametrize("H,KV", [(8, 2), (6, 3), (4, 4)])
def test_kernel_parity_gqa(H, KV):
    """GQA group sizes 4, 2 and 1 (MHA degenerate case)."""
    rng = np.random.default_rng(3)
    pk, pv = _pool(rng, KV=KV, hd=16)
    q = jnp.asarray(rng.normal(size=(2, 1, H, 16)), jnp.float32)
    table = jnp.asarray([[9, 1, 6, -1, -1], [0, 5, -1, -1, -1]],
                        jnp.int32)
    pos = jnp.asarray([2 * P + 3, P + 6], jnp.int32)
    _assert_parity(q, pk, pv, table, pos)


def test_kernel_parity_bf16_pool():
    """The serving dtype: bf16 pool + bf16 queries (cache_dtype
    default); parity bar loosened to bf16 resolution."""
    rng = np.random.default_rng(4)
    pk, pv = _pool(rng, KV=2, hd=16, dtype=jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(2, 1, 4, 16)), jnp.bfloat16)
    table = jnp.asarray([[7, 2, -1, -1, -1], [4, 11, 3, -1, -1]],
                        jnp.int32)
    pos = jnp.asarray([P + 5, 2 * P + 7], jnp.int32)
    for layer in CHECK_LAYERS:
        want = paged_attention(q, pk, pv, layer, table, pos)
        got = ragged_paged_attention(q, pk, pv, layer, table, pos,
                                     interpret=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=3e-2, rtol=3e-2)


def _assert_mixed_parity(q, pk, pv, table, pos, qlen, atol=1e-5):
    """fold reference == interpret-mode mixed kernel, on REAL query
    columns only (padding columns past q_len are garbage by contract —
    the step fn samples at column q_len - 1)."""
    for layer in CHECK_LAYERS:
        want = np.asarray(paged_attention_mixed(q, pk, pv, layer, table,
                                                pos, qlen))
        got = np.asarray(ragged_paged_attention_mixed(
            q, pk, pv, layer, table, pos, qlen, interpret=True))
        for b in range(q.shape[0]):
            n = int(qlen[b])
            np.testing.assert_allclose(got[b, :n], want[b, :n],
                                       atol=atol, rtol=atol)


def test_mixed_kernel_parity_decode_and_chunk_rows():
    """One launch mixing a decode row (q_len=1), a chunk row straddling
    a page boundary at an arbitrary offset, and a chunk row starting
    mid-page — the token-level continuous-batching shape."""
    rng = np.random.default_rng(10)
    pk, pv = _pool(rng, KV=2, hd=16)
    C = 6
    q = jnp.asarray(rng.normal(size=(3, C, 4, 16)), jnp.float32)
    table = jnp.asarray([[7, 2, 9, -1, -1],
                         [4, 11, 3, -1, -1],
                         [1, 8, -1, -1, -1]], jnp.int32)
    # row0 decode at 2P+5; row1 chunk of 6 from P+3 (straddles into
    # page 2); row2 chunk of 5 from 3 (mid-page start)
    pos = jnp.asarray([2 * P + 5, P + 3, 3], jnp.int32)
    qlen = jnp.asarray([1, 6, 5], jnp.int32)
    _assert_mixed_parity(q, pk, pv, table, pos, qlen)


def test_mixed_kernel_parity_page_boundary_offsets():
    """Chunk windows whose first token sits exactly at a page edge
    (last slot of a page / first of the next): the early-exit count
    must flip at ceil((pos + q_len) / P)."""
    rng = np.random.default_rng(11)
    pk, pv = _pool(rng, KV=2, hd=16)
    C = 4
    q = jnp.asarray(rng.normal(size=(4, C, 4, 16)), jnp.float32)
    table = jnp.asarray([[3, 6, 0, 10, 5]] * 4, jnp.int32)
    pos = jnp.asarray([P - 1, P, 2 * P - 1, 2 * P], jnp.int32)
    qlen = jnp.asarray([4, 4, 1, 3], jnp.int32)
    _assert_mixed_parity(q, pk, pv, table, pos, qlen)


@pytest.mark.parametrize("H,KV", [(8, 2), (6, 3), (4, 4), (16, 1)])
def test_mixed_kernel_parity_gqa(H, KV):
    """GQA group sizes 4, 2, 1 and 16 on a mixed decode+chunk batch."""
    rng = np.random.default_rng(12)
    pk, pv = _pool(rng, KV=KV, hd=16)
    C = 5
    q = jnp.asarray(rng.normal(size=(2, C, H, 16)), jnp.float32)
    table = jnp.asarray([[9, 1, 6, -1, -1], [0, 5, 2, -1, -1]],
                        jnp.int32)
    pos = jnp.asarray([2 * P + 3, P + 6], jnp.int32)
    qlen = jnp.asarray([1, 5], jnp.int32)
    _assert_mixed_parity(q, pk, pv, table, pos, qlen)


def test_mixed_kernel_parity_unmapped_holes():
    """-1 holes inside the live range, a chunk row whose window's own
    page is mapped but an EARLIER page is a hole, and a fully-dead row
    (q_len=0) emitting zeros."""
    rng = np.random.default_rng(13)
    pk, pv = _pool(rng, KV=2, hd=16)
    C = 4
    q = jnp.asarray(rng.normal(size=(3, C, 4, 16)), jnp.float32)
    table = jnp.asarray([[4, -1, 11, 3, -1],       # hole at page 1
                         [-1, 2, 7, -1, -1],       # hole at page 0
                         [-1, -1, -1, -1, -1]],    # dead row
                        jnp.int32)
    pos = jnp.asarray([2 * P + 2, P + 1, 0], jnp.int32)
    qlen = jnp.asarray([4, 3, 0], jnp.int32)
    _assert_mixed_parity(q, pk, pv, table, pos, qlen)
    dead = ragged_paged_attention_mixed(q, pk, pv, LAYER, table, pos,
                                        qlen, interpret=True)[2]
    np.testing.assert_array_equal(np.asarray(dead),
                                  np.zeros_like(np.asarray(dead)))


def test_mixed_fold_decode_row_bitwise_matches_decode_fold():
    """A q_len=1 mixed row through the fold reference is bit-identical
    to the decode fold — the mixed == dense token-equality bar rests
    on this."""
    rng = np.random.default_rng(14)
    pk, pv = _pool(rng, KV=2, hd=16)
    q = jnp.asarray(rng.normal(size=(2, 1, 4, 16)), jnp.float32)
    table = jnp.asarray([[7, 2, -1, -1, -1], [4, 11, 3, -1, -1]],
                        jnp.int32)
    pos = jnp.asarray([P + 5, 2 * P + 7], jnp.int32)
    want = paged_attention(q, pk, pv, LAYER, table, pos)
    got = paged_attention_mixed(q, pk, pv, LAYER, table, pos,
                                jnp.ones(2, jnp.int32))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# -- the decode kernel walks a row's live pages itself ---------------------------
#
# One grid step a row; the row's pages come through a ring of
# RING_DEPTH slots (decode_ring_depth gives a test's 1 KiB page the
# longest ring there is, which a five-page table never wraps), the
# copies ahead running on into the next rows.

RING_DEPTH = 3
FULL = MAX_PAGES * P - 1        # the last position a table holds
_DEAD = [-1] * MAX_PAGES
# name: (table rows, positions)
WALK_CASES = {
    # an idle slot between two live rows: the copies ahead of row 0
    # are row 2's
    "dead_row_between": ([[7, 2, 9, -1, -1], _DEAD, [4, 11, 3, 1, -1]],
                         [2 * P + 5, 0, 3 * P + 2]),
    # dead rows first and last: the call's first copies skip a row,
    # its last row starts none
    "dead_rows_at_the_ends": ([_DEAD, [5, 8, -1, -1, -1], _DEAD],
                              [P + 4, P + 1, 0]),
    "live_pages_fill_the_table": ([[3, 6, 1, 10, 5], [8, 2, 7, 4, 9]],
                                  [FULL, FULL - P + 1]),
    "one_live_page": ([[6, -1, -1, -1, -1], [9, -1, -1, -1, -1]],
                      [0, P - 1]),
    # more live pages than the ring has slots, then fewer
    "ring_wraps_and_does_not": ([[3, 6, 1, 10, 5], [8, 2, -1, -1, -1],
                                 [11, 4, 7, 9, -1]],
                                [FULL, P + 3, 3 * P + 6]),
    # holes inside the live range, page 0 of the pool poisoned: a hole
    # that read page 0 (as a clamped block index did) would show
    "hole_reads_no_page": ([[4, -1, 11, 3, -1], [-1, 2, -1, 7, -1],
                            [-1, -1, 5, -1, -1]],
                           [3 * P + 2, 3 * P, 2 * P + 1]),
}


def _poisoned(kind, pool):
    """`pool` with NaN wherever a read of its page 0 would pick it up:
    in the page of a float pool, in the page's scales otherwise."""
    if kind == "f32":
        return pool.at[:, 0].set(jnp.nan)
    return pool._replace(scale=pool.scale.at[:, 0].set(jnp.nan))


@pytest.mark.parametrize("case", sorted(WALK_CASES))
@pytest.mark.parametrize("kind", ["f32", "int8", "int4"])
def test_decode_kernel_walks_live_pages(kind, case, monkeypatch):
    monkeypatch.setattr(rpa, "decode_ring_depth", lambda page_bytes:
                        RING_DEPTH)
    rng = np.random.default_rng(40)
    pk, pv = (_poisoned(kind, pool)
              for pool in _SWEEP_POOLS[kind](rng, 2, 16))
    rows, pos = WALK_CASES[case]
    assert 0 not in {p for row in rows for p in row}
    table = jnp.asarray(rows, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    q = jnp.asarray(rng.normal(size=(len(rows), 1, 4, 16)), jnp.float32)
    for layer in CHECK_LAYERS:
        want = np.asarray(paged_attention(q, pk, pv, layer, table, pos))
        if kind == "f32":
            got = ragged_paged_attention(q, pk, pv, layer, table, pos,
                                         interpret=True)
        else:
            got = ragged_paged_attention(
                q, pk.q, pv.q, layer, table, pos, scale_k=pk.scale,
                scale_v=pv.scale, packed4=kind == "int4", interpret=True)
        got = np.asarray(got)
        assert np.isfinite(want).all()
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        for b, row in enumerate(rows):
            if all(p < 0 for p in row):
                assert not got[b].any()


# -- the decode kernel under a selection (a sparse indexer's set) ------------

SEL_H, SEL_KV = 32, 4           # a group of 8, Keye's


def _sel_all(pos):
    return np.arange(MAX_PAGES * P) <= pos


def _sel_pages_skipped(skipped):
    """Every visible key but those of the logical pages `skipped`."""
    def sel(pos):
        m = _sel_all(pos)
        for j in skipped:
            m[j * P:(j + 1) * P] = False
        return m
    return sel


def _sel_scattered(pos):
    rng = np.random.default_rng(pos + 1)
    return _sel_all(pos) & (rng.random(MAX_PAGES * P) < 0.3)


# name: (table rows, positions, a row's position -> its selection
# [MAX_PAGES * P] bool)
SELECTED_CASES = {
    # a row shorter than topk: every visible key selected, the
    # unselected kernel's result bit for bit
    "all_visible_selected": ([[7, 2, 9, -1, -1], [4, 11, -1, -1, -1]],
                             [2 * P + 5, P + 3], _sel_all),
    "scattered": ([[7, 2, 9, 5, -1], [4, 11, 3, 1, 8]],
                  [3 * P + 2, FULL], _sel_scattered),
    # a page with NO selected key: the row's first (nothing folded yet:
    # the guard), one in mid-row, and the row's last
    "first_page_unselected": ([[7, 2, 9, -1, -1], [4, 11, 3, -1, -1]],
                              [2 * P + 5, 2 * P], _sel_pages_skipped([0])),
    "mid_page_unselected": ([[7, 2, 9, 5, -1], [4, 11, 3, -1, -1]],
                            [3 * P + 2, 2 * P + 7], _sel_pages_skipped([1])),
    "last_page_unselected": ([[7, 2, 9, 5, -1], [4, 11, 3, -1, -1]],
                             [3 * P + 2, 2 * P + 7],
                             lambda pos: _sel_pages_skipped([pos // P])(pos)),
    # an idle row (position -1) between two live ones: zeros, no trip
    "idle_row_between": ([[7, 2, 9, -1, -1], _DEAD, [4, 11, 3, 1, -1]],
                         [2 * P + 5, -1, 3 * P + 2], _sel_scattered),
    # a mask that marks keys PAST the position: never attended
    "selects_past_the_position": (
        [[7, 2, 9, 5, 6], [4, 11, 3, 1, 8]], [P + 2, 2 * P + 1],
        lambda pos: np.ones(MAX_PAGES * P, bool)),
    # a row whose selection is empty: zeros
    "nothing_selected": ([[7, 2, 9, -1, -1], [4, 11, -1, -1, -1]],
                         [2 * P + 5, P + 3],
                         lambda pos: np.zeros(MAX_PAGES * P, bool)),
}


def _selected_inputs(case):
    rng = np.random.default_rng(43)
    pk, pv = _pool(rng, KV=SEL_KV, hd=16)
    rows, pos, sel = SELECTED_CASES[case]
    q = jnp.asarray(rng.normal(size=(len(rows), 1, SEL_H, 16)), jnp.float32)
    mask = np.stack([sel(p) for p in pos])
    return (q, pk, pv, jnp.asarray(rows, jnp.int32),
            jnp.asarray(pos, jnp.int32), mask)


@pytest.mark.parametrize("case", sorted(SELECTED_CASES))
def test_decode_kernel_attends_under_a_selection(case, monkeypatch):
    """`selected=` [B, max_pages, page]: the kernel walks the row's own
    live pages and attends the marked keys alone; against the fold
    given the same mask, and against exact softmax attention over the
    chosen keys."""
    monkeypatch.setattr(rpa, "decode_ring_depth", lambda page_bytes:
                        RING_DEPTH)
    q, pk, pv, table, pos, mask = _selected_inputs(case)
    B = q.shape[0]
    selected = jnp.asarray(mask, jnp.float32).reshape(B, MAX_PAGES, P)
    for layer in CHECK_LAYERS:
        want = np.asarray(paged_attention(q, pk, pv, layer, table, pos,
                                          selected=selected))
        got = np.asarray(ragged_paged_attention(
            q, pk, pv, layer, table, pos, selected=selected, interpret=True))
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        for b in range(B):
            seen = mask[b] & (np.arange(MAX_PAGES * P) <= int(pos[b]))
            if not seen.any():
                assert not got[b].any()
                continue
            pages = np.asarray(table[b])
            keys = np.asarray(pk[layer])[pages].reshape(-1, SEL_KV, 16)[seen]
            vals = np.asarray(pv[layer])[pages].reshape(-1, SEL_KV, 16)[seen]
            qb = np.asarray(q[b, 0]).reshape(SEL_KV, SEL_H // SEL_KV, 16)
            s = np.einsum("kgd,skd->kgs", qb, keys) / 4.0
            p = np.exp(s - s.max(-1, keepdims=True))
            exact = np.einsum("kgs,skd->kgd", p / p.sum(-1, keepdims=True),
                              vals)
            np.testing.assert_allclose(got[b, 0].reshape(exact.shape), exact,
                                       atol=2e-5)
        if case == "all_visible_selected":
            plain = ragged_paged_attention(q, pk, pv, layer, table, pos,
                                           interpret=True)
            assert np.array_equal(got, np.asarray(plain))


@pytest.mark.parametrize("refused", ["quantized", "window"])
def test_a_selection_is_refused_over_a_quantized_pool_or_a_band(refused):
    q, pk, pv, table, pos, mask = _selected_inputs("scattered")
    selected = jnp.asarray(mask, jnp.float32).reshape(-1, MAX_PAGES, P)
    kw = {}
    if refused == "quantized":
        qk, qv = _qpools(np.random.default_rng(0), SEL_KV, 16)
        pk, pv, kw = qk.q, qv.q, dict(scale_k=qk.scale, scale_v=qv.scale)
    else:
        kw = dict(window=P)
    with pytest.raises(ValueError, match="float pool without a band"):
        ragged_paged_attention(q, pk, pv, LAYER, table, pos,
                               selected=selected, interpret=True, **kw)
    if refused == "window":
        with pytest.raises(ValueError, match="without a band"):
            paged_attention(q, pk, pv, LAYER, table, pos, window=P,
                            selected=selected)


# sha256 of the kernel's lowered text with no selection, taken on the
# commit before the kernel took `selected=` (c8c6a0b, PR 65's tree; this
# file's call under conftest's settings): None adds no operand, no
# branch and no instruction
DECODE_LOWERED_BEFORE = (
    "9d9f68f57bf15f6ad37c43bb3e2cde087c4d3118334f12c069bbda3f6af8904b")


def test_no_selection_lowers_to_the_kernel_it_was():
    import hashlib
    rng = np.random.default_rng(41)
    pk, pv = _pool(rng, KV=2, hd=16)
    lowered = jax.jit(lambda *a: ragged_paged_attention(
        *a, interpret=True)).lower(
        jnp.zeros((3, 1, 4, 16), jnp.float32), pk, pv, jnp.int32(LAYER),
        jnp.zeros((3, MAX_PAGES), jnp.int32), jnp.zeros(3, jnp.int32))
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    assert digest == DECODE_LOWERED_BEFORE, digest
    # and with one: a fourth block, the row's [max_pages, page] of it
    jaxpr = jax.make_jaxpr(lambda *a: ragged_paged_attention(
        *a[:-1], selected=a[-1], interpret=True))(
        jnp.zeros((3, 1, 4, 16), jnp.float32), pk, pv, jnp.int32(LAYER),
        jnp.zeros((3, MAX_PAGES), jnp.int32), jnp.zeros(3, jnp.int32),
        jnp.ones((3, MAX_PAGES, P), jnp.float32))
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    blocks = [str(bm.block_aval)
              for bm in call.params["grid_mapping"].block_mappings]
    assert len(blocks) == 5 and f"[1,{MAX_PAGES},{P}]" in blocks[3]


def test_decode_kernel_grid_is_one_step_a_row():
    """The traced call: a grid of (rows,), whatever the table's width,
    and the pool handed over whole, outside VMEM."""
    rng = np.random.default_rng(41)
    pk, pv = _pool(rng, KV=2, hd=16)
    q = jnp.zeros((3, 1, 4, 16), jnp.float32)
    table = jnp.zeros((3, MAX_PAGES), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: ragged_paged_attention(
        *a, interpret=True))(q, pk, pv, jnp.int32(LAYER), table,
                             jnp.zeros(3, jnp.int32))
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    mapping = call.params["grid_mapping"]
    assert mapping.grid == (3,)
    assert call.params["name"] == "cake_decode_attn"
    blocks = [str(bm.block_aval) for bm in mapping.block_mappings]
    assert [b.startswith("Ref<any>") for b in blocks] == [
        False, True, True, False]                  # q, pool_k, pool_v, out


def test_decode_ring_depth_follows_the_page_bytes():
    """A page copy ahead for every _RING_BYTES_AHEAD / (K + V page)
    bytes, one at least: the cells' shapes (128-token bf16 pages of 2,
    8 and 16 KV heads) and the int8 tier's."""
    KiB = 1024
    assert [rpa.decode_ring_depth(b * KiB) for b in (64, 256, 512)] == [
        9, 3, 2]
    assert rpa.decode_ring_depth(128 * KiB) == 5       # int8, 8 KV heads
    assert rpa.decode_ring_depth(4096 * KiB) == 2
    assert rpa.decode_ring_depth(1 * KiB) == 1 + rpa._RING_PAGES_AHEAD_MAX


# -- the shapes the 8B server dispatches, on whatever backend runs them --------
#
# H=32, KV=8, hd=128, 128-token pages, bf16 queries: Llama-3-8B's
# attention exactly, at a few rows and pages so the interpret-mode CPU
# lane takes seconds. interpret=None, so under CAKE_TESTS_TPU=1 these
# are the REAL Mosaic kernels on the chip — the only tests that are
# (every other case pins interpret=True at hd=16).
#
# Tolerance 2e-2 (abs and rel), and why: outputs are bf16, whose half
# ulp is 2**-9 ~ 2e-3 below 1 and 8e-3 below 4, and the two sides round
# at different points — the kernel casts p to bf16 before the PV dot on
# a float pool and keeps int pages exact (scale applied in f32 after
# the dot), the fold rounds dequantized pages to bf16 and merges page
# stats in another order. Each is a ~2**-9 relative effect; 2e-2 is a
# few output ulps. A wrong page, a wrong mask edge or a swapped head is
# an O(1) error on unit-variance values, 50x over the bar.

PROD = dict(H=32, KV=8, hd=128, P=128, n_pages=6)
PROD_LAYERS = 2         # the kernels read the LAST layer of the stack
PROD_TOL = 2e-2


def _prod_pool(rng, kind):
    """(pool_k, pool_v) of `kind` bf16 | int8 | int4 at PROD shapes, as
    models/llama/paged.py holds them: stacked over PROD_LAYERS layers
    of different data, the two minor axes flattened (QuantPool /
    Int4Pool halves for the quantized kinds)."""
    from cake_tpu.kv.quantized_pool import (
        Int4Pool, QuantPool, pack_page_nibbles,
    )
    shape = (PROD_LAYERS, PROD["n_pages"], PROD["P"], PROD["KV"],
             PROD["hd"])

    def flat(a):
        return a.reshape(a.shape[:-2] + (-1,))

    def half():
        x = rng.normal(size=shape).astype(np.float32)
        if kind == "bf16":
            return jnp.asarray(flat(x), jnp.bfloat16)
        qmax = 127.0 if kind == "int8" else 7.0
        scale = np.abs(x).max(axis=(2, 4)) / qmax          # [L, N, KV]
        q = np.clip(np.round(x / scale[:, :, None, :, None]), -qmax, qmax)
        if kind == "int8":
            return QuantPool(q=jnp.asarray(flat(q), jnp.int8),
                             scale=jnp.asarray(scale, jnp.float32))
        return Int4Pool(
            q=flat(pack_page_nibbles(jnp.asarray(q, jnp.int8))),
            scale=jnp.asarray(scale, jnp.float32))

    return half(), half()


_PROD_TABLE = [[4, 1, 5], [2, 0, -1], [3, -1, -1]]


@pytest.mark.parametrize("kind", ["bf16", "int8", "int4"])
def test_decode_kernel_real_backend_production_shapes(kind):
    rng = np.random.default_rng(20)
    pk, pv = _prod_pool(rng, kind)
    P = PROD["P"]
    q = jnp.asarray(rng.normal(size=(3, 1, PROD["H"], PROD["hd"])),
                    jnp.bfloat16)
    table = jnp.asarray(_PROD_TABLE, jnp.int32)
    # mid third page, last slot of the first page, first token
    pos = jnp.asarray([2 * P + 37, P - 1, 0], jnp.int32)
    layer = jnp.int32(PROD_LAYERS - 1)
    want = paged_attention(q, pk, pv, layer, table, pos, impl="fold")
    got = paged_attention(q, pk, pv, layer, table, pos, impl="pallas")
    assert got.dtype == jnp.bfloat16 and got.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=PROD_TOL, rtol=PROD_TOL)


@pytest.mark.parametrize("kind", ["bf16", "int8", "int4"])
def test_mixed_kernel_real_backend_production_shapes(kind):
    """C=128 is the width a default 8B server resolves to
    (engine._resolve_paged_attn): a decode row, a full window that
    straddles a page edge, and a short window from position 0."""
    rng = np.random.default_rng(21)
    pk, pv = _prod_pool(rng, kind)
    P, C = PROD["P"], 128
    q = jnp.asarray(rng.normal(size=(3, C, PROD["H"], PROD["hd"])),
                    jnp.bfloat16)
    table = jnp.asarray(_PROD_TABLE, jnp.int32)
    pos = jnp.asarray([2 * P + 37, 100, 0], jnp.int32)
    qlen = jnp.asarray([1, 128, 77], jnp.int32)
    layer = jnp.int32(PROD_LAYERS - 1)
    want = np.asarray(paged_attention_mixed(
        q, pk, pv, layer, table, pos, qlen, impl="fold"), np.float32)
    got = np.asarray(paged_attention_mixed(
        q, pk, pv, layer, table, pos, qlen, impl="pallas"), np.float32)
    assert np.isfinite(got).all()
    for b, n in enumerate(np.asarray(qlen)):
        np.testing.assert_allclose(got[b, :n], want[b, :n],
                                   atol=PROD_TOL, rtol=PROD_TOL)


def test_supported_gate():
    assert not ragged_paged_supported(P, H=5, KV=2, hd=16)  # H % KV
    # interpret mode takes any shape; so does the chip for a float pool
    # down to hd=16 and 8-token pages (checked on a v5e, PR 21) whose
    # page row fills whole lane tiles (KV*hd a multiple of 128, PR 42)
    assert ragged_paged_supported(P, H=8, KV=8, hd=16)
    assert ragged_paged_supported(128, H=4, KV=2, hd=128)


def test_supported_gate_on_chip_shape_classes(monkeypatch):
    """On a TPU the gate admits what was verified on silicon: float
    pools down to hd=16 / 8-token pages, quantized pools at the
    production class only, and the mixed kernel only for power-of-two
    GQA groups (Mosaic refuses the [C, 7, hd] -> [7C, hd] cast)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ragged_paged_supported(8, H=8, KV=8, hd=16)
    assert ragged_paged_supported(128, H=16, KV=4, hd=64)
    assert not ragged_paged_supported(8, H=4, KV=2, hd=20)
    assert not ragged_paged_supported(8, H=4, KV=2, hd=16, quantized=True)
    assert ragged_paged_supported(128, H=32, KV=8, hd=128, quantized=True)
    assert not ragged_paged_supported(32, H=32, KV=8, hd=128,
                                      packed4=True)
    assert ragged_paged_supported(128, H=28, KV=4, hd=128)       # decode
    assert not ragged_paged_mixed_supported(128, H=28, KV=4, hd=128,
                                            q_width=16)          # G=7
    assert ragged_paged_mixed_supported(128, H=32, KV=4, hd=128,
                                        q_width=16)              # G=8


# (KV, hd): what the v5e compiler said of the decode kernel's copies at
# each page row (ahead of time, PR 42; float32 and bfloat16, pages of
# 8, 16, 64 and 128 tokens alike)
@pytest.mark.parametrize("KV,hd,compiles", [
    (2, 16, False), (1, 64, False), (3, 64, False), (2, 96, False),
    (8, 16, True), (2, 64, True), (1, 128, True), (4, 96, True)])
def test_decode_gate_wants_whole_lane_tiles(monkeypatch, KV, hd, compiles):
    """The kernels' own copies slice a (page, KV*hd) tile out of the
    pool in HBM, which Mosaic admits at a multiple of 128 lanes only:
    the decode kernel's since PR 42, the mixed kernel's since it walks
    its pages too (PR 62); off the chip (interpret mode) every shape
    passes."""
    H = 2 * KV
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ragged_paged_supported(16, H=H, KV=KV, hd=hd)
    assert ragged_paged_mixed_supported(16, H=H, KV=KV, hd=hd, q_width=8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ragged_paged_supported(16, H=H, KV=KV, hd=hd) == compiles
    assert ragged_paged_mixed_supported(16, H=H, KV=KV, hd=hd,
                                        q_width=8) == compiles


def test_mixed_supported_gate_bounds_scratch_vmem(monkeypatch):
    """The mixed kernel's VMEM scratch scales linearly with the query
    width C — the gate must send an oversized --prefill-chunk to the
    fold reference instead of letting Mosaic fail allocation at the
    first mixed dispatch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # production-tileable shape (hd=128, page%16): decode-width OK ...
    assert ragged_paged_mixed_supported(16, H=32, KV=8, hd=128, q_width=1)
    assert ragged_paged_mixed_supported(16, H=32, KV=8, hd=128, q_width=64)
    # ... and the 8B line the compiler drew (v5e, PR 21): C=128 needs
    # 11 MiB of the 16 MiB scoped limit and compiles, C=256 needs 21
    assert ragged_paged_mixed_supported(128, H=32, KV=8, hd=128,
                                        q_width=128)
    assert not ragged_paged_mixed_supported(128, H=32, KV=8, hd=128,
                                            q_width=256)
    assert not ragged_paged_mixed_supported(16, H=32, KV=8, hd=128,
                                            q_width=512)
    # the decode gate's rules still apply before the VMEM bound
    assert not ragged_paged_mixed_supported(P, H=4, KV=2, hd=20, q_width=1)


def test_supported_gate_bounds_int8_scale_smem(monkeypatch):
    """The int8 kernels scalar-prefetch whole-pool [N_pages, KV] f32
    scale arrays into SMEM — the gate must send a pathologically
    page-count-heavy pool to the fold instead of letting Mosaic fail
    SMEM allocation at the first dispatch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # production-scale pool fits (4096 pages x 8 kv heads = 256 KB of
    # the 1 MiB: the scales ride flat, not padded [N, KV] rows)
    assert ragged_paged_supported(128, H=32, KV=8, hd=128,
                                  quantized=True, n_pages=4096)
    assert not ragged_paged_supported(128, H=32, KV=8, hd=128,
                                      quantized=True, n_pages=100_000)
    # the page table shares that memory, minor dim padded to 128 words
    assert not ragged_paged_supported(128, H=32, KV=8, hd=128,
                                      slots=4096, max_pages=64)
    # the bound is int8-only (f32 pools carry no scale operands) and
    # rides through the mixed gate
    assert ragged_paged_supported(128, H=32, KV=8, hd=128,
                                  n_pages=100_000)
    assert not ragged_paged_mixed_supported(128, H=32, KV=8, hd=128,
                                            q_width=1, quantized=True,
                                            n_pages=100_000)


def test_engine_pallas_matches_fold(tiny_config):
    """Engine-level smoke: a paged engine with paged_attn="pallas"
    produces identical token ids to "fold" on a 2-request workload.

    f32 cache AND f32 params: the parity bar is the KERNEL against the
    fold at equal numeric precision. With bf16 activations the fold
    downcasts the f32 pool to the query dtype on read
    (partial_attention_stats) while the kernel streams the pages at
    storage precision — a real 1e-2-scale asymmetry that flips greedy
    near-ties and would test the mixed-precision policy, not the
    kernel. (Production configs store bf16 pages, where both impls
    read identical values.)"""
    import jax.numpy as jnp

    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.models.llama.params import init_params
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    # 2 KV heads of 64: a page row of 128 lanes, the narrowest the
    # decode kernel's copies take on a chip (ragged_paged_supported)
    tiny_config = type(tiny_config).tiny(hidden_size=256)
    params = init_params(tiny_config, jax.random.PRNGKey(0),
                         dtype=jnp.float32)
    prompts = [[5] * 9, [3, 7, 9, 11, 2]]

    def run(impl):
        eng = InferenceEngine(
            tiny_config, params,
            ByteTokenizer(tiny_config.vocab_size),
            max_slots=2, max_seq_len=64,
            sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
            cache_dtype=jnp.float32,
            kv_pages=10, kv_page_size=8, paged_attn=impl)
        assert eng.paged_attn == impl
        with eng:
            hs = [eng.submit(p, max_new_tokens=5, temperature=0.0,
                             repeat_penalty=1.0) for p in prompts]
            assert all(h.wait(timeout=300) for h in hs)
            return [list(h._req.out_tokens) for h in hs]

    assert run("pallas") == run("fold")


def test_engine_pallas_records_step_histogram(tiny_config, tiny_params):
    """The paged engine observes cake_paged_attn_step_seconds on both
    of its paths: the mixed step and the pure-decode step."""
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.obs import metrics as obs_metrics
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    fam = obs_metrics.REGISTRY.get("cake_paged_attn_step_seconds")
    assert fam is not None
    paths = ("decode", "mixed")
    before = {p: fam.labels(path=p).count for p in paths}

    eng = InferenceEngine(
        tiny_config, tiny_params,
        ByteTokenizer(tiny_config.vocab_size),
        max_slots=2, max_seq_len=64,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        kv_pages=10, kv_page_size=8, paged_attn="fold")
    with eng:
        h = eng.submit([5] * 9, max_new_tokens=4, temperature=0.0,
                       repeat_penalty=1.0)
        assert h.wait(timeout=300)
    assert fam.labels(path="mixed").count > before["mixed"]
    assert fam.labels(path="decode").count > before["decode"]
    rendered = obs_metrics.REGISTRY.render()
    assert 'cake_paged_attn_step_seconds_bucket{path="decode"' in rendered


# -- int8 KV parity (cake_tpu/kv quantized pool) ------------------------------
#
# The fold over a QuantPool (dequantize per page inside the loop) is
# the bit-exact reference for the int8 kernels, exactly as the f32
# fold is for the f32 kernels; the int8 kernels stream int8 pages and
# apply the per-(page, kv-head) scales to the dot outputs.


def _qpools(rng, KV, hd):
    """Two quantized pools (k, v) built through the production writer
    (qwrite_prompt_pages), so every page carries its own per-head
    scale from its own amax."""
    from cake_tpu.kv.quantized_pool import QuantPool

    pool = QuantPool(
        q=jnp.zeros((LAYERS, N_PAGES, P, KV * hd), jnp.int8),
        scale=jnp.zeros((LAYERS, N_PAGES, KV), jnp.float32))
    return _fill_layers(rng, pool, KV, hd), _fill_layers(rng, pool, KV, hd)


def _fill_layers(rng, pool, KV, hd):
    """Every page of every layer of a stacked quantized pool written
    through the production writer, each layer with its own data."""
    from cake_tpu.kv.quantized_pool import qwrite_prompt_pages

    for layer in range(LAYERS):
        vals = jnp.asarray(rng.normal(size=(1, N_PAGES * P, KV, hd)),
                           jnp.float32)
        pool = qwrite_prompt_pages(
            pool, layer, vals, jnp.arange(N_PAGES, dtype=jnp.int32))
    return pool


def _assert_parity_q8(q, pk, pv, table, pos, atol=2e-5, packed4=False):
    for layer in CHECK_LAYERS:
        want = paged_attention(q, pk, pv, layer, table, pos)
        got = ragged_paged_attention(q, pk.q, pv.q, layer, table, pos,
                                     scale_k=pk.scale, scale_v=pv.scale,
                                     packed4=packed4, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=atol, rtol=atol)


def _assert_mixed_parity_q8(q, pk, pv, table, pos, qlen, atol=2e-5,
                            packed4=False):
    for layer in CHECK_LAYERS:
        want = np.asarray(paged_attention_mixed(q, pk, pv, layer, table,
                                                pos, qlen))
        got = np.asarray(ragged_paged_attention_mixed(
            q, pk.q, pv.q, layer, table, pos, qlen, scale_k=pk.scale,
            scale_v=pv.scale, packed4=packed4, interpret=True))
        for b in range(q.shape[0]):
            n = int(qlen[b])
            np.testing.assert_allclose(got[b, :n], want[b, :n],
                                       atol=atol, rtol=atol)


def test_kernel_parity_int8_page_boundaries():
    """int8 decode kernel at page-edge positions: the early exit must
    flip at ceil((pos+1)/P) with scales following the page stream."""
    rng = np.random.default_rng(20)
    pk, pv = _qpools(rng, KV=2, hd=16)
    q = jnp.asarray(rng.normal(size=(4, 1, 4, 16)), jnp.float32)
    table = jnp.asarray([[3, 6, 0, 10, 5]] * 4, jnp.int32)
    pos = jnp.asarray([P - 1, P, 2 * P - 1, 2 * P], jnp.int32)
    _assert_parity_q8(q, pk, pv, table, pos)


@pytest.mark.parametrize("H,KV", [(8, 2), (6, 3), (4, 4)])
def test_kernel_parity_int8_gqa(H, KV):
    """int8 decode kernel at GQA group sizes 4, 2 and 1: each query
    group must read its own kv head's scale."""
    rng = np.random.default_rng(21)
    pk, pv = _qpools(rng, KV=KV, hd=16)
    q = jnp.asarray(rng.normal(size=(2, 1, H, 16)), jnp.float32)
    table = jnp.asarray([[9, 1, 6, -1, -1], [0, 5, -1, -1, -1]],
                        jnp.int32)
    pos = jnp.asarray([2 * P + 3, P + 6], jnp.int32)
    _assert_parity_q8(q, pk, pv, table, pos)


def test_kernel_parity_int8_unmapped_holes():
    """int8 decode kernel with -1 holes inside the live range and a
    fully-dead row: holes masked (their clamped page-0 scale must not
    leak), dead row zeros."""
    rng = np.random.default_rng(22)
    pk, pv = _qpools(rng, KV=2, hd=16)
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 16)), jnp.float32)
    table = jnp.asarray([[4, -1, 11, 3, -1],
                         [-1, 2, 7, -1, -1],
                         [-1, -1, -1, -1, -1]], jnp.int32)
    pos = jnp.asarray([3 * P + 2, 2 * P + 1, P + 4], jnp.int32)
    _assert_parity_q8(q, pk, pv, table, pos)
    dead = ragged_paged_attention(q, pk.q, pv.q, LAYER, table, pos,
                                  scale_k=pk.scale, scale_v=pv.scale,
                                  interpret=True)[2]
    np.testing.assert_array_equal(np.asarray(dead),
                                  np.zeros_like(np.asarray(dead)))


def test_mixed_kernel_parity_int8_offsets_and_holes():
    """int8 MIXED kernel: a decode row, a chunk row straddling a page
    boundary at an arbitrary offset, a chunk row behind an unmapped
    hole, and an idle row (q_len=0) in one launch."""
    rng = np.random.default_rng(23)
    pk, pv = _qpools(rng, KV=2, hd=16)
    C = 6
    q = jnp.asarray(rng.normal(size=(4, C, 4, 16)), jnp.float32)
    table = jnp.asarray([[7, 2, 9, -1, -1],
                         [4, 11, 3, -1, -1],
                         [-1, 8, 5, -1, -1],
                         [-1, -1, -1, -1, -1]], jnp.int32)
    pos = jnp.asarray([2 * P + 5, P + 3, P + 2, 0], jnp.int32)
    qlen = jnp.asarray([1, 6, 4, 0], jnp.int32)
    _assert_mixed_parity_q8(q, pk, pv, table, pos, qlen)


@pytest.mark.parametrize("H,KV", [(8, 2), (6, 3), (4, 4), (16, 1)])
def test_mixed_kernel_parity_int8_gqa(H, KV):
    """int8 mixed kernel at GQA group sizes 4, 2, 1 and 16."""
    rng = np.random.default_rng(24)
    pk, pv = _qpools(rng, KV=KV, hd=16)
    C = 5
    q = jnp.asarray(rng.normal(size=(2, C, H, 16)), jnp.float32)
    table = jnp.asarray([[9, 1, 6, -1, -1], [0, 5, 2, -1, -1]],
                        jnp.int32)
    pos = jnp.asarray([2 * P + 3, P + 6], jnp.int32)
    qlen = jnp.asarray([1, 5], jnp.int32)
    _assert_mixed_parity_q8(q, pk, pv, table, pos, qlen)


def test_supported_gate_int8_page_tiling():
    """On silicon an int8 pool needs page_size % 32 (the int8 sublane
    tile); interpret mode takes any shape."""
    if jax.default_backend() == "tpu":
        assert ragged_paged_supported(128, H=4, KV=2, hd=128,
                                      quantized=True)
        assert not ragged_paged_supported(16, H=4, KV=2, hd=128,
                                          quantized=True)
        assert ragged_paged_supported(16, H=4, KV=2, hd=128)
    else:
        assert ragged_paged_supported(P, H=4, KV=2, hd=16,
                                      quantized=True)


# -- int4 KV parity (cake_tpu/kv nibble-packed pool) --------------------------
#
# Same contract as int8 one tier down: the fold over an Int4Pool
# (unpack + dequantize per page inside the loop) is the bit-exact
# reference; the int4 kernels stream nibble-PACKED uint8 pages,
# unpack in-register, and apply the per-(page, kv-head) scales to the
# dot outputs.


def _q4pools(rng, KV, hd):
    """Two nibble-packed pools (k, v) built through the production
    writer (qwrite_prompt_pages dispatches on the pool type), so every
    page carries its own per-head scale from its own amax."""
    from cake_tpu.kv.quantized_pool import Int4Pool

    pool = Int4Pool(
        q=jnp.zeros((LAYERS, N_PAGES, P // 2, KV * hd), jnp.uint8),
        scale=jnp.zeros((LAYERS, N_PAGES, KV), jnp.float32))
    return _fill_layers(rng, pool, KV, hd), _fill_layers(rng, pool, KV, hd)


def _assert_parity_q4(q, pk, pv, table, pos):
    _assert_parity_q8(q, pk, pv, table, pos, packed4=True)


def _assert_mixed_parity_q4(q, pk, pv, table, pos, qlen):
    _assert_mixed_parity_q8(q, pk, pv, table, pos, qlen, packed4=True)


def test_kernel_parity_int4_page_boundaries():
    """int4 decode kernel at page-edge positions: the early exit flips
    at ceil((pos+1)/P) in REAL tokens (the packed axis holds P//2
    rows), with scales following the page stream."""
    rng = np.random.default_rng(30)
    pk, pv = _q4pools(rng, KV=2, hd=16)
    q = jnp.asarray(rng.normal(size=(4, 1, 4, 16)), jnp.float32)
    table = jnp.asarray([[3, 6, 0, 10, 5]] * 4, jnp.int32)
    pos = jnp.asarray([P - 1, P, 2 * P - 1, 2 * P], jnp.int32)
    _assert_parity_q4(q, pk, pv, table, pos)


@pytest.mark.parametrize("H,KV", [(8, 2), (6, 3), (4, 4)])
def test_kernel_parity_int4_gqa(H, KV):
    """int4 decode kernel at GQA group sizes 4, 2 and 1: each query
    group must read its own kv head's scale through the unpack."""
    rng = np.random.default_rng(31)
    pk, pv = _q4pools(rng, KV=KV, hd=16)
    q = jnp.asarray(rng.normal(size=(2, 1, H, 16)), jnp.float32)
    table = jnp.asarray([[9, 1, 6, -1, -1], [0, 5, -1, -1, -1]],
                        jnp.int32)
    pos = jnp.asarray([2 * P + 3, P + 6], jnp.int32)
    _assert_parity_q4(q, pk, pv, table, pos)


def test_kernel_parity_int4_unmapped_holes():
    """int4 decode kernel with -1 holes inside the live range and a
    fully-dead row: holes masked (their clamped page-0 nibbles and
    scale must not leak), dead row zeros."""
    rng = np.random.default_rng(32)
    pk, pv = _q4pools(rng, KV=2, hd=16)
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 16)), jnp.float32)
    table = jnp.asarray([[4, -1, 11, 3, -1],
                         [-1, 2, 7, -1, -1],
                         [-1, -1, -1, -1, -1]], jnp.int32)
    pos = jnp.asarray([3 * P + 2, 2 * P + 1, P + 4], jnp.int32)
    _assert_parity_q4(q, pk, pv, table, pos)
    dead = ragged_paged_attention(q, pk.q, pv.q, LAYER, table, pos,
                                  scale_k=pk.scale, scale_v=pv.scale,
                                  packed4=True, interpret=True)[2]
    np.testing.assert_array_equal(np.asarray(dead),
                                  np.zeros_like(np.asarray(dead)))


def test_mixed_kernel_parity_int4_offsets_and_holes():
    """int4 MIXED kernel: a decode row, a chunk row straddling a page
    boundary at an arbitrary offset (the straddle crosses the packed
    low/high nibble halves), a chunk row behind an unmapped hole, and
    an idle row (q_len=0) in one launch."""
    rng = np.random.default_rng(33)
    pk, pv = _q4pools(rng, KV=2, hd=16)
    C = 6
    q = jnp.asarray(rng.normal(size=(4, C, 4, 16)), jnp.float32)
    table = jnp.asarray([[7, 2, 9, -1, -1],
                         [4, 11, 3, -1, -1],
                         [-1, 8, 5, -1, -1],
                         [-1, -1, -1, -1, -1]], jnp.int32)
    pos = jnp.asarray([2 * P + 5, P + 3, P + 2, 0], jnp.int32)
    qlen = jnp.asarray([1, 6, 4, 0], jnp.int32)
    _assert_mixed_parity_q4(q, pk, pv, table, pos, qlen)


@pytest.mark.parametrize("H,KV", [(8, 2), (6, 3), (4, 4), (16, 1)])
def test_mixed_kernel_parity_int4_gqa(H, KV):
    """int4 mixed kernel at GQA group sizes 4, 2, 1 and 16."""
    rng = np.random.default_rng(34)
    pk, pv = _q4pools(rng, KV=KV, hd=16)
    C = 5
    q = jnp.asarray(rng.normal(size=(2, C, H, 16)), jnp.float32)
    table = jnp.asarray([[9, 1, 6, -1, -1], [0, 5, 2, -1, -1]],
                        jnp.int32)
    pos = jnp.asarray([2 * P + 3, P + 6], jnp.int32)
    qlen = jnp.asarray([1, 5], jnp.int32)
    _assert_mixed_parity_q4(q, pk, pv, table, pos, qlen)


def test_supported_gate_int4_page_tiling(monkeypatch):
    """On silicon a packed int4 pool needs page_size % 64 (the packed
    uint8 axis carries page//2 sublanes, tiled by 32); odd page sizes
    can't nibble-pack anywhere, and the scale-SMEM bound rides through
    from the int8 gate."""
    # odd pages can't pack two tokens per byte on ANY backend
    assert not ragged_paged_supported(7, H=4, KV=2, hd=16, packed4=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ragged_paged_supported(128, H=4, KV=2, hd=128, packed4=True)
    # 32-token pages satisfy the int8 tile but pack to only 16 sublanes
    assert not ragged_paged_supported(32, H=4, KV=2, hd=128,
                                      packed4=True)
    assert ragged_paged_supported(32, H=4, KV=2, hd=128, quantized=True)
    # whole-pool scale arrays still bound against SMEM
    assert not ragged_paged_supported(128, H=32, KV=8, hd=128,
                                      packed4=True, n_pages=100_000)
    assert not ragged_paged_mixed_supported(128, H=32, KV=8, hd=128,
                                            q_width=1, packed4=True,
                                            n_pages=100_000)


# -- the mixed kernel's work follows q_len (PR 34) ---------------------------
#
# A row whose real queries lie in its first tile of MIXED_Q_TILE queries
# folds, initialises and finishes that tile alone; any other its whole
# window. The three bodies (float, int8, int4 pools) share one fold.

TQ = MIXED_Q_TILE
SWEEP_C = 2 * TQ + 4
SWEEP_Q_LENS = sorted({0, 1, TQ - 1, TQ, TQ + 1, SWEEP_C - 1, SWEEP_C})
# a window's first query before, on and after a page edge
SWEEP_POS = (P - 1, P, P + 1)
_SWEEP_POOLS = {"f32": lambda rng, KV, hd: _pool(rng, KV, hd),
                "int8": lambda rng, KV, hd: _qpools(rng, KV, hd),
                "int4": lambda rng, KV, hd: _q4pools(rng, KV, hd)}


def _mixed(kind, q, pk, pv, table, pos, qlen, layer=LAYER):
    if kind == "f32":
        return np.asarray(ragged_paged_attention_mixed(
            q, pk, pv, layer, table, pos, qlen, interpret=True))
    return np.asarray(ragged_paged_attention_mixed(
        q, pk.q, pv.q, layer, table, pos, qlen, scale_k=pk.scale,
        scale_v=pv.scale, packed4=kind == "int4", interpret=True))


def _own_pages(rows):
    """[rows, MAX_PAGES]: three mapped pages a row (pages are only
    read, so rows may share them), the rest unmapped."""
    table = np.full((rows, MAX_PAGES), -1, np.int32)
    for b in range(rows):
        table[b, :3] = (3 * b + np.arange(3)) % N_PAGES
    return jnp.asarray(table)


@pytest.mark.parametrize("G", [1, 4, 16])
@pytest.mark.parametrize("kind", ["f32", "int8", "int4"])
def test_mixed_kernel_follows_q_len(kind, G):
    """Every q_len around the tile and the window's edges (0, 1, Tq-1,
    Tq, Tq+1, C-1, C) at a first position before, on and after a page
    edge, one row each in one launch: the real columns match the fold,
    EVERY column is finite, and the columns of a tile the row did not
    fold are zero."""
    rng = np.random.default_rng(40 + G)
    KV = 1 if G == 16 else 2
    pk, pv = _SWEEP_POOLS[kind](rng, KV, 16)
    cases = [(p, n) for p in SWEEP_POS for n in SWEEP_Q_LENS]
    pos = jnp.asarray([p for p, _n in cases], jnp.int32)
    qlen = jnp.asarray([n for _p, n in cases], jnp.int32)
    q = jnp.asarray(rng.normal(size=(len(cases), SWEEP_C, KV * G, 16)),
                    jnp.float32)
    table = _own_pages(len(cases))
    want = np.asarray(paged_attention_mixed(q, pk, pv, LAYER, table, pos,
                                            qlen))
    got = _mixed(kind, q, pk, pv, table, pos, qlen)
    assert np.isfinite(got).all()
    atol = 1e-5 if kind == "f32" else 2e-5
    for b, (_p, n) in enumerate(cases):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=atol,
                                   rtol=atol, err_msg=str(cases[b]))
        if mixed_q_tiles(n, SWEEP_C) == 1:
            assert not got[b, TQ:].any(), cases[b]


@pytest.mark.parametrize("windows", [0, 1, 2])
@pytest.mark.parametrize("block", [1, 2, 4])
def test_mixed_row_is_bit_equal_whatever_rows_share_its_call(
        block, windows, monkeypatch):
    """A decode row beside 0, 1 and 2 window rows, and a window row
    alone or in company, at 1, 2 and 4 pages a fold: rows do not see
    each other, and a query's recurrence does not depend on how much of
    its row's window is folded (both spans take the same block, and a
    block's mask is the query's own). The decode row's column 0 is the
    decode kernel's answer at the same position."""
    monkeypatch.setattr(rpa, "mixed_block", lambda *a, **kw: block)
    rng = np.random.default_rng(50)
    pk, pv = _pool(rng, KV=2, hd=16)
    C = SWEEP_C
    q = jnp.asarray(rng.normal(size=(3, C, 8, 16)), jnp.float32)
    table = _own_pages(3)
    pos = jnp.asarray([2 * P + 3, P - 2, 3], jnp.int32)

    def run(qlen):
        return _mixed("f32", q, pk, pv, table, pos,
                      jnp.asarray(qlen, jnp.int32))

    alone = run([1, 0, 0])
    got = run([1] + [C, C - 1][:windows] + [1, 1][windows:])
    np.testing.assert_array_equal(got[0], alone[0])
    decode = np.asarray(ragged_paged_attention(
        q[:, :1], pk, pv, LAYER, table, pos, interpret=True))
    np.testing.assert_allclose(got[0, 0], decode[0, 0], atol=1e-5,
                               rtol=1e-5)
    if windows:
        # the window row's first query, folded with its whole window
        # here and alone in its tile there
        np.testing.assert_array_equal(got[1, 0], run([0, 1, 0])[1, 0])
        np.testing.assert_array_equal(got[1], run([0, C, 0])[1])


# -- the mixed kernel walks its rows' live pages --------------------------------
#
# One grid step a row; the row's pages from the page of its first
# query's first key to that of its LAST real query come through the
# decode kernel's walk (walk_live_pages), `mixed_block` of them side by
# side in a ring slot and a softmax update. The table holds 5 pages, so
# blocks of 2 and of 4 divide neither it nor most live counts.

WALK_C = SWEEP_C
_BAND = 2 * P - 3       # a band that is no multiple of the page
# name: (table rows, first positions, q_len, window); (kinds it runs in)
MIXED_WALK_CASES = {
    # decode rows at ragged positions, a dead table between them
    "decode_rows": ([[7, 2, 9, 5, -1], _DEAD, [4, 11, 3, 1, 8], [6, 10, -1, -1, -1]],
                    [2 * P + 5, 0, FULL, P], [1, 1, 1, 1], None),
    # idle rows first, between and last: they take no trip, and the
    # copies ahead skip them
    "idle_rows": ([[5, 8, 2, -1, -1], [7, 2, 9, -1, -1], [3, 6, -1, -1, -1],
                   [4, 11, 1, -1, -1]],
                  [P + 4, 2 * P + 1, 3, 2 * P], [0, WALK_C, 0, 0], None),
    # windows that start before, on and after a page edge, whole and
    # short, beside a decode row
    "prefill_at_page_offsets": ([[3, 6, 1, 10, 5], [8, 2, 7, 4, 9],
                                 [11, 4, 7, -1, -1], [1, 9, 3, 6, -1]],
                                [P - 2, 4 * P, P + 1, 3 * P - WALK_C],
                                [WALK_C, WALK_C, 3, WALK_C - 1], None),
    # holes inside the live range, page 0 of the pool poisoned
    "hole_in_the_live_range": ([[4, -1, 11, 3, -1], [-1, 2, -1, 7, 5],
                                [-1, -1, 5, -1, -1]],
                               [3 * P + 2, 4 * P - 3, 2 * P + 1],
                               [1, WALK_C, WALK_C], None),
    # a band over a ring: logical pages past the table's five entries,
    # read through p mod 5
    "band_over_a_ring": ([[3, 6, 1, 10, 5], [8, 2, 7, 4, 9], [11, 4, 7, 9, 2]],
                         [7 * P + 3, 9 * P - 2, 1], [1, WALK_C, WALK_C],
                         _BAND),
    # more trips than the ring has slots, then fewer
    "ring_wraps_and_does_not": ([[3, 6, 1, 10, 5], [8, 2, -1, -1, -1],
                                 [11, 4, 7, 9, -1]],
                                [FULL - WALK_C + 1, P + 3, 3 * P + 1],
                                [WALK_C, 1, WALK_C], None),
}


def _walk_inputs(kind, case):
    """(q, pk, pv, table, pos, q_len, window) of a case: seeded, so
    that the file of the parent's results stays true to them."""
    rows, pos, qlen, window = MIXED_WALK_CASES[case]
    rng = np.random.default_rng(62)
    pk, pv = (_poisoned(kind, pool)
              for pool in _SWEEP_POOLS[kind](rng, 2, 16))
    assert 0 not in {p for row in rows for p in row}
    q = jnp.asarray(rng.normal(size=(len(rows), WALK_C, 8, 16)), jnp.float32)
    return (q, pk, pv, jnp.asarray(rows, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(qlen, jnp.int32),
            window)


def _walk_selection(case):
    """A per-(query, key) selection [B, max_pages, C, P] for a case:
    about half of the keys, every query's own among them."""
    rows, pos, _qlen, _w = MIXED_WALK_CASES[case]
    rng = np.random.default_rng(63)
    sel = rng.random((len(rows), MAX_PAGES, WALK_C, P)) < 0.5
    for b, first in enumerate(pos):
        for i in range(WALK_C):
            at = min(first + i, FULL)
            sel[b, at // P, i, at % P] = True
    return jnp.asarray(sel, jnp.float32)


def _walked(kind, inputs, selected=None):
    q, pk, pv, table, pos, qlen, window = inputs
    kw = {} if selected is None else {"selected": selected}
    if kind == "f32":
        return np.asarray(ragged_paged_attention_mixed(
            q, pk, pv, LAYER, table, pos, qlen, window=window,
            interpret=True, **kw))
    return np.asarray(ragged_paged_attention_mixed(
        q, pk.q, pv.q, LAYER, table, pos, qlen, scale_k=pk.scale,
        scale_v=pv.scale, packed4=kind == "int4", window=window,
        interpret=True))


_WALK_PARAMS = ([(case, kind, False) for case in sorted(MIXED_WALK_CASES)
                 for kind in ("f32", "int8", "int4")]
                + [(case, "f32", True) for case in sorted(MIXED_WALK_CASES)
                   if MIXED_WALK_CASES[case][3] is None])


@pytest.mark.parametrize("block", [1, 2, 4])
@pytest.mark.parametrize("case,kind,selecting", _WALK_PARAMS)
def test_mixed_kernel_walks_live_pages(case, kind, selecting, block,
                                       monkeypatch):
    """The walked kernel against the fold reference at 1, 2 and 4 pages
    a fold, over a ring of three slots: the real columns match, every
    column is finite, a row that folds its first tile alone (a decode
    row, an idle row) holds zeros past it, an idle row zeros only."""
    monkeypatch.setattr(rpa, "decode_ring_depth", lambda nbytes: RING_DEPTH)
    monkeypatch.setattr(rpa, "mixed_block", lambda *a, **kw: block)
    inputs = _walk_inputs(kind, case)
    q, pk, pv, table, pos, qlen, window = inputs
    selected = _walk_selection(case) if selecting else None
    want = np.asarray(paged_attention_mixed(
        q, pk, pv, LAYER, table, pos, qlen, window=window,
        selected=selected))
    got = _walked(kind, inputs, selected)
    assert np.isfinite(got).all()
    atol = 1e-5 if kind == "f32" else 2e-5
    for b, n in enumerate(np.asarray(qlen)):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=atol,
                                   rtol=atol, err_msg=f"row {b}")
        if mixed_q_tiles(n, WALK_C) == 1:
            assert not got[b, TQ:].any(), b
        if n == 0:
            assert not got[b].any(), b


PARENT_RESULTS = (pathlib.Path(__file__).parent / "data"
                  / "mixed_attn_grid_results.npz")


@pytest.mark.parametrize("case,kind,selecting", _WALK_PARAMS)
def test_one_page_a_fold_is_bit_equal_to_the_grid_it_replaced(
        case, kind, selecting, monkeypatch):
    """At one page a fold the walk changes no bit of a column a caller
    reads (a real query's): tests/data/mixed_attn_grid_results.npz
    holds what the (rows, pages) grid's kernel gave on these inputs at
    commit bca0bd8 (PR 61), interpreted as here."""
    monkeypatch.setattr(rpa, "mixed_block", lambda *a, **kw: 1)
    inputs = _walk_inputs(kind, case)
    got = _walked(kind, inputs,
                  _walk_selection(case) if selecting else None)
    with np.load(PARENT_RESULTS) as saved:
        want = saved[f"{case}-{kind}-{int(selecting)}"]
    for b, n in enumerate(np.asarray(inputs[5])):
        np.testing.assert_array_equal(got[b, :n], want[b, :n],
                                      err_msg=f"row {b}")


# mixed_block at the cells' shapes (the rule's answers, stated)
MIXED_BLOCKS = {"keye": 4, "mistral": 4, "olmoe": 4, "zaya": 4,
                "nemotron": 4, "granite": 4, "kexaone": 4,
                "kexaone_ring": 2}


def test_mixed_block_and_the_vmem_count_follow_the_shapes():
    """`mixed_block` from the shapes alone, at the seven cells' calls:
    4 pages a fold where the table admits it and the count fits the
    plan, 2 on K-EXAONE's ring of 6 entries, 1 on a ring of 3; the
    count's arithmetic at one page a fold; an entry's width is still
    what the 16 MiB count admits."""
    from cake_tpu.models.moe.exaone_moe import query_tile
    from cake_tpu.ops.ragged_paged_attention import (
        _MIXED_VMEM_LIMIT, _MIXED_VMEM_PLAN, _VMEM_SCOPED_LIMIT,
        decode_ring_depth, mixed_block, mixed_scratch_bytes,
        mixed_vmem_bytes, mixed_walk,
    )

    # (page, H, KV, hd, C, table): Keye, Mistral, OLMoE, ZAYA,
    # Nemotron, Granite, K-EXAONE full and banded
    cells = {"keye": (128, 32, 4, 128, 128, 260),
             "mistral": (128, 32, 8, 128, 128, 16),
             "olmoe": (128, 16, 16, 128, 128, 16),
             "zaya": (128, 8, 2, 128, 128, 40),
             "nemotron": (128, 32, 2, 128, 128, 40),
             "granite": (128, 32, 8, 64, 128, 20),
             "kexaone": (128, 64, 8, 128, 64, 76),
             "kexaone_ring": (128, 64, 8, 128, 64, 6)}
    for name, (page, H, KV, hd, C, table) in cells.items():
        f = mixed_block(page, H, KV, hd, C, table,
                        selecting=name == "keye")
        assert f == MIXED_BLOCKS[name], name
        assert mixed_vmem_bytes(page, H, KV, hd, C, block=f,
                                selecting=name == "keye") <= _MIXED_VMEM_PLAN
        assert mixed_vmem_bytes(page, H, KV, hd, C) <= _VMEM_SCOPED_LIMIT
    assert _MIXED_VMEM_PLAN < _MIXED_VMEM_LIMIT
    # the count, term by term, at Mistral's call
    page_bytes = 128 * 8 * 128 * 2
    assert mixed_vmem_bytes(128, 32, 8, 128, 128) == (
        mixed_scratch_bytes(32, 128, 128) + 4 * 128 * 32 * 128 * 2
        + decode_ring_depth(page_bytes) * 2 * page_bytes
        + 128 * 4 * 128 * (8 + 2))
    # a ring of 3 entries would pay 4 pages a row at 4 a fold
    assert mixed_block(128, 64, 8, 128, 64, 3) == 1
    # entry widths: what they were
    assert query_tile(512, 32, 4, 128, 128, 2, 2) == 128
    assert query_tile(512, 64, 8, 128, 128, 2, 2) == 64
    assert query_tile(512, 32, 8, 64, 128, 2, 2) == 128
    # the host's count of the walk
    assert mixed_walk(0, 0, 128, 16, 4) == (0, 0)
    assert mixed_walk(300, 1, 128, 16, 4) == (3, 1)
    assert mixed_walk(1000, 128, 128, 16, 4) == (9, 3)
    assert mixed_walk(1000, 128, 128, 16, 1) == (9, 9)
    assert mixed_walk(1000, 64, 128, 6, 2, window=128) == (3, 2)
    assert mixed_walk(10**6, 1, 128, 16, 4) == (16, 4)


def test_mixed_q_tiles_counts_what_the_kernel_folds():
    """The host's count (obs/steps `attn_q_tiles`): one tile for a row
    whose real queries lie in its first tile, the window's otherwise.
    14 decode rows beside windows of 128 and 37 tokens, width 128."""
    C = 128
    full = mixed_q_tiles(C, C)
    assert full == -(-C // TQ) and mixed_q_tiles(0, C) == 1
    assert mixed_q_tiles(1, C) == mixed_q_tiles(TQ, C) == 1
    assert mixed_q_tiles(TQ + 1, C) == mixed_q_tiles(C - 1, C) == full
    step = [1] * 14 + [128, 37]
    assert sum(mixed_q_tiles(n, C) for n in step) == 14 + 2 * full
