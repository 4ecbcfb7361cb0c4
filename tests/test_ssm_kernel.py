"""`cake_ssm_step` (ops/ssm.step) against the XLA form it replaced.

The kernel, interpreted, over a stacked state [L, B, H, P, N]: layer j's
stepping rows hold what `nemotron_h.ssm_step` gives from their stored
state (a fresh row: from zeros, whatever its block held), every staying
row and every other layer keeps its bits, a staying row's `y` is zero.
Then through Granite's served trunks: a mixed dispatch (a window from
position 0, decoding rows, a fresh row, an idle row), one whose window
goes on from its stored state, a decode dispatch and a mixed dispatch
in which NO row holds a window give the tokens and the state that
`ssm_step_fold` in the kernel's place gives, and the step counters
count the kernel's three codes.

What is held to what. A stepping row's STATE is the vector unit's, in
ssm_step's order: on the chip bit-equal to the fold's
(tools/ssm_step_bench.py's check; PERF.md section 6, PR 57); on the CPU
the interpreter's products and sums compile apart from the fold's
(XLA:CPU contracts a multiply and an add where it likes), so float32
rounding here. Its `y` sums a head's 128 products S C on the MATRIX
unit (the order of that one sum is not the fold's), so float32
round-off of a sum on the chip as here.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.paged import PagedKVCache, mixed_token_buckets
from cake_tpu.models.moe import granite_hybrid as gh
from cake_tpu.models.moe import nemotron_h as nh
from cake_tpu.models.moe.config import GraniteHybridConfig
from cake_tpu.models.moe.params import init_params
from cake_tpu.ops import kda, ssm

STAY, STEP, FRESH = ssm.STAY, ssm.STEP, ssm.FRESH
ROUNDING = dict(rtol=2e-6, atol=2e-6)
# y: a sum of N products of ~0.3 x 1 in another order
SUM_ROUNDING = dict(rtol=1e-5, atol=1e-5)

# name -> (L, B, H, P, N), groups, the rows' codes, the layer, heads a
# block (None: what kda.STEP_BLOCK_BYTES gives) and the ring's depth
CASES = {
    "mixed_codes": ((3, 5, 6, 8, 16), 1, [STEP, STAY, FRESH, STEP, STAY],
                    1, 3, 4),
    "all_step": ((3, 5, 6, 8, 16), 1, [STEP] * 5, 1, 3, 4),
    "none_step": ((3, 5, 6, 8, 16), 1, [STAY] * 5, 1, 3, 4),
    "all_fresh": ((2, 3, 4, 8, 16), 2, [FRESH] * 3, 0, 2, 4),
    "first_layer": ((3, 5, 6, 8, 16), 3, [STAY, STEP, STEP, FRESH, STEP],
                    0, 3, 4),
    "last_layer": ((3, 5, 6, 8, 16), 1, [STEP, STEP, STAY, STAY, FRESH],
                   2, 3, 4),
    # Nemotron's grouping: eight groups, two heads each; a block of four
    # heads spans two groups
    "eight_groups": ((2, 4, 16, 8, 16), 8, [STEP, FRESH, STAY, STEP],
                     1, 4, 4),
    # 7 rows of 5 heads: a prime count of heads takes one a block
    "one_head_a_block": ((2, 7, 5, 8, 16), 1,
                         [STEP, FRESH, STAY, STEP, STEP, STAY, STEP],
                         1, 1, 4),
    "a_row_a_block": ((2, 5, 6, 8, 16), 2, [STEP, STAY, STEP, FRESH, STEP],
                      1, 6, 4),
    "ring_of_two": ((2, 5, 6, 8, 16), 1, [STEP, STEP, FRESH, STAY, STEP],
                    0, 2, 2),
    "ring_that_wraps": ((2, 5, 8, 8, 16), 1, [STEP, FRESH, STEP, STEP, STAY],
                        1, 1, 3),
    "only_last_row": ((2, 4, 4, 8, 16), 1, [STAY, STAY, STAY, STEP], 0, 2, 4),
    # more heads than a lane tile holds: y's block in two tiles
    "two_lane_tiles": ((1, 2, 132, 8, 16), 1, [STEP, FRESH], 0, 66, 4),
    # one head at the published widths, the block the constant gives
    "published_head": ((2, 3, 1, 64, 128), 1, [STEP, STAY, FRESH],
                       1, None, 4),
}


def inputs(shape, G=1, seed=0):
    L, B, H, P, N = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (B, H)))
    return dict(
        state=jax.random.normal(ks[0], shape, jnp.float32) * 0.3,
        x=jax.random.normal(ks[1], (B, H, P)).astype(jnp.bfloat16),
        Bm=jax.random.normal(ks[2], (B, G, N)).astype(jnp.bfloat16),
        Cm=jax.random.normal(ks[3], (B, G, N)).astype(jnp.bfloat16),
        dt=dt, a=-dt * jnp.exp(jax.random.normal(ks[5], (H,)))[None, :],
        D=jax.random.normal(ks[6], (H,)))


def operands(x):
    return tuple(x[k] for k in ("x", "Bm", "Cm", "dt", "a", "D"))


def run_kernel(x, j, code, heads=None, depth=4):
    """ops/ssm's kernel, interpreted, traced anew (the module's jitted
    wrapper caches on shapes, not on ops/kda's two constants)."""
    H, P, N = x["state"].shape[2:]
    old = kda.STEP_BLOCK_BYTES, kda.RING_DEPTH
    if heads is not None:
        kda.STEP_BLOCK_BYTES = heads * P * N * 4
    kda.RING_DEPTH = depth
    try:
        assert heads is None or kda.block_heads(H, P * N * 4) == heads
        call = jax.jit(functools.partial(ssm._step_pallas.__wrapped__,
                                         interpret=True))
        return call(x["state"], jnp.int32(j), jnp.asarray(code, jnp.int32),
                    *operands(x))
    finally:
        kda.STEP_BLOCK_BYTES, kda.RING_DEPTH = old


@functools.lru_cache(maxsize=None)
def case(name):
    shape, G, code, j, heads, depth = CASES[name]
    x = inputs(shape, G)
    code = np.asarray(code, np.int32)
    got = run_kernel(x, j, code, heads, depth)
    want = nh.ssm_step_fold(x["state"], j, jnp.asarray(code), *operands(x))
    return (np.asarray(x["state"]), j, code, *map(np.asarray, got),
            *map(np.asarray, want))


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_stepping_rows_state_is_the_folds(name):
    _, j, code, S, _, want, _ = case(name)
    steps = code != STAY
    np.testing.assert_allclose(S[j][steps], want[j][steps], **ROUNDING)


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_stepping_rows_output_is_the_folds(name):
    _, _, code, _, y, _, want = case(name)
    steps = code != STAY
    np.testing.assert_allclose(y[steps], want[steps], **SUM_ROUNDING)


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_staying_row_keeps_its_bits_and_reads_zero(name):
    before, j, code, S, y, _, _ = case(name)
    stays = code == STAY
    np.testing.assert_array_equal(S[j][stays], before[j][stays])
    np.testing.assert_array_equal(y[stays], 0.0)


@pytest.mark.parametrize("name", sorted(CASES))
def test_every_other_layer_keeps_its_bits(name):
    before, j, _, S, _, _, _ = case(name)
    others = np.arange(S.shape[0]) != j
    np.testing.assert_array_equal(S[others], before[others])


@pytest.mark.parametrize("name", ["mixed_codes", "all_fresh", "ring_of_two",
                                  "eight_groups", "published_head"])
def test_a_fresh_row_never_reads_its_stored_block(name):
    """NaN in the fresh rows' stored blocks: the step from zeros, with
    no NaN anywhere."""
    shape, G, code, j, heads, depth = CASES[name]
    x = inputs(shape, G)
    code = np.asarray(code, np.int32)
    fresh = jnp.asarray(code == FRESH)[:, None, None, None]
    zeros = x["state"].at[j].set(jnp.where(fresh, 0.0, x["state"][j]))
    x["state"] = x["state"].at[j].set(jnp.where(fresh, jnp.nan,
                                                x["state"][j]))
    S, y = run_kernel(x, j, code, heads, depth)
    want_S, want_y = run_kernel(dict(x, state=zeros), j,
                                np.where(code == FRESH, STEP, code), heads,
                                depth)
    at = code == FRESH
    np.testing.assert_array_equal(np.asarray(S)[j][at],
                                  np.asarray(want_S)[j][at])
    np.testing.assert_array_equal(np.asarray(y)[at], np.asarray(want_y)[at])
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("G", [1, 4])
def test_the_kernel_is_the_recurrence_over_several_tokens(G):
    """Eight tokens a row through the kernel, layer by layer of the
    stack in turn, against ssm_step carried in jax.numpy: the state is
    read where the last call wrote it."""
    shape = (2, 3, 4, 8, 16)
    state = inputs(shape, G)["state"]
    S = [state[0], state[1]]
    for t in range(8):
        x = inputs(shape, G, seed=10 + t)
        j = t % 2
        code = [STEP, STAY, STEP] if t else [STEP, STAY, FRESH]
        state, y = ssm.step(state, j, jnp.asarray(code, jnp.int32),
                            *operands(x))
        S_in = S[j].at[2].set(0.0) if not t else S[j]
        S_new, want = nh.ssm_step(S_in, *operands(x))
        S[j] = S_new.at[1].set(S[j][1])
        np.testing.assert_allclose(y[0], want[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y[2], want[2], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state, jnp.stack(S), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("H,head_bytes,heads", [
    (64, 64 * 128 * 4, 16),     # Granite's: 512 KiB of 32 KiB heads
    (128, 64 * 128 * 4, 16),    # Nemotron's
    (8, 16 * 16 * 4, 8),        # a tiny row's state whole
    (48, 64 * 128 * 4, 16),
    (24, 64 * 128 * 4, 12),     # 16 fit; 12 divides
    (11, 64 * 128 * 4, 11),
    (34, 64 * 128 * 4, 2),      # 2 x 17: a prime past the block
    (4, 512 * 512 * 4, 1)])     # a head larger than the block
def test_a_block_is_whole_heads_by_their_bytes(H, head_bytes, heads):
    assert kda.block_heads(H, head_bytes) == heads
    assert H % heads == 0


@pytest.mark.parametrize("P,N", [(4, 128), (8, 64), (12, 16)])
def test_a_width_the_chip_cannot_tile_is_refused_by_name(P, N):
    x = inputs((1, 2, 2, P, N))
    with pytest.raises(ValueError, match="cake_ssm_step cannot run"):
        ssm.step(x["state"], 0, jnp.ones((2,), jnp.int32), *operands(x),
                 interpret=False)


def test_step_codes_read_the_rows():
    rows = nh.Rows(jnp.arange(6), jnp.array([1, 0, 1, 5, 1, 0]),
                   jnp.array([7, 0, 0, 0, 3, 9]))
    np.testing.assert_array_equal(
        nh.step_codes(rows), [STEP, STAY, FRESH, STAY, STEP, STAY])


def test_the_fold_is_mamba_blocks_select_and_write_back():
    """`ssm_step_fold`, the comparison every case above is held to, is
    the XLA form as `mamba_block` holds it (Nemotron's trunk): zeros in
    for a fresh row, `ssm_step`, the stepping rows' results kept."""
    x = inputs((2, 4, 4, 8, 16), 2)
    code = np.asarray([STEP, STAY, FRESH, STEP], np.int32)
    S, y = nh.ssm_step_fold(x["state"], 1, jnp.asarray(code), *operands(x))
    S_in = jnp.where(jnp.asarray(code == FRESH)[:, None, None, None], 0.0,
                     x["state"][1])
    S_new, want = nh.ssm_step(S_in, *operands(x))
    steps = code != STAY
    np.testing.assert_array_equal(np.asarray(S[1])[steps],
                                  np.asarray(S_new)[steps])
    np.testing.assert_array_equal(np.asarray(S[1])[~steps],
                                  np.asarray(x["state"][1])[~steps])
    np.testing.assert_array_equal(np.asarray(S[0]), np.asarray(x["state"][0]))
    np.testing.assert_array_equal(np.asarray(y)[steps],
                                  np.asarray(want)[steps])
    np.testing.assert_array_equal(np.asarray(y)[~steps], 0.0)


# -- through Granite's served trunks -------------------------------------------

B, C, PAGE, MAX_SEQ = 4, 12, 8, 64
# (pos, q_len) a dispatch, after row 0's prompt of C + 5: a window from
# position 0 in row 1 beside rows 0 and 3 (3 from a fresh state), row 2
# idle; row 1's window going on from its stored state; a decode
# dispatch; a mixed dispatch in which every row holds one token
DISPATCHES = {
    "mixed": ([C + 5, 0, 0, 0], [1, 9, 0, 1]),
    "mixed_on": ([C + 6, 9, 0, 1], [1, 7, 0, 1]),
    "decode": ([C + 7, 16, 0, 2], [1, 1, 0, 1]),
    "mixed_no_window": ([C + 8, 17, 0, 3], [1, 1, 0, 1]),
}


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


def dispatches(model, step):
    """A prompt's two windows with no company, then DISPATCHES in
    order, with `step` in ops/ssm.step's place. Returns per dispatch
    the tokens it chooses, the state and tails after it and its
    counters."""
    c, params = model
    old, ssm.step = ssm.step, step
    try:
        # new function objects: jit traces them with `step` in place
        mixed = jax.jit(lambda *a: gh.mixed_trunk(
            *a, c, "fold", mixed_token_buckets(B, C, (1,))[-1])[0])
        decode = jax.jit(lambda *a: gh.decode_trunk(*a, c, "fold"))
        rng = np.random.default_rng(3)
        cache, out = fresh_cache(c), {}
        toks = rng.integers(0, c.vocab_size, (B, C)).astype(np.int32)

        def run(name, pos, qlen):
            nonlocal cache
            pos, qlen = np.asarray(pos, np.int32), np.asarray(qlen, np.int32)
            if name == "decode":
                res = decode(params, jnp.asarray(toks[:, :1]), cache,
                             jnp.asarray(pos), jnp.asarray(qlen > 0))
            else:
                res = mixed(params, jnp.asarray(toks), jnp.asarray(pos),
                            jnp.asarray(qlen), jnp.asarray(qlen > 0), cache)
            cache = res.cache
            out[name] = (
                np.asarray(jnp.argmax(gh.logits_of(res.x, params, c), -1)),
                np.asarray(cache.ssm), np.asarray(cache.conv),
                np.asarray(res.counters))

        run("prompt", [0, 0, 0, 0], [C, 0, 0, 0])
        run("prompt_on", [C, 0, 0, 0], [5, 0, 0, 0])
        for name, (pos, qlen) in DISPATCHES.items():
            run(name, pos, qlen)
        return out
    finally:
        ssm.step = old


@pytest.fixture(scope="module")
def served(model):
    return dispatches(model, ssm.step)


@pytest.fixture(scope="module")
def folded(model):
    return dispatches(model, nh.ssm_step_fold)


@pytest.fixture(scope="module")
def both(served, folded):
    return served, folded


@pytest.mark.parametrize("dispatch", sorted(DISPATCHES))
def test_a_dispatch_chooses_the_tokens_the_fold_chose(both, dispatch):
    np.testing.assert_array_equal(both[0][dispatch][0], both[1][dispatch][0])


@pytest.mark.parametrize("dispatch", sorted(DISPATCHES))
def test_a_dispatch_leaves_the_state_the_fold_left(both, dispatch):
    (_, S, tails, _), (_, want_S, want_tails, _) = (
        both[0][dispatch], both[1][dispatch])
    np.testing.assert_allclose(S, want_S, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(tails, want_tails, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dispatch", sorted(DISPATCHES))
def test_the_idle_row_keeps_its_bits_through_a_dispatch(both, dispatch):
    """Row 2 never holds a token: zeros as created, in every layer."""
    np.testing.assert_array_equal(both[0][dispatch][1][:, 2], 0.0)


@pytest.mark.parametrize("dispatch", ["mixed", "mixed_on"])
def test_the_window_row_starts_from_its_state_before_the_step(both,
                                                              dispatch):
    """Row 1's window rides a mixed dispatch whose kernel call steps
    rows 0 and 3 of the same layer in place: from position 0 its state
    is the chunked scan's from zeros, and its next window goes on from
    what that one stored, as with the fold."""
    (_, S, _, _), (_, want, _, _) = both[0][dispatch], both[1][dispatch]
    assert np.abs(want[:, 1]).max() > 0
    np.testing.assert_allclose(S[:, 1], want[:, 1], rtol=1e-5, atol=1e-5)


def test_a_one_token_row_steps_where_no_row_holds_a_window(both):
    """A mixed dispatch of one-token rows alone: the window's write
    falls back to what the kernel left in its row, not to what the row
    held before the step."""
    before, after = both[0]["decode"][1], both[0]["mixed_no_window"][1]
    for row in (0, 1, 3):
        assert np.abs(after[:, row] - before[:, row]).max() > 0
    np.testing.assert_allclose(after, both[1]["mixed_no_window"][1],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dispatch", sorted(DISPATCHES))
def test_the_counters_count_the_kernels_codes(model, both, dispatch):
    """`ssm_tokens_stepped` is the rows whose code is not STAY, a layer;
    `ssm_state_resets` the FRESH ones and a window that starts at
    position 0; `ssm_state_rows` the stepping rows and the window's."""
    pos, qlen = (np.asarray(v) for v in DISPATCHES[dispatch])
    code = np.asarray(nh.step_codes(nh.Rows(
        jnp.arange(B), jnp.asarray(qlen), jnp.asarray(pos))))
    window = qlen > 1
    Lm = len(model[0].mamba_layers)
    rows, scanned, stepped, resets = both[0][dispatch][3]
    assert stepped == Lm * np.sum(code != STAY)
    assert resets == np.sum(code == FRESH) + np.sum(window & (pos == 0))
    assert rows == Lm * (np.sum(code != STAY) + np.sum(window))
    assert scanned == Lm * np.sum(qlen[window])
    np.testing.assert_array_equal(both[0][dispatch][3],
                                  both[1][dispatch][3])


def test_the_bench_tool_rehearses_and_checks_the_kernel(capsys):
    """tools/ssm_step_bench.py at tiny widths: one JSON line, the
    kernel's call compared with the fold's where they lie, at both
    shapes' grouping."""
    import importlib.util
    import json
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "ssm_step_bench.py"
    spec = importlib.util.spec_from_file_location("ssm_step_bench", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearse", "--calls", "2"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    for shape in ("granite", "nemotron"):
        check = line[shape]["check"]
        assert check["S_err"] < 2e-6 and check["y_err"] < 2e-5
        assert (check["stay_bits"] and check["others_bits"]
                and check["y_stay_zero"])
    assert {"xla_served", "xla_eight_groups", "kernel_all",
            "kernel_one_stays", "kernel_some_fresh",
            "kernel_no_lane_sum"} <= set(line["granite"])
    assert {"xla_served", "kernel_all"} <= set(line["nemotron"])
