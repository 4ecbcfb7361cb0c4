"""`cake_kda_step` (ops/kda.step) against the form it replaced.

The kernel, interpreted, over a stacked state [L, B, H, dk, dv]: layer
j's stepping rows hold what `bailing_hybrid.kda_step` gives from their
stored state (a fresh row: from zeros, whatever its block held), every
staying row and every other layer keeps its bits, a staying row's `o` is
zero. Then through the served trunks: a decode dispatch and a mixed one
(a window, decoding rows, an idle row) give the tokens and the state
that `kda_step_fold` in the kernel's place gives.

On the CPU the interpreter's products and sums compile apart from the
fold's (XLA:CPU contracts a multiply and an add where it likes), so a
stepping row is held to float32 rounding here; on the chip the two are
bit-equal (tools/kda_step_bench.py's check; PERF.md section 6, PR 52).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import PagedKVCache, mixed_token_buckets
from cake_tpu.models.moe import bailing_hybrid as bh
from cake_tpu.models.moe.config import BailingHybridConfig
from cake_tpu.models.moe.params import init_params
from cake_tpu.ops import kda

STAY, STEP, FRESH = kda.STAY, kda.STEP, kda.FRESH
ROUNDING = dict(rtol=2e-6, atol=2e-6)

# name -> (L, B, H, dk, dv), the rows' codes, the layer, heads a block
# (None: what STEP_BLOCK_BYTES gives) and the ring's depth
CASES = {
    "mixed_codes": ((3, 5, 6, 8, 16), [STEP, STAY, FRESH, STEP, STAY], 1, 3, 4),
    "all_step": ((3, 5, 6, 8, 16), [STEP] * 5, 1, 3, 4),
    "none_step": ((3, 5, 6, 8, 16), [STAY] * 5, 1, 3, 4),
    "all_fresh": ((2, 3, 4, 8, 16), [FRESH] * 3, 0, 2, 4),
    "first_layer": ((3, 5, 6, 8, 16), [STAY, STEP, STEP, FRESH, STEP], 0, 3, 4),
    "last_layer": ((3, 5, 6, 8, 16), [STEP, STEP, STAY, STAY, FRESH], 2, 3, 4),
    # 7 rows of 5 heads: a prime count of heads takes one a block
    "one_head_a_block": ((2, 7, 5, 8, 16), [STEP, FRESH, STAY, STEP, STEP,
                                            STAY, STEP], 1, 1, 4),
    "a_row_a_block": ((2, 5, 6, 8, 16), [STEP, STAY, STEP, FRESH, STEP],
                      1, 6, 4),
    "ring_of_two": ((2, 5, 6, 8, 16), [STEP, STEP, FRESH, STAY, STEP],
                    0, 2, 2),
    "ring_of_three": ((2, 5, 6, 8, 16), [STEP, FRESH, STEP, STEP, STAY],
                      1, 2, 3),
    "last_row_stays": ((2, 4, 4, 8, 16), [STEP, STEP, STEP, STAY], 1, 2, 4),
    "only_last_row": ((2, 4, 4, 8, 16), [STAY, STAY, STAY, STEP], 0, 2, 4),
    # one head at the published widths, the block the constant gives
    "published_head": ((2, 3, 1, 128, 128), [STEP, STAY, FRESH], 1, None, 4),
}


def inputs(shape, seed=0):
    L, B, H, dk, dv = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    k = jax.random.normal(ks[2], (B, H, dk))
    return dict(
        state=jax.random.normal(ks[0], shape, jnp.float32) * 0.3,
        q=jax.random.normal(ks[1], (B, H, dk)) * dk ** -0.5,
        k=k / jnp.linalg.norm(k, axis=-1, keepdims=True),
        v=jax.random.normal(ks[3], (B, H, dv)).astype(jnp.bfloat16),
        g=-5 * jax.random.uniform(ks[4], (B, H, dk)) ** 4,
        beta=jax.random.uniform(ks[5], (B, H)))


def run_kernel(x, j, code, heads=None, depth=4):
    """ops/kda's kernel, interpreted, traced anew (the module's jitted
    wrapper caches on shapes, not on the module's two constants)."""
    H, dk, dv = x["state"].shape[2:]
    old = kda.STEP_BLOCK_BYTES, kda.RING_DEPTH
    if heads is not None:
        kda.STEP_BLOCK_BYTES = heads * dk * dv * 4
    kda.RING_DEPTH = depth
    try:
        assert heads is None or kda.block_heads(H, dk * dv * 4) == heads
        call = jax.jit(functools.partial(kda._step_pallas.__wrapped__,
                                         interpret=True))
        return call(x["state"], jnp.int32(j), jnp.asarray(code, jnp.int32),
                    x["q"], x["k"], x["v"], x["g"], x["beta"])
    finally:
        kda.STEP_BLOCK_BYTES, kda.RING_DEPTH = old


@functools.lru_cache(maxsize=None)
def case(name):
    shape, code, j, heads, depth = CASES[name]
    x = inputs(shape)
    code = np.asarray(code, np.int32)
    got = run_kernel(x, j, code, heads, depth)
    want = bh.kda_step_fold(x["state"], j, jnp.asarray(code), x["q"], x["k"],
                            x["v"], x["g"], x["beta"])
    return (np.asarray(x["state"]), j, code, *map(np.asarray, got),
            *map(np.asarray, want))


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_stepping_rows_state_is_the_folds(name):
    _, j, code, S, _, want, _ = case(name)
    steps = code != STAY
    np.testing.assert_allclose(S[j][steps], want[j][steps], **ROUNDING)


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_stepping_rows_output_is_the_folds(name):
    _, _, code, _, o, _, want = case(name)
    steps = code != STAY
    np.testing.assert_allclose(o[steps], want[steps], **ROUNDING)


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_staying_row_keeps_its_bits_and_reads_zero(name):
    before, j, code, S, o, _, _ = case(name)
    stays = code == STAY
    np.testing.assert_array_equal(S[j][stays], before[j][stays])
    np.testing.assert_array_equal(o[stays], 0.0)


@pytest.mark.parametrize("name", sorted(CASES))
def test_every_other_layer_keeps_its_bits(name):
    before, j, _, S, _, _, _ = case(name)
    others = np.arange(S.shape[0]) != j
    np.testing.assert_array_equal(S[others], before[others])


@pytest.mark.parametrize("name", ["mixed_codes", "all_fresh", "ring_of_two",
                                  "published_head"])
def test_a_fresh_row_never_reads_its_stored_block(name):
    """NaN in the fresh rows' stored blocks: the step from zeros, with
    no NaN anywhere."""
    shape, code, j, heads, depth = CASES[name]
    x = inputs(shape)
    code = np.asarray(code, np.int32)
    fresh = jnp.asarray(code == FRESH)[:, None, None, None]
    zeros = x["state"].at[j].set(jnp.where(fresh, 0.0, x["state"][j]))
    x["state"] = x["state"].at[j].set(jnp.where(fresh, jnp.nan,
                                                x["state"][j]))
    S, o = run_kernel(x, j, code, heads, depth)
    want_S, want_o = run_kernel(dict(x, state=zeros), j,
                                np.where(code == FRESH, STEP, code), heads,
                                depth)
    at = code == FRESH
    np.testing.assert_array_equal(np.asarray(S)[j][at],
                                  np.asarray(want_S)[j][at])
    np.testing.assert_array_equal(np.asarray(o)[at], np.asarray(want_o)[at])
    assert np.isfinite(np.asarray(o)).all()


def test_the_kernel_is_the_recurrence_over_several_tokens():
    """Eight tokens a row through the kernel, layer by layer of the
    stack in turn, against kda_step carried in jax.numpy: the state is
    read where the last call wrote it."""
    shape = (2, 3, 4, 8, 16)
    state = inputs(shape)["state"]
    S = [state[0], state[1]]
    for t in range(8):
        x = inputs(shape, seed=10 + t)
        j = t % 2
        code = [STEP, STAY, STEP] if t else [STEP, STAY, FRESH]
        state, o = kda.step(state, j, jnp.asarray(code, jnp.int32), x["q"],
                            x["k"], x["v"], x["g"], x["beta"])
        S_in = S[j].at[2].set(0.0) if not t else S[j]
        S_new, want = bh.kda_step(S_in, x["q"], x["k"], x["v"], x["g"],
                                  x["beta"])
        S[j] = S_new.at[1].set(S[j][1])
        np.testing.assert_allclose(o[0], want[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(o[2], want[2], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state, jnp.stack(S), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("H,head_bytes,heads", [
    (32, 128 * 128 * 4, 8),     # Ling's: 512 KiB of 64 KiB heads
    (32, 64 * 64 * 4, 32),      # a row's state whole
    (6, 128 * 128 * 4, 6),
    (48, 128 * 128 * 4, 8),
    (12, 128 * 128 * 4, 6),     # 8 fit; 6 divides
    (7, 128 * 128 * 4, 7),
    (11, 128 * 128 * 4, 1),     # a prime past the block: a head a block
    (4, 512 * 512 * 4, 1),      # a head larger than the block
    (16, 256 * 128 * 4, 4)])
def test_a_block_is_whole_heads_by_their_bytes(H, head_bytes, heads):
    assert kda.block_heads(H, head_bytes) == heads
    assert H % heads == 0


@pytest.mark.parametrize("dk,dv", [(4, 128), (8, 64), (12, 16)])
def test_a_width_the_chip_cannot_tile_is_refused_by_name(dk, dv):
    x = inputs((1, 2, 2, dk, dv))
    with pytest.raises(ValueError, match="cake_kda_step cannot run"):
        kda.step(x["state"], 0, jnp.ones((2,), jnp.int32), x["q"], x["k"],
                 x["v"], x["g"], x["beta"], interpret=False)


def test_step_codes_read_the_rows():
    rows = bh.Rows(jnp.arange(6), jnp.array([1, 0, 1, 5, 1, 0]),
                   jnp.array([7, 0, 0, 0, 3, 9]))
    np.testing.assert_array_equal(
        bh.step_codes(rows), [STEP, STAY, FRESH, STAY, STEP, STAY])


# -- through the served trunks -------------------------------------------------

B, C, PAGE, MAX_SEQ = 4, 12, 8, 64


@pytest.fixture(scope="module")
def model():
    c = BailingHybridConfig.tiny_ling()
    return (c, init_params(c, jax.random.PRNGKey(0), jnp.float32),
            RopeTables.create(c, MAX_SEQ))


def fresh_cache(c):
    cache = PagedKVCache.create(c, B, 1 + B * (MAX_SEQ // PAGE), PAGE,
                                MAX_SEQ, dtype=jnp.float32)
    table = np.stack([1 + b * (MAX_SEQ // PAGE) + np.arange(MAX_SEQ // PAGE)
                      for b in range(B)]).astype(np.int32)
    return cache._replace(table=jnp.asarray(table))


def dispatches(model, step):
    """A prompt's two windows with no company, a mixed dispatch (row 1's
    window from position 0, rows 0 and 3 decoding, row 3 from a fresh
    state, row 2 idle), then a decode dispatch (rows 0, 1, 3; row 2
    idle), with `step` in ops/kda.step's place. Returns the tokens the
    last two dispatches choose and the state and tails after each."""
    c, params, rope = model
    old, kda.step = kda.step, step
    try:
        # new function objects: jit traces them with `step` in place
        mixed = jax.jit(lambda *a: bh.mixed_trunk(
            *a, rope, c, "fold", mixed_token_buckets(B, C, (1,))[-1])[0])
        decode = jax.jit(lambda *a: bh.decode_trunk(*a, rope, c, "fold"))
        rng = np.random.default_rng(3)
        cache, out = fresh_cache(c), []
        toks = rng.integers(0, c.vocab_size, (B, C)).astype(np.int32)
        for pos, qlen in (([0, 0, 0, 0], [C, 0, 0, 0]),
                          ([C, 0, 0, 0], [5, 0, 0, 0]),
                          ([C + 5, 0, 0, 0], [1, 9, 0, 1])):
            pos, qlen = np.asarray(pos, np.int32), np.asarray(qlen, np.int32)
            res = mixed(params, jnp.asarray(toks), jnp.asarray(pos),
                        jnp.asarray(qlen), jnp.asarray(qlen > 0), cache)
            cache = res.cache
        head = bh.dequantized(params["lm_head"])
        out.append((np.asarray(jnp.argmax(res.x @ head, -1)),
                    np.asarray(cache.ssm), np.asarray(cache.conv)))
        res = decode(params, jnp.asarray(toks[:, :1]), cache,
                     jnp.asarray([C + 6, 9, 0, 1], jnp.int32),
                     jnp.asarray([True, True, False, True]))
        out.append((np.asarray(jnp.argmax(res.x @ head, -1)),
                    np.asarray(res.cache.ssm), np.asarray(res.cache.conv)))
        return out
    finally:
        kda.step = old


@pytest.fixture(scope="module")
def both(model):
    return dispatches(model, kda.step), dispatches(model, bh.kda_step_fold)


@pytest.mark.parametrize("dispatch", ["mixed", "decode"])
def test_a_dispatch_chooses_the_tokens_the_fold_chose(both, dispatch):
    at = ("mixed", "decode").index(dispatch)
    np.testing.assert_array_equal(both[0][at][0], both[1][at][0])


@pytest.mark.parametrize("dispatch", ["mixed", "decode"])
def test_a_dispatch_leaves_the_state_the_fold_left(both, dispatch):
    at = ("mixed", "decode").index(dispatch)
    (_, S, tails), (_, want_S, want_tails) = both[0][at], both[1][at]
    np.testing.assert_allclose(S, want_S, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(tails, want_tails, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dispatch", ["mixed", "decode"])
def test_the_idle_row_keeps_its_bits_through_a_dispatch(both, dispatch):
    """Row 2 never holds a token: zeros as created, in every layer."""
    at = ("mixed", "decode").index(dispatch)
    np.testing.assert_array_equal(both[0][at][1][:, 2], 0.0)


def test_the_window_row_starts_from_its_state_before_the_step(both):
    """Row 1's window of 9 from position 0 rides the mixed dispatch
    whose kernel call steps rows 0 and 3 of the same layer in place:
    its state is the chunked rule's from zeros, as with the fold."""
    (_, S, _), (_, want, _) = both[0][0], both[1][0]
    assert np.abs(want[:, 1]).max() > 0
    np.testing.assert_allclose(S[:, 1], want[:, 1], rtol=1e-5, atol=1e-5)


def test_the_bench_tool_rehearses_and_checks_the_kernel(capsys):
    """tools/kda_step_bench.py at tiny widths: one JSON line, the
    kernel's call compared with the fold's where they lie."""
    import importlib.util
    import json
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "kda_step_bench.py"
    spec = importlib.util.spec_from_file_location("kda_step_bench", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearse", "--calls", "2"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["check"] == {"S_err": 0.0, "o_err": 0.0, "stay_bits": True,
                             "others_bits": True, "o_stay_zero": True}
    assert {"fold_all", "kernel_all", "kernel_some", "kernel_none"} <= set(line)
