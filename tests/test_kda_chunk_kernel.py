"""`cake_kda_chunk` (ops/kda.chunked) against the form it replaced.

The kernel, interpreted, over one row's window: the state it leaves and
the outputs it gives are `bailing_hybrid.kda_chunked`'s (XLA's chunked
form: the comparison's, no step program calls it) AND the recurrence's
itself, token by token in float64, to 2e-5: a window that starts from a
state, windows that fill whole chunks and that do not, one past a block
of chunks, every channel at the decay's bound, tokens past the window's
own (g = 0, beta = 0: the state passes through them), two sizes of head
block, bfloat16 values. Then through the served trunk: tiny Ling's
mixed dispatches choose the tokens and leave the state that
`kda_chunked` in the kernel's place leaves.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.paged import mixed_token_buckets
from cake_tpu.models.moe import bailing_hybrid as bh
from cake_tpu.ops import kda
from tests.test_kda_kernel import B, C, fresh_cache, model  # noqa: F401

ATOL = 2e-5

# name -> (C, H, dk, dv, heads a program, what is special)
CASES = {
    "whole_chunks": (64, 4, 8, 16, 4, ()),
    "part_of_a_chunk": (12, 4, 8, 16, 4, ()),
    "chunks_and_a_part": (40, 4, 8, 16, 2, ()),
    "a_ragged_window": (100, 2, 8, 8, 2, ()),
    "past_a_block": (144, 2, 8, 8, 1, ()),
    "from_zeros": (40, 4, 8, 16, 4, ("zero_state",)),
    # (at the bound a chunk's last token holds q e^-80: a channel of q
    # under 6.5e-4 falls below float32's least normal number there and
    # is flushed, in XLA's form as in the kernel; this draw holds none)
    "at_the_bound": (64, 4, 8, 16, 2, ("bound", "seed_7")),
    "past_its_own_tokens": (64, 4, 8, 16, 4, ("own_27",)),
    "none_its_own": (32, 4, 8, 16, 4, ("own_0",)),
    "two_heads_a_program": (64, 4, 8, 16, 2, ()),
    "a_head_a_program": (40, 4, 8, 16, 1, ()),
    "values_in_bfloat16": (64, 4, 8, 16, 4, ("bf16",)),
    # one head at the published widths
    "published_head": (32, 1, 128, 128, 1, ()),
}


def inputs(C, H, dk, dv, special=(), seed=0):
    seed = next((int(s[5:]) for s in special if s.startswith("seed_")), seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    k = jax.random.normal(ks[2], (C, H, dk))
    x = dict(
        S0=jax.random.normal(ks[0], (H, dk, dv), jnp.float32) * 0.3,
        q=jax.random.normal(ks[1], (C, H, dk)) * dk ** -0.5,
        k=k / jnp.linalg.norm(k, axis=-1, keepdims=True),
        v=jax.random.normal(ks[3], (C, H, dv)),
        g=-5 * jax.random.uniform(ks[4], (C, H, dk)) ** 4,
        beta=jax.random.uniform(ks[5], (C, H)))
    for s in special:
        if s == "zero_state":
            x["S0"] = jnp.zeros_like(x["S0"])
        elif s == "bound":
            x["g"] = jnp.full_like(x["g"], -5.0)
        elif s == "bf16":
            x["v"] = x["v"].astype(jnp.bfloat16)
        elif s.startswith("own_"):
            own = jnp.arange(C) < int(s[4:])
            x["g"] = jnp.where(own[:, None, None], x["g"], 0.0)
            x["beta"] = jnp.where(own[:, None], x["beta"], 0.0)
    return x


def recurrence(S0, q, k, v, g, beta):
    """The delta rule token by token, float64."""
    S0, q, k, v, g, beta = (np.asarray(jnp.asarray(a, jnp.float32),
                                       np.float64)
                            for a in (S0, q, k, v, g, beta))
    S, out = S0.copy(), []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[..., None] * S
        u = beta[t][:, None] * (v[t] - np.einsum("hk,hkv->hv", k[t], S))
        S = S + k[t][..., None] * u[:, None, :]
        out.append(np.einsum("hk,hkv->hv", q[t], S))
    return S, np.stack(out)


@functools.lru_cache(maxsize=None)
def case(name):
    C, H, dk, dv, heads, special = CASES[name]
    x = inputs(C, H, dk, dv, special)
    got = kda._chunk_pallas(*x.values(), heads=heads, interpret=True)
    return (x, *map(np.asarray, got),
            *map(np.asarray, bh.kda_chunked(*x.values())),
            *recurrence(*x.values()))


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_is_xlas_chunked_form(name):
    _, S, o, want_S, want_o, _, _ = case(name)
    np.testing.assert_allclose(S, want_S, rtol=0, atol=ATOL)
    np.testing.assert_allclose(o, want_o, rtol=0, atol=ATOL)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_is_the_recurrence_in_float64(name):
    _, S, o, _, _, want_S, want_o = case(name)
    assert np.isfinite(S).all() and np.isfinite(o).all()
    np.testing.assert_allclose(S, want_S, rtol=0, atol=ATOL)
    np.testing.assert_allclose(o, want_o, rtol=0, atol=ATOL)


@pytest.mark.parametrize("name,own", [("past_its_own_tokens", 27),
                                      ("none_its_own", 0)])
def test_the_state_passes_through_tokens_not_the_windows_own(name, own):
    """Past the window's own tokens g = 0 and beta = 0: the state the
    kernel leaves is the one the own tokens alone leave (the state it
    started from where there are none), whatever q, k and v hold there."""
    x, S, _, _, _, _, _ = case(name)
    if not own:
        np.testing.assert_array_equal(S, np.asarray(x["S0"]))
        return
    want, _ = recurrence(*(a if i == 0 else a[:own]
                           for i, a in enumerate(x.values())))
    np.testing.assert_allclose(S, want, rtol=0, atol=ATOL)


def test_a_window_goes_on_where_the_last_one_ended():
    """Two windows through the kernel, the second from the state the
    first left: the recurrence over both."""
    a = inputs(40, 4, 8, 16, seed=1)
    b = inputs(24, 4, 8, 16, seed=2)
    S, o1 = kda.chunked(*a.values())
    S, o2 = kda.chunked(S, *list(b.values())[1:])
    both = [a["S0"]] + [jnp.concatenate([a[n], b[n]]) for n in
                        ("q", "k", "v", "g", "beta")]
    want_S, want_o = recurrence(*both)
    np.testing.assert_allclose(S, want_S, rtol=0, atol=ATOL)
    np.testing.assert_allclose(jnp.concatenate([o1, o2]), want_o, rtol=0,
                               atol=ATOL)


@pytest.mark.parametrize("H,heads", [(32, 8), (4, 4), (6, 6), (12, 6),
                                     (7, 7), (11, 1), (48, 8)])
def test_a_program_holds_whole_heads_that_divide_the_layers(H, heads):
    assert kda.chunk_heads(H) == heads


@pytest.mark.parametrize("H,dk,dv", [(4, 8, 128), (32, 128, 64),
                                     (12, 128, 128)])
def test_a_shape_the_chip_cannot_tile_is_refused_by_name(H, dk, dv):
    x = inputs(16, H, dk, dv)
    with pytest.raises(ValueError, match="cake_kda_chunk cannot run"):
        kda.chunked(*x.values(), interpret=False)


# -- through the served trunk --------------------------------------------------


def dispatches(model, chunked):
    """test_kda_kernel.dispatches' mixed dispatches (a prompt's two
    windows with no company, then row 1's window of 9 from position 0
    beside two decoding rows and an idle one) with `chunked` in
    ops/kda.chunked's place. Returns the tokens the last dispatch
    chooses and the state and tails after each."""
    c, params, rope = model
    old, kda.chunked = kda.chunked, chunked
    try:
        # a new function object: jit traces it with `chunked` in place
        mixed = jax.jit(lambda *a: bh.mixed_trunk(
            *a, rope, c, "fold", mixed_token_buckets(B, C, (1,))[-1])[0])
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
            out.append((np.asarray(jnp.argmax(
                res.x @ bh.dequantized(params["lm_head"]), -1)),
                np.asarray(cache.ssm), np.asarray(cache.conv)))
        return out
    finally:
        kda.chunked = old


@pytest.fixture(scope="module")
def both(model):  # noqa: F811
    return dispatches(model, kda.chunked), dispatches(model, bh.kda_chunked)


DISPATCHES = ["first_window", "second_window", "window_beside_decodes"]


@pytest.mark.parametrize("dispatch", DISPATCHES)
def test_a_dispatch_chooses_the_tokens_xlas_form_chose(both, dispatch):
    at = DISPATCHES.index(dispatch)
    np.testing.assert_array_equal(both[0][at][0], both[1][at][0])


@pytest.mark.parametrize("dispatch", DISPATCHES)
def test_a_dispatch_leaves_the_state_xlas_form_left(both, dispatch):
    at = DISPATCHES.index(dispatch)
    (_, S, tails), (_, want_S, want_tails) = both[0][at], both[1][at]
    assert np.abs(want_S).max() > 0
    np.testing.assert_allclose(S, want_S, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(tails, want_tails, rtol=1e-5, atol=1e-5)


def test_the_bench_tool_rehearses_and_checks_the_kernel(capsys):
    """tools/kda_chunk_bench.py at tiny widths: one JSON line, the
    kernel's call compared with XLA's form, every case timed."""
    import importlib.util
    import json
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "kda_chunk_bench.py"
    spec = importlib.util.spec_from_file_location("kda_chunk_bench", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearse", "--calls", "2", "--no-arith"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert max(line["check"].values()) <= tool.TOLERANCE
    assert {"fold_sliced", "kernel_sliced", "kernel",
            "kernel_no_arith"} <= set(line)
