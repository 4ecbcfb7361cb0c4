"""Sampling ops: repeat penalty, top-k/top-p filtering, greedy/categorical."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.ops.sampling import (
    SamplingConfig, apply_repeat_penalty, nucleus_floor, sample_tokens,
    sample_tokens_ragged, update_ring, _mask_top_k, _mask_top_p,
)


def test_repeat_penalty_semantics():
    # candle semantics: logit>=0 divided, logit<0 multiplied (llama.rs:311-320)
    logits = jnp.asarray([[2.0, -2.0, 4.0, 1.0]])
    recent = jnp.asarray([[0, 1, -1, -1]], dtype=jnp.int32)  # -1 = empty slot
    out = np.asarray(apply_repeat_penalty(logits, recent, 2.0))
    np.testing.assert_allclose(out, [[1.0, -4.0, 4.0, 1.0]])


def test_repeat_penalty_noop_at_one():
    logits = jnp.asarray([[2.0, -2.0]])
    recent = jnp.asarray([[0]], dtype=jnp.int32)
    out = np.asarray(apply_repeat_penalty(logits, recent, 1.0))
    np.testing.assert_allclose(out, [[2.0, -2.0]])


def test_top_k_mask():
    logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0]])
    out = np.asarray(_mask_top_k(logits, 2))
    assert np.isinf(out[0, 0]) and np.isinf(out[0, 3])
    assert out[0, 1] == 5.0 and out[0, 2] == 3.0


def test_top_p_keeps_head_of_distribution():
    logits = jnp.asarray([[10.0, 1.0, 0.0, -5.0]])
    out = np.asarray(_mask_top_p(logits, 0.9))
    assert out[0, 0] == 10.0          # top token always survives
    assert np.isinf(out[0, 3])        # tail is cut


def test_greedy_sampling():
    cfg = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
    logits = jnp.asarray([[0.0, 3.0, 1.0]])
    recent = jnp.full((1, 4), -1, dtype=jnp.int32)
    tok = sample_tokens(jax.random.PRNGKey(0), logits, recent, cfg)
    assert int(tok[0]) == 1


def test_categorical_respects_filtering():
    cfg = SamplingConfig(temperature=1.0, top_k=1, repeat_penalty=1.0)
    logits = jnp.asarray([[0.0, 5.0, 1.0]])
    recent = jnp.full((1, 4), -1, dtype=jnp.int32)
    for seed in range(5):
        tok = sample_tokens(jax.random.PRNGKey(seed), logits, recent, cfg)
        assert int(tok[0]) == 1


def test_ring_buffer():
    ring = jnp.full((1, 3), -1, dtype=jnp.int32)
    for step, t in enumerate([7, 8, 9, 10]):
        ring = update_ring(ring, jnp.asarray([t], dtype=jnp.int32), step)
    assert np.asarray(ring).tolist() == [[10, 8, 9]]


# -- the nucleus without a sort (PR 38) --------------------------------------
# The sort-based rule the search replaced, kept here as the plain reference.


def reference_floor(scaled, top_p):
    """Order the row, keep a token iff the mass strictly above it is < p,
    the top token always; ties of the last kept value survive (`<`)."""
    sorted_logits = jnp.sort(scaled, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < jnp.clip(top_p, 0.0, 1.0)[:, None]
    keep = keep.at[..., 0].set(True)
    kth = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                  keepdims=True)
    return jnp.where(scaled < kth, -jnp.inf, scaled)


def reference_sample_ragged(keys, logits, temperature, top_p):
    """The parent's `sample_tokens_ragged` at repeat_penalty 1, no top-k."""
    logits = logits.astype(jnp.float32)
    greedy = temperature <= 0.0
    scaled = logits / jnp.where(greedy, 1.0, temperature)[:, None]
    filtered = reference_floor(scaled, top_p)
    sampled = jax.vmap(jax.random.categorical)(keys, filtered)
    ids = jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled)
    lp = jax.nn.log_softmax(logits, axis=-1)
    return (ids.astype(jnp.int32),
            jnp.take_along_axis(lp, ids[:, None], axis=-1)[:, 0])


def kept(filtered):
    return np.isfinite(np.asarray(filtered))


def ragged(keys, logits, temperature, top_p, **kw):
    B = logits.shape[0]
    return sample_tokens_ragged(
        keys, logits, jnp.full((B, 4), -1, jnp.int32),
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(top_p, jnp.float32), jnp.ones(B, jnp.float32), **kw)


def logits_of(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * 3.0


@pytest.mark.parametrize("p", [0.5, 0.9, 0.95])
@pytest.mark.parametrize("shape", [(4, 1000), (8, 32768), (2, 262272)])
def test_kept_set_equals_the_sorted_reference(shape, p):
    x = logits_of(shape, seed=shape[1])
    pv = jnp.full((shape[0],), p)
    got, want = kept(nucleus_floor(x, pv)), kept(reference_floor(x, pv))
    assert got.sum(axis=-1).min() >= 1
    # a token may fall on the other side only where the mass above it is
    # within rounding of p (the masked sum adds in another order than the
    # cumsum): the same rule, not another one
    x64 = np.asarray(x, np.float64)
    for r, i in zip(*np.nonzero(got != want)):
        e = np.exp(x64[r] - x64[r].max())
        above = e[x64[r] > x64[r, i]].sum() / e.sum()
        assert abs(above - p) < 1e-6, (r, i, above)


def test_ties_at_the_boundary_survive_together():
    # 0.4 | 0.2 0.2 | 0.1 0.1: at p = 0.5 the mass above both 0.2s is 0.4
    probs = np.asarray([[0.1, 0.2, 0.4, 0.2, 0.1]], np.float32)
    got = kept(nucleus_floor(jnp.log(probs), jnp.asarray([0.5])))
    assert got.tolist() == [[False, True, True, True, False]]
    # +0.0 and -0.0 are one value to `<`, so one value to the keys
    zeros = jnp.asarray([[0.0, -0.0, -3.0, -0.0, -9.0]])
    got = kept(nucleus_floor(zeros, jnp.asarray([0.5])))
    assert got.tolist() == [[True, True, False, True, False]]
    assert (got == kept(reference_floor(zeros, jnp.asarray([0.5])))).all()


@pytest.mark.parametrize("p", [0.3, 0.9])
def test_rows_a_top_k_mask_left(p):
    x = logits_of((3, 512), seed=5)
    x = x.at[0].set(_mask_top_k(x[:1], 40)[0])       # 40 finite logits
    x = x.at[1].set(jnp.where(jnp.arange(512) == 77, x[1], -jnp.inf))
    pv = jnp.full((3,), p)
    out = nucleus_floor(x, pv)
    got, want = kept(out), kept(reference_floor(x, pv))
    assert (got == want).all()
    assert got[0].sum() <= 40 and not got[0][~kept(x[0])].any()
    assert got[1].tolist() == (np.arange(512) == 77).tolist()
    # what survives keeps its value
    assert (np.asarray(out)[got] == np.asarray(x)[got]).all()


@pytest.mark.parametrize("p", [0.0, -1.0])
def test_p_at_or_under_zero_keeps_the_maximum_and_its_ties(p):
    x = jnp.asarray([[1.0, 4.0, 2.0, 4.0], [-7.0, -9.0, -8.0, -jnp.inf]])
    got = kept(nucleus_floor(x, jnp.full((2,), p)))
    assert got.tolist() == [[False, True, False, True],
                            [True, False, False, False]]


@pytest.mark.parametrize("p", [1.0, 1.5])
def test_p_at_or_over_one_keeps_every_finite_logit(p):
    # the sorted rule drops a far tail here (its f32 cumsum reaches 1.0
    # before the row ends); "p >= 1 keeps every token" is now literal
    x = logits_of((2, 32768), seed=9)
    x = x.at[1, :100].set(-jnp.inf)
    out = np.asarray(nucleus_floor(x, jnp.full((2,), p)))
    assert (out == np.asarray(x)).all()


def test_one_p_a_row_in_one_batch():
    x = jnp.tile(logits_of((1, 4096), seed=3), (5, 1))
    pv = jnp.asarray([0.0, 0.5, 0.9, 1.0, 0.95])
    got = kept(nucleus_floor(x, pv))
    for r, p in enumerate(np.asarray(pv)):
        alone = kept(nucleus_floor(x[r:r + 1], jnp.asarray([p])))[0]
        assert (got[r] == alone).all()
    counts = got.sum(axis=-1)
    assert counts[0] == 1 and counts[3] == 4096
    assert counts[0] < counts[1] < counts[2] < counts[4] < counts[3]
    assert (got == kept(reference_floor(x, pv)))[[0, 1, 2, 4]].all()


def test_greedy_rows_return_argmax_and_the_same_logprob():
    logits = jnp.asarray([[0.0, 3.0, 1.0, -2.0], [2.5, 2.0, -1.0, 0.5],
                          [-4.0, -4.5, -3.0, -6.0]])
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    for top_p in ([1.0, 1.0, 1.0], [0.9, 0.0, 0.5]):
        ids, lp, top_ids, top_lps = ragged(keys, logits, [0.0] * 3, top_p)
        assert np.asarray(ids).tolist() == [1, 0, 2]
        # log_softmax at the argmax, computed by hand from the logits
        np.testing.assert_allclose(
            np.asarray(lp), [-0.17551536, -0.57214459, -0.49518190],
            rtol=0, atol=1e-4)     # the chip's log rounds at 2e-5
        want_ids, want_lp = reference_sample_ragged(
            keys, logits, jnp.zeros(3), jnp.asarray(top_p))
        assert (np.asarray(ids) == np.asarray(want_ids)).all()
        assert (np.asarray(lp) == np.asarray(want_lp)).all()
        assert top_ids.shape == (3, 0) and top_lps.shape == (3, 0)


@pytest.mark.parametrize("p", [0.5, 0.9, 0.95])
def test_a_sampled_row_draws_what_the_reference_filter_gives_it(p):
    logits = logits_of((6, 2048), seed=11)
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    temperature = jnp.asarray([0.7, 1.0, 0.0, 0.6, 1.3, 1.0])
    top_p = jnp.asarray([p, p, p, 1.0, p, 0.0])
    ids, lp, _, _ = ragged(keys, logits, temperature, top_p)
    want_ids, want_lp = reference_sample_ragged(keys, logits, temperature,
                                                top_p)
    assert np.asarray(ids).tolist() == np.asarray(want_ids).tolist()
    assert (np.asarray(lp) == np.asarray(want_lp)).all()
    assert int(ids[2]) == int(jnp.argmax(logits[2]))
    assert int(ids[5]) == int(jnp.argmax(logits[5]))    # p = 0: the maximum


@pytest.mark.parametrize("p", [0.5, 0.95])
def test_one_nucleus_for_both_samplers(p):
    logits = logits_of((4, 1000), seed=2)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    filtered = _mask_top_p(logits, p)
    assert (kept(filtered)
            == kept(nucleus_floor(logits, jnp.full((4,), p)))).all()
    ids, _, _, _ = ragged(keys, logits, [1.0] * 4, [p] * 4)
    want = jax.vmap(jax.random.categorical)(keys, filtered)
    assert np.asarray(ids).tolist() == np.asarray(want).tolist()
    # the offline sampler filters through the same function
    cfg = SamplingConfig(temperature=1.0, top_p=p, repeat_penalty=1.0)
    tok = sample_tokens(keys[0], logits, jnp.full((4, 4), -1, jnp.int32), cfg)
    assert np.asarray(tok).tolist() == np.asarray(
        jax.random.categorical(keys[0], filtered, axis=-1)).tolist()


@pytest.mark.parametrize("shape", [(4, 1000), (32, 262272)])
def test_the_sampled_program_holds_no_sort(shape):
    B, V = shape
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    lowered = sample_tokens_ragged.lower(
        jax.ShapeDtypeStruct((B, 2), jnp.uint32), f32(B, V),
        jax.ShapeDtypeStruct((B, 128), jnp.int32), f32(B), f32(B), f32(B),
        top_k=None, n_top=0)
    text = lowered.as_text()
    assert "stablehlo.while" in text          # the search is there
    assert "stablehlo.sort" not in text and "sort(" not in text
    assert "top_k" not in text                # n_top = 0: no lax.top_k
    if V <= 1000:
        assert not re.search(r"\bsort\b", lowered.compile().as_text())
