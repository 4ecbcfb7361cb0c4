"""The accept/resample arithmetic of a speculative round (spec/accept.py),
held directly.

Every function there is branch-free arithmetic on stacked arrays, so
each case builds the arrays by hand and reads the answer off: how many
drafts an exact-match row keeps, what Leviathan et al.'s rule (2023,
"Fast Inference from Transformers via Speculative Decoding", alg. 1)
accepts and what it resamples from, where the correction token lands in
a row's burst, and whose PRNG stream a round may touch.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.spec.accept import (
    advance_row_keys, assemble_sampled, greedy_accept, rejection_accept,
)

V = 11


# -- greedy rows: the longest exact-match prefix -------------------------------


@pytest.mark.parametrize("gamma,kept", [
    (g, n) for g in (1, 2, 4) for n in range(g + 1)])
def test_greedy_accept_counts_the_matching_prefix(gamma, kept):
    """`kept` drafts agree with the target's argmax, the next one does
    not, and the ones after it agree again: only the prefix counts. A
    second row that agrees everywhere rides the same call."""
    targets = np.arange(3, 3 + gamma + 1, dtype=np.int32)
    drafts = targets[:gamma].copy()
    if kept < gamma:
        drafts[kept] = 0
    both = jnp.asarray(np.stack([drafts, targets[:gamma]]))
    n_acc = greedy_accept(both, jnp.asarray(np.stack([targets, targets])))
    assert n_acc.tolist() == [kept, gamma]


# -- sampled rows: rejection sampling with the leftover residual --------------


def _probs(rng, *shape):
    p = rng.random(shape + (V,)) ** 3 + 0.01     # peaked, never zero
    return (p / p.sum(-1, keepdims=True)).astype(np.float32)


def leviathan(drafts, d_probs, t_probs, u):
    """Alg. 1 of the paper, one row, as written: keep draft i while
    u_i < p(x_i) / q(x_i); at the first refusal n resample from
    norm(max(0, p_n - q_n)); with every draft kept, from p_gamma."""
    gamma = len(drafts)
    n = 0
    while n < gamma and u[n] < min(
            1.0, t_probs[n, drafts[n]] / d_probs[n, drafts[n]]):
        n += 1
    resid = (np.maximum(t_probs[n] - d_probs[n], 0.0) if n < gamma
             else t_probs[gamma])
    return n, resid / resid.sum()


@pytest.mark.parametrize("gamma", [1, 2, 4])
def test_identical_distributions_accept_every_draft(gamma):
    """p == q: the ratio is 1 and every u in [0, 1) is under it; the
    bonus token is drawn from the target's own next distribution."""
    rng = np.random.default_rng(gamma)
    t = _probs(rng, 2, gamma + 1)
    drafts = rng.integers(0, V, (2, gamma)).astype(np.int32)
    u = rng.random((2, gamma)).astype(np.float32)
    n_acc, resid = rejection_accept(
        jnp.asarray(drafts), jnp.asarray(t[:, :gamma]), jnp.asarray(t),
        jnp.asarray(u), gamma)
    assert n_acc.tolist() == [gamma, gamma]
    np.testing.assert_allclose(resid, t[:, gamma], rtol=1e-6)


@pytest.mark.parametrize("gamma", [1, 2, 4])
def test_disjoint_distributions_accept_none(gamma):
    """The draft samples only where the target has no mass: the first
    draft is refused whatever u is, the residual is the target's first
    distribution whole, and the correction drawn from it is a token the
    target could have produced."""
    low, high = np.arange(V) < V // 2, np.arange(V) >= V // 2
    rng = np.random.default_rng(10 + gamma)
    t = _probs(rng, 3, gamma + 1) * high
    d = _probs(rng, 3, gamma) * low
    t, d = t / t.sum(-1, keepdims=True), d / d.sum(-1, keepdims=True)
    drafts = rng.integers(0, V // 2, (3, gamma)).astype(np.int32)
    n_acc, resid = rejection_accept(
        jnp.asarray(drafts), jnp.asarray(d), jnp.asarray(t),
        jnp.zeros((3, gamma), jnp.float32), gamma)
    assert n_acc.tolist() == [0, 0, 0]
    np.testing.assert_allclose(resid, t[:, 0], rtol=1e-6)
    keys = jax.random.split(jax.random.PRNGKey(gamma), 3)
    correction = jax.vmap(jax.random.categorical)(
        keys, jnp.log(jnp.maximum(resid, 1e-20)))
    assert all(high[int(c)] for c in correction)


@pytest.mark.parametrize("gamma,seed", [(2, 0), (2, 1), (4, 2), (4, 3)])
def test_rejection_accept_is_the_papers_rule(gamma, seed):
    """Fixed draws, eight rows of unrelated distributions: the count
    and the residual are what the paper's loop gives row by row, and
    the rows between them stop at more than one length."""
    rng = np.random.default_rng(seed)
    B = 8
    t, d = _probs(rng, B, gamma + 1), _probs(rng, B, gamma)
    drafts = rng.integers(0, V, (B, gamma)).astype(np.int32)
    u = rng.random((B, gamma)).astype(np.float32)
    n_acc, resid = rejection_accept(
        jnp.asarray(drafts), jnp.asarray(d), jnp.asarray(t),
        jnp.asarray(u), gamma)
    want = [leviathan(drafts[b], d[b], t[b], u[b]) for b in range(B)]
    assert n_acc.tolist() == [n for n, _ in want]
    assert len(set(n_acc.tolist())) > 1
    np.testing.assert_allclose(resid, np.stack([r for _, r in want]),
                               rtol=1e-5, atol=1e-7)


# -- a sampled row's burst -----------------------------------------------------


@pytest.mark.parametrize("kept", [0, 1, 2, 3])
def test_assemble_sampled_puts_the_correction_after_the_kept_drafts(kept):
    """[kept drafts, correction, padding]: the caller emits kept + 1
    tokens, so what lies past the correction only has to be there."""
    gamma = 3
    drafts = jnp.asarray([[4, 5, 6]], jnp.int32)
    out = assemble_sampled(drafts, jnp.asarray([9], jnp.int32),
                           jnp.asarray([kept], jnp.int32), gamma)
    assert out.shape == (1, gamma + 1)
    assert out[0, :kept + 1].tolist() == [4, 5, 6][:kept] + [9]


# -- whose PRNG stream a round touches -----------------------------------------


@pytest.mark.parametrize("who", ["idle", "greedy"])
def test_advance_row_keys_leaves_a_masked_rows_key_alone(who):
    """A round advances the key of an active sampled row alone: an idle
    slot's and a greedy row's streams are what they were, so what else
    is in the batch cannot change a request's sampled tokens. The row
    that does advance gets jax.random.split's two halves."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    active = np.array([True, who != "idle", True])
    greedy = np.array([False, who == "greedy", False])
    new, subs = advance_row_keys(keys, jnp.asarray(active & ~greedy))
    np.testing.assert_array_equal(new[1], keys[1])
    for b in (0, 2):
        nk, sub = jax.random.split(keys[b])
        np.testing.assert_array_equal(new[b], nk)
        np.testing.assert_array_equal(subs[b], sub)
