"""The packed mixed step (PR 27) against the window program.

`mixed_step_paged(..., n_tokens=T)` packs a step's real tokens out of
their `[B, C]` windows and runs every layer over `[1, T, D]`; only the
attention call still sees windows. `n_tokens=None` is the window
program, the reference here. Same tokens, same mathematics: the last
token's logits of every active row, the whole page pool and, for a
sparse model, the five expert counters must agree at every size of a
ladder (the full `B*C` one included), for the fold and for the
Pallas-interpreted kernel, over steps that mix decode rows, mid-prompt
windows, a window that ends its prompt, idle rows, and a step that
fills its bucket exactly.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import (
    PagedKVCache, mixed_bucket_for, mixed_step_paged, mixed_token_buckets,
    pack_plan,
)

B, C, PAGE, T = 6, 16, 8, 64
# the sizes an engine of these slots and windows would run at and three
# more, up to every position of every window: the program takes any
# size that holds the step's tokens
LADDER = (32, 48, 64, 80, 96)
# float32 on the CPU: the two programs differ by a matmul's M alone
TOL = 1e-5

# (pos, q_len, active) a row. "mixed": a decode row, a mid-prompt
# window, a window that ends its prompt (5 of 16), two idle rows (one
# of them with a stale q_len), another decode row: 23 tokens.
# "full": 32 tokens, exactly the smallest bucket.
STEPS = {
    "mixed": ([37, 16, 32, 0, 9, 0], [1, 16, 5, 0, 1, 3],
              [True, True, True, False, True, False]),
    "full": ([0, 20, 0, 16, 40, 0], [16, 1, 0, 14, 1, 0],
             [True, True, False, True, True, False]),
}


def _config(family):
    if family == "olmoe":
        from cake_tpu.models.moe import MoEConfig
        return MoEConfig.tiny_olmoe(num_hidden_layers=2)
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    if family == "qkv_bias":
        cfg = dataclasses.replace(cfg, attention_bias=True)
    return cfg


@pytest.fixture(scope="module")
def models():
    out = {}
    for family in ("gqa", "qkv_bias", "olmoe"):
        cfg = _config(family)
        if family == "olmoe":
            from cake_tpu.models.moe import init_params
        else:
            from cake_tpu.models.llama.params import init_params
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        if family == "qkv_bias":
            # a zero bias would not show a bias applied to the wrong row
            blocks = dict(params["blocks"])
            for i, name in enumerate(("bq", "bk", "bv")):
                blocks[name] = 0.5 * jax.random.normal(
                    jax.random.PRNGKey(10 + i), blocks[name].shape)
            params = dict(params, blocks=blocks)
        out[family] = (cfg, params)
    return out


def _cache(cfg):
    """A pool that already holds something everywhere, every row mapped
    to pages of its own: a write that lands on the wrong page or row
    shows in the comparison of the whole pool."""
    per = T // PAGE
    cache = PagedKVCache.create(cfg, B, B * per + 1, PAGE, T,
                                dtype=jnp.float32)
    table = 1 + np.arange(B * per, dtype=np.int32).reshape(B, per)
    return cache._replace(
        table=jnp.asarray(table),
        k=jax.random.normal(jax.random.PRNGKey(1), cache.k.shape),
        v=jax.random.normal(jax.random.PRNGKey(2), cache.v.shape))


def _run(cfg, params, step, attn, n_tokens):
    pos, q_len, active = (jnp.asarray(a) for a in STEPS[step])
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, C), 0,
                                cfg.vocab_size)
    return mixed_step_paged(
        params, tokens, pos.astype(jnp.int32), q_len.astype(jnp.int32),
        active, _cache(cfg), RopeTables.create(cfg, T), config=cfg,
        attn=attn, n_tokens=n_tokens)


def test_the_sizes_of_the_benchmarks_engine():
    """16 slots of 128: what one and what two prefilling rows need
    beside decode rows in every other slot; a dispatch runs at the
    smaller size if it holds its tokens."""
    sizes = mixed_token_buckets(16, 128)
    assert sizes == (144, 272)
    assert [mixed_bucket_for(sizes, n) for n in (1, 144, 145, 272)] \
        == [144, 144, 272, 272]
    assert mixed_token_buckets(B, C) == (32, 48)
    # never more than every position of every window
    assert mixed_token_buckets(2, 16) == (32,)
    assert mixed_token_buckets(1, 64) == (64,)


def test_pack_plan_places_rows_in_slot_order():
    pos, q_len, active = STEPS["mixed"]
    plan = pack_plan(jnp.asarray(q_len), jnp.asarray(active), 32, C)
    assert plan.start.tolist() == [0, 1, 17, 22, 22, 23]
    assert int(plan.real.sum()) == 23
    cells = list(zip(plan.row.tolist(), plan.col.tolist()))[:23]
    assert cells == ([(0, 0)] + [(1, i) for i in range(16)]
                     + [(2, i) for i in range(5)] + [(4, 0)])
    # the bucket's padding stays inside the window grid
    assert set(plan.row[23:].tolist()) == {B - 1}
    assert set(plan.col[23:].tolist()) == {0}


@pytest.mark.parametrize("n_tokens", LADDER)
@pytest.mark.parametrize("attn", ["fold", "pallas"])
@pytest.mark.parametrize("family", ["gqa", "qkv_bias", "olmoe"])
def test_packed_step_equals_window_step(models, family, attn, n_tokens):
    cfg, params = models[family]
    for step, (_, q_len, active) in STEPS.items():
        real = sum(q for q, a in zip(q_len, active) if a)
        assert real <= n_tokens
        want = _run(cfg, params, step, attn, None)
        got = _run(cfg, params, step, attn, n_tokens)
        rows = np.asarray(active)
        np.testing.assert_allclose(np.asarray(got[0])[rows],
                                   np.asarray(want[0])[rows],
                                   atol=TOL, rtol=TOL, err_msg=step)
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(getattr(got[1], name)),
                np.asarray(getattr(want[1], name)), atol=TOL, rtol=TOL,
                err_msg=f"{step}: pool {name}")
        assert len(got) == len(want) == (3 if family == "olmoe" else 2)
        if family == "olmoe":
            # rows, padded rows, busiest and average expert, touched
            np.testing.assert_allclose(np.asarray(got[2]),
                                       np.asarray(want[2]), rtol=1e-6)
            assert float(got[2][0]) == (real * cfg.num_experts_per_tok
                                        * cfg.num_hidden_layers)
