"""The paged step programs against `model.forward` over the same tokens.

Every block kind that runs `model.block_skeleton_stats` (dense GQA, a
QKV bias, query/key norm with sparse experts), with full-precision and
with int8 weights: three ragged rows are prefilled through the mixed
step in two dispatches (a window that ends its prompt, a window
mid-prompt, a decode row among them), then decoded; at every point a
row's logits are what the plain forward gives over that row's tokens
from position 0 on a dense cache. Both sides project q and k through
the same skeleton (PR 43: the head split happens on the activations,
behind a barrier), so what this holds is that the step programs' own
writes, tables and packing add nothing to it.
"""

import dataclasses
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.models.llama.cache import KVCache
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.model import RopeTables, forward
from cake_tpu.models.llama.paged import (
    PagedKVCache, decode_step_ragged_paged, mixed_step_paged,
)
from cake_tpu.ops.quant import quantize_params

PAGE, T, C = 8, 32, 8
# tests/test_paged.py's bar for a paged program against the dense path
TOL = 2e-4
# prompt lengths a row; the mixed step's two dispatches as (pos, q_len)
PROMPTS = (9, 14, 5)
WINDOWS = (((0, 8), (0, 8), (0, 5)),      # row 2 ends its prompt
           ((8, 1), (8, 6), (5, 1)))      # rows 0, 1 end; row 2 decodes
DECODES = 2


def _model(block, weights):
    if block == "q_norm":
        from cake_tpu.models.moe import MoEConfig, init_params
        cfg = MoEConfig.tiny_olmoe(num_hidden_layers=2)
    else:
        from cake_tpu.models.llama.params import init_params
        cfg = LlamaConfig.tiny(num_hidden_layers=2)
        if block == "qkv_bias":
            cfg = dataclasses.replace(cfg, attention_bias=True)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    if block == "qkv_bias":
        # a zero bias would not show a bias applied to the wrong row
        blocks = dict(params["blocks"])
        for i, name in enumerate(("bq", "bk", "bv")):
            blocks[name] = 0.5 * jax.random.normal(
                jax.random.PRNGKey(10 + i), blocks[name].shape)
        params = dict(params, blocks=blocks)
    if weights == "int8":
        params = quantize_params(params, bits=8)
    return cfg, params


@partial(jax.jit, static_argnames=("cfg",))
def _reference(params, tokens, held, rope, cfg):
    """[B, vocab] logits after each row's first `held` tokens, by the
    plain forward from position 0 (causal: what follows is not seen)."""
    cache = KVCache.create(cfg, tokens.shape[0], T, dtype=jnp.float32)
    return forward(params, tokens, cache, jnp.int32(0), rope, cfg,
                   last_idx=held - 1)[0]


@pytest.mark.parametrize("n_tokens", [None, 24], ids=["windows", "packed"])
@pytest.mark.parametrize("weights", ["float", "int8"])
@pytest.mark.parametrize("block", ["dense", "qkv_bias", "q_norm"])
def test_step_programs_match_forward(block, weights, n_tokens):
    cfg, params = _model(block, weights)
    rope = RopeTables.create(cfg, T)
    B, per = len(PROMPTS), T // PAGE
    cache = PagedKVCache.create(cfg, B, B * per + 1, PAGE, T,
                                dtype=jnp.float32)
    cache = cache._replace(table=jnp.asarray(
        1 + np.arange(B * per, dtype=np.int32).reshape(B, per)))
    rng = np.random.default_rng(7)
    rows = rng.integers(3, cfg.vocab_size, (B, T))
    active = jnp.ones((B,), bool)

    def check(out, held):
        want = _reference(params, jnp.asarray(rows, jnp.int32),
                          jnp.asarray(held, jnp.int32), rope, cfg)
        np.testing.assert_allclose(
            np.asarray(out[0]), np.asarray(want), atol=TOL, rtol=TOL,
            err_msg=f"after {held} tokens")
        return out[1]

    held = [0] * B
    for step, windows in enumerate(WINDOWS):
        tokens = np.zeros((B, C), np.int32)
        for b, (pos, n) in enumerate(windows):
            assert pos == held[b]
            tokens[b, :n] = rows[b][pos:pos + n]
            held[b] = pos + n
        pos, q_len = (jnp.asarray(a, jnp.int32) for a in zip(*windows))
        out = mixed_step_paged(params, jnp.asarray(tokens), pos, q_len,
                               active, cache, rope, config=cfg,
                               n_tokens=n_tokens)
        # a row mid-prompt returns logits nobody reads: all are read here
        cache = check(out, held)
    assert held == [PROMPTS[0], PROMPTS[1], PROMPTS[2] + 1]
    for _ in range(DECODES):
        tokens = jnp.asarray([[rows[b][held[b]]] for b in range(B)],
                             jnp.int32)
        out = decode_step_ragged_paged(
            params, tokens, jnp.asarray(held, jnp.int32), active, cache,
            rope, config=cfg)
        held = [n + 1 for n in held]
        cache = check(out, held)
