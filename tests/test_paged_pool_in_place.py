"""The page pool stays where it lies (PR 25).

A paged step program carries the STACKED pool through its layer loop,
scatters the new token rows into `pool[layer]` in place, and hands the
kernels the stacked pool plus the layer index. Three things pin that:

  (a) structure — in the jaxprs of the two step programs the layer loop
      has the pool in its carry, scans no pool-sized xs/ys, and no
      equation but the scatters and the kernel call touches an array
      with a layer's pool's element count or more;
  (b) the layer index is honoured — writers and kernels, at two layers
      of a stacked pool whose layers hold different data, match the
      fold reference run on that layer alone (bf16, int8 and int4);
  (c) donation — a step deletes the input cache's buffers, aliases them
      to its outputs and needs no temporary of a pool's size.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.kv.quantized_pool import (
    Int4PagedKVCache, Int4Pool, QuantizedPagedKVCache, QuantPool,
    dequantize_pages, qwrite_prompt_pages,
)
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import (
    PagedKVCache, _kernel_pools, decode_step_ragged_paged, mixed_step_paged,
    paged_attention, paged_attention_mixed, update_pool_per_row,
    write_prompt_pages, write_windows_pages,
)
from cake_tpu.models.llama.params import init_params
from cake_tpu.ops.ragged_paged_attention import (
    ragged_paged_attention, ragged_paged_attention_mixed,
)

PAGE = 8
T = 64
SLOTS = 2
C = 4                   # mixed window width
KINDS = ("bf16", "int8", "int4")
CONTAINERS = {"scan", "while", "cond", "pjit", "jit", "closed_call",
              "core_call", "custom_jvp_call", "custom_vjp_call", "remat",
              "checkpoint"}


@pytest.fixture(scope="module")
def params(tiny_config):
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


def _cache(cfg, kind, n_pages):
    if kind == "int8":
        return QuantizedPagedKVCache.create(cfg, SLOTS, n_pages, PAGE, T)
    if kind == "int4":
        return Int4PagedKVCache.create(cfg, SLOTS, n_pages, PAGE, T)
    return PagedKVCache.create(cfg, SLOTS, n_pages, PAGE, T,
                               dtype=jnp.bfloat16)


def _step_args(kind_of_step):
    pos = jnp.asarray([3, 9], jnp.int32)
    active = jnp.asarray([True, True])
    if kind_of_step == "decode":
        return (jnp.zeros((SLOTS, 1), jnp.int32), pos, active)
    return (jnp.zeros((SLOTS, C), jnp.int32), pos,
            jnp.asarray([1, C], jnp.int32), active)


def _sampled(attn: str, lower: bool = False):
    """The sampled one-step decode program (PR 29: the step the engine
    keeps in flight) in decode_step_ragged_paged's call shape."""
    from cake_tpu.models.llama.paged import FAMILY

    progs = FAMILY.decode_programs
    fn = partial(progs.step.lower if lower else progs.step, attn=attn)

    def run(params, tokens, pos, active, cache, rope, config):
        B = tokens.shape[0]
        return fn(params, tokens[:, 0], pos, active, cache, rope, config,
                  jax.random.split(jax.random.PRNGKey(0), B),
                  jnp.full((B, 8), -1, jnp.int32), jnp.zeros(B, jnp.int32),
                  jnp.zeros(B, jnp.float32), jnp.ones(B, jnp.float32),
                  jnp.ones(B, jnp.float32), jnp.ones(B, jnp.int32),
                  top_k=None, n_top=0)
    return run


# "mixed@T": the packed mixed step at n_tokens = T (PR 27). The step
# arguments hold 1 + C = 5 tokens: 6 is a packed size just above them,
# SLOTS * C = 8 the packed program at the windows' own size.
STEPS = {"decode": decode_step_ragged_paged, "mixed": mixed_step_paged,
         "mixed@6": partial(mixed_step_paged, n_tokens=6),
         "mixed@8": partial(mixed_step_paged, n_tokens=SLOTS * C)}


# -- (a) structure -------------------------------------------------------------


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr               # ClosedJaxpr
            elif hasattr(x, "eqns"):
                yield x                     # Jaxpr


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _size(var):
    return int(np.prod(getattr(var.aval, "shape", ()), dtype=np.int64))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("step", ["decode", "mixed", "mixed@6",
                                  "decode+sample"])
def test_step_program_never_handles_a_pool(tiny_config, params, step, kind,
                                           monkeypatch):
    import cake_tpu.ops.ragged_paged_attention as rpa

    # nothing runs here, so the tiny shapes need not pass the chip's
    # gate when this file runs in the on-chip lane
    monkeypatch.setattr(rpa, "_on_tpu", lambda: False)
    cfg = tiny_config
    width = cfg.num_key_value_heads * cfg.head_dim
    biggest = max(leaf.size for leaf in jax.tree.leaves(params))
    # a layer's pool outgrows every weight, so element count alone
    # tells a pool from anything else in the program
    n_pages = biggest // (PAGE // 2 * width) + 8
    cache = _cache(cfg, kind, n_pages)
    pool = cache.k.q if kind != "bf16" else cache.k
    layer_elems = int(np.prod(pool.shape[1:]))
    assert layer_elems > biggest
    rope = RopeTables.create(cfg, T)
    if step == "decode+sample":
        jaxpr = jax.make_jaxpr(
            lambda c: _sampled("pallas")(params, *_step_args("decode"), c,
                                         rope, cfg))(cache).jaxpr
    else:
        jaxpr = jax.make_jaxpr(
            lambda c: STEPS[step](params, *_step_args(step), c, rope,
                                  config=cfg, attn="pallas"))(cache).jaxpr

    # the layer loop: the one scan over the blocks
    loops = [e for e in _walk(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == cfg.num_hidden_layers]
    assert len(loops) == 1
    loop = loops[0]
    n_const, n_carry = loop.params["num_consts"], loop.params["num_carry"]
    carry = loop.invars[n_const:n_const + n_carry]
    xs = loop.invars[n_const + n_carry:]
    ys = loop.outvars[n_carry:]
    assert sum(v.aval.shape == pool.shape for v in carry) == 2   # k and v
    assert all(_size(v) < layer_elems for v in list(xs) + list(ys))
    assert all(_size(v) < layer_elems for v in loop.invars[:n_const])

    # a quantized writer reads the pages it rewrites: its gather takes
    # the pool as operand and returns those pages alone
    touch = {"scatter", "pallas_call"} | ({"gather"} if kind != "bf16"
                                          else set())
    # inside the decode kernel: its own copies name the pool, a
    # reference to where it lies (never a value), and move a page of it
    copies = {"dma_start", "dma_wait"}
    seen = set()
    for eqn in _walk(jaxpr):
        name = eqn.primitive.name
        if name in CONTAINERS:
            continue
        big = [v for v in list(eqn.invars) + list(eqn.outvars)
               if hasattr(v, "aval") and _size(v) >= layer_elems]
        if big:
            assert name in touch | copies, (name,
                                            [v.aval for v in eqn.invars])
            seen.add(name)
            if name == "gather":
                assert all(_size(v) < layer_elems for v in eqn.outvars)
            if name in copies:
                assert all(str(v.aval).startswith("Ref<any>") for v in big)
    assert {"scatter", "pallas_call"} <= seen


# -- (b) the layer index is honoured -------------------------------------------

L, N, KV, HD, H = 3, 10, 2, 16, 4


def _stacked(rng, kind):
    """(pool_k, pool_v), stacked over L layers of different data."""
    def half():
        x = rng.normal(size=(L, N, PAGE, KV, HD)).astype(np.float32)
        if kind == "bf16":
            return jnp.asarray(x.reshape(L, N, PAGE, KV * HD),
                               jnp.bfloat16)
        zeros = dict(
            int8=QuantPool(jnp.zeros((L, N, PAGE, KV * HD), jnp.int8),
                           jnp.zeros((L, N, KV), jnp.float32)),
            int4=Int4Pool(jnp.zeros((L, N, PAGE // 2, KV * HD), jnp.uint8),
                          jnp.zeros((L, N, KV), jnp.float32)))[kind]
        pool = zeros
        for layer in range(L):
            pool = qwrite_prompt_pages(
                pool, layer,
                jnp.asarray(x[layer].reshape(1, N * PAGE, KV, HD)),
                jnp.arange(N, dtype=jnp.int32))
        return pool
    return half(), half()


def _one_layer(pool, layer):
    """The pool's layer `layer` as a stack of one (a layer-blind
    reference: its only valid index is 0)."""
    return jax.tree.map(lambda a: a[layer:layer + 1], pool)


def _leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


TABLE = jnp.asarray([[7, 2, 9, -1], [4, 1, -1, -1]], jnp.int32)
TOL = dict(bf16=3e-2, int8=2e-5, int4=2e-5)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_writers_and_kernels_honour_the_layer(step, kind):
    rng = np.random.default_rng(7)
    pk0, pv0 = _stacked(rng, kind)
    dt = jnp.bfloat16 if kind == "bf16" else jnp.float32
    pos = jnp.asarray([2 * PAGE + 5, PAGE + 3], jnp.int32)
    active = jnp.asarray([True, True])
    n_q = 1 if step == "decode" else C
    q = jnp.asarray(rng.normal(size=(2, n_q, H, HD)), dt)
    k = jnp.asarray(rng.normal(size=(2, n_q, KV, HD)), dt)
    v = jnp.asarray(rng.normal(size=(2, n_q, KV, HD)), dt)
    q_len = jnp.asarray([1, 3], jnp.int32)

    @jax.jit
    def write(pk, pv, layer):
        if step == "decode":
            return update_pool_per_row(pk, pv, layer, k, v, pos, active,
                                       TABLE)
        return write_windows_pages(pk, pv, layer, k, v, pos, q_len,
                                   active, TABLE)

    def attend(pk, pv, layer, impl):
        if impl == "pallas":        # the kernels themselves, interpreted
            kq, vq, kw = _kernel_pools(pk, pv)
            if step == "decode":
                return ragged_paged_attention(q, kq, vq, layer, TABLE, pos,
                                              interpret=True, **kw)
            return ragged_paged_attention_mixed(
                q, kq, vq, layer, TABLE, pos, q_len, interpret=True, **kw)
        if step == "decode":
            return paged_attention(q, pk, pv, layer, TABLE, pos)
        return paged_attention_mixed(q, pk, pv, layer, TABLE, pos, q_len)

    outs = []
    for layer in (0, L - 1):
        pk, pv = write(pk0, pv0, jnp.int32(layer))
        # the write landed in `layer` and nowhere else, and is the
        # write a pool of that layer alone receives
        for new, old in ((pk, pk0), (pv, pv0)):
            for other in range(L):
                same = _leaves_equal(_one_layer(new, other),
                                     _one_layer(old, other))
                assert same == (other != layer)
        ak, av = write(_one_layer(pk0, layer), _one_layer(pv0, layer),
                       jnp.int32(0))
        assert _leaves_equal(ak, _one_layer(pk, layer))
        assert _leaves_equal(av, _one_layer(pv, layer))
        # the kernel reads `layer`: it matches the fold there, and the
        # fold there is the fold over that layer alone
        want = np.asarray(attend(pk, pv, jnp.int32(layer), "fold"),
                          np.float32)
        alone = np.asarray(attend(ak, av, 0, "fold"), np.float32)
        got = np.asarray(jax.jit(attend, static_argnums=3)(
            pk, pv, jnp.int32(layer), "pallas"), np.float32)
        np.testing.assert_array_equal(want, alone)
        rows = [(0, 1), (1, 3)] if step == "mixed" else [(0, 1), (1, 1)]
        for b, n in rows:
            np.testing.assert_allclose(got[b, :n], want[b, :n],
                                       atol=TOL[kind], rtol=TOL[kind])
        outs.append(want)
    assert np.abs(outs[0] - outs[1]).max() > 0.1      # the layers differ


@pytest.mark.parametrize("kind", KINDS)
def test_prefill_window_writers_honour_the_layer(kind):
    """write_prompt_pages (the whole-window prefill programs' writer)
    and write_windows_pages for one row at an offset inside a page, at
    the last layer of a stacked pool."""
    rng = np.random.default_rng(8)
    pk0, pv0 = _stacked(rng, kind)
    layer = L - 1
    row = jnp.asarray([3, 5, -1, -1], jnp.int32)
    k = jnp.asarray(rng.normal(size=(1, PAGE + 3, KV, HD)), jnp.float32)
    for write in (
            lambda a, b, l: write_prompt_pages(a, b, l, k, k, row,
                                               jnp.int32(PAGE + 3)),
            lambda a, b, l: write_windows_pages(
                a, b, l, k, k, jnp.asarray([2], jnp.int32),
                jnp.asarray([PAGE + 3], jnp.int32), jnp.asarray([True]),
                row[None])):
        pk, pv = write(pk0, pv0, jnp.int32(layer))
        ak, av = write(_one_layer(pk0, layer), _one_layer(pv0, layer), 0)
        assert _leaves_equal(ak, _one_layer(pk, layer))
        assert _leaves_equal(av, _one_layer(pv, layer))
        for other in range(layer):
            assert _leaves_equal(_one_layer(pk, other),
                                 _one_layer(pk0, other))
    if kind != "bf16":          # and the reader of what they wrote
        got = dequantize_pages(pk, layer, jnp.asarray([3]))
        assert got.shape == (1, PAGE, KV, HD)


# -- (c) donation --------------------------------------------------------------


@pytest.mark.parametrize("step", list(STEPS))
def test_step_donates_the_pool_and_copies_none(tiny_config, params, step):
    cfg = tiny_config
    cache = PagedKVCache.create(cfg, SLOTS, 600, PAGE, T,
                                dtype=jnp.float32)
    cache = cache._replace(
        table=cache.table.at[:, :2].set(jnp.asarray([[1, 2], [3, 4]])))
    rope = RopeTables.create(cfg, T)
    args = _step_args(step)
    pool_bytes = cache.k.nbytes
    # the fold: the interpreter of a Pallas kernel copies its operands
    # on the CPU, which the chip's kernel does not
    fn = STEPS[step]
    lower = getattr(fn, "func", fn).lower
    compiled = lower(params, *args, cache, rope, config=cfg, attn="fold",
                     **getattr(fn, "keywords", {})).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // cfg.num_hidden_layers
    k_in, v_in = cache.k, cache.v
    _, out = STEPS[step](params, *args, cache, rope, config=cfg,
                         attn="fold")
    assert k_in.is_deleted() and v_in.is_deleted()
    assert out.k.shape == k_in.shape and out.k.nbytes == pool_bytes
    assert out.v.shape == v_in.shape and out.v.nbytes == pool_bytes


def test_sampled_decode_step_donates_the_pool_and_copies_none(
        tiny_config, params):
    """The same for the program that samples: the pool (and the keys
    and the ring) donated in and aliased out, no temporary of a layer's
    pool's size beside it (the fold, as above)."""
    cfg = tiny_config
    cache = PagedKVCache.create(cfg, SLOTS, 600, PAGE, T,
                                dtype=jnp.float32)
    cache = cache._replace(
        table=cache.table.at[:, :2].set(jnp.asarray([[1, 2], [3, 4]])))
    rope = RopeTables.create(cfg, T)
    args = _step_args("decode")
    pool_bytes = cache.k.nbytes
    mem = _sampled("fold", lower=True)(
        params, *args, cache, rope, cfg).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // cfg.num_hidden_layers
    k_in, v_in = cache.k, cache.v
    toks, _lp, _ti, _tl, out, *_ = _sampled("fold")(
        params, *args, cache, rope, cfg)
    assert toks.shape == (SLOTS, 1)
    assert k_in.is_deleted() and v_in.is_deleted()
    assert out.k.shape == k_in.shape and out.k.nbytes == pool_bytes
    assert out.v.shape == v_in.shape and out.v.nbytes == pool_bytes


# -- (d) the packed mixed step computes on its tokens --------------------------


def test_packed_step_holds_no_window_sized_value(tiny_config, params):
    """Below slots x window the packed program runs its layers over T
    positions: no value of the FFN's intermediate width over all B*C
    window positions, and the pool is written T rows a layer, not B*C.
    The attention call alone still sees [B, C, H, hd] windows."""
    cfg = tiny_config
    B, Cw, n_tokens = 4, 8, 16
    F = cfg.intermediate_size
    width = cfg.num_key_value_heads * cfg.head_dim
    cache = PagedKVCache.create(cfg, B, 40, PAGE, T, dtype=jnp.float32)
    rope = RopeTables.create(cfg, T)
    args = (jnp.zeros((B, Cw), jnp.int32), jnp.zeros(B, jnp.int32),
            jnp.asarray([1, Cw, 0, 3], jnp.int32),
            jnp.asarray([True, True, False, True]))

    def shapes(n):
        jaxpr = jax.make_jaxpr(
            lambda c: mixed_step_paged.__wrapped__(
                params, *args, c, rope, cfg, attn="fold", n_tokens=n))(
                    cache).jaxpr
        values, scatters = set(), []
        for eqn in _walk(jaxpr):
            values |= {tuple(v.aval.shape) for v in eqn.outvars}
            if eqn.primitive.name == "scatter":
                scatters.append(tuple(eqn.invars[2].aval.shape))
        return values, scatters

    windows = {(B, Cw, F), (B * Cw, F), (1, B * Cw, F)}
    values, scatters = shapes(None)
    assert values & windows                     # the detector detects
    assert scatters and set(scatters) == {(B, Cw, width)}
    values, scatters = shapes(n_tokens)
    assert not values & windows
    assert (1, n_tokens, F) in values
    assert scatters and set(scatters) == {(n_tokens, width)}
    H, hd = cfg.num_attention_heads, cfg.head_dim
    assert (B, Cw, H, hd) in values             # the kernel's operand
