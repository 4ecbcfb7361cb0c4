"""The dense cache stays where it lies (PR 36).

A dense step program carries the STACKED cache [L, B, T, KV, hd] through
its layer loop and, under a topology, through the GPipe tick around it;
a writer scatters the step's rows at [layer, row, pos], and what must not
be written (an inactive row, a pipeline bubble's microbatch) is gated at
those rows. Three things pin that:

  (a) structure: in the jaxprs of the step programs the layer loop (and
      the tick) has the cache in its carry, scans no cache-sized xs/ys,
      no `select_n` takes a cache-sized operand, and nothing but the row
      writes returns a cache-sized value;
  (b) the gate: a pipelined step's cache equals the single-device
      step's bit for bit, an inactive row's line and every line a step
      does not write are untouched, and a step with `active` all false
      returns the cache it was given;
  (c) donation: a step deletes its input cache, aliases it to its output
      and needs no temporary of the cache's size.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.models.llama.cache import KVCache
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.model import (
    RopeTables, decode_step_ragged, decode_step_ragged_ring, forward,
    forward_window_ragged, prefill_slot_chunk,
)
from cake_tpu.models.llama.params import init_params
from cake_tpu.parallel.mesh import make_mesh
from cake_tpu.parallel.pipeline import (
    make_engine_step_fns, make_pipeline_forward, place_for_pipeline,
)

CFG = LlamaConfig.tiny(num_hidden_layers=8, vocab_size=128)
B = 4
CONTAINERS = {"scan", "while", "cond", "pjit", "jit", "closed_call",
              "core_call", "custom_jvp_call", "custom_vjp_call", "remat",
              "checkpoint", "shard_map"}


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _mesh(tp: int):
    return make_mesh(dp=1, stage=2, tp=tp, devices=jax.devices()[:2 * tp])


def _pipelined(params, tp: int, microbatches: int = 1, ring: bool = False):
    """(the engine's pipelined step fns on two stages, placed params,
    cache placer)."""
    mesh = _mesh(tp)
    fns = make_engine_step_fns(mesh, CFG, num_microbatches=microbatches,
                               tp=tp > 1, ring=ring)

    def place(cache):
        return place_for_pipeline(params, cache, mesh, tp=tp > 1)[1]

    placed = place_for_pipeline(params, KVCache.create(CFG, 1, 8), mesh,
                                tp=tp > 1)[0]
    return fns, placed, place


def _random_cache(T: int, seed: int = 3) -> KVCache:
    shape = (CFG.num_hidden_layers, B, T, CFG.num_key_value_heads,
             CFG.head_dim)
    k, v = jax.random.normal(jax.random.PRNGKey(seed), (2,) + shape,
                             jnp.float32)
    return KVCache(k, v)


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


T_BIG = 4096        # nothing runs: a layer's lines outgrow every weight


def _step_jaxpr(params, program):
    """(jaxpr of one step over a [L, B, T_BIG] cache, stages, tp, the
    primitives that may RETURN a cache-sized value)."""
    cache = KVCache.create(CFG, B, T_BIG, dtype=jnp.float32)
    rope = RopeTables.create(CFG, T_BIG)
    pos = jnp.asarray([3, 9, 0, 70], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    tok = jnp.zeros((B, 1), jnp.int32)
    kind, _, shape = program.partition("@")
    stages, tp, mb = {"": (1, 1, 1), "pp2": (2, 1, 1), "pp2-tp2": (2, 2, 1),
                      "pp2-mb2": (2, 1, 2)}[shape]
    writes = {"scatter"}
    if stages == 1:
        if kind == "decode":
            fn = lambda c: decode_step_ragged(params, tok, pos, active, c,
                                              rope, CFG)
        elif kind == "ring":
            fn = lambda c: decode_step_ragged_ring(params, tok, pos, active,
                                                   c, rope, CFG)
        elif kind == "window":
            fn = lambda c: forward_window_ragged(
                params, jnp.zeros((B, 4), jnp.int32), c, pos, active, rope,
                CFG)
        else:
            assert kind == "uniform"
            writes = {"dynamic_update_slice"}
            fn = lambda c: forward(params, jnp.zeros((B, 2), jnp.int32), c,
                                   jnp.int32(5), rope, CFG)
    else:
        mesh = _mesh(tp)
        if kind == "decode":
            step = make_engine_step_fns(mesh, CFG, num_microbatches=mb,
                                        tp=tp > 1)[1]
            fn = lambda c: step(params, tok, pos, active, c, rope,
                                config=CFG)
        else:
            assert kind == "uniform"
            writes = {"dynamic_update_slice"}
            pf = make_pipeline_forward(mesh, CFG, num_microbatches=mb,
                                       tp=tp > 1)
            fn = lambda c: pf(params, jnp.zeros((B, 2), jnp.int32), c,
                              jnp.int32(5), rope)
    return jax.make_jaxpr(fn)(cache).jaxpr, stages, tp, writes


PROGRAMS = ["decode", "ring", "window", "uniform", "decode@pp2",
            "decode@pp2-tp2", "decode@pp2-mb2", "uniform@pp2",
            "uniform@pp2-mb2"]


@pytest.mark.parametrize("program", PROGRAMS)
def test_step_program_carries_the_cache(params, program):
    jaxpr, stages, tp, writes = _step_jaxpr(params, program)
    L = CFG.num_hidden_layers // stages
    local = (L, B, T_BIG, CFG.num_key_value_heads // tp, CFG.head_dim)
    layer_elems = int(np.prod(local[1:]))
    assert layer_elems > max(leaf.size for leaf in jax.tree.leaves(params))

    def carried(loop):
        n_const, n_carry = (loop.params["num_consts"],
                            loop.params["num_carry"])
        carry = loop.invars[n_const:n_const + n_carry]
        rest = (list(loop.invars[:n_const])
                + list(loop.invars[n_const + n_carry:])
                + list(loop.outvars[n_carry:]))
        assert sum(v.aval.shape == local for v in carry) == 2   # k and v
        assert all(_size(v) < layer_elems for v in rest)

    scans = [e for e in _walk(jaxpr) if e.primitive.name == "scan"]
    # the layer loop: the one scan over the blocks; the tick: the scan
    # around it, which scans nothing
    layer_loops = [e for e in scans if e.params["length"] == L
                   and len(e.invars) > (e.params["num_consts"]
                                        + e.params["num_carry"])]
    assert len(layer_loops) == 1
    carried(layer_loops[0])
    ticks = [e for e in scans if e is not layer_loops[0]
             and any(s is layer_loops[0] for sub in _sub_jaxprs(e)
                     for s in _walk(sub))]
    assert len(ticks) == (stages > 1)
    for tick in ticks:
        carried(tick)

    # one microbatch's lines of one layer: what attention may read
    rows_elems = layer_elems // (2 if program.endswith("mb2") else 1)
    seen = set()
    for eqn in _walk(jaxpr):
        name = eqn.primitive.name
        if name in CONTAINERS:
            continue
        ins = [_size(v) for v in eqn.invars if hasattr(v, "aval")]
        outs = [_size(v) for v in eqn.outvars]
        if name == "select_n":
            assert max(ins) < rows_elems, [v.aval for v in eqn.invars]
        if max(outs, default=0) > rows_elems:       # a cache comes out
            assert name in writes, (name, [v.aval for v in eqn.invars])
            seen.add(name)
        elif max(ins, default=0) > rows_elems:      # a cache goes in
            # the attention read (a layer's rows) and a quantity of
            # the written rows (the window a bubble tick puts back)
            assert name in ("dynamic_slice", "gather"), name
    assert seen == writes


# -- (b) the gate --------------------------------------------------------------

T = 32
POS = np.asarray([3, 9, 20, 31], np.int32)
ACTIVE = np.asarray([True, True, False, True])


def _ragged_args(active=ACTIVE):
    tok = jax.random.randint(jax.random.PRNGKey(5), (B, 1), 0,
                             CFG.vocab_size, dtype=jnp.int32)
    return tok, jnp.asarray(POS), jnp.asarray(active)


def _written(active=ACTIVE):
    """[L, B, T] bool: the lines a ragged step at POS writes."""
    m = np.zeros((CFG.num_hidden_layers, B, T), bool)
    for b in range(B):
        m[:, b, POS[b]] = active[b]
    return m


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("microbatches", [1, 2, 4])
def test_pipelined_step_writes_the_single_device_rows(params, tp,
                                                      microbatches):
    rope = RopeTables.create(CFG, T)
    before = _random_cache(T)
    ref_logits, ref = decode_step_ragged(params, *_ragged_args(),
                                         _random_cache(T), rope, CFG)
    fns, placed, place = _pipelined(params, tp, microbatches)
    logits, out = fns[1](placed, *_ragged_args(), place(_random_cache(T)),
                         rope, config=CFG)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               atol=1e-4, rtol=1e-4)
    w = _written()
    for new, old, want in ((out.k, before.k, ref.k), (out.v, before.v,
                                                      ref.v)):
        new, old, want = map(np.asarray, (new, old, want))
        # an inactive row's line, and every row's lines off `pos`
        np.testing.assert_array_equal(new[~w], old[~w])
        assert (new[w] != old[w]).any(axis=(-1, -2)).all()
        if tp == 1 and microbatches < B:
            np.testing.assert_array_equal(new, want)
        else:       # the psum over tp, and a matmul of ONE row, add in
            # another order
            np.testing.assert_allclose(new, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("program", ["decode", "ring", "window",
                                     "decode@pp2", "decode@pp2-mb2",
                                     "ring@pp2-mb2", "decode@pp2-tp2"])
def test_no_active_row_returns_the_cache_it_was_given(params, program):
    rope = RopeTables.create(CFG, T)
    before = _random_cache(T)
    args = _ragged_args(np.zeros(B, bool))
    kind, _, shape = program.partition("@")
    if not shape:
        step = {"decode": decode_step_ragged, "ring": decode_step_ragged_ring,
                "window": None}[kind]
        if kind == "window":
            _, out = jax.jit(forward_window_ragged, static_argnums=6)(
                params, jnp.zeros((B, 3), jnp.int32), _random_cache(T),
                args[1], args[2], rope, CFG)
        else:
            _, out = step(params, *args, _random_cache(T), rope, CFG)
    else:
        fns, placed, place = _pipelined(
            params, 2 if "tp2" in shape else 1,
            2 if "mb2" in shape else 1, ring=kind == "ring")
        _, out = fns[1](placed, *args, place(_random_cache(T)), rope,
                        config=CFG)
    np.testing.assert_array_equal(np.asarray(out.k), np.asarray(before.k))
    np.testing.assert_array_equal(np.asarray(out.v), np.asarray(before.v))


@pytest.mark.parametrize("microbatches", [1, 2, 4])
def test_uniform_tick_writes_its_window_alone(params, microbatches):
    """The uniform body (prefill, generate): a bubble tick puts back the
    [mb, S] window it would have written, so the cache is the
    single-device forward's and nothing outside pos..pos+S moved."""
    rope = RopeTables.create(CFG, T)
    S, pos = 6, 5
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0,
                                CFG.vocab_size, dtype=jnp.int32)
    before = _random_cache(T)
    _, ref = forward(params, tokens, _random_cache(T), jnp.int32(pos), rope,
                     CFG)
    mesh = _mesh(1)
    pf = make_pipeline_forward(mesh, CFG, num_microbatches=microbatches)
    placed, cache = place_for_pipeline(params, _random_cache(T), mesh)
    _, out = pf(placed, tokens, cache, jnp.int32(pos), rope)
    np.testing.assert_array_equal(np.asarray(out.k), np.asarray(ref.k))
    np.testing.assert_array_equal(np.asarray(out.v), np.asarray(ref.v))
    outside = np.ones(T, bool)
    outside[pos:pos + S] = False
    np.testing.assert_array_equal(np.asarray(out.k)[:, :, outside],
                                  np.asarray(before.k)[:, :, outside])
    assert (np.asarray(out.k)[:, :, ~outside]
            != np.asarray(before.k)[:, :, ~outside]).any(axis=(-1, -2)).all()


def test_chunked_slot_prefill_leaves_the_other_slots(params):
    """One slot's window through the pipelined chunk program: that
    slot's lines are the single-device program's, every other slot's
    are the input's."""
    rope = RopeTables.create(CFG, T)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, 8), 0,
                                CFG.vocab_size, dtype=jnp.int32)
    args = (tokens, jnp.asarray([6], jnp.int32), jnp.int32(2), jnp.int32(4))
    before = _random_cache(T)
    _, ref = prefill_slot_chunk(params, *args, _random_cache(T), rope, CFG)
    fns, placed, place = _pipelined(params, 1)
    _, out = fns[3](placed, *args, place(_random_cache(T)), rope,
                    config=CFG)
    np.testing.assert_array_equal(np.asarray(out.k), np.asarray(ref.k))
    np.testing.assert_array_equal(np.asarray(out.v), np.asarray(ref.v))
    others = [0, 1, 3]
    np.testing.assert_array_equal(np.asarray(out.k)[:, others],
                                  np.asarray(before.k)[:, others])


# -- (c) donation --------------------------------------------------------------


@pytest.mark.parametrize("program", ["decode", "decode@pp2",
                                     "decode@pp2-tp2", "decode@pp2-mb2"])
def test_step_donates_the_cache_and_copies_none(params, program):
    T_mem = 512         # a layer's lines outgrow the program's other values
    rope = RopeTables.create(CFG, T_mem)
    cache = KVCache.create(CFG, B, T_mem, dtype=jnp.float32)
    args = _ragged_args()
    _, _, shape = program.partition("@")
    if not shape:
        step, p, devices = decode_step_ragged, params, 1
    else:
        tp = 2 if "tp2" in shape else 1
        fns, p, place = _pipelined(params, tp, 2 if "mb2" in shape else 1)
        step, cache, devices = fns[1], place(cache), 2 * tp
    local_bytes = cache.k.nbytes // devices
    mem = step.lower(p, *args, cache, rope, config=CFG).compile(
        ).memory_analysis()
    if mem is not None:         # where the backend gives one
        assert mem.alias_size_in_bytes >= 2 * local_bytes
        assert mem.temp_size_in_bytes < local_bytes
    k_in, v_in = cache.k, cache.v
    _, out = step(p, *args, cache, rope, config=CFG)
    assert k_in.is_deleted() and v_in.is_deleted()
    assert out.k.shape == k_in.shape and out.k.nbytes == k_in.nbytes
    assert out.v.shape == v_in.shape and out.v.nbytes == v_in.nbytes
    assert out.k.sharding.is_equivalent_to(k_in.sharding, k_in.ndim)
