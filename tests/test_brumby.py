"""Brumby (`brumby`) at a tiny size on seeded weights: the feature
map's identity in both layouts; the three forms of power retention
(quadratic, recurrence, window) against each other; the served path
(mixed-step prefill in windows, decode through the rows' state, rows
admitted and retired mid-run, slots reused) against the plain float32
reference's full forward in its QUADRATIC form, logits; the kernel
interpreted against its fold; a state a query head against a state a
GQA group; altered references that must fail; the config's refusals;
the engine around a page pool of NO layers. (The step programs of the
families whose shared code this family's PR touched are pinned with the
others': tests/test_keye_vl2.py::LOWERED_BEFORE.)

3 layers, 4 query heads over 2 K/V heads of 16 (a group of 2 shares a
state of [1, 16, 256]), windows of 12.
"""

import json
import logging
import os
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import load_config_dict
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import (
    HybridPagedCache, PagedKVCache, mixed_token_buckets,
)
from cake_tpu.models.moe import brumby as br
from cake_tpu.models.moe import nemotron_h as nh
from cake_tpu.models.moe.config import BrumbyConfig
from cake_tpu.models.moe.params import hf_layout, init_params
from cake_tpu.models.reference import brumby as ref
from cake_tpu.ops import kda, retention

B, C, PAGE, MAX_SEQ = 4, 12, 8, 64
REF_KEYS = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta")
# float32 on both sides at `highest` matmul precision; the two differ in
# the FORM alone (a state of products phi(k) v^T summed token by token
# and window by window against the quadratic form's one sum over keys):
# a few 1e-6 on logits of ~4 (read: 6e-6). The altered references below
# must leave it TENFOLD; the nearest, a bfloat16 state, reads 6e-2
ATOL = 5e-5
# one layer's forms against each other on random q, k, v (see
# test_quadratic_form_is_the_recurrence)
FORMS = dict(rtol=2e-4, atol=5e-5)


def ref_config(c, **over):
    return dict({k: getattr(c, k) for k in REF_KEYS}, **over)


def ref_params(params, c):
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": nh.dequantized(params["lm_head"]),
            "layers": list(br.reference_layers(params["blocks"], c))}


@pytest.fixture(scope="module")
def model():
    c = BrumbyConfig.tiny_brumby()
    return c, init_params(c, jax.random.PRNGKey(0), jnp.float32)


# -- the feature map and the three forms -----------------------------------------


@pytest.mark.parametrize("hd", [16, 32, 48])
@pytest.mark.parametrize("layout", ["tiled", "exact", "reference"])
def test_phi_is_the_square_of_the_inner_product(hd, layout):
    """phi(a) . phi(b) = (a . b)^2 in the 16 x 16 tiles the state keeps,
    in the exact triangle, and in the reference's own phi (rounding of a
    sum of hd^2 products of ~1: 1e-5 relative, 1e-4 where the square is
    near zero)."""
    rng = np.random.default_rng(hd)
    a, b = rng.normal(size=(2, 5, hd)).astype(np.float32)
    f = {"tiled": retention.phi, "exact": retention.phi_exact,
         "reference": ref.phi}[layout]
    width = {"tiled": retention.state_width(hd)}.get(
        layout, retention.exact_width(hd))
    pa, pb = np.asarray(f(jnp.asarray(a))), np.asarray(f(jnp.asarray(b)))
    assert pa.shape == (5, width)
    np.testing.assert_allclose(np.sum(pa * pb, -1), np.sum(a * b, -1) ** 2,
                               rtol=1e-5, atol=1e-4)


def test_the_published_head_keeps_9216_of_which_8256_are_needed():
    assert retention.state_width(128) == 9216
    assert retention.exact_width(128) == 8256
    assert retention.state_shape(8, 128, 128) == (8, 9, 128, 1024)
    assert len(retention.tile_pairs(128)) == 36
    with pytest.raises(ValueError, match="tiles of 16"):
        retention.tile_pairs(24)


@pytest.fixture(scope="module")
def layer_inputs():
    """One layer's q, k, v and gates over 29 tokens: 2 K/V heads of 16,
    2 query heads each."""
    rng = np.random.default_rng(7)
    S, KV, R, hd = 29, 2, 2, 16
    q = rng.normal(size=(S, KV * R, hd)).astype(np.float32)
    k = rng.normal(size=(S, KV, hd)).astype(np.float32)
    v = rng.normal(size=(S, KV, hd)).astype(np.float32)
    lg = (-np.abs(rng.normal(size=(S, KV))) * 0.2).astype(np.float32)
    return tuple(map(jnp.asarray, (q, k, v, lg)))


def test_quadratic_form_is_the_recurrence(layer_inputs):
    """The reference's two forms: 29 tokens of products of ~1 summed in
    two orders, then a QUOTIENT whose normaliser may be small (random q
    and k: a sum of a few squares): 2e-4 relative beside 5e-5."""
    cfg = dict(rms_norm_eps=1e-6)
    with jax.default_matmul_precision("highest"):
        y = ref.quadratic(*layer_inputs, cfg)
        y_rec, _ = ref.recurrent(*layer_inputs, cfg)
    np.testing.assert_allclose(y, y_rec, **FORMS)


def test_the_two_precision_switches_round_what_they_name(layer_inputs):
    """The reference's two precision switches on one layer: `read_dtype`
    rounds the operands of the state's read and leaves the float32 state
    alone (the final state's bits are the plain run's), `state_dtype`
    rounds what is carried; each moves y by far more than the forms
    differ by."""
    cfg = dict(rms_norm_eps=1e-6)
    with jax.default_matmul_precision("highest"):
        y, (S, z) = ref.recurrent(*layer_inputs, cfg)
        y_read, (S_read, z_read) = ref.recurrent(
            *layer_inputs, dict(cfg, read_dtype="bfloat16"))
        y_state, (S_state, _) = ref.recurrent(
            *layer_inputs, dict(cfg, state_dtype="bfloat16"))
    assert np.array_equal(S, S_read) and np.array_equal(z, z_read)
    assert not np.array_equal(S, S_state)
    for altered in (y_read, y_state):
        assert float(np.abs(altered - y).max()) > 20 * FORMS["atol"]


@pytest.mark.parametrize("width", [4, 7, 12, 29, 32])
def test_window_form_is_the_quadratic_form(layer_inputs, width):
    """ops/retention.window over the sequence in windows of `width`
    (the last one ragged, its tail not the row's own), each from the
    state the one before left, against the reference's quadratic form;
    and the state it leaves against the recurrence's, brought to the
    exact layout."""
    q, k, v, lg = layer_inputs
    S, H, hd = q.shape
    KV = k.shape[1]
    St = jnp.zeros(retention.state_shape(KV, hd, hd), jnp.float32)
    zt = jnp.zeros((KV, retention.state_width(hd)), jnp.float32)
    ys = []
    for t0 in range(0, S, width):
        n = min(width, S - t0)
        pad = lambda x: jnp.pad(x[t0:t0 + n],
                                ((0, width - n),) + ((0, 0),) * (x.ndim - 1),
                                constant_values=3.0)
        St, zt, y = retention.window(
            St, zt, pad(q).reshape(width, KV, H // KV, hd), pad(k), pad(v),
            pad(lg), jnp.arange(width) < n)
        ys.append(y[:n].reshape(n, H, hd))
    with jax.default_matmul_precision("highest"):
        want = ref.quadratic(q, k, v, lg, dict(rms_norm_eps=1e-6))
        _, (S_ref, z_ref) = ref.recurrent(q, k, v, lg, dict(rms_norm_eps=1e-6))
    np.testing.assert_allclose(jnp.concatenate(ys), want, **FORMS)
    from chip_compare import brumby_exact_state
    np.testing.assert_allclose(brumby_exact_state(St, hd), S_ref, atol=2e-5)  # (sums of <= 29 products of ~1)


# -- the kernel, interpreted, against its fold -----------------------------------

STAY, STEP, FRESH = retention.STAY, retention.STEP, retention.FRESH
# name -> (L, rows, KV, R, hd), codes, layer, pairs a block (None: the
# rule's), ring depth
KERNEL_CASES = {
    "mixed_codes": ((3, 4, 2, 2, 16), (STEP, STAY, FRESH, STEP), 1, None, 4),
    "all_step": ((2, 3, 2, 2, 16), (STEP,) * 3, 0, None, 4),
    "all_fresh": ((2, 3, 2, 2, 16), (FRESH,) * 3, 1, None, 4),
    "all_stay": ((2, 3, 2, 2, 16), (STAY,) * 3, 1, None, 4),
    # a head's state in THREE blocks along D: the sums carried across
    "three_blocks": ((3, 4, 2, 3, 32), (STEP, FRESH, STAY, STEP), 2, 1, 4),
    "ring_of_two": ((2, 4, 1, 2, 32), (STEP, STEP, STAY, FRESH), 0, 1, 2),
    "five_heads_a_group": ((2, 2, 2, 5, 16), (STEP, FRESH), 1, None, 4),
}


def kernel_inputs(shape, seed=0):
    L, rows, KV, R, hd = shape
    rng = np.random.default_rng(seed)
    draw = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return {"S": draw(L, rows, *retention.state_shape(KV, hd, hd)),
            "z": jnp.abs(draw(L, rows, KV, retention.state_width(hd))) * 20,
            "q": draw(rows, KV, R, hd), "k": draw(rows, KV, hd),
            "v": draw(rows, KV, hd),
            "lg": -jnp.abs(draw(rows, KV)) * 0.1}


def run_kernel(x, j, code, pairs, depth):
    old = kda.STEP_BLOCK_BYTES, kda.RING_DEPTH
    hd = x["k"].shape[-1]
    if pairs is not None:
        kda.STEP_BLOCK_BYTES = pairs * hd * retention.PAIR * 4
    kda.RING_DEPTH = depth
    try:
        call = jax.jit(partial(retention._step_pallas.__wrapped__,
                               interpret=True))
        return call(x["S"], x["z"], jnp.int32(j), jnp.asarray(code),
                    x["q"], x["k"], x["v"], x["lg"])
    finally:
        kda.STEP_BLOCK_BYTES, kda.RING_DEPTH = old


@lru_cache(maxsize=None)
def kernel_case(name):
    shape, code, j, pairs, depth = KERNEL_CASES[name]
    hd = shape[-1]
    old = kda.STEP_BLOCK_BYTES
    if pairs is not None:       # the state's SHAPE is the block rule's
        kda.STEP_BLOCK_BYTES = pairs * hd * retention.PAIR * 4
    try:
        x = kernel_inputs(shape)
    finally:
        kda.STEP_BLOCK_BYTES = old
    code = np.asarray(code, np.int32)
    got = run_kernel(x, j, code, pairs, depth)
    want = jax.jit(retention.step_fold)(
        x["S"], x["z"], jnp.int32(j), jnp.asarray(code), x["q"], x["k"],
        x["v"], x["lg"])
    return x, j, code, [np.asarray(a) for a in got], [
        np.asarray(a) for a in want]


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_a_stepping_rows_state_is_the_folds(name):
    """S and z take the same operations in the same order on both sides
    (gamma x + v phi(k), elementwise): the same numbers to ONE rounding,
    the compiler's choice of a fused multiply-add inside the kernel's
    loop or outside the fold's (read: 1 ulp, 1.9e-6 on entries of ~20;
    on the chip tools/retention_step_bench.py reads what it reads)."""
    _, j, code, got, want = kernel_case(name)
    steps = code != STAY
    np.testing.assert_allclose(got[0][j][steps], want[0][j][steps],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[1][j][steps], want[1][j][steps],
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_a_stepping_rows_output_is_the_folds(name):
    """y's sums run in another order (lane tiles, then blocks, then the
    matrix unit's 128 lanes): float32 round-off of a sum of D products
    over a normaliser that, in a FRESH row, is ONE square (q . k)^2 as a
    sum of D products of both signs: where q . k is small it cancels to
    a few digits on either side: 1e-3 relative beside 1e-5 (read: 2.4e-4
    relative in a fresh row, 2.7e-5 in a row with a state)."""
    _, _, code, got, want = kernel_case(name)
    steps = code != STAY
    np.testing.assert_allclose(got[2][steps], want[2][steps], atol=1e-5,
                               rtol=1e-3)


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_a_staying_row_and_every_other_layer_keep_their_bits(name):
    x, j, code, got, _ = kernel_case(name)
    stays = code == STAY
    others = np.arange(x["S"].shape[0]) != j
    for stored, was in ((got[0], x["S"]), (got[1], x["z"])):
        was = np.asarray(was)
        np.testing.assert_array_equal(stored[j][stays], was[j][stays])
        np.testing.assert_array_equal(stored[others], was[others])
    np.testing.assert_array_equal(got[2][stays], 0.0)


@pytest.mark.parametrize("name", ["mixed_codes", "all_fresh",
                                  "three_blocks", "ring_of_two"])
def test_a_fresh_row_never_reads_its_stored_state(name):
    """NaN in the fresh rows' stored S and z: the step from zeros, with
    no NaN anywhere."""
    shape, code, j, pairs, depth = KERNEL_CASES[name]
    x, _, code, _, _ = kernel_case(name)
    fresh = jnp.asarray(code == FRESH)
    poison = lambda a, value: a.at[j].set(jnp.where(
        fresh.reshape((-1,) + (1,) * (a.ndim - 2)), value, a[j]))
    bad = dict(x, S=poison(x["S"], jnp.nan), z=poison(x["z"], jnp.nan))
    zero = dict(x, S=poison(x["S"], 0.0), z=poison(x["z"], 0.0))
    got = run_kernel(bad, j, code, pairs, depth)
    want = run_kernel(zero, j, np.where(code == FRESH, STEP, code), pairs,
                      depth)
    at = code == FRESH
    for stack in (0, 1):        # S and z, layer j
        np.testing.assert_array_equal(np.asarray(got[stack])[j][at],
                                      np.asarray(want[stack])[j][at])
    np.testing.assert_array_equal(np.asarray(got[2])[at],
                                  np.asarray(want[2])[at])
    assert np.isfinite(np.asarray(got[2])).all()


def test_the_bench_tool_rehearses_and_checks_the_kernel(capsys):
    """tools/retention_step_bench.py at tiny widths: one JSON line, the
    kernel's call compared with the fold's where they lie."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "retention_step_bench.py"
    spec = importlib.util.spec_from_file_location("retention_step_bench",
                                                  path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearse", "--calls", "2"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    check = line["check"]
    assert max(check["S_err"], check["z_err"]) < 1e-6
    assert check["y_err"] < 1e-4
    assert check["stay_bits"] and check["others_bits"]
    assert check["y_stay_zero"]
    assert {"kernel_all", "kernel_one_stays", "kernel_some_fresh"} <= set(
        line)


def test_the_kernel_refuses_on_a_chip_what_its_tiles_cannot_hold():
    x = kernel_inputs((2, 2, 2, 2, 16))
    with pytest.raises(ValueError, match="multiples of 128"):
        retention.step(x["S"], x["z"], 0, jnp.asarray([STEP, STEP]),
                       x["q"], x["k"], x["v"], x["lg"], interpret=False)


# -- the served path against the reference -------------------------------------


def fresh_cache(c):
    cache = PagedKVCache.create(c, B, 1 + B * (MAX_SEQ // PAGE), PAGE,
                                MAX_SEQ, dtype=jnp.float32)
    table = np.stack([1 + b * (MAX_SEQ // PAGE) + np.arange(MAX_SEQ // PAGE)
                      for b in range(B)]).astype(np.int32)
    return cache._replace(table=jnp.asarray(table))


def mixed(model, cache, toks, pos, qlen, attn="fold"):
    c, params = model
    return jax.jit(br.mixed_trunk, static_argnames=(
        "config", "attn", "n_tokens"))(
        params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(qlen),
        jnp.asarray(qlen > 0), cache, RopeTables.create(c, MAX_SEQ),
        config=c, attn=attn, n_tokens=mixed_token_buckets(B, C, (1,))[-1])


def decode(model, cache, toks, pos, active, attn="fold"):
    c, params = model
    return jax.jit(br.decode_trunk, static_argnames=("config", "attn"))(
        params, jnp.asarray(toks), cache, jnp.asarray(pos),
        jnp.asarray(active), RopeTables.create(c, MAX_SEQ), config=c,
        attn=attn)


def serve(model, sequences, prompts, cache=None, attn="fold"):
    """Every sequence through the step programs as an engine would run
    them, teacher-forced: a sequence takes the lowest free slot in
    order; ONE window a step, the prompts mid-prefill in admission
    order, the rows past their prompt riding it as one-token rows; the
    decode program where no prompt is open; a finished sequence leaves
    its slot to the next. Returns (per sequence {position: logits},
    cache, the slot each took, the counters of every step)."""
    c, params = model
    cache = fresh_cache(c) if cache is None else cache
    waiting = list(range(len(sequences)))
    slot_of, off = {}, {}
    took = [None] * len(sequences)
    got = [dict() for _ in sequences]
    counted = []
    while waiting or slot_of:
        free = sorted(set(range(B)) - set(slot_of.values()))
        while waiting and free:
            i = waiting.pop(0)
            slot_of[i], off[i] = free.pop(0), 0
            took[i] = slot_of[i]
        open_ = [i for i in slot_of if off[i] < prompts[i]]
        width = C if open_ else 1
        toks = np.zeros((B, width), np.int32)
        pos = np.zeros(B, np.int32)
        qlen = np.zeros(B, np.int32)
        for i, b in slot_of.items():
            n = (min(C, prompts[i] - off[i]) if open_ and i == open_[0]
                 else 0 if off[i] < prompts[i] else 1)
            toks[b, :n] = sequences[i][off[i]:off[i] + n]
            pos[b], qlen[b] = off[i], n
        if open_:
            out, plan = mixed(model, cache, toks, pos, qlen, attn)
            start = np.asarray(plan.start)
        else:
            out = decode(model, cache, toks, pos, qlen > 0, attn)
            start = np.arange(B)
        cache = out.cache
        counted.append((("mixed" if open_ else "decode"), qlen.copy(),
                        pos.copy(), np.asarray(out.counters)))
        logits = np.asarray(br.logits_of(out.x, params))
        for i, b in list(slot_of.items()):
            for j in range(qlen[b]):
                got[i][off[i] + j] = logits[start[b] + j]
            off[i] += int(qlen[b])
            if off[i] == len(sequences[i]):
                del slot_of[i]
    return got, cache, took, counted


@pytest.fixture(scope="module")
def traffic(model):
    """Seven requests over four slots: three take reused slots; one
    prompt is shorter than a window, one a whole number of windows."""
    rng = np.random.default_rng(0)
    prompts = (37, 9, 48, 12, 5, 30, 24)
    outs = (8, 3, 6, 11, 14, 4, 7)
    return [rng.integers(0, model[0].vocab_size, p + o)
            for p, o in zip(prompts, outs)], prompts


@pytest.fixture(scope="module")
def reference_run(model, traffic):
    c, params = model
    kept = [[] for _ in traffic[0]]
    logits = ref.forward(ref_params(params, c), traffic[0], ref_config(c),
                         kept=kept)
    return [np.asarray(x) for x in logits], kept


@pytest.fixture(scope="module")
def served_run(model, traffic):
    return serve(model, *traffic)


@pytest.mark.parametrize("request_index", range(7))
def test_served_path_matches_the_reference_forward(
        served_run, reference_run, traffic, request_index):
    """Prefill in windows of 12, then decode through the state, beside
    rows that come and go, one-token rows riding the windows' steps:
    every position's LOGITS (ATOL: its reason is beside it)."""
    got, want = served_run[0][request_index], reference_run[0][request_index]
    assert sorted(got) == list(range(len(traffic[0][request_index])))
    for position, logits in got.items():
        np.testing.assert_allclose(logits, want[position], atol=ATOL,
                                   err_msg=f"position {position}")


def test_rows_were_admitted_mid_run_into_reused_slots(served_run):
    took = served_run[2]
    assert took[:4] == [0, 1, 2, 3]
    assert len(took[4:]) == 3 and set(took[4:]) <= set(range(B))


def test_a_reused_slot_gives_the_request_what_it_gets_alone(model, traffic,
                                                            served_run):
    """Request 4 ran in a slot whose state another left behind: position
    0 zeroes it inside the step program, and with ONE packed size a
    row's bits do not depend on its company."""
    sequences, prompts = traffic
    alone, *_ = serve(model, [sequences[4]], [prompts[4]])
    for position, logits in alone[0].items():
        assert np.array_equal(logits, served_run[0][4][position])


def test_counters_on_the_known_schedule(served_run, traffic):
    """Each step's vector against the schedule that made it: rows with
    tokens x 3 layers, the window's tokens x 3, the single tokens x 3,
    the rows that started at position 0."""
    assert {kind for kind, *_ in served_run[3]} == {"mixed", "decode"}
    for kind, qlen, pos, counters in served_run[3]:
        assert list(counters) == [
            3 * int((qlen > 0).sum()), 3 * int(qlen[qlen > 1].sum()),
            3 * int((qlen == 1).sum()), int(((qlen > 0) & (pos == 0)).sum())]
    windowed = sum(c[1] for *_, c in served_run[3])
    # a 1-token last window goes through the one-step form
    assert windowed == 3 * sum(p - (p % C == 1) for p in traffic[1])
    assert sum(c[3] for *_, c in served_run[3]) == len(traffic[1])


@pytest.mark.parametrize("altered,why", [
    (dict(form="recurrent", state_dtype="bfloat16"), "a bfloat16 state"),
    (dict(form="recurrent", read_dtype="bfloat16"),
     "a float32 state read at one bfloat16 pass"),
    (dict(gate=False), "the gate dropped"),
    (dict(normaliser=False), "the normaliser dropped"),
    (dict(rope=False), "the rotation dropped"),
    (dict(degree=1), "degree 1 in place of 2"),
    ("state_not_zeroed", "a slot's state inherited")])
def test_an_altered_reference_is_another_model(model, traffic, served_run,
                                               reference_run, altered, why):
    """What chip_compare.py holds to fail on the chip, here at float32
    where nothing hides it: each altered reference leaves the served
    path's tolerance tenfold or more."""
    c, params = model
    seq = traffic[0][0]
    if altered == "state_not_zeroed":
        kw = dict(config=ref_config(c), before=[reference_run[1][2]])
    else:
        kw = dict(config=ref_config(c, **altered))
    logits = np.asarray(ref.forward(ref_params(params, c), [seq], **kw)[0])
    apart = max(float(np.abs(logits[p] - got).max())
                for p, got in served_run[0][0].items())
    assert apart > 10 * ATOL, (why, apart)


@pytest.mark.parametrize("kind", ["mixed", "decode"])
def test_a_row_with_no_token_keeps_its_state(model, served_run, kind):
    cache = served_run[1]
    before = (np.asarray(cache.ssm), np.asarray(cache.conv))
    toks = np.ones((B, C if kind == "mixed" else 1), np.int32)
    pos = np.asarray([45, 17, 60, 0], np.int32)
    if kind == "mixed":
        qlen = np.asarray([1, 0, 0, 7], np.int32)
        out, _ = mixed(model, cache, toks, pos, qlen)
    else:
        out = decode(model, cache, toks, pos,
                     np.asarray([True, False, False, True]))
    for stored, was in zip((out.cache.ssm, out.cache.conv), before):
        stored = np.asarray(stored)
        assert np.array_equal(stored[:, 1:3], was[:, 1:3])
        assert not np.array_equal(stored[:, 0], was[:, 0])
        assert not np.array_equal(stored[:, 3], was[:, 3])
    assert list(np.asarray(out.counters)) == (
        [6, 21, 3, 1] if kind == "mixed" else [6, 0, 6, 1])


def test_the_cache_is_a_state_beside_a_pool_of_no_layers(model):
    c, _ = model
    assert mixed_token_buckets(16, 512, (1,)) == (528,)
    cache = PagedKVCache.create(c, 4, 10, 8, 64, dtype=jnp.bfloat16)
    assert isinstance(cache, HybridPagedCache)
    assert cache.k.shape == cache.v.shape == (0, 10, 8, 2 * 16)
    assert cache.memory_bytes() == 0
    assert (cache.n_pages, cache.page_size, cache.max_seq_len) == (10, 8, 64)
    assert cache.ssm.shape == (3, 4, 2, 1, 16, 256)
    assert cache.conv.shape == (3, 4, 2, 256)
    assert cache.ssm.dtype == cache.conv.dtype == jnp.float32
    assert cache.state_bytes() == cache.beside_bytes() == (
        3 * 4 * 2 * 256 * 17 * 4)
    assert br.FAMILY.create_cache is br.create_cache
    assert br.FAMILY.kernel_rows == () and br.FAMILY.prefill_rows == (1,)


@pytest.mark.parametrize("attn", ["fold", "pallas"])
def test_both_impls_serve_the_same_model(model, traffic, served_run, attn):
    """The step programs through the kernel (interpreted) give the
    fold's logits: request 0, four windows and eight single tokens."""
    got, *_ = serve(model, [traffic[0][0]], [traffic[1][0]], attn=attn)
    alone, *_ = serve(model, [traffic[0][0]], [traffic[1][0]])
    for position, logits in got[0].items():
        np.testing.assert_allclose(logits, alone[0][position], atol=ATOL)


def test_a_state_a_group_is_a_state_a_query_head(model, traffic, served_run):
    """GQA: the same model with K, V and the gate repeated to 4 K/V
    heads (a state a query head, twice the state) gives the same logits:
    the group's R heads read ONE state and nothing of each other."""
    c, params = model
    R = c.group_size
    wide = BrumbyConfig.tiny_brumby(num_key_value_heads=4)
    blocks = dict(params["blocks"])
    L, D, hd = c.num_hidden_layers, c.hidden_size, c.head_dim
    for name in ("wk", "wv"):
        blocks[name] = jnp.repeat(
            blocks[name].reshape(L, D, -1, hd), R, axis=2).reshape(L, D, -1)
    blocks["w_g"] = jnp.repeat(blocks["w_g"], R, axis=2)
    blocks["b_g"] = jnp.repeat(blocks["b_g"], R, axis=1)
    got, cache, *_ = serve((wide, dict(params, blocks=blocks)),
                           [traffic[0][0]], [traffic[1][0]])
    assert cache.ssm.shape[2] == 4
    for position, logits in got[0].items():
        np.testing.assert_allclose(logits, served_run[0][0][position],
                                   atol=ATOL)


# -- the config ------------------------------------------------------------------


def published():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "brumby-14b-int8-10of40", "config.json")
    with open(path) as f:
        return json.load(f)


def test_published_config_parses():
    c = load_config_dict(published())
    assert isinstance(c, BrumbyConfig) and c.is_moe
    assert c.family is br.FAMILY
    assert (c.hidden_size, c.intermediate_size, c.vocab_size) == (
        5120, 17408, 151936)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.group_size) == (40, 8, 128, 5)
    assert (c.num_hidden_layers, c.rope_theta, c.rms_norm_eps) == (
        10, 1e6, 1e-6)
    assert not c.tie_word_embeddings and c.eos_token_ids == (151936,)
    assert c.sliding_window is None and c.chat_template == "chatml"


def test_published_config_invents_no_key_for_the_mechanism():
    raw = published()
    assert not [k for k in raw if "deg" in k or "gate" in k or "chunk" in k
                or "retention" in k]
    # max_window_layers is published and NOT read: any value parses alike
    assert load_config_dict(dict(raw, max_window_layers=3)) == \
        load_config_dict(raw)


def test_the_cells_rehearsal_config_loads():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "brumby-14b-int8-10of40", "cell.json")
    with open(path) as f:
        cell = json.load(f)
    c = load_config_dict(dict(published(), **cell["rehearse"]["config"]))
    assert (c.hidden_size, c.head_dim, c.num_hidden_layers,
            c.group_size) == (64, 16, 3, 2)
    assert cell["expect_impl"] == {"mixed": "paged-retention-pallas",
                                   "decode": "paged-retention-pallas"}
    assert cell["server_args"]["require-model-type"] == "brumby"


RAW = dict(
    model_type="brumby", vocab_size=64, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=48, hidden_act="silu", attention_bias=False,
    tie_word_embeddings=False, use_sliding_window=False, sliding_window=None,
    rope_scaling=None, rope_theta=1000000, max_window_layers=2)


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("sliding_window", 4096),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"), ("head_dim", 24)])
def test_what_is_not_served_is_refused_by_its_key(key, value):
    assert isinstance(load_config_dict(RAW), BrumbyConfig)
    with pytest.raises(ValueError, match=key):
        load_config_dict(dict(RAW, **{key: value}))


def test_a_checkpoint_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="brumby.*not guessed"):
        hf_layout(BrumbyConfig.tiny_brumby())


# -- the engine ----------------------------------------------------------------


def make_engine(**kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    c = BrumbyConfig.tiny_brumby(vocab_size=300, eos_token_ids=(300,))
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    opts = dict(max_slots=4, max_seq_len=120, cache_dtype=jnp.float32,
                sampling=SamplingConfig(temperature=0.0,
                                        repeat_penalty=1.0),
                kv_pages=64, kv_page_size=8, prefill_chunk=12)
    opts.update(kw)
    return c, params, InferenceEngine(c, params, ByteTokenizer(c.vocab_size),
                                      **opts)


@pytest.fixture(scope="module")
def engine_run():
    from cake_tpu.obs import steps as obs_steps
    said = []

    class Keep(logging.Handler):
        def emit(self, record):
            said.append(record.getMessage())

    log = logging.getLogger("cake_tpu.serve.engine")
    keep, level = Keep(), log.level
    log.addHandler(keep)
    log.setLevel(logging.INFO)
    try:
        c, params, eng = make_engine()
    finally:
        log.removeHandler(keep)
        log.setLevel(level)
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(3, 250, n)))
               for n in (40, 7, 70, 21, 33, 12)]
    before = {k: s.value for k, s in obs_steps.RETENTION_COUNTERS}
    with eng:
        handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for h in handles:
            assert h.wait(180)
        records = eng.flight.dump()
    after = {k: s.value for k, s in obs_steps.RETENTION_COUNTERS}
    return (c, params, prompts, [h.token_ids for h in handles], records,
            {k: after[k] - before[k] for k in after}, eng, said)


@pytest.mark.parametrize("request_index", range(6))
def test_engine_serves_the_references_greedy_tokens(engine_run,
                                                    request_index):
    """Through submit -> _do_mixed -> the in-flight decode step: four
    requests over four rows and two behind them in REUSED slots, none of
    which maps a K/V page that holds anything. Teacher-forced: the
    reference's forward over the prompt and the tokens the engine gave
    must choose each of them."""
    c, params, prompts, tokens, *_ = engine_run
    prompt, out = prompts[request_index], tokens[request_index]
    assert len(out) == 10
    logits = np.asarray(ref.forward(
        ref_params(params, c), np.asarray(prompt + out), ref_config(c)))
    for i, tok in enumerate(out):
        at = logits[len(prompt) - 1 + i]
        top2 = np.sort(at)[-2:]
        if top2[1] - top2[0] > 1e-3:        # a near-tie may fall either way
            assert tok == int(np.argmax(at)), i


def test_step_records_and_metrics_carry_the_state(engine_run):
    from cake_tpu.obs import steps as obs_steps
    c, _, prompts, _, records, moved, eng, _ = engine_run
    assert {r["kind"] for r in records} >= {"mixed", "decode"}
    assert {r["impl"] for r in records} == {"paged-retention-fold"}
    counted = [r for r in records if "retention_state_rows" in r]
    assert counted and all("moe_rows" not in r for r in records)
    assert all("attn_pages" not in r for r in records)
    assert all(r["retention_state_rows"] % 3 == 0 for r in counted)
    assert moved["retention_state_resets"] == len(prompts)
    assert moved["retention_tokens_windowed"] == 3 * sum(
        len(p) - (len(p) % 12 == 1) for p in prompts)
    assert eng._mixed_buckets == (16,) and not eng._prefix_capable
    assert eng.flight._counters == br.COUNTERS == tuple(
        k for k, _ in obs_steps.RETENTION_COUNTERS)


def test_a_pool_of_no_layers_is_a_stated_case(engine_run):
    """The start-up log and /metrics state a pool of 0 bytes and the
    state's bytes, and nothing divides by the pool's size."""
    from cake_tpu.obs import metrics as obs_metrics
    from cake_tpu.obs import steps as obs_steps
    *_, eng, said = engine_run
    state = 3 * 4 * 2 * 256 * 17 * 4
    assert eng.cache.memory_bytes() == 0
    assert eng.cache.state_bytes() == state
    assert any("a pool of NO layers, 0 bytes" in line
               and "64 pages x 8 tokens" in line for line in said)
    assert any(line.startswith("retention state:")
               and f"({state} bytes) beside the pool, 4 rows" in line
               for line in said)
    assert not any("GiB pool; dense" in line for line in said)
    obs_steps.refresh_page_gauges(eng)
    scraped = [line.rsplit(" ", 1)
               for line in obs_metrics.REGISTRY.render().splitlines()
               if line and not line.startswith("#")]
    read = {name: float(value) for name, value in scraped}
    assert read["cake_retention_state_bytes"] == state
    assert read["cake_engine_kv_pages_total"] == 64
    pool = [value for name, value in read.items()
            if name.startswith("cake_kv_pool_bytes{")
            and 'tier="device"' in name]
    assert pool and all(value == 0.0 for value in pool)


@pytest.mark.parametrize("refused,named", [
    (dict(kv_pages=None), "--kv-pages"),
    (dict(kv_dtype="int8"), "--kv-dtype"),
    (dict(kv_host_pages=8), "--kv-host-pages"),
    (dict(auto_prefix_system=True), "--auto-prefix"),
    (dict(disagg="prefill"), "--disagg")])
def test_engine_refuses_by_name_what_a_state_does_not_serve(refused, named):
    with pytest.raises(ValueError) as e:
        make_engine(**refused)
    assert "brumby" in str(e.value) and named in str(e.value)


def test_prefix_registration_is_refused_by_name():
    *_, eng = make_engine()
    with pytest.raises(ValueError, match="retention state"):
        eng.register_prefix([5, 6, 7, 8, 9, 10, 11, 12, 13])


def test_the_family_refuses_a_draft_a_topology_and_a_reconfigure():
    f = br.FAMILY
    assert not f.moves("--spec-draft") and not f.moves("topology")
    assert not f.moves("reconfigure") and not f.moves("register_prefix")
    said = f.refusal({"--spec-draft": True, "topology": True})
    assert "model_type brumby" in said and "--spec-draft" in said
    assert "a retention state a row beside a page pool of no layers" in said
