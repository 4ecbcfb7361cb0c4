"""dots3-note (`dots3_note`) at a tiny size on seeded weights: the served
path (mixed-step prefill in windows, decode through the three pools,
decode rows beside prefilling ones) against the plain float32
reference's full forward; the ring of window pages (its bound, its
inequality, a slot's stale rows); the pieces one by one (absorbed
against up-projected attention in both geometries, the shares of a
sparse layer, the config); and the engine around them.

The window is 6 keys, `index_topk` 8, pages hold 4 tokens and windows 8,
so a row's ring is 5 pages (20 positions) and the contexts, up to 78
tokens, pass the window, the indexer's limit and three turns of the
ring."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama import paged
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import (
    WindowedPagedCache, mixed_token_buckets, ring_holds,
)
from cake_tpu.models.moe import glm_dsa
from cake_tpu.models.moe.config import Dots3NoteConfig, GlmMoeDsaConfig
from cake_tpu.models.moe.params import init_params
from cake_tpu.models.reference import dots3_note as ref
from cake_tpu.obs import steps as obs_steps
from cake_tpu.ops import mla_attention as mla
from cake_tpu.ops import moe as moe_ops
from cake_tpu.ops.quant import QTensor, qmatmul

B, C, PAGE, MAX_SEQ = 4, 8, 4, 96
REF_KEYS = ("hidden_size", "rms_norm_eps", "sliding_window_size",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "swa_num_attention_heads",
            "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
            "swa_rope_theta", "index_n_heads", "index_head_dim",
            "index_topk", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "scoring_func")


def ref_config(c, **over):
    return dict({k: getattr(c, k) for k in REF_KEYS},
                layer_types=c.indexer_types, **over)


def dequantized(leaf):
    if isinstance(leaf, QTensor):
        return (leaf.q.astype(jnp.float32)
                * jnp.expand_dims(leaf.scale, leaf.q.ndim - 2))
    return jnp.asarray(leaf, jnp.float32)


def ref_layers(params, c):
    """The per-layer float32 dicts the reference walks."""
    out = []
    for i in range(c.num_hidden_layers):
        lp = glm_dsa.layer_leaves(params["blocks"], c, i)
        out.append({
            k: dequantized(jax.tree.map(lambda a: a[int(v.layer)], v.stacked)
                           if isinstance(v, moe_ops.LayerOf) else v)
            for k, v in lp.items()})
    return out


def ref_params(params, c):
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": dequantized(params["lm_head"]),
            "layers": ref_layers(params, c)}


@pytest.fixture(scope="module")
def model():
    c = Dots3NoteConfig.tiny_dots3()
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    return c, params, RopeTables.create(c, MAX_SEQ)


def ring_of(c):
    return c.window_ring_pages(PAGE, C)


def fresh_cache(c):
    """Every row's pages mapped; page 0 of the full layers' pools
    belongs to no row (an unmapped read lands there). The rings are
    where create puts them."""
    per_row = MAX_SEQ // PAGE
    cache = WindowedPagedCache.create(
        c, B, 1 + B * per_row, PAGE, MAX_SEQ, ring_of(c),
        dtype=jnp.float32)
    table = np.stack([1 + b * per_row + np.arange(per_row)
                      for b in range(B)]).astype(np.int32)
    return cache._replace(table=jnp.asarray(table))


_mixed = jax.jit(glm_dsa.mixed_trunk,
                 static_argnames=("config", "attn", "n_tokens"))
_decode = jax.jit(glm_dsa.decode_trunk, static_argnames=("config", "attn"))


def serve(model, sequences, prompts, attn="fold", company=True, cache=None,
          slots=None):
    """Every sequence through the step programs: prompts in C-wide
    windows, one window a dispatch, the rows that finished their prompt
    riding the other rows' mixed steps as one-token rows (when
    `company`), then the decode program. slots: the row each sequence
    takes. Returns per sequence {position: logits}, the selections
    [L_full][position] -> set, the cache, and the counters summed."""
    c, params, rope = model
    T = mixed_token_buckets(B, C, (1,))[-1]
    cache = fresh_cache(c) if cache is None else cache
    slots = list(range(len(sequences))) if slots is None else slots
    off = [0] * len(sequences)
    got = [dict() for _ in sequences]
    sets = [dict() for _ in sequences]
    counters = 0.0

    def keep(i, position, x, out, col=None):
        got[i][position] = np.asarray(x)
        if col is None:
            n = int(out.n_selected[slots[i]])
            sets[i][position] = [
                set(np.asarray(out.selected[f, slots[i], :n]).tolist())
                for f in range(out.selected.shape[0])]
        else:
            sets[i][position] = [
                set(np.flatnonzero(out.selected_window[f, col]).tolist())
                for f in range(out.selected_window.shape[0])]

    head = params["lm_head"]
    while any(off[i] < prompts[i] for i in range(len(sequences))):
        i0 = next(i for i in range(len(sequences)) if off[i] < prompts[i])
        toks = np.zeros((B, C), np.int32)
        pos = np.zeros(B, np.int32)
        qlen = np.zeros(B, np.int32)
        for i, seq in enumerate(sequences):
            if i == i0:
                n = min(C, prompts[i] - off[i])
            elif company and prompts[i] <= off[i] < len(seq):
                n = 1
            else:
                continue
            b = slots[i]
            toks[b, :n], pos[b], qlen[b] = seq[off[i]:off[i] + n], off[i], n
        out, plan = _mixed(
            params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(qlen),
            jnp.asarray(qlen > 0), cache, rope, config=c, attn=attn,
            n_tokens=T)
        cache = out.cache
        counters = counters + np.asarray(out.counters)
        logits = out.x @ head
        for i in range(len(sequences)):
            b = slots[i]
            for j in range(qlen[b]):
                keep(i, off[i] + j, logits[int(plan.start[b]) + j], out,
                     j if qlen[b] > 1 else None)
            off[i] += int(qlen[b])
    while any(off[i] < len(s) for i, s in enumerate(sequences)):
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        for i, seq in enumerate(sequences):
            if off[i] < len(seq):
                b = slots[i]
                toks[b, 0], pos[b], active[b] = seq[off[i]], off[i], True
        out = _decode(params, jnp.asarray(toks), cache, jnp.asarray(pos),
                      jnp.asarray(active), rope, config=c, attn=attn)
        cache = out.cache
        counters = counters + np.asarray(out.counters)
        logits = out.x @ head
        for i in range(len(sequences)):
            if active[slots[i]]:
                keep(i, off[i], logits[slots[i]], out)
                off[i] += 1
    return got, sets, cache, counters


# contexts: under the window (5), past it and index_topk (13 + 8), past
# three turns of the 20-position ring (70 + 8), and one in between
PROMPTS = (70, 5, 13, 37)
N_DECODE = 8


@pytest.fixture(scope="module")
def traffic(model):
    rng = np.random.default_rng(0)
    sequences = [rng.integers(0, model[0].vocab_size, p + N_DECODE)
                 for p in PROMPTS]
    return sequences, PROMPTS


@pytest.fixture(scope="module")
def reference_run(model, traffic):
    c, params, _ = model
    sequences, _ = traffic
    selections = [[] for _ in sequences]
    logits = ref.forward(ref_params(params, c), sequences, ref_config(c),
                         selections=selections)
    return [np.asarray(x) for x in logits], selections


@pytest.fixture(scope="module")
def served_run(model, traffic):
    return serve(model, *traffic)


@pytest.mark.parametrize("row", range(len(PROMPTS)))
def test_served_path_matches_the_reference_forward(
        served_run, reference_run, traffic, row):
    """Prefill in windows, then decode through the pools, decode rows
    beside prefilling ones: every position's logits, under the window,
    past it, past index_topk and after the ring has turned three times."""
    got, want = served_run[0][row], reference_run[0][row]
    assert sorted(got) == list(range(len(traffic[0][row])))
    assert ring_of(model_config()) == 5
    for position, logits in got.items():
        np.testing.assert_allclose(logits, want[position], atol=1e-4,
                                   err_msg=f"position {position}")


def model_config():
    return Dots3NoteConfig.tiny_dots3()


@pytest.mark.parametrize("full_layer", [0, 1])
def test_every_full_layer_selects_its_own_keys(served_run, reference_run,
                                               traffic, full_layer):
    """The indexer's exact top-k, per query and per FULL layer (nothing
    is shared: the two layers' sets differ), and the reference's sliding
    layers attended the band."""
    c = model_config()
    differ = 0
    for row, seq in enumerate(traffic[0]):
        masks = reference_run[1][row]
        mask = masks[c.full_layers[full_layer]]
        differ += int(not np.array_equal(masks[c.full_layers[0]],
                                         masks[c.full_layers[1]]))
        for position in range(len(seq)):
            want = set(np.flatnonzero(mask[position]).tolist())
            assert served_run[1][row][position][full_layer] == want
            assert len(want) == min(position + 1, c.index_topk)
        band = masks[c.sliding_layers[0]]
        assert band[-1].sum() == min(len(seq), c.sliding_window_size)
        assert band[-1, -c.sliding_window_size:].all()
    assert differ


def test_kernels_match_the_fold(model, traffic, served_run):
    """cake_mla_attn / cake_swa_attn and the two window kernels
    (interpreted) against the XLA fold, through the whole served path:
    the long row (the ring wraps) and a short one beside it."""
    sequences, prompts = traffic
    got, *_ = serve(model, sequences[:2], prompts[:2], attn="pallas")
    for row in range(2):
        for position, logits in got[row].items():
            np.testing.assert_allclose(
                logits, served_run[0][row][position], atol=1e-4,
                err_msg=f"row {row} position {position}")


def test_decode_rows_share_dispatches_with_windows(model, traffic,
                                                   served_run):
    """A row's logits do not depend on its company: alone (no decode
    row rides a mixed step) it reads what it read beside the others."""
    sequences, prompts = traffic
    alone, *_ = serve(model, sequences, prompts, company=False)
    for row in range(len(sequences)):
        for position, logits in alone[row].items():
            np.testing.assert_allclose(
                logits, served_run[0][row][position], atol=1e-4)


@pytest.mark.parametrize("switch,over", [
    ("window + 1", dict(sliding_window_size=7)),
    ("window - 1", dict(sliding_window_size=5)),
    ("no gate", dict(gate=False)),
    ("no rescale", dict(rescale=False)),
    ("dense full layers", dict(dense_attention=True)),
])
def test_an_altered_reference_fails_the_comparison(
        model, traffic, served_run, switch, over):
    """Each of the tool's switches is another model: the served path
    must NOT match it (the comparison that passes above can tell)."""
    c, params, _ = model
    seq = traffic[0][3]
    moved = np.asarray(ref.forward(ref_params(params, c), seq,
                                   ref_config(c, **over)))
    got = served_run[0][3]
    worst = max(np.abs(got[p] - moved[p]).max()
                for p in range(12, len(seq)))
    assert worst > 1e-3, switch


@pytest.mark.parametrize("window,same", [(6, True), (5, False), (7, False)])
def test_the_probe_is_the_first_sliding_layer_from_the_inside(
        model, traffic, window, same):
    """TrunkOut.probe (what chip_compare.py reads the window by): the
    layer's normed input, its attention's output and its FFN's normed
    input. The reference's layer on that input gives that output at the
    served window, and another at a window off by one."""
    c, params, rope = model
    seq = traffic[0][3]
    T = mixed_token_buckets(B, C, (1,))[-1]
    cache, taps, chosen = fresh_cache(c), [], []
    for lo in range(0, len(seq) - len(seq) % C, C):
        toks = np.zeros((B, C), np.int32)
        toks[0] = seq[lo:lo + C]
        qlen = np.asarray([C] + [0] * (B - 1), np.int32)
        out, plan = _mixed(
            params, jnp.asarray(toks), jnp.asarray(qlen * 0 + lo),
            jnp.asarray(qlen), jnp.asarray(qlen > 0), cache, rope, config=c,
            attn="fold", n_tokens=T)
        cache = out.cache
        at = int(plan.start[0])
        taps.append([np.asarray(x[at:at + C]) for x in out.probe])
        chosen.append(np.asarray(
            out.experts[c.sparse_layers.index(1), at:at + C]))
    h_attn, attn_out, h_mlp = (np.concatenate(x) for x in zip(*taps))
    layer = c.sliding_layers[0]
    assert layer == 1 and h_attn.shape == (len(seq) - len(seq) % C,
                                           c.hidden_size)
    lp = ref_layers(params, c)[layer]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.attention(
            lp, jnp.asarray(h_attn), ref_config(c, sliding_window_size=window),
            "sliding"))
    worst = np.abs(attn_out - want).max(axis=1) / np.abs(want).max()
    assert (worst[:5] < 1e-5).all()        # under every window
    assert (worst.max() < 1e-5) == same, worst.max()
    # and the FFN's input is what the router reads: its own choice there
    # is the served one
    own = np.asarray(ref.router(lp, jnp.asarray(h_mlp), ref_config(c))[2])
    assert [set(x) for x in own.tolist()] == [
        set(x) for x in np.concatenate(chosen).tolist()]


# -- the ring ------------------------------------------------------------------


def test_a_stale_slot_does_not_reach_the_next_request(model, traffic,
                                                      reference_run):
    """A request admitted into a slot whose last owner left its rows
    behind, in all three pools and all over the ring: the short
    sequences after the long one, in ITS slot, read what the reference
    reads."""
    sequences, prompts = traffic
    _, _, cache, _ = serve(model, sequences[:1], prompts[:1])
    assert np.abs(np.asarray(cache.w)).sum() > 0
    for i in (1, 3):
        got, _, cache, _ = serve(model, [sequences[i]], [prompts[i]],
                                 cache=cache, slots=[0])
        for position, logits in got[0].items():
            np.testing.assert_allclose(
                logits, reference_run[0][i][position], atol=1e-4,
                err_msg=f"sequence {i} position {position}")


@pytest.mark.parametrize("page,width,window", [(4, 8, 6), (128, 512, 513),
                                               (8, 8, 6), (16, 4, 33)])
def test_the_rings_inequality_at_every_alignment(page, width, window):
    """R pages hold every key a dispatch's queries need, wherever the
    window starts and however many tokens it writes; R - 1 do not."""
    c = Dots3NoteConfig.tiny_dots3(sliding_window_size=window)
    R = c.window_ring_pages(page, width)
    if (page, width, window) == (128, 512, 513):
        assert R == 9
    starts = range(0, 3 * R * page + 1,
                   1 if page <= 16 else 37)
    for start in starts:
        for n in {1, 2, width // 2, width - 1, width}:
            if n >= 1:
                assert ring_holds(page, R, window, start, n), (start, n)
    assert not all(ring_holds(page, R - 2, window, start, width)
                   for start in starts)


def test_ring_positions_name_what_each_slot_holds():
    """After writing positions 0..last through the ring, slot (p // page)
    % R, offset p % page holds the NEWEST position congruent to it."""
    page, R = 4, 5
    for last in (0, 3, 4, 19, 20, 37, 77):
        held = np.full(R * page, -1)
        for p in range(last + 1):
            held[((p // page) % R) * page + p % page] = p
        named = np.asarray(glm_dsa.ring_key_positions(jnp.int32(last), page,
                                                      R))
        # written slots are named exactly; a slot not yet written again
        # is named by a position past `last` (masked by causality) or
        # below 0 (nothing there)
        written = held >= 0
        fresh = named <= last
        assert np.array_equal(named[written & fresh], held[written & fresh])
        assert (named[~fresh] > last).all()
        assert ((named < 0) | (named > last))[~written].all()
        # and every position a window of 6 needs is named where it lies
        for p in range(max(0, last - 5), last + 1):
            assert named[((p // page) % R) * page + p % page] == p


@pytest.mark.parametrize("newest", [13, 36, 77])
def test_window_kernel_walks_a_ring_under_the_band(newest):
    """cake_swa_window_attn (interpreted) against the XLA fold the way
    attend_sliding calls it: the row's ring of 9 pages as the table
    (four blocks of two and one of one), the band of 6 keys as the bias
    over ring_key_positions, the walk's end the ring's last index,
    whatever the row has reached; and against the band by hand."""
    rng = np.random.default_rng(newest)
    L, N, P, W, R, H, ring, C_, band = 2, 12, 4, 24, 16, 4, 9, 8, 6
    pool = jnp.asarray(rng.standard_normal((L, N, P, W)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((C_, H, W)), jnp.float32)
    wtable = jnp.asarray(rng.permutation(N)[:ring], jnp.int32)
    assert mla.window_tiles(C_, H, W, R, P, ring, 4, True) == (8, 2)
    t = (newest - C_ + 1 + np.arange(C_))[:, None]
    held = np.asarray(glm_dsa.ring_key_positions(jnp.int32(newest), P,
                                                 ring))[None, :]
    seen = (held >= 0) & (held <= t) & (held > t - band)
    bias = jnp.where(seen, 0.0, mla.NEG_INF).astype(jnp.float32)
    args = (q, pool, 1, wtable, bias, jnp.int32(ring * P - 1), R, 0.2)
    want = np.asarray(mla.attend_window(*args, impl="fold", scope="swa"))
    got = np.asarray(mla.attend_window(*args, impl="pallas", interpret=True,
                                       scope="swa"))
    np.testing.assert_allclose(got, want, atol=1e-5)
    slots = np.asarray(pool[1, np.asarray(wtable)]).reshape(-1, W)
    for c_ in (0, C_ - 1):
        keys = slots[seen[c_]]
        assert len(keys) == min(band, int(t[c_, 0]) + 1)
        s = np.asarray(q[c_]) @ keys.T * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        np.testing.assert_allclose(
            got[c_], (p / p.sum(-1, keepdims=True)) @ keys[:, :R], atol=1e-5)
    assert "cake_swa_window_attn" in str(jax.make_jaxpr(
        lambda *a: mla.attend_window(*a, R, 0.2, impl="pallas",
                                     interpret=True, scope="swa"))(
        q, pool, 1, wtable, bias, jnp.int32(ring * P - 1)))


def test_pools_by_kind_of_layer():
    c = model_config()
    R = ring_of(c)
    cache = WindowedPagedCache.create(c, 4, 10, 4, 64, R,
                                      dtype=jnp.bfloat16)
    assert cache.k.shape == (2, 10, 4, 16 + 8)        # the full layers'
    assert cache.v.shape == (2, 10, 4, 16)            # an index key each
    assert cache.w.shape == (4, 20, 4, 24 + 4)        # the sliding layers'
    assert cache.table.shape == (4, 16) and cache.wtable.shape == (4, 5)
    # slot i owns ring pages i*R .. (i+1)*R - 1, for good
    assert np.array_equal(np.asarray(cache.wtable),
                          np.arange(4 * R).reshape(4, R))
    assert (cache.n_window_pages, cache.ring_pages) == (4 * R, R)
    assert cache.memory_bytes() == cache.k.nbytes + cache.v.nbytes
    # the window pool does not depend on max_seq_len
    longer = WindowedPagedCache.create(c, 4, 10, 4, 640, R,
                                       dtype=jnp.bfloat16)
    assert longer.window_bytes() == cache.window_bytes()
    with pytest.raises(ValueError, match="WindowedPagedCache"):
        paged.PagedKVCache.create(c, 4, 10, 4, 64)
    big = Dots3NoteConfig.from_hf_dict(published())
    assert (big.latent_row, big.swa_latent_row) == (640, 1152)
    assert big.window_ring_pages(128, 512) == 9


# -- the pieces ----------------------------------------------------------------


@pytest.mark.parametrize("quant", [None, 8])
@pytest.mark.parametrize("layer", [0, 1], ids=["full", "sliding"])
def test_absorbed_attention_is_the_up_projected_one(layer, quant):
    """In BOTH geometries: q_nope W_kvb^K against the (rescaled) c_kv
    and the attended latent through W_kvb^V, gated (the served path,
    here over ALL keys) against per-head keys and values up-projected
    from the latent (the reference)."""
    c = model_config()
    params = init_params(c, jax.random.PRNGKey(1), jnp.float32, bits=quant)
    lp = glm_dsa.layer_leaves(params["blocks"], c, layer)
    geo = c.geometry(layer)
    S = 24
    h = jax.random.normal(jax.random.PRNGKey(2), (S, c.hidden_size))
    rope = RopeTables.create(c, 64)
    cos, sin = (getattr(rope, name)[:S] for name in geo.rope)
    pool = jnp.zeros((1, 6, 4, geo.row), jnp.float32)
    table = jnp.arange(6, dtype=jnp.int32)[None]
    slot = jnp.zeros(S, jnp.int32)
    position = jnp.arange(S, dtype=jnp.int32)
    q_cat, pool, _ = glm_dsa.project_latent(
        lp, h, cos, sin, slot, position, jnp.ones(S, bool), pool, 0, table,
        c, geo)
    idx = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (S, S))
    o_lat = glm_dsa.attend(
        q_cat, pool, 0, jnp.broadcast_to(table, (S, 6)), slot, position,
        glm_dsa.Selection(idx, position + 1, None), c, "fold", None, geo)
    o = glm_dsa.unabsorb_value(o_lat, lp["wkv_b_v"])
    o = o * jax.nn.sigmoid(qmatmul(h, lp["w_attn_gate"]))[..., None]
    got = qmatmul(o.reshape(S, -1), lp["wo"])
    flat = {k: dequantized(v) for k, v in lp.items()
            if not isinstance(v, moe_ops.LayerOf)}
    kind = "sliding" if layer else "full"
    with jax.default_matmul_precision("highest"):
        want = ref.attention(flat, h, ref_config(
            c, dense_attention=True, sliding_window_size=S), kind)
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("side", ["reference", "served"])
def test_eight_shares_and_one_shared_expert_are_the_uncut_layer(side):
    """Each of 8 chips holds 2 of a layer's 16 routed experts and routes
    over all of them; their parts, with the shared expert counted once,
    add up to what the uncut reference gives for the layer."""
    c = Dots3NoteConfig.tiny_dots3(num_local_experts=16,
                                   n_routed_experts_total=16)
    params = init_params(c, jax.random.PRNGKey(14), jnp.float32)
    lp = ref_layers(params, c)[1]
    h = jax.random.normal(jax.random.PRNGKey(15), (21, c.hidden_size))
    cfg = ref_config(c)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_ffn(lp, h, cfg)
        total = ref.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    for chip in range(8):
        e = 2 * chip
        share = {k: (v[e:e + 2] if k.startswith("we_") else v)
                 for k, v in lp.items()}
        if side == "reference":
            with jax.default_matmul_precision("highest"):
                part = ref.moe_ffn(share, h, cfg, held=(e, 2), shared=False)
        else:
            routed = {k: v for k, v in share.items()
                      if not k.startswith("ws_")}
            part, stats = moe_ops.moe_mlp(
                routed, h[None], 2, c.norm_topk_prob, first_expert=e,
                scoring=c.scoring_func, scale=c.routed_scaling_factor)
            part = part[0]
            assert float(stats.rows_routed) == 21 * 2
        total = total + part
    np.testing.assert_allclose(total, whole, atol=1e-4)


def test_counters_count_what_the_reference_attends(model):
    """One prompt of 20 tokens in windows of 8, then 2 decode steps:
    visible 1+..+22 = 253 a layer; a sliding layer attends
    min(visible, 6): 21 + 16 * 6 = 117; the indexer's counters count the
    2 full layers alone."""
    c = model[0]
    seq = np.arange(22) % 200
    *_, counters = serve(model, [seq], [20])
    names = ("moe_rows", "moe_rows_padded", "moe_load_max", "moe_load_mean",
             "moe_experts_touched", "moe_rows_routed", "dsa_keys_visible",
             "dsa_keys_selected", "dsa_rows_distinct", "dsa_index_layers",
             "dsa_index_reused", "dsa_select_keys_walked",
             "dsa_select_keys_table", "dsa_index_keys_scored",
             "swa_keys_visible",
             "swa_keys_attended", "swa_layers")
    got = dict(zip(names, counters.tolist()))
    assert len(counters) == glm_dsa.N_COUNTERS + len(obs_steps.SWA_COUNTERS)
    assert got["swa_keys_visible"] == 4 * 253
    assert got["swa_keys_attended"] == 4 * 117
    assert got["swa_layers"] == 4 * 5                 # 3 windows, 2 steps
    assert got["dsa_keys_visible"] == 2 * 253
    assert got["dsa_keys_selected"] == 2 * (36 + 14 * 8)
    assert got["dsa_index_layers"] == 2 * 5 and got["dsa_index_reused"] == 0
    # 3 windows x 2 full layers over a table of one block
    assert (got["dsa_select_keys_walked"] == got["dsa_select_keys_table"]
            == 3 * 2 * MAX_SEQ)
    # ... and score passes of one block too (index_tiles: 4 chunks of 8)
    assert got["dsa_index_keys_scored"] == 3 * 2 * min(MAX_SEQ, 32)
    assert c.family.counters == names
    assert names[-3:] == tuple(k for k, _ in obs_steps.SWA_COUNTERS)


# -- the config ----------------------------------------------------------------


def published():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "dots3-note-int8-share8", "config.json")
    with open(path) as f:
        return json.load(f)


def test_published_config_parses():
    from cake_tpu.models.llama.config import load_config_dict
    c = load_config_dict(published())
    assert isinstance(c, Dots3NoteConfig)
    assert (c.num_hidden_layers, c.hidden_size, c.vocab_size) == (
        9, 5120, 19008)
    assert c.full_layers == (0, 4, 8)
    assert c.sliding_layers == (1, 2, 3, 5, 6, 7)
    assert c.latent_layers == c.full_layers
    assert c.sparse_layers == tuple(range(1, 9))
    assert (c.num_local_experts, c.n_routed_experts_total,
            c.num_experts_per_tok) == (32, 256, 8)
    full, swa = c.geometry(0), c.geometry(1)
    assert (full.heads, full.q_lora_rank, full.kv_lora_rank,
            full.qk_nope_head_dim, full.qk_rope_head_dim, full.v_head_dim,
            full.row, full.window) == (128, 1024, 512, 128, 64, 128, 640,
                                       None)
    assert (swa.heads, swa.q_lora_rank, swa.kv_lora_rank,
            swa.qk_nope_head_dim, swa.qk_rope_head_dim, swa.v_head_dim,
            swa.row, swa.window) == (64, 1024, 1024, 192, 64, 128, 1152, 513)
    assert c.rope_theta == 8e7 and c.swa_rope_theta == 5e4
    assert full.kv_scale == pytest.approx(10 ** 0.5)
    assert swa.kv_scale == pytest.approx(5 ** 0.5)
    assert full.gated and swa.gated and c.sliding_window is None
    assert c.routed_scaling_factor == 1 and c.scoring_func == "sigmoid"
    rope = RopeTables.create(c, 64)
    assert rope.swa_cos.shape == rope.cos.shape == (64, 32)
    assert not np.allclose(rope.cos, rope.swa_cos)


SMALL = dict(
    model_type="dots3_note", vocab_size=64, hidden_size=32,
    intermediate_size=64, num_hidden_layers=3, num_attention_heads=2,
    q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, index_n_heads=2,
    index_head_dim=8, index_topk=4, moe_intermediate_size=16,
    n_routed_experts=16, n_routed_experts_total=256,
    num_experts_per_tok=2, first_k_dense_replace=1,
    layer_types=["full_attention", "sliding_attention", "full_attention"],
    swa_num_attention_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=16,
    swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4, swa_v_head_dim=8,
    swa_rope_theta=5e4, sliding_window_size=5,
    attention_gate_type="headwise", swa_attention_gate_type="headwise",
    apply_mla_qkv_lora_rescale=True)


@pytest.mark.parametrize("key,value,says", [
    ("n_group", 8, "n_group"), ("topk_group", 4, "topk_group"),
    ("num_nextn_predict_layers", 1, "multi-token-prediction"),
    ("first_routed_expert", 250, "router"),
    ("vision_config", {"depth": 42}, "towers"),
    ("audio_config", {"depth": 2}, "towers"),
    ("index_topk_freq", 4, "its own key sets"),
    ("attention_gate_type", "elementwise", "attention_gate_type"),
    ("attention_gate_type", None, "attention_gate_type"),
    ("swa_attention_gate_type", None, "swa_attention_gate_type"),
    ("apply_mla_qkv_lora_rescale", False, "apply_mla_qkv_lora_rescale"),
    ("apply_mla_qkv_lora_rescale", None, "apply_mla_qkv_lora_rescale"),
    ("layer_types", ["full_attention", "chunked_attention",
                     "full_attention"], "layer_types"),
    ("swa_num_key_value_heads", 1, "a key a head"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("sliding_window_size", 0, "sliding_window_size"),
])
def test_what_is_not_served_is_refused_by_name(key, value, says):
    from cake_tpu.models.llama.config import load_config_dict
    c = load_config_dict(SMALL)
    assert c.indexer_types == ("full", "sliding", "full")
    with pytest.raises(ValueError, match=says):
        load_config_dict(dict(SMALL, **{key: value}))


def test_glm_keeps_one_geometry_and_one_pool():
    """The trunk this model shares: GLM's layers read the one geometry,
    unscaled, ungated, and its cache has no window pool."""
    c = GlmMoeDsaConfig.tiny_glm()
    geo = c.geometry(3)
    assert (geo.scope, geo.q_scale, geo.kv_scale, geo.window) == (
        "mla", 1.0, 1.0, None)
    assert c.sliding_layers == () and c.latent_layers == tuple(range(5))
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    assert "swa" not in params["blocks"]
    assert "w_attn_gate" not in params["blocks"]
    cache = paged.PagedKVCache.create(c, 2, 4, 8, 64)
    assert not hasattr(cache, "w")


@pytest.mark.parametrize("stack,leaf,rank,scaled_by", [
    (None, "wq_b", "q_lora_rank", "q_scale"),
    (None, "wi_q", "q_lora_rank", "q_scale"),
    (None, "wkv_b_k", "kv_lora_rank", "kv_scale"),
    (None, "wkv_b_v", "kv_lora_rank", "kv_scale"),
    ("swa", "wq_b", "q_lora_rank", "q_scale"),
    ("swa", "wkv_b_k", "kv_lora_rank", "kv_scale"),
    ("swa", "wkv_b_v", "kv_lora_rank", "kv_scale"),
])
def test_the_seeded_draw_reads_a_rescaled_latent_at_its_own_variance(
        stack, leaf, rank, scaled_by):
    """A matrix that reads a rescaled latent is drawn at 1 / its scale,
    a fan-in of rank x scale^2 = the hidden size: q, k and v then have
    the variance they have without a rescale (a softmax seven times as
    sharp made the seeded model chaotic on the chip)."""
    c = Dots3NoteConfig.tiny_dots3()
    layer = c.sliding_layers[0] if stack else c.full_layers[0]
    geo = c.geometry(layer)
    scale = getattr(geo, scaled_by)
    assert scale == (c.hidden_size / getattr(geo, rank)) ** 0.5 > 1.0
    sigma = (getattr(geo, rank) * scale ** 2) ** -0.5
    assert sigma == pytest.approx(c.hidden_size ** -0.5)
    for bits in (None, 8):
        blocks = init_params(c, jax.random.PRNGKey(0), jnp.float32,
                             bits=bits)["blocks"]
        got = blocks[stack][leaf] if stack else blocks[leaf]
        if bits:
            # uniform int8 under one scale a channel: exact
            np.testing.assert_allclose(
                got.scale, 3 ** 0.5 / 127 * sigma, rtol=1e-6)
        assert float(jnp.std(dequantized(got))) == pytest.approx(
            sigma, rel=0.1)


def test_glm_is_neither_gated_nor_rescaled():
    """What the trunk reads of a geometry to leave GLM's program as it
    was: no scale, no gate, no gate leaf in its tree."""
    geo = GlmMoeDsaConfig.tiny_glm().geometry(0)
    assert (geo.q_scale, geo.kv_scale, geo.gated) == (1.0, 1.0, False)


# -- the engine ----------------------------------------------------------------


def make_engine(**kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    c = Dots3NoteConfig.tiny_dots3(vocab_size=300, eos_token_ids=(300,))
    params = init_params(c, jax.random.PRNGKey(0), jnp.float32)
    opts = dict(max_slots=4, max_seq_len=128, cache_dtype=jnp.float32,
                sampling=SamplingConfig(temperature=0.0,
                                        repeat_penalty=1.0),
                kv_pages=128, kv_page_size=4, prefill_chunk=8)
    opts.update(kw)
    return c, params, InferenceEngine(c, params, ByteTokenizer(c.vocab_size),
                                      **opts)


@pytest.fixture(scope="module")
def engine_run():
    c, params, eng = make_engine()
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(3, 250, n)))
               for n in (40, 7, 90, 21, 33)]
    names = obs_steps.DSA_COUNTERS + obs_steps.SWA_COUNTERS
    before = {k: s.value for k, s in names}
    with eng:
        handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for h in handles:
            assert h.wait(240)
        records = eng.flight.dump()
    after = {k: s.value for k, s in names}
    return (c, params, prompts, [h.token_ids for h in handles], records,
            {k: after[k] - before[k] for k in after}, eng)


@pytest.mark.parametrize("request_index", range(5))
def test_engine_serves_the_references_greedy_tokens(engine_run,
                                                    request_index):
    """Through submit -> _do_mixed -> the in-flight decode step: four
    requests over four rows and a fifth behind them in a slot its last
    owner left full, prompts of 1 to 12 windows (100 tokens: five turns
    of the ring). Teacher-forced: the reference's forward over the
    prompt and the tokens the engine gave must choose each of them."""
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


def test_a_row_holds_the_ring_and_no_more(engine_run):
    """R = 5 window pages a row by the table's shape, the pool slots x
    R, and five requests over four slots (a 100-token one among them)
    left the table where create put it: admission and release never
    touch the window pool."""
    *_, eng = engine_run
    R = eng.cache.ring_pages
    assert R == 5 and eng.cache.w.shape[1] == 4 * R
    assert np.array_equal(np.asarray(eng.cache.wtable),
                          np.arange(4 * R).reshape(4, R))
    assert eng._pager.live_pages == 0


def test_a_rebuilt_cache_has_the_same_rings(engine_run):
    """The post-error rebuild makes the pools anew: the rings with
    them, no allocator to reset."""
    *_, eng = engine_run
    fresh = eng._fresh_pool(eng.cache.n_pages, eng.cache.page_size)
    assert fresh.w.shape == eng.cache.w.shape
    assert np.array_equal(np.asarray(fresh.wtable),
                          np.asarray(eng.cache.wtable))
    assert (np.asarray(fresh.table) == -1).all()


def test_step_records_carry_the_window_counters(engine_run):
    *_, records, moved, eng = engine_run
    for r in records:
        assert r["impl"] == "paged-dsa-fold"
    counted = [r for r in records if "swa_keys_visible" in r]
    assert counted and {r["kind"] for r in counted} >= {"mixed", "decode"}
    for r in counted:
        assert 0 < r["swa_keys_attended"] <= r["swa_keys_visible"]
        # 4 sliding layers, 2 full: the indexer's counters count the
        # full layers alone, and nothing is reused
        assert r["swa_keys_visible"] == 2 * r["dsa_keys_visible"]
        assert r["swa_layers"] == 2 * r["dsa_index_layers"]
        assert r["dsa_index_reused"] == 0
    assert any(r.get("chained") for r in records if r["kind"] == "decode")
    assert all(v > 0 for k, v in moved.items()
               if k != "dsa_index_reused"), moved
    assert moved["swa_keys_attended"] < 0.2 * moved["swa_keys_visible"]
    assert eng._mixed_buckets == (16,) and not eng._prefix_capable


def test_mixed_records_count_both_kinds_of_walk(engine_run):
    """window_pages / window_folds: a full layer walks the row's live
    pages, a sliding layer its whole ring whatever the row holds, each
    kind at its own pages a fold."""
    c, *_, records, _moved, eng = engine_run
    full, sliding = len(c.latent_layers), len(c.sliding_layers)
    ring = eng.cache.ring_pages
    mixed = [r for r in records if r["kind"] == "mixed"]
    assert mixed and all("window_pages" not in r for r in records
                         if r["kind"] != "mixed")
    for r in mixed:
        assert r["window_pages"] >= full + sliding * ring
        assert (r["window_pages"] / 4 <= r["window_folds"]
                <= r["window_pages"])
    # (a ring of 5 pages folds a page at a time: blocks would pad it
    # by more than an eighth; the table of 32, four)
    assert ring == 5 and eng._window_walk(0) == (full + sliding * ring,
                                                 full + sliding * ring)
    assert eng._window_walk(89) == (full * 23 + sliding * ring,
                                    full * 6 + sliding * ring)


@pytest.mark.parametrize("kw,says", [
    (dict(kv_pages=None), "kv-pages"),
    (dict(kv_dtype="int8"), "kv-dtype"),
    (dict(kv_host_pages=8), "kv-host-pages"),
    (dict(auto_prefix_system=True), "auto-prefix"),
])
def test_what_the_latent_pool_refuses_is_refused_by_name(kw, says):
    with pytest.raises(ValueError, match=says) as err:
        make_engine(**kw)
    assert "dots3_note" in str(err.value)


def test_the_sliding_layers_kernel_calls_carry_their_own_names(model):
    """A reader of a trace tells the sliding layers' kernel events from
    the full layers' by name, though the bodies are the same."""
    c, params, rope = model
    T = mixed_token_buckets(B, C, (1,))[-1]
    qlen = jnp.asarray([C, 1, 0, 0], jnp.int32)
    jaxpr = str(jax.make_jaxpr(
        lambda cache: glm_dsa.mixed_trunk(
            params, jnp.zeros((B, C), jnp.int32), jnp.zeros(B, jnp.int32),
            qlen, qlen > 0, cache, rope, c, "pallas", T)[0].x)(
        fresh_cache(c)))
    for name in ("cake_mla_attn", "cake_mla_window_attn", "cake_swa_attn",
                 "cake_swa_window_attn"):
        assert f"name={name}" in jaxpr, name
