"""MoE family: routing math, expert-parallel equivalence, generation.

Expert parallelism is tested on the virtual CPU mesh both ways it ships:
XLA-SPMD (jit + NamedSharding on the expert axis) and manual shard_map
with psum (the pipeline path), each checked against the unsharded result.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from cake_tpu.models.llama.cache import KVCache
from cake_tpu.models.llama.model import RopeTables, decode_step, prefill
from cake_tpu.models.moe import MoEConfig, init_params, param_specs
from cake_tpu.ops.moe import moe_mlp, route

CFG = MoEConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def test_route_top_k_selects_and_normalises():
    """Mixtral semantics through the shared routing function: the two
    largest logits, their weights the softmax over those two."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 6)), jnp.float32)
    weights, experts = (np.asarray(a) for a in
                        route(x, w, k=2, norm_topk_prob=True))
    assert weights.shape == experts.shape == (5, 2)
    logits = np.asarray(x) @ np.asarray(w)
    for n in range(5):
        hi, lo = np.argsort(logits[n])[-1], np.argsort(logits[n])[-2]
        # heavier weight on the higher logit, which comes first
        assert list(experts[n]) == [hi, lo]
        assert weights[n].sum() == pytest.approx(1.0, abs=1e-6)
        top = np.exp(logits[n, [hi, lo]] - logits[n, hi])
        np.testing.assert_allclose(weights[n], top / top.sum(), atol=1e-6)


def test_moe_mlp_matches_per_token_loop(params):
    lp = jax.tree.map(lambda x: x[0], params["blocks"])
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(2, 3, CFG.hidden_size)), jnp.float32)
    out = np.asarray(moe_mlp(lp, h, CFG.num_experts_per_tok)[0])

    router = np.asarray(lp["router"])
    wg, wu, wd = (np.asarray(lp[k]) for k in ("we_gate", "we_up", "we_down"))
    x = np.asarray(h).reshape(-1, CFG.hidden_size)
    expect = np.zeros_like(x)
    for n, tok in enumerate(x):
        logits = tok @ router
        top = np.argsort(logits)[-CFG.num_experts_per_tok:]
        w = np.exp(logits[top] - logits[top].max())
        w /= w.sum()
        for wi, e in zip(w, top):
            act = (tok @ wg[e]) / (1 + np.exp(-(tok @ wg[e]))) * (tok @ wu[e])
            expect[n] += wi * (act @ wd[e])
    np.testing.assert_allclose(
        out.reshape(-1, CFG.hidden_size), expect, rtol=2e-4, atol=2e-4)


def test_prefill_decode_runs(params):
    cache = KVCache.create(CFG, 1, 32, dtype=jnp.float32)
    rope = RopeTables.create(CFG, 32)
    toks = jnp.ones((1, 8), jnp.int32)
    logits, cache = prefill(params, toks, jnp.array([8]), cache, rope, CFG)
    assert logits.shape == (1, CFG.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    logits2, _ = decode_step(params, jnp.ones((1, 1), jnp.int32),
                             jnp.int32(8), cache, rope, CFG)
    assert np.isfinite(np.asarray(logits2)).all()


def test_ep_sharded_forward_matches_single_device(params):
    """jit + NamedSharding on the expert axis == unsharded logits."""
    cache = KVCache.create(CFG, 2, 32, dtype=jnp.float32)
    rope = RopeTables.create(CFG, 32)
    toks = jnp.arange(16, dtype=jnp.int32).reshape(2, 8) % CFG.vocab_size
    plen = jnp.array([8, 8])
    ref, _ = prefill(params, toks, plen, cache, rope, CFG)

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("ep",))
    specs = param_specs(tp_axis=None, ep_axis="ep")
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
    cache_s = jax.device_put(
        KVCache.create(CFG, 2, 32, dtype=jnp.float32),
        NamedSharding(mesh, P()))
    with mesh:
        got, _ = prefill(sharded, toks, plen, cache_s, rope, CFG)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-4, atol=1e-4)


def test_ep_shard_map_matches_unsharded(params):
    """Manual shard_map EP (local expert slice + psum) == full moe_mlp."""
    from jax import shard_map

    lp = jax.tree.map(lambda x: x[0], params["blocks"])
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(1, 4, CFG.hidden_size)), jnp.float32)
    ref = np.asarray(moe_mlp(lp, h, CFG.num_experts_per_tok)[0])

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("ep",))
    lp_specs = {k: P() for k in lp}
    for k in ("we_gate", "we_up", "we_down"):
        lp_specs[k] = P("ep")

    def f(lp_local, h_local):
        return moe_mlp(lp_local, h_local, CFG.num_experts_per_tok,
                       ep_axis="ep")[0]

    # check_vma=False as in parallel/pipeline.py: the grouped matmul is
    # a pallas_call, whose result carries no varying-axes annotation
    got = shard_map(f, mesh=mesh, in_specs=(lp_specs, P()),
                    out_specs=P(), check_vma=False)(lp, h)
    np.testing.assert_allclose(ref, np.asarray(got), rtol=1e-4, atol=1e-4)


def test_shard_params_places_moe_pytree(params):
    """shard_params derives specs from the block leaves (dense or MoE)."""
    from cake_tpu.parallel.mesh import make_mesh
    from cake_tpu.parallel.sharding import shard_params

    mesh = make_mesh(dp=1, stage=1, tp=2, devices=jax.devices()[:2])
    placed = shard_params(params, mesh)
    assert placed["blocks"]["we_gate"].shape == \
        params["blocks"]["we_gate"].shape


def test_pipeline_with_moe_blocks_matches_single(params):
    """MoE blocks through the shard_map pipeline == single-device logits."""
    from cake_tpu.models.llama.model import forward
    from cake_tpu.parallel.mesh import make_mesh
    from cake_tpu.parallel.pipeline import (
        make_pipeline_forward, place_for_pipeline,
    )

    rope = RopeTables.create(CFG, 32)
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(4, 8) % CFG.vocab_size
    ref, _ = forward(params, tokens, KVCache.create(CFG, 4, 32,
                                                    dtype=jnp.float32),
                     jnp.int32(0), rope, CFG)

    mesh = make_mesh(dp=1, stage=2, tp=1, devices=jax.devices()[:2])
    pf = make_pipeline_forward(mesh, CFG, num_microbatches=2)
    p, cache = place_for_pipeline(
        params, KVCache.create(CFG, 4, 32, dtype=jnp.float32), mesh)
    logits, _ = pf(p, tokens, cache, jnp.int32(0), rope)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_sp_forward_with_moe_blocks_matches_single(params):
    """MoE blocks through the sequence-parallel ring path == single-chip."""
    from cake_tpu.parallel.context_parallel import make_sp_forward

    ctx_len, tail_len = 32, 8
    rope = RopeTables.create(CFG, ctx_len + tail_len)
    B = 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, ctx_len), 0,
                                CFG.vocab_size)
    plen = jnp.full((B,), ctx_len, jnp.int32)
    ref, _ = prefill(
        params, tokens, plen,
        KVCache.create(CFG, B, ctx_len + tail_len, dtype=jnp.float32),
        rope, CFG)

    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
    sp_prefill, _ = make_sp_forward(mesh, CFG, ctx_len, tail_len)
    got, _ = sp_prefill(params, tokens, plen, rope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_generator_with_moe_model(params):
    from cake_tpu.models.llama.generator import ByteTokenizer, LlamaGenerator
    from cake_tpu.ops.sampling import SamplingConfig

    f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    gen = LlamaGenerator(
        CFG, f32, ByteTokenizer(CFG.vocab_size), max_seq_len=256,
        batch_size=1,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
    )
    from cake_tpu.models.chat import Message
    gen.add_message(Message.user("hi"))
    toks = [gen.next_token(i) for i in range(4)]
    assert all(t.id >= 0 for t in toks)


def test_load_params_from_hf_mixtral_layout(tmp_path):
    """Synthetic Mixtral-layout safetensors round-trips into the pytree."""
    from cake_tpu.models.moe.params import load_params_from_hf
    from cake_tpu.utils.loading import save_safetensors

    c = MoEConfig.tiny(num_hidden_layers=1, num_local_experts=2)
    rng = np.random.default_rng(3)
    D, F, E = c.hidden_size, c.intermediate_size, c.num_local_experts
    hd, H, KV = c.head_dim, c.num_attention_heads, c.num_key_value_heads

    tensors = {
        "model.embed_tokens.weight": rng.normal(size=(c.vocab_size, D)),
        "model.norm.weight": rng.normal(size=(D,)),
        "lm_head.weight": rng.normal(size=(c.vocab_size, D)),
    }
    pre = "model.layers.0"
    tensors.update({
        f"{pre}.input_layernorm.weight": rng.normal(size=(D,)),
        f"{pre}.post_attention_layernorm.weight": rng.normal(size=(D,)),
        f"{pre}.self_attn.q_proj.weight": rng.normal(size=(H * hd, D)),
        f"{pre}.self_attn.k_proj.weight": rng.normal(size=(KV * hd, D)),
        f"{pre}.self_attn.v_proj.weight": rng.normal(size=(KV * hd, D)),
        f"{pre}.self_attn.o_proj.weight": rng.normal(size=(D, H * hd)),
        f"{pre}.block_sparse_moe.gate.weight": rng.normal(size=(E, D)),
    })
    for e in range(E):
        base = f"{pre}.block_sparse_moe.experts.{e}"
        tensors[f"{base}.w1.weight"] = rng.normal(size=(F, D))
        tensors[f"{base}.w2.weight"] = rng.normal(size=(D, F))
        tensors[f"{base}.w3.weight"] = rng.normal(size=(F, D))
    tensors = {k: v.astype(np.float32) for k, v in tensors.items()}
    save_safetensors(str(tmp_path / "model.safetensors"), tensors)

    params = load_params_from_hf(str(tmp_path), c, dtype=jnp.float32)
    assert params["blocks"]["router"].shape == (1, D, E)
    assert params["blocks"]["we_gate"].shape == (1, E, D, F)
    np.testing.assert_allclose(
        np.asarray(params["blocks"]["we_down"][0, 1]),
        tensors[f"{pre}.block_sparse_moe.experts.1.w2.weight"].T)
    np.testing.assert_allclose(
        np.asarray(params["blocks"]["router"][0]),
        tensors[f"{pre}.block_sparse_moe.gate.weight"].T)


def test_engine_serves_moe_matches_generator(params):
    """The continuous-batching engine over a MoE model (shared block
    skeleton dispatches the expert MLP) == the sequential generator."""
    from cake_tpu.models.llama.generator import ByteTokenizer, LlamaGenerator
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    greedy = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
    prompt = [5, 9, 2 + 2, 7]
    engine = InferenceEngine(CFG, params, ByteTokenizer(CFG.vocab_size),
                             max_slots=2, max_seq_len=64, sampling=greedy,
                             cache_dtype=jnp.float32)
    with engine:
        h = engine.submit(prompt, max_new_tokens=6)
        assert h.wait(timeout=300)
    got = h._req.out_tokens[:6]

    gen = LlamaGenerator(CFG, params, ByteTokenizer(CFG.vocab_size),
                         max_seq_len=64, sampling=greedy,
                         cache_dtype=jnp.float32)
    want = gen.generate_on_device(
        np.asarray([prompt], np.int32),
        np.asarray([len(prompt)], np.int32), 6)[0].tolist()
    # the oracle doesn't early-exit on EOS; the engine does — compare the
    # full stream up to the oracle's first EOS (vacuous-prefix guard)
    eos_at = next((i for i, t in enumerate(want)
                   if t in CFG.eos_token_ids), 6)
    assert got[:eos_at + 1] == want[:min(eos_at + 1, 6)][:len(got)]
    assert len(got) >= min(eos_at + 1, 6)


@pytest.mark.slow  # heaviest cases -> slow lane (tier-1 wall budget)
def test_engine_serves_moe_over_topology(tmp_path):
    """MoE + topology through make_engine: the pipelined engine step fns
    run the expert MLP inside each stage."""
    from cake_tpu.args import Args
    from cake_tpu.context import Context
    from cake_tpu.master import Master

    topo = tmp_path / "topology.yml"
    topo.write_text(
        "s0:\n  layers:\n    - model.layers.0\n"
        "s1:\n  layers:\n    - model.layers.1\n"
    )
    args = Args(model="", topology=str(topo), max_seq_len=64,
                temperature=0.0, repeat_penalty=1.0,
                flash_attention=False).validate()
    ctx = Context.from_args(args)
    ctx.llama_config = CFG
    gen = ctx.load_text_model()
    master = Master(args, text_generator=gen)
    engine = master.make_engine(max_slots=2)
    prompt = [5, 9, 4, 7]
    with engine:
        h = engine.submit(prompt, max_new_tokens=4)
        assert h.wait(timeout=300)
    got = h._req.out_tokens

    # oracle: the same MoE model through the unsharded generator
    from cake_tpu.models.llama.generator import ByteTokenizer, LlamaGenerator
    from cake_tpu.models import load_text_params
    from cake_tpu.ops.sampling import SamplingConfig
    oracle_params = load_text_params(CFG, "", gen.params["embed"].dtype)
    oracle = LlamaGenerator(CFG, oracle_params,
                            ByteTokenizer(CFG.vocab_size), max_seq_len=64,
                            sampling=SamplingConfig(temperature=0.0,
                                                    repeat_penalty=1.0))
    want = oracle.generate_on_device(
        np.asarray([prompt], np.int32),
        np.asarray([len(prompt)], np.int32), 4)[0].tolist()
    eos_at = next((i for i, t in enumerate(want)
                   if t in CFG.eos_token_ids), 4)
    assert got[:eos_at + 1] == want[:min(eos_at + 1, 4)][:len(got)]
    assert len(got) >= min(eos_at + 1, 4)


# -- cake_moe_gmm's output tile (PERF.md §6, PR 49) ----------------------------

# cell -> (held experts, experts a token, a mixed step's tokens, a decode
# step's rows, the expert projections K -> N): what tools/moe_grid.py
# reads from benchmarks/configs/*/ (test_moe_grid_tool_reads_the_cells)
CELLS = {
    "nemotron3-super-int8-share4": (128, 22, 544, 32,
                                    ((1024, 2688), (2688, 1024))),
    "ling-3.0-flash-int8-share4": (128, 8, 544, 32,
                                   ((2560, 768), (768, 2560))),
    "olmoe-1b-7b-int8": (64, 8, 144, 16, ((2048, 1024), (1024, 2048))),
    "zaya1-8b-int8": (16, 1, 288, 32, ((2048, 2048),)),
    "glm-5.2-int8-share16": (16, 8, 528, 8, ((6144, 2048), (2048, 6144))),
    "dots3-note-int8-share8": (32, 8, 544, 32,
                               ((5120, 1536), (1536, 5120))),
    "deepseek-v2-int8-share8": (20, 6, 544, 32,
                                ((5120, 1536), (1536, 5120))),
    "k-exaone-236b-int8-share8": (16, 8, 544, 32,
                                  ((6144, 2048), (2048, 6144))),
}
GMM_CALLS = [(cell, kind, K, N) for cell, shape in CELLS.items()
             for kind in ("decode", "mixed") for K, N in shape[4]]


@pytest.fixture(scope="module")
def grid_tool():
    import importlib.util
    import pathlib
    spec = importlib.util.spec_from_file_location(
        "moe_grid", pathlib.Path(__file__).resolve().parents[1]
        / "tools" / "moe_grid.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cell,kind,K,N", GMM_CALLS, ids=[
    f"{c.split('-')[0]}-{k}-{K}x{N}" for c, k, K, N in GMM_CALLS])
def test_out_tile_is_the_widest_that_fits(grid_tool, cell, kind, K, N):
    """Every cell's expert calls, bf16 rows on int8 per-channel weights:
    the tile divides the width in multiples of 128, fits the budget by
    the function's own count where the next wider candidate does not,
    is never narrower than the {512, 256, 128} rule's, and `gmm_grid`
    counts the grid `dispatch_plan` and `grouped_matmul` build."""
    from cake_tpu.ops import moe

    E, k, mixed_tokens, decode_rows, _ = CELLS[cell]
    n_pairs = (decode_rows if kind == "decode" else mixed_tokens) * k
    tm = moe.row_tile(n_pairs)
    tn = moe.out_tile(K, N, tm, 2, 1, True)
    assert N % tn == 0 and tn % 128 == 0
    assert moe.gmm_vmem_bytes(tm, K, tn, 2, 1, True) <= moe.GMM_VMEM_BUDGET
    wider = [t for t in range(tn + 128, N + 1, 128) if N % t == 0]
    assert all(moe.gmm_vmem_bytes(tm, K, t, 2, 1, True)
               > moe.GMM_VMEM_BUDGET for t in wider)
    assert tn >= grid_tool.tile_before(N)
    grid = moe.gmm_grid(n_pairs, E, K, N, 2, 1, True)
    plan = jax.eval_shape(
        lambda e: moe.dispatch_plan(e, E),
        jax.ShapeDtypeStruct((n_pairs // k, k), jnp.int32))
    assert grid == (tn, N // tn, plan.visit_tile.shape[0],
                    N // tn * plan.visit_tile.shape[0])


@pytest.mark.parametrize("K,N,tm", [(1024, 2688, 128), (1024, 2688, 16),
                                    (2560, 768, 128), (2560, 768, 16)])
def test_out_tile_of_a_width_512_and_256_do_not_divide(K, N, tm):
    """Nemotron's 2,688 = 21 x 128 took 128 and Ling's 768 took 256
    under the old rule: 21 and 3 column tiles where one block fits."""
    from cake_tpu.ops import moe

    assert moe.out_tile(K, N, tm, 2, 1, True) == N


@pytest.mark.parametrize("K,N,tm,x_bytes,w_bytes,scaled,want", [
    (64, 96, 16, 4, 4, False, 96),        # no multiple of 128: one block
    (6144, 2048, 128, 2, 1, True, 512),   # the fullest block: as it was
    (6144, 2048, 128, 2, 2, False, 512),  # bf16: stored as it is used
    (1 << 20, 256, 128, 2, 1, True, 128),  # nothing fits: the least tile
])
def test_out_tile_at_the_edges(K, N, tm, x_bytes, w_bytes, scaled, want):
    from cake_tpu.ops import moe

    assert moe.out_tile(K, N, tm, x_bytes, w_bytes, scaled) == want


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_moe_grid_tool_reads_the_cells(grid_tool, cell):
    """tools/moe_grid.py, which prints PERF.md's table, finds in a
    cell's directory the shapes this file's cases are made of."""
    import pathlib

    E, k, mixed_tokens, decode_rows, projections = CELLS[cell]
    rows = grid_tool.rows(str(pathlib.Path(__file__).resolve().parents[1]
                              / "benchmarks" / "configs" / cell))
    assert {(r["kind"], r["n_tokens"]) for r in rows} >= {
        ("decode", decode_rows), ("mixed", mixed_tokens)}
    assert {(r["K"], r["N"]) for r in rows} == set(projections)
    for r in rows:
        assert (r["n_experts"], r["n_pairs"]) == (E, r["n_tokens"] * k)
        assert r["steps"] <= r["steps_before"]
        assert r["steps"] == r["column_tiles"] * r["visits"]


def _sorted_rows_case(n_out, kind):
    """58 (token, expert) pairs over 4 experts, one of them empty, 9
    pairs dropped, in tiles of 16 that straddle experts; weights [1, 4,
    K, n_out] int8 per-channel or bf16."""
    from cake_tpu.ops import moe
    from cake_tpu.ops.quant import QTensor

    rng = np.random.default_rng(0)
    K, E = 256, 4
    experts = rng.choice([0, 2, 3], size=(29, 2), p=[0.35, 0.15, 0.5])
    valid = rng.random((29, 2)) > 0.15
    plan = moe.dispatch_plan(jnp.asarray(experts, jnp.int32), E,
                             jnp.asarray(valid))
    assert plan.tm == 16 and int(plan.counts[1]) == 0
    x = jnp.asarray(rng.normal(size=(plan.src_token.shape[0], K)),
                    jnp.bfloat16)
    if kind == "int8":
        w = QTensor(jnp.asarray(rng.integers(-127, 128, (1, E, K, n_out)),
                                jnp.int8),
                    jnp.asarray(rng.uniform(0.5, 1.5, (1, E, n_out)) / 2048,
                                jnp.float32))
        dense = np.asarray(w.q, np.float32) * np.asarray(w.scale)[:, :, None]
    else:
        w = jnp.asarray(rng.normal(size=(1, E, K, n_out)) / 16, jnp.bfloat16)
        dense = np.asarray(w.astype(jnp.float32))
    return plan, x, w, dense[0]


@pytest.mark.parametrize("kind", ["int8", "bf16"])
@pytest.mark.parametrize("n_out", [384, 640, 896])
def test_grouped_matmul_is_the_same_under_two_tiles(monkeypatch, n_out, kind):
    """Widths that 256 does not divide, interpreted: every sorted row is
    its own expert's product (a tile that straddles experts keeps each
    visit's rows, the empty expert takes no visit's), and one block of
    `n_out` columns gives the bits that blocks of 128 give."""
    from cake_tpu.ops import moe

    plan, x, w, dense = _sorted_rows_case(n_out, kind)
    walk = (plan.visit_tile, plan.visit_expert, plan.visit_lo, plan.visit_hi)
    got = {}
    for tn in (moe.out_tile(256, n_out, 16, 2, 1 if kind == "int8" else 2,
                            kind == "int8"), 128):
        # (the jitted wrapper caches on its static arguments, not on the
        # module's globals: call what it wraps)
        monkeypatch.setattr(moe, "out_tile", lambda *a, _tn=tn: _tn)
        got[tn] = np.asarray(jax.jit(
            lambda x, w: moe.grouped_matmul.__wrapped__(
                x, w, jnp.int32(0), *walk, tm=plan.tm, interpret=True)
        )(x, w).astype(jnp.float32))
    assert sorted(got) == [128, n_out]
    counts = np.asarray(plan.counts)
    n_rows = int(counts.sum())
    assert n_rows > 32 and np.array_equal(got[n_out][:n_rows],
                                          got[128][:n_rows])
    expert_of = np.repeat(np.arange(len(counts)), counts)
    xs = np.asarray(x.astype(jnp.float32))
    for r in range(n_rows):
        np.testing.assert_allclose(got[n_out][r], xs[r] @ dense[expert_of[r]],
                                   rtol=2e-2, atol=2e-2)
