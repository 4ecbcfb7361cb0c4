"""The paged step programs stream their int8 weights where they lie.

Compiled ahead of time for a described v5e (no chip; tools/step_hlo.py),
the decode and the mixed program of every block kind that runs
`model.block_skeleton_stats` must hold no `copy` with an `s8[...]`
result and no stand-alone slice of a layer's weight: each matmul slices
its layer out of the stacked `[L, in, out]` leaf inside its own fusion.
Before PR 43 XLA folded the head split that follows the q and k
projections into `wq` and `wk` and paid for it with a slice and a
transposing copy of both, every layer of every step (PERF.md §6).

This holds the property, not the fix: any change that brings a
materialised int8 weight back into these programs fails here, at no
chip time. Every compile of this file runs in the test's own process
(one process holds libtpu), and the topology is described in a fixture,
never at import (the on-chip-measurement guide, section 2).
"""

import dataclasses
import importlib.util
import pathlib
import re

import pytest

import jax

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIGS = ROOT / "benchmarks" / "configs"

# block kind -> the cell whose widths it is compiled at
BLOCKS = {
    "dense": "mistral-7b-int8",            # Llama/Mistral: GQA 32/8
    "qkv_bias": "qwen2.5-32b-int8-4chip",  # Qwen2: bq/bk/bv, GQA 40/8
    "q_norm": "olmoe-1b-7b-int8",          # OLMoE: q_norm/k_norm, MHA 16
}
# the mixed kernel takes no group of 5 query heads a KV head
# (rpa.ragged_paged_mixed_supported): such a model's mixed step folds
FOLDS = {("qkv_bias", "mixed")}


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "step_hlo", ROOT / "tools" / "step_hlo.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def one_chip(tool):
    try:
        return tool.describe_v5e()
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("program", ["decode", "mixed"])
@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_step_program_materialises_no_int8_weight(tool, one_chip, block,
                                                  program):
    from cake_tpu.models.llama.config import load_config

    config = dataclasses.replace(load_config(str(CONFIGS / BLOCKS[block])),
                                 num_hidden_layers=2)
    decode, mixed = tool.step_fns(config)
    attn = "fold" if (block, program) in FOLDS else "pallas"
    # the served program: conftest's `highest` is the CPU goldens' need
    with jax.default_matmul_precision("default"):
        if program == "decode":
            compiled = tool.compile_step(decode, config, one_chip, attn=attn)
        else:
            compiled = tool.compile_step(mixed, config, one_chip, attn=attn,
                                         width=128, n_tokens=144)
    hlo = compiled.as_text()
    assert attn == "fold" or "tpu_custom_call" in hlo, (
        "the Pallas kernels were interpreted")
    found = tool.materialised_int8(hlo)
    assert not found, (
        f"{block} {program} step writes int8 weights to memory again: "
        + ", ".join(map(str, found)))


def test_materialised_int8_reads_the_parent_program(tool):
    """The reader on the lines PR 42's decode program held (and on the
    forms it must pass over): a fusion's own slice, a prefetch into
    faster memory, a buffer handed on."""
    hlo = """\
HloModule jit_decode_step_ragged_paged

%fused_computation.18 (param_0.476: s8[32,4096,4096], param_1.525: s32[]) -> s8[4096,4096] {
  %param_0.476 = s8[32,4096,4096]{2,1,0:T(8,128)(4,1)} parameter(0)
  %dynamic_slice.247 = s8[1,4096,4096]{2,1,0:T(8,128)(4,1)} dynamic-slice(%param_0.476, %param_1.525), dynamic_slice_sizes={1,4096,4096}
  ROOT %bitcast.180 = s8[4096,4096]{1,0:T(8,128)(4,1)} bitcast(%dynamic_slice.247)
}

%while_body (p: (s32[], s8[32,4096,4096])) -> (s32[], s8[32,4096,4096]) {
  %get-tuple-element.7 = s8[32,4096,4096]{2,1,0:T(8,128)(4,1)} get-tuple-element(%p), index=1
  %constant_dynamic-slice_fusion.4 = s8[1,4096,4096]{2,1,0:T(8,128)(4,1)S(1)} fusion(%get-tuple-element.7, %i), kind=kLoop, calls=%fused_computation.18
  %copy.41 = s8[1,4096,4096]{1,2,0:T(8,128)(4,1)S(1)} copy(%constant_dynamic-slice_fusion.4)
  %bitcast.208 = s8[32,128,4096]{2,1,0:T(8,128)(4,1)S(1)} bitcast(%copy.41)
  %slice-done = s8[1,14336,4096]{2,1,0:T(8,128)(4,1)S(1)} slice-done(%slice-start)
  %copy-done.3 = s8[2,4096,1024]{2,1,0:T(8,128)(4,1)S(1)} copy-done(%copy-start.3)
  %custom-call.18 = s8[2,14336,4096]{2,1,0:T(8,128)(4,1)S(1)} custom-call(%slice-done, %slice-done.1), custom_call_target="ConcatBitcast"
  ROOT %tuple.1 = (s32[], s8[32,4096,4096]) tuple(%i, %get-tuple-element.7)
}

ENTRY %main (w: s8[32,4096,4096]) -> s8[32,4096,4096] {
  %w = s8[32,4096,4096]{2,1,0:T(8,128)(4,1)} parameter(0)
  ROOT %while.1 = (s32[], s8[32,4096,4096]) while(%t), condition=%c, body=%while_body
}
"""
    found = tool.materialised_int8(hlo)
    assert [(m.name, m.shape, m.opcode) for m in found] == [
        ("constant_dynamic-slice_fusion.4", "s8[1,4096,4096]", "fusion"),
        ("copy.41", "s8[1,4096,4096]", "copy"),
    ]


@pytest.mark.parametrize("heads,table_pages", [(128, 40), (32, 76)],
                         ids=["dsv2", "ling3"])
def test_latent_page_walk_kernel_compiles_at_the_cells_widths(
        tool, one_chip, heads, table_pages):
    """`cake_mla_decode_attn` at dsv2.code-closed's shapes (32 rows, 128
    heads over a 640-wide stored row, 512-wide values, pages of 128,
    40 pages a row) and at ling3.longreply-closed's (32 heads, 76 pages
    a row) goes through Mosaic at the four pages a fold its rule takes
    there: its ring of three slots of four 160 KiB pages, the [128,
    512] float32 scores and accumulator and the dynamic trip count fit
    a v5e core's scoped VMEM and SMEM."""
    import jax.numpy as jnp

    from cake_tpu.ops import mla_attention as mla
    from cake_tpu.ops import ragged_paged_attention as rpa

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, pool, table, pos):
        return mla.attend_pages(q, pool, jnp.int32(1), table, pos, 512,
                                0.1147, "pallas")

    on_tpu, rpa._on_tpu = rpa._on_tpu, lambda: True
    try:
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(call).lower(
                sds((32, heads, 640), jnp.bfloat16),
                sds((2, 64, 128, 640), jnp.bfloat16),
                sds((32, table_pages), jnp.int32),
                sds((32,), jnp.int32)).compile()
    finally:
        rpa._on_tpu = on_tpu
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "cake_mla_decode_attn" in hlo
    assert mla.decode_block(heads, 640, 512, 128, table_pages, 2) == 4
    slot = 4 * 128 * 640 * 2
    assert mla.pages_ring_depth(slot) * slot < 2 * 2**20


@pytest.mark.parametrize("kernel", ["decode", "mixed"])
@pytest.mark.parametrize("window,table_pages", [(128, 6), (None, 76)])
def test_banded_attention_kernels_compile_at_the_cells_widths(
        one_chip, kernel, window, table_pages):
    """`cake_decode_attn` and `cake_mixed_attn` at
    kexaone.longreply-closed's shapes (64 query heads over 8 K/V heads
    of 128, bf16 pages of 128) go through Mosaic banded over a ring of 6
    entries a row and unbanded over the full layers' table of 76: the
    decode kernel for 32 rows, the mixed kernel for a 512-token window
    as 8 entries of 64 queries (what its scoped VMEM holds at 64 heads:
    `exaone_moe.query_tile`; one entry of 128 is refused by the gate)."""
    import jax.numpy as jnp

    from cake_tpu.models.moe.exaone_moe import query_tile
    from cake_tpu.ops import ragged_paged_attention as rpa

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    H, KV, hd, P = 64, 8, 128, 128
    tile = query_tile(512, H, KV, hd, P, 2, 2)
    assert tile == 64
    pool = sds((3, 192, P, KV * hd), jnp.bfloat16)
    on_tpu, rpa._on_tpu = rpa._on_tpu, lambda: True
    try:
        assert not rpa.ragged_paged_mixed_supported(P, H, KV, hd, 128)
        with jax.default_matmul_precision("default"):
            if kernel == "decode":
                compiled = jax.jit(lambda q, k, v, t, p: (
                    rpa.ragged_paged_attention(
                        q, k, v, jnp.int32(1), t, p, window=window,
                        interpret=False))).lower(
                    sds((32, 1, H, hd), jnp.bfloat16), pool, pool,
                    sds((32, table_pages), jnp.int32),
                    sds((32,), jnp.int32)).compile()
            else:
                n = 512 // tile
                compiled = jax.jit(lambda q, k, v, t, p, n_q: (
                    rpa.ragged_paged_attention_mixed(
                        q, k, v, jnp.int32(1), t, p, n_q, window=window,
                        interpret=False))).lower(
                    sds((n, tile, H, hd), jnp.bfloat16), pool, pool,
                    sds((n, table_pages), jnp.int32), sds((n,), jnp.int32),
                    sds((n,), jnp.int32)).compile()
    finally:
        rpa._on_tpu = on_tpu
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and f"cake_{kernel}_attn" in hlo


@pytest.mark.parametrize("kernel", ["decode", "mixed"])
def test_attention_kernels_compile_at_heads_of_64_under_a_stated_scale(
        one_chip, kernel):
    """`cake_decode_attn` and `cake_mixed_attn` at
    granite4h.sessions-closed's shapes (32 query heads over 8 K/V heads
    of 64: a pool row of 512 lanes, bf16 pages of 128, a table of 20
    pages a row, `scale=` 1/64) go through Mosaic: the decode kernel for
    64 rows, the mixed kernel for a 512-token window as 4 sub-windows of
    128 queries (`granite_hybrid.subwindow`: the kernel's own VMEM
    count; 256 queries are refused by the compiler for 16.88 MiB of its
    16, which is what the count's padding of a head to 128 lanes
    states)."""
    import jax.numpy as jnp

    from cake_tpu.models.moe.exaone_moe import query_tile
    from cake_tpu.ops import ragged_paged_attention as rpa

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    H, KV, hd, P, scale = 32, 8, 64, 128, 1.0 / 64
    sub = query_tile(512, H, KV, hd, P, 2, 2)
    assert sub == 128
    assert rpa.mixed_vmem_bytes(P, H, KV, hd, 256) > rpa._VMEM_SCOPED_LIMIT
    pool = sds((4, 1280, P, KV * hd), jnp.bfloat16)
    on_tpu, rpa._on_tpu = rpa._on_tpu, lambda: True
    try:
        assert rpa.ragged_paged_supported(P, H, KV, hd, n_pages=1280,
                                          slots=64, max_pages=20)
        assert rpa.ragged_paged_mixed_supported(P, H, KV, hd, sub)
        assert not rpa.ragged_paged_mixed_supported(P, H, KV, hd, 256)
        with jax.default_matmul_precision("default"):
            if kernel == "decode":
                compiled = jax.jit(lambda q, k, v, t, p: (
                    rpa.ragged_paged_attention(
                        q, k, v, jnp.int32(1), t, p, scale=scale,
                        interpret=False))).lower(
                    sds((64, 1, H, hd), jnp.bfloat16), pool, pool,
                    sds((64, 20), jnp.int32), sds((64,), jnp.int32)).compile()
            else:
                n = 512 // sub
                compiled = jax.jit(lambda q, k, v, t, p, n_q: (
                    rpa.ragged_paged_attention_mixed(
                        q, k, v, jnp.int32(1), t, p, n_q, scale=scale,
                        interpret=False))).lower(
                    sds((n, sub, H, hd), jnp.bfloat16), pool, pool,
                    sds((n, 20), jnp.int32), sds((n,), jnp.int32),
                    sds((n,), jnp.int32)).compile()
    finally:
        rpa._on_tpu = on_tpu
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and f"cake_{kernel}_attn" in hlo


# (H, KV, hd, entries, queries an entry, pages a table row, selecting):
# each cell's `cake_mixed_attn` call (K-EXAONE's two and Granite's are
# the two tests above)
MIXED_CALLS = {
    "keyevl2": (32, 4, 128, 4, 128, 260, True),
    "mistral7b": (32, 8, 128, 16, 128, 16, False),
    "olmoe7b": (16, 16, 128, 16, 128, 16, False),
    "zaya1": (8, 2, 128, 32, 128, 40, False),
    "nemotron3s": (32, 2, 128, 4, 128, 40, False),
}


@pytest.mark.parametrize("cell", sorted(MIXED_CALLS))
def test_walked_mixed_kernel_compiles_at_the_cells_calls(one_chip, cell):
    """`cake_mixed_attn` as it walks its pages (grid (rows,), the K and
    V rings of `mixed_block` pages a slot, Keye's selection beside
    them, the softmax's m and l as the loop's carries) goes through
    Mosaic at each cell's call under the limit it states, and
    `mixed_vmem_bytes` at that block lies under what `mixed_block`
    plans with. Nemotron's group of 16 alone was refused for 0.9 MiB
    while the kernel stated no limit (PR 34)."""
    import jax.numpy as jnp

    from cake_tpu.ops import ragged_paged_attention as rpa

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    H, KV, hd, B, C, pages, selecting = MIXED_CALLS[cell]
    P = 128
    block = rpa.mixed_block(P, H, KV, hd, C, pages, selecting=selecting)
    assert rpa.mixed_vmem_bytes(P, H, KV, hd, C, block=block,
                                selecting=selecting) <= rpa._MIXED_VMEM_PLAN
    pool = sds((2, B * 8, P, KV * hd), jnp.bfloat16)

    def call(q, k, v, t, p, n_q, *sel):
        return rpa.ragged_paged_attention_mixed(
            q, k, v, jnp.int32(1), t, p, n_q, interpret=False,
            **({"selected": sel[0]} if sel else {}))

    args = [sds((B, C, H, hd), jnp.bfloat16), pool, pool,
            sds((B, pages), jnp.int32), sds((B,), jnp.int32),
            sds((B,), jnp.int32)]
    if selecting:
        args.append(sds((B, pages, C, P), jnp.float32))
    on_tpu, rpa._on_tpu = rpa._on_tpu, lambda: True
    try:
        assert rpa.ragged_paged_mixed_supported(P, H, KV, hd, C)
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(call).lower(*args).compile()
    finally:
        rpa._on_tpu = on_tpu
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "cake_mixed_attn" in hlo


def test_kda_step_kernel_compiles_in_place_at_the_cells_widths(one_chip):
    """`cake_kda_step` at ling3.longreply-closed's shapes (10 layers of
    32 rows x 32 heads of 128 x 128 float32) goes through Mosaic, and
    the program that donates the stack holds it ONCE: the 640 MiB are
    aliased in and out, with no second copy among the temporaries."""
    import jax.numpy as jnp

    from cake_tpu.ops import kda
    from cake_tpu.ops import ragged_paged_attention as rpa

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L, B, H, dk, dv = 10, 32, 32, 128, 128
    on_tpu, rpa._on_tpu = rpa._on_tpu, lambda: True
    try:
        compiled = jax.jit(kda.step, donate_argnums=(0,)).lower(
            sds((L, B, H, dk, dv), jnp.float32), sds((), jnp.int32),
            sds((B,), jnp.int32), sds((B, H, dk), jnp.float32),
            sds((B, H, dk), jnp.float32), sds((B, H, dv), jnp.bfloat16),
            sds((B, H, dk), jnp.float32), sds((B, H), jnp.float32)).compile()
    finally:
        rpa._on_tpu = on_tpu
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "cake_kda_step" in hlo
    memory = compiled.memory_analysis()
    stack = L * B * H * dk * dv * 4
    assert memory.alias_size_in_bytes == stack
    assert memory.temp_size_in_bytes < 16 * 2**20
    assert (kda.RING_DEPTH * kda.block_heads(H, dk * dv * 4) * dk * dv * 4
            == 2 * 2**20)


def test_kda_chunk_kernel_compiles_at_the_cells_widths(one_chip):
    """`cake_kda_chunk` at ling3.longreply-closed's shapes (a window of
    512 tokens, 32 heads of 128 x 128, float32 operands and state) goes
    through Mosaic inside the scoped VMEM it states (its double-buffered
    blocks and its scratch pass the compiler's default of 16 MiB), and
    the program holds nothing beside its operands and results."""
    import jax.numpy as jnp

    from cake_tpu.ops import kda
    from cake_tpu.ops import ragged_paged_attention as rpa

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    C, H, dk, dv = 512, 32, 128, 128
    on_tpu, rpa._on_tpu = rpa._on_tpu, lambda: True
    try:
        compiled = jax.jit(kda.chunked).lower(
            sds((H, dk, dv), jnp.float32), sds((C, H, dk), jnp.float32),
            sds((C, H, dk), jnp.float32), sds((C, H, dv), jnp.float32),
            sds((C, H, dk), jnp.float32), sds((C, H), jnp.float32)).compile()
    finally:
        rpa._on_tpu = on_tpu
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "cake_kda_chunk" in hlo
    # the call's scoped memory is the limit the kernel states
    assert "scoped_memory_configs" in hlo
    assert f'"size":"{kda.CHUNK_VMEM_BYTES}"' in hlo
    assert kda.chunk_heads(H) * kda.CHUNK == 128    # ONE tile of rows
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_lings_mixed_program_holds_no_loop_under_kda_chunk(tool, one_chip):
    """A cut of Ling's served mixed program (a KDA layer and a latent
    one at the cell's widths, slots and window) for the described v5e:
    ONE `cake_kda_chunk` call a KDA layer, and no `while` anywhere whose
    body, condition or own name lies under the scope `kda_chunk`: XLA's
    32-step scan over the state is gone, not moved."""
    import json
    import re

    from cake_tpu.models.llama.config import load_config

    cell = CONFIGS / "ling-3.0-flash-int8-share4"
    config = load_config(str(cell))
    config = dataclasses.replace(
        config, num_hidden_layers=2, indexer_types=("kda", "dense"),
        mlp_layer_types=config.mlp_layer_types[:1] + ("sparse",))
    with open(cell / "cell.json") as f:
        cell = json.load(f)
    sa = cell["server_args"]
    width = sa["prefill-chunk"]
    _, mixed = tool.step_fns(config)
    with jax.default_matmul_precision("default"):
        compiled = tool.compile_step(
            mixed, config, one_chip, width=width,
            n_tokens=width + sa["max-slots"], slots=sa["max-slots"],
            n_pages=sa["kv-pages"], page_size=sa["kv-page-size"],
            max_seq_len=sa["max-seq-len"])
    hlo = compiled.as_text()
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and "cake_kda_chunk" in line]
    assert len(calls) == 1
    assert "kda_chunk" in calls[0]          # the call lies under the scope
    # every computation a `while` names, and the lines under the scope
    loops = set(re.findall(r"(?:body|condition)=%?([\w.\-]+)", hlo))
    under, inside = [], None
    for line in hlo.splitlines():
        head = tool._COMPUTATION.match(line)
        if head:
            inside = head.group(1)
        if "kda_chunk" in line and (inside in loops or " while(" in line):
            under.append(line.strip()[:200])
    assert not under, "a loop under kda_chunk again: " + "; ".join(under)


def test_ssm_step_kernel_compiles_in_place_at_the_cells_widths(one_chip):
    """`cake_ssm_step` at granite4h.sessions-closed's shapes (36 layers
    of 64 rows x 64 heads of 64 x 128 float32, one group) goes through
    Mosaic inside the default scoped VMEM (the kernel sets no limit),
    and the program that donates the stack holds it ONCE: the 4.5 GiB
    are aliased in and out, with no second copy among the temporaries."""
    import jax.numpy as jnp

    from cake_tpu.ops import kda, ssm
    from cake_tpu.ops import ragged_paged_attention as rpa

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L, B, H, P, N, G = 36, 64, 64, 64, 128, 1
    on_tpu, rpa._on_tpu = rpa._on_tpu, lambda: True
    try:
        compiled = jax.jit(ssm.step, donate_argnums=(0,)).lower(
            sds((L, B, H, P, N), jnp.float32), sds((), jnp.int32),
            sds((B,), jnp.int32), sds((B, H, P), jnp.bfloat16),
            sds((B, G, N), jnp.bfloat16), sds((B, G, N), jnp.bfloat16),
            sds((B, H), jnp.float32), sds((B, H), jnp.float32),
            sds((H,), jnp.float32)).compile()
    finally:
        rpa._on_tpu = on_tpu
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "cake_ssm_step" in hlo
    assert "vmem_limit_bytes" not in hlo
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == L * B * H * P * N * 4
    assert memory.temp_size_in_bytes < 16 * 2**20
    assert (kda.RING_DEPTH * kda.block_heads(H, P * N * 4) * P * N * 4
            == 2 * 2**20)


def test_retention_step_kernel_compiles_in_place_at_the_cells_widths(
        one_chip):
    """`cake_retention_step` at brumby14b.longreply16-closed's shapes
    (10 layers of 16 rows x 8 K/V heads of [9, 128, 1024] float32, 5
    query heads a group) goes through Mosaic inside the default scoped
    VMEM, and the program that donates the stacks holds them ONCE: the
    6.09 GB of S and z are aliased in and out, nothing among the
    temporaries."""
    import jax.numpy as jnp

    from cake_tpu.ops import kda, retention
    from cake_tpu.ops import ragged_paged_attention as rpa

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L, B, G, R, hd = 10, 16, 8, 5, 128
    D = retention.state_width(hd)
    assert retention.state_shape(G, hd, hd) == (G, 9, 128, 1024)
    on_tpu, rpa._on_tpu = rpa._on_tpu, lambda: True
    try:
        compiled = jax.jit(retention.step, donate_argnums=(0, 1)).lower(
            sds((L, B, G, 9, 128, 1024), jnp.float32),
            sds((L, B, G, D), jnp.float32), sds((), jnp.int32),
            sds((B,), jnp.int32), sds((B, G, R, hd), jnp.float32),
            sds((B, G, hd), jnp.float32), sds((B, G, hd), jnp.bfloat16),
            sds((B, G), jnp.float32)).compile()
    finally:
        rpa._on_tpu = on_tpu
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "cake_retention_step" in hlo
    assert "vmem_limit_bytes" not in hlo
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == L * B * G * D * (hd + 1) * 4
    assert memory.temp_size_in_bytes < 16 * 2**20
    assert kda.RING_DEPTH * hd * 1024 * 4 == 2 * 2**20


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_brumbys_step_programs_hold_the_state_once(tool, one_chip, program):
    """A two-layer cut of Brumby's served programs at the cell's widths,
    slots and window for the described v5e: ONE `cake_retention_step`
    call a layer over the whole stacks, which are aliased in and out
    (2 x 16 rows x 37.75 MB + z), and the temporaries (the window form's:
    phi(Q) of a K/V group, 94 MB, and its products; 0.29 GB) stay under
    ONE layer's state (609 MB): neither the kernel's write in place nor
    the window row's read behind its barrier costs a copy of a layer,
    let alone of the stack."""
    import json

    from cake_tpu.models.llama.config import load_config

    cell = CONFIGS / "brumby-14b-int8-10of40"
    config = dataclasses.replace(load_config(str(cell)), num_hidden_layers=2)
    with open(cell / "cell.json") as f:
        cell = json.load(f)
    sa = cell["server_args"]
    shape = dict(slots=sa["max-slots"], n_pages=sa["kv-pages"],
                 page_size=sa["kv-page-size"], max_seq_len=sa["max-seq-len"])
    decode, mixed = tool.step_fns(config)
    with jax.default_matmul_precision("default"):
        if program == "decode":
            compiled = tool.compile_step(decode, config, one_chip, **shape)
        else:
            width = cell["shape"]["mixed_width"]
            compiled = tool.compile_step(
                mixed, config, one_chip, width=width,
                n_tokens=width + sa["max-slots"], **shape)
    hlo = compiled.as_text()
    stack = f"f32[2,{sa['max-slots'] * 8},9,128,1024]"
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and "cake_retention_step" in line]
    assert len(calls) == 2 and all(stack in line for line in calls)
    assert not tool.materialised_int8(hlo)
    memory = compiled.memory_analysis()
    state = 2 * sa["max-slots"] * 8 * 9216 * 129 * 4
    assert memory.alias_size_in_bytes >= state
    assert memory.temp_size_in_bytes < state // 2


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_granites_step_programs_move_the_state_in_the_kernel_alone(
        tool, one_chip, program):
    """A cut of Granite's served programs (mamba, attention, mamba at
    the cell's widths, slots and window) for the described v5e: ONE
    `cake_ssm_step` call a Mamba layer; no fusion under `ssm_step` /
    `ssm_state` reads the state to reduce `y` (XLA's second pass over
    it before PR 57) and none gives a whole layer's state (the mixed
    program's fourth); the only other writer of the stack is the window
    row's scatter, one a Mamba layer of the mixed program. Neither the
    third pass nor the fourth can come back unseen."""
    import json
    import re

    from cake_tpu.models.llama.config import load_config

    cell = CONFIGS / "granite-4.0-h-micro-int8"
    config = dataclasses.replace(
        load_config(str(cell)), num_hidden_layers=3,
        layer_types=("mamba", "attention", "mamba"))
    with open(cell / "cell.json") as f:
        cell = json.load(f)
    sa = cell["server_args"]
    shape = dict(slots=sa["max-slots"], n_pages=sa["kv-pages"],
                 page_size=sa["kv-page-size"], max_seq_len=sa["max-seq-len"])
    decode, mixed = tool.step_fns(config)
    with jax.default_matmul_precision("default"):
        if program == "decode":
            compiled = tool.compile_step(decode, config, one_chip, **shape)
        else:
            width = cell["shape"]["mixed_width"]
            compiled = tool.compile_step(
                mixed, config, one_chip, width=width,
                n_tokens=width + sa["max-slots"], **shape)
    hlo = compiled.as_text()
    c = config
    layer = (f"f32[{sa['max-slots']},{c.mamba_num_heads},"
             f"{c.mamba_head_dim},{c.ssm_state_size}]")
    stack = layer.replace("f32[", "f32[2,")
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and "cake_ssm_step" in line]
    assert len(calls) == 2 and all(stack in line for line in calls)
    # what an instruction outside the fusions' bodies GIVES: its result
    # type(s), before the opcode
    gives = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s"
                       r"(?:fusion|custom-call|copy|dynamic-update-slice)\(")
    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo))
    whole_layers, stack_writers, inside = [], [], None
    for line in hlo.splitlines():
        head = tool._COMPUTATION.match(line)
        if head:
            inside = head.group(1)
        m = gives.match(line)
        if not m or inside in fused or "cake_ssm_step" in line:
            continue
        if layer in m.group(1):
            whole_layers.append(line.strip()[:200])
        if stack in m.group(1):
            stack_writers.append(line.strip())
    assert not whole_layers, "a whole layer's state is a value again: " \
        + "; ".join(whole_layers)
    assert not re.search(r"ssm_step/reduce_sum", hlo), (
        "XLA reduces y over the state again")
    # (a `copy` of the stack lands here too: XLA keeping the old stack
    # beside the kernel's in-place write, 4.5 GiB a layer)
    assert len(stack_writers) == (2 if program == "mixed" else 0), \
        [line[:300] for line in stack_writers]
    assert all("ssm_state/scatter" in line for line in stack_writers)


@pytest.mark.parametrize("heads,row,value,pages,masked_by,scope", [
    (128, 640, 512, 40, "positions", "mla"),      # dsv2.code-closed
    (64, 640, 512, 100, "bias", "mla"),           # glm52.longdoc-closed
    (128, 640, 512, 132, "bias", "mla"),          # dots3's full layers
    (64, 1152, 1024, 9, "bias", "swa")],          # dots3's 9-page ring
    ids=["dsv2", "glm", "dots3_full", "dots3_ring"])
def test_latent_window_kernel_compiles_at_the_cells_widths(
        tool, one_chip, heads, row, value, pages, masked_by, scope):
    """`cake_mla_window_attn` / `cake_swa_window_attn` at the three
    latent cells' shapes (a 512-token window, pages of 128) goes through
    Mosaic at the tiles `window_tiles` picks: the resident tile, its
    float32 accumulator, the two ring slots of a block of pages, the
    block's score and probability tiles and a bias array's rows fit the
    VMEM the call asks for, above the default 16 MiB."""
    import jax.numpy as jnp

    from cake_tpu.ops import mla_attention as mla

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    biased = masked_by == "bias"

    def call(q, pool, table, mask, last):
        return mla.attend_window(
            q, pool, jnp.int32(1), table, mask if biased else None, last,
            value, 0.1147, impl="pallas", interpret=False, scope=scope,
            positions=None if biased else mask)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(call).lower(
            sds((512, heads, row), jnp.bfloat16),
            sds((2, 64, 128, row), jnp.bfloat16), sds((pages,), jnp.int32),
            (sds((512, pages * 128), jnp.float32) if biased
             else sds((512,), jnp.int32)), sds((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert f"cake_{scope}_window_attn" in hlo
    tq, block = mla.window_tiles(512, heads, row, value, 128, pages, 2,
                                 biased)
    assert tq * heads == (1024 if row == 640 else 512)
    assert block == (2 if pages == 9 else 4)


@pytest.mark.parametrize("S", [33280, 12800, 16896],
                         ids=["keyevl2", "glm52", "dots3"])
def test_dsa_select_kernel_compiles_at_the_cells_widths(one_chip, S):
    """`cake_dsa_select` at the three selecting cells' shapes (a window
    of 512 queries, top 2,048, the table 33,280 / 12,800 / 16,896 keys
    wide) goes through Mosaic inside the scoped VMEM it states: a tile
    of 128 queries' resident codes (17 MB at 33,280) and the result's
    two buffers pass the compiler's default of 16 MiB. The float32
    scores reach the kernel as they lie (no copy in front of it) and
    the program holds nothing beside its operands and result."""
    import jax.numpy as jnp

    from cake_tpu.ops import mla_attention as mla
    from cake_tpu.ops import ragged_paged_attention as rpa

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    C, K = 512, 2048
    on_tpu, rpa._on_tpu = rpa._on_tpu, lambda: True
    try:
        compiled = jax.jit(
            lambda s, p, last: mla.select_window(s, p, last, K)).lower(
            sds((C, S), jnp.float32), sds((C,), jnp.int32),
            sds((), jnp.int32)).compile()
    finally:
        rpa._on_tpu = on_tpu
    hlo = compiled.as_text()
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and "cake_dsa_select" in line]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    assert f'"size":"{mla._SELECT_VMEM_LIMIT}"' in calls[0]
    # the scores reach it as they lie: no [512, S] array is written
    # in front of the call
    assert not re.search(
        r"= (?:f32|s32)\[512,%d\]\S* (?:copy|bitcast-convert|fusion)\(" % S,
        hlo)
    tq, chunk, block = mla.select_tiles(C, S)
    assert (tq, chunk) == (128, 128) and S % block == 0
    assert tq * S * 4 + 2 * tq * S < mla._SELECT_VMEM_LIMIT
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.fixture(scope="module")
def keyes_programs(tool, one_chip):
    """Keye's served step programs at the cell's widths (two of its
    alike layers: the trunk is one scan) for the described v5e ->
    {"decode" | "mixed": optimised HLO}."""
    import json

    from cake_tpu.models.llama.config import load_config

    cell = CONFIGS / "keye-vl-2.0-lm-int8-8of48"
    config = dataclasses.replace(load_config(str(cell)), num_hidden_layers=2)
    with open(cell / "cell.json") as f:
        sa = json.load(f)["server_args"]
    width = sa["prefill-chunk"]
    shape = dict(slots=sa["max-slots"], n_pages=sa["kv-pages"],
                 page_size=sa["kv-page-size"], max_seq_len=sa["max-seq-len"])
    decode, mixed = tool.step_fns(config)
    with jax.default_matmul_precision("default"):
        return {
            "decode": tool.compile_step(decode, config, one_chip,
                                        **shape).as_text(),
            "mixed": tool.compile_step(
                mixed, config, one_chip, width=width,
                n_tokens=width + sa["max-slots"], **shape).as_text()}


def test_keyes_mixed_program_holds_two_select_calls_a_layer(keyes_programs):
    """TWO `cake_dsa_select` calls in the mixed program's layer scan,
    both under the scope `index_topk`: the window's (a mask
    s8[512,33280]) and the single-token rows' (ONE tile of 8 queries,
    s8[8,33280]); under that scope no running count over the window's
    [512, 33,280] codes (XLA's `reduce-window`s over s32[512,260,128],
    a `cumsum` by name) and no counting loop."""
    hlo = keyes_programs["mixed"]
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and "cake_dsa_select" in line]
    assert len(calls) == 2
    assert all("index_topk" in call for call in calls)
    assert sorted(re.search(r"= \(?(s8\[\d+,33280\])", call).group(1)
                  for call in calls) == ["s8[512,33280]", "s8[8,33280]"]
    under = []
    for line in hlo.splitlines():
        if "index_topk" not in line:
            continue
        if ("reduce-window(" in line or "cumsum" in line
                or "[512,260,128]" in line or " while(" in line):
            under.append(line.strip()[:200])
    assert not under, ("the selection's XLA form under index_topk again: "
                       + "; ".join(under))


def _indexers_window_pass(hlo, S):
    """(the `cake_dsa_index` calls, what is left of the blocked map) of
    a mixed program's optimised HLO over a table of S keys: the calls'
    lines, and every instruction that gives a stack of score blocks
    ([blocks, 512, block] float32, or one block of it), a copy or a
    transpose of the window's [512, S] scores, or a loop or a branch
    under the scope `indexer`."""
    # (by the instruction's own name: `cake_dsa_select`'s line names
    # the scores too, as its operand)
    calls = [line for line in hlo.splitlines()
             if re.match(r"\s*(?:ROOT )?%cake_dsa_index[\w.\-]* = ", line)]
    left = [line.strip()[:200] for line in hlo.splitlines()
            if re.search(r"= f32\[512,%d\]\S* (?:copy|transpose)\(" % S, line)
            or ("/indexer/" in line and re.search(
                r"= \(?f32\[\d+,512,\d+\]| (?:while|conditional)\(", line))]
    return calls, left


def test_keyes_mixed_program_scores_its_window_in_one_call(keyes_programs):
    """ONE `cake_dsa_index` call in the mixed program's layer scan,
    under the scope `indexer`, whose f32[512,33280] result is the
    window's `cake_dsa_select`'s operand as it lies: no stack of score
    blocks (f32[52,512,640] at the parent), no block of one
    (f32[1,512,640]), no transposing copy of f32[512,33280], no loop
    or branch under `indexer`. The decode program holds no such call:
    its rows' pass is `index_scores_rows` as it was."""
    hlo = keyes_programs["mixed"]
    calls, left = _indexers_window_pass(hlo, 33280)
    assert len(calls) == 1 and "/indexer/" in calls[0]
    assert "tpu_custom_call" in calls[0]
    scores = re.match(r"\s*(%[\w.\-]+) = f32\[512,33280\]", calls[0])
    assert scores, calls[0][:200]
    select = [line for line in hlo.splitlines()
              if "cake_dsa_select" in line and "s8[512,33280]" in line
              and "custom-call(" in line]
    assert len(select) == 1 and scores.group(1) + ")" in select[0]
    assert not left, "the blocked map under indexer again: " + "; ".join(left)
    assert "cake_dsa_index" not in keyes_programs["decode"]


@pytest.mark.parametrize("cell,cut,S", [
    ("glm-5.2-int8-share16", dict(indexer_types=("full",)), 12800),
    ("dots3-note-int8-share8", dict(indexer_types=("full",)), 16896)],
    ids=["glm52", "dots3"])
def test_latent_mixed_programs_score_their_window_in_one_call(
        tool, one_chip, cell, cut, S):
    """GLM's and dots3's mixed programs at the cells' widths (ONE full
    indexer layer with a dense FFN: every full layer is alike) for the
    described v5e: one `cake_dsa_index` call under `indexer`, at 32
    heads of 128 over 12,800 keys and at 64 of 128 over 16,896, through
    Mosaic at the tiles `index_tiles` picks; its result is
    `cake_dsa_select`'s operand, and nothing of the blocked map is left
    (`_indexers_window_pass`)."""
    import json

    from cake_tpu.models.llama.config import load_config

    cell = CONFIGS / cell
    config = dataclasses.replace(
        load_config(str(cell)), num_hidden_layers=1,
        mlp_layer_types=("dense",), **cut)
    with open(cell / "cell.json") as f:
        sa = json.load(f)["server_args"]
    width = sa["prefill-chunk"]
    mixed = tool.step_fns(config)[1]
    with jax.default_matmul_precision("default"):
        hlo = tool.compile_step(
            mixed, config, one_chip, width=width,
            n_tokens=width + sa["max-slots"], slots=sa["max-slots"],
            n_pages=sa["kv-pages"], page_size=sa["kv-page-size"],
            max_seq_len=sa["max-seq-len"]).as_text()
    calls, left = _indexers_window_pass(hlo, S)
    assert len(calls) == 1 and "/indexer/" in calls[0]
    scores = re.match(r"\s*(%%[\w.\-]+) = f32\[512,%d\]" % S, calls[0])
    assert scores, calls[0][:200]
    select = [line for line in hlo.splitlines()
              if "cake_dsa_select" in line and "custom-call(" in line]
    assert len(select) == 1 and scores.group(1) + ")" in select[0]
    assert not left, "the blocked map under indexer again: " + "; ".join(left)


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_keyes_single_token_rows_attend_where_their_keys_lie(
        keyes_programs, program):
    """A single-token row's selected keys stay in the pools: in both
    programs the rows' selection is one `cake_dsa_select` call of 8
    queries and their attention one `cake_decode_attn` call that takes
    the mask by page (f32[8,260,128]) beside the layer's own pools; no
    `sort` is left under the scope `attn` (the rows' `lax.top_k` was a
    full sort of f32[8,33280], `jnp.sort` one of s32[8,2048]; the
    router's and the dispatch's, under `ffn`, stay), nothing is
    gathered out of the K and V pools (bf16[16384,512] each, 8 rows x
    2,048 keys of 1 KB) or looked up for it (s32[16384]), and no scope
    `dsa_gather` remains."""
    hlo = keyes_programs[program]
    calls = [line for line in hlo.splitlines() if "custom-call(" in line]
    rows = [c for c in calls if "cake_dsa_select" in c and "s8[8,33280]" in c]
    attend = [c for c in calls if "cake_decode_attn" in c]
    assert len(rows) == 1 and len(attend) == 1
    assert "f32[8,260,128]" in attend[0] and "bf16[8,1,32,128]" in attend[0]
    sorts = [line.strip()[:200] for line in hlo.splitlines()
             if re.search(r"\bsort\(", line)]
    assert sorts and not [line for line in sorts if "/attn/" in line
                          or "[8,33280]" in line or "[8,2048]" in line]
    assert "[16384,512]" not in hlo and "s32[16384]" not in hlo
    assert "dsa_gather" not in hlo


@pytest.mark.parametrize("n_pairs,E,K,N", [
    (528 * 8, 16, 6144, 2048),    # GLM's gate and up, mixed: the budget's
    (32 * 6, 20, 1536, 5120),     # DeepSeek-V2's down, decode: tn 2,560
    (32 * 6, 20, 5120, 1536),     # DeepSeek-V2's gate and up, decode: 768
    (544 * 22, 128, 1024, 2688),  # Nemotron's up, mixed: one block
    (32 * 22, 128, 1024, 2688),   # ... and in a decode step
    (544 * 22, 128, 2688, 1024)],  # Nemotron's down, mixed
    ids=["glm_up", "dsv2_down_decode", "dsv2_up_decode", "nemotron_up",
         "nemotron_up_decode", "nemotron_down"])
def test_expert_matmul_compiles_at_the_tile_the_budget_admits(
        tool, one_chip, n_pairs, E, K, N):
    """`cake_moe_gmm` at the cells' fullest blocks by `gmm_vmem_bytes`'
    count (bf16 rows, int8 per-channel weights) goes through Mosaic
    under the compiler's own scoped VMEM: `GMM_VMEM_BUDGET` admits
    nothing the chip's compiler refuses."""
    import jax.numpy as jnp

    from cake_tpu.ops import moe
    from cake_tpu.ops.quant import QTensor

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tm = moe.row_tile(n_pairs)
    M = -(-n_pairs // tm) * tm
    grid = moe.gmm_grid(n_pairs, E, K, N, 2, 1, True)
    walk = [sds((grid.visits,), jnp.int32)] * 4
    compiled = jax.jit(
        lambda x, w, layer, *walk: moe.grouped_matmul.__wrapped__(
            x, w, layer, *walk, tm=tm, interpret=False)).lower(
        sds((M, K), jnp.bfloat16),
        QTensor(sds((2, E, K, N), jnp.int8), sds((2, E, N), jnp.float32)),
        sds((), jnp.int32), *walk).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "cake_moe_gmm" in hlo
    assert grid.tn > 512 or (K, N) == (6144, 2048)
    assert moe.gmm_vmem_bytes(tm, K, grid.tn, 2, 1, True) > 10 * 2**20


def test_longcats_step_programs_run_both_latent_kernels_at_64_heads(
        tool, one_chip):
    """A one-layer cut (two sublayers) of LongCat-Flash's served programs
    at the cell's widths, slots, pool and window for the described v5e:
    `cake_mla_decode_attn` in every sublayer of both programs and
    `cake_mla_window_attn` in every sublayer of the mixed one go through
    Mosaic at 64 heads (four pages a fold over a table of 76), the
    layer's routed experts are three `cake_moe_gmm` calls, and the only
    int8 arrays written to memory are the per-head views of the two
    latent up-projections (`s8[1,512,8192]`), which every model of this
    trunk copies (PERF.md section 7, PR 43's question): no dense FFN,
    projection, expert or head is materialised."""
    import json

    from cake_tpu.models.llama.config import load_config
    from cake_tpu.ops import mla_attention as mla

    cell = CONFIGS / "longcat-flash-int8-share32"
    config = load_config(str(cell))
    config = dataclasses.replace(
        config, num_hidden_layers=2, mlp_layer_types=("shortcut", "dense"),
        indexer_types=("dense",) * 2)
    with open(cell / "cell.json") as f:
        cell = json.load(f)
    sa = cell["server_args"]
    shape = dict(slots=sa["max-slots"], n_pages=sa["kv-pages"],
                 page_size=sa["kv-page-size"], max_seq_len=sa["max-seq-len"])
    width = cell["shape"]["mixed_width"]
    decode, mixed = tool.step_fns(config)
    with jax.default_matmul_precision("default"):
        programs = {
            "decode": tool.compile_step(decode, config, one_chip, **shape),
            "mixed": tool.compile_step(
                mixed, config, one_chip, width=width,
                n_tokens=width + sa["max-slots"], **shape)}
    assert mla.decode_block(64, 640, 512, 128, 76, 2) == 4
    for name, compiled in programs.items():
        hlo = compiled.as_text()
        calls = [line for line in hlo.splitlines() if "custom-call(" in line]

        def count(kernel):
            return sum(kernel in line for line in calls)

        assert count("cake_mla_decode_attn") == 2, name
        assert count("cake_mla_window_attn") == (2 if name == "mixed"
                                                 else 0), name
        assert count("cake_moe_gmm") == 3, name
        assert {m.shape for m in tool.materialised_int8(hlo)} <= {
            "s8[1,512,8192]"}, name
