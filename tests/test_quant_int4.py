"""int4 group-wise quantization: packing, kernel, model integration.

The reference has no quantization (f16 floor, cake/mod.rs:54-60); int4 is
a perf capability beyond parity, so the oracle is our own f32 math:
pack/unpack round-trips, the Pallas kernel (interpret mode on CPU) against
the dequantize matmul, and the quantized tiny model end-to-end.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.ops.int4_matmul import (
    int4_matmul, kernel_supported, pack_int4, unpack_int4,
)
from cake_tpu.ops.quant import (
    QTensor, expand_specs_for_quant, is_groupwise, pick_group, qmatmul,
    quantize_group, quantize_params,
)


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    q = rng.integers(-8, 8, (64, 32), dtype=np.int8)
    g = 16
    packed = pack_int4(jnp.asarray(q), g)
    assert packed.shape == (32, 32) and packed.dtype == jnp.uint8
    back = unpack_int4(packed, g)
    np.testing.assert_array_equal(np.asarray(back), q)


def test_quantize_group_dequant_error_bounded():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(256, 64)).astype(np.float32))
    qt = quantize_group(w, 0, group=128)
    assert is_groupwise(qt)
    assert qt.q.shape == (128, 64) and qt.scale.shape == (2, 64)
    vals = unpack_int4(qt.q, 128).astype(jnp.float32)
    deq = (vals.reshape(2, 128, 64)
           * qt.scale[:, None, :]).reshape(256, 64)
    # symmetric rounding: |err| <= scale/2 per element
    err = np.abs(np.asarray(deq - w))
    bound = np.asarray(qt.scale[:, None, :] * 0.55).repeat(128, 1
                                                           ).reshape(256, 64)
    assert (err <= bound).all()


def test_qmatmul_groupwise_matches_dequant():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(128, 256)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(3, 128)).astype(np.float32))
    qt = quantize_group(w, 0, group=32)
    vals = unpack_int4(qt.q, 32).astype(jnp.float32)
    G = qt.scale.shape[0]
    deq = (vals.reshape(G, 32, 256) * qt.scale[:, None, :]).reshape(128, 256)
    got = qmatmul(x, qt)
    # M=3 dispatches to the Pallas kernel (interpret on CPU), whose
    # per-group accumulation order differs from the reference matmul
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ deq),
                               rtol=2e-4, atol=2e-4)


def test_pallas_kernel_matches_fallback():
    rng = np.random.default_rng(3)
    In, Out, g = 256, 256, 128
    w = jnp.asarray(rng.normal(size=(In, Out)).astype(np.float32))
    qt = quantize_group(w, 0, group=g)
    x = jnp.asarray(rng.normal(size=(5, In)).astype(np.float32))
    assert kernel_supported(5, In, g, Out)
    got = int4_matmul(x, qt.q, qt.scale, g=g, interpret=True)
    vals = unpack_int4(qt.q, g).astype(jnp.float32)
    G = qt.scale.shape[0]
    deq = (vals.reshape(G, g, Out) * qt.scale[:, None, :]).reshape(In, Out)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ deq),
                               rtol=2e-4, atol=2e-4)


def test_pallas_kernel_real_backend_production_shapes():
    """The REAL (non-interpret) kernel at production-like lane-aligned
    shapes. On the CPU lane interpret=None resolves to interpret mode;
    under CAKE_TESTS_TPU=1 this compiles and runs the actual Mosaic
    kernel on silicon — the coverage the interpret=True test above
    cannot give (tiny sub-128-lane shapes are gated off hardware by
    kernel_supported instead)."""
    rng = np.random.default_rng(7)
    In, Out, g = 512, 256, 128
    w = jnp.asarray(rng.normal(size=(In, Out)).astype(np.float32))
    qt = quantize_group(w, 0, group=g)
    x = jnp.asarray(rng.normal(size=(4, In)).astype(np.float32))
    assert kernel_supported(4, In, g, Out)
    got = int4_matmul(x, qt.q, qt.scale, g=g)   # interpret=None: real
    vals = unpack_int4(qt.q, g).astype(jnp.float32)
    G = qt.scale.shape[0]
    deq = (vals.reshape(G, g, Out) * qt.scale[:, None, :]).reshape(In, Out)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ deq),
                               rtol=5e-3, atol=5e-3)


def test_quantize_params_int4_structure_matches_direct_init(tiny_config):
    from cake_tpu.models.llama.params import (
        init_params, init_params_quantized,
    )
    full = init_params(tiny_config, jax.random.PRNGKey(0))
    via_quant = quantize_params(full, bits=4)
    direct = init_params_quantized(tiny_config, jax.random.PRNGKey(0),
                                   bits=4)
    sa = jax.tree.structure(via_quant)
    sb = jax.tree.structure(direct)
    assert sa == sb
    for a, b in zip(jax.tree.leaves(via_quant), jax.tree.leaves(direct)):
        assert a.shape == b.shape, (a.shape, b.shape)
        assert a.dtype == b.dtype, (a.dtype, b.dtype)


def test_generator_int4_end_to_end(tiny_config):
    """Greedy decode with int4 weights: scan path == step path."""
    from cake_tpu.models.llama.generator import ByteTokenizer, LlamaGenerator
    from cake_tpu.models.llama.params import init_params
    from cake_tpu.ops.sampling import SamplingConfig

    params = quantize_params(
        init_params(tiny_config, jax.random.PRNGKey(0)), bits=4)
    gen = LlamaGenerator(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        max_seq_len=128,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0))
    prompt = np.full((1, 9), 5, np.int32)
    plen = np.full((1,), 9, np.int32)
    out = gen.generate_on_device(prompt, plen, 6)
    assert out.shape == (1, 6)

    from cake_tpu.models.chat import Message
    gen.add_message(Message.user("hi"))
    toks = [gen.next_token(i).id for i in range(3)]
    assert len(toks) == 3


def test_expand_specs_groupwise_keeps_contract_spec(tiny_config):
    from jax.sharding import PartitionSpec as P

    from cake_tpu.models.llama.params import init_params_quantized
    params = init_params_quantized(tiny_config, jax.random.PRNGKey(0),
                                   bits=4)
    spec = {
        "embed": P(), "final_norm": P(), "lm_head": P(None, "tp"),
        "blocks": {k: (P("stage", None, "tp")
                       if k in ("wq", "wk", "wv", "w_gate", "w_up")
                       else P("stage"))
                   for k in params["blocks"]},
    }
    out = expand_specs_for_quant(params, spec)
    wq = out["blocks"]["wq"]
    assert isinstance(wq, QTensor)
    # group-wise: scale keeps ALL dims (group dim inherits contract spec)
    assert wq.q == P("stage", None, "tp")
    assert wq.scale == P("stage", None, "tp")


def test_int4_moe_raises(tiny_config):
    params = {"blocks": {"we_gate": jnp.zeros((2, 2, 8, 16))},
              "lm_head": jnp.zeros((8, 16))}
    with pytest.raises(NotImplementedError, match="int4"):
        quantize_params(params, bits=4)


def test_args_accept_int4():
    from cake_tpu.args import Args
    assert Args(quant="int4").validate().quant == "int4"
    with pytest.raises(ValueError):
        Args(quant="int2").validate()


def test_pick_group_shrinks_for_tiny_dims():
    assert pick_group(4096) == 128
    assert pick_group(64) == 64
    assert pick_group(96) == 32
