"""What the chip bring-up (PR 21) added below the entry point: the
compile-cache placement rule, a loader that quantizes without ever
holding the full-precision tree, and the engine's per-step-kind
attention resolution as /api/v1/steps and /api/v1/health report it.
(Sorts late on purpose, like test_tpu_chip_smoke.py: the tier-1 lane is
cut at a wall-clock cap.)"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.ops.quant import QTensor

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


# -- compile cache -------------------------------------------------------------


def test_compile_cache_placement_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and the code then sets no path;
    otherwise the fixed <checkout>/.jax_cache. Small programs are
    cached either way."""
    from cake_tpu.utils import compile_cache as cc

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert cc.enable_compile_cache() == fixed
    assert updates == {
        "jax_compilation_cache_dir": fixed,
        "jax_persistent_cache_min_compile_time_secs": 0.0}
    updates.clear()
    monkeypatch.setenv(cc.ENV_VAR, "/somewhere/else")
    assert cc.enable_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in updates


# -- loader --------------------------------------------------------------------


def _no_full_tree(monkeypatch):
    """Make every tree-level path that holds the whole full-precision
    tree raise: the loader must not go near them."""
    import cake_tpu.models.llama.params as lp
    import cake_tpu.ops.quant as quant

    def boom(*a, **k):
        raise AssertionError("built / quantized the full-precision tree")
    monkeypatch.setattr(lp, "init_params", boom)
    monkeypatch.setattr(quant, "quantize_params", boom)
    monkeypatch.setattr(quant, "quantize_params_leafwise", boom)


@pytest.mark.parametrize("quant,bits", [("int8", 8), ("int4", 4)])
def test_load_text_params_quant_without_weights(tiny_config, monkeypatch,
                                                quant, bits):
    """No weights on disk: the quantized leaves are initialised
    directly — same tree as quantize_params(init_params(...)) by
    structure, shape and dtype, with init_params never called."""
    from cake_tpu.models import load_text_params
    from cake_tpu.models.llama.params import init_params
    from cake_tpu.ops.quant import quantize_params

    want = jax.eval_shape(lambda: quantize_params(
        init_params(tiny_config, jax.random.PRNGKey(0)), bits=bits))
    _no_full_tree(monkeypatch)
    got = load_text_params(tiny_config, "", jnp.bfloat16, quant=quant)
    assert isinstance(got["blocks"]["wq"], QTensor)
    assert isinstance(got["lm_head"], QTensor)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)


def test_load_text_params_quantizes_leaf_by_leaf(tiny_config, tmp_path,
                                                 monkeypatch):
    """Weights on disk: each tensor is quantized as it lands (one call
    of the leaf quantizer per weight leaf, never a tree-level pass) and
    the result is load-then-quantize's, to the last ulp of a scale (the
    per-leaf programs are jitted, the tree-level reference runs op by
    op) and so at most one int8 step of a weight."""
    from test_stream_load import write_tiny_hf_checkpoint

    import cake_tpu.ops.quant as quant
    from cake_tpu.models import load_text_params
    from cake_tpu.models.llama.params import load_params_from_hf

    hf = write_tiny_hf_checkpoint(tmp_path / "model", tiny_config)
    want = quant.quantize_params(
        load_params_from_hf(hf, tiny_config, dtype=jnp.float32), bits=8)

    seen = []
    make = quant.make_leaf_quantizer

    def counting(bits, group=128):
        qz = make(bits, group)

        def spy(name, leaf):
            seen.append(name)
            return qz(name, leaf)
        return spy
    monkeypatch.setattr(quant, "make_leaf_quantizer", counting)
    _no_full_tree(monkeypatch)
    got = load_text_params(tiny_config, hf, jnp.float32, quant="int8")
    assert sorted(seen) == sorted(list(want["blocks"]) + ["lm_head"])
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        if g.dtype == jnp.int8:
            assert np.abs(np.asarray(g, np.int32)
                          - np.asarray(w, np.int32)).max() <= 1
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6)


# -- resolved attention --------------------------------------------------------


def _paged_engine(tiny_config, tiny_params, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    return InferenceEngine(
        tiny_config, tiny_params, ByteTokenizer(tiny_config.vocab_size),
        max_slots=2, max_seq_len=64,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        kv_pages=10, kv_page_size=8, **kw)


def test_steps_report_the_resolved_attention_not_the_requested(
        tiny_config, tiny_params, monkeypatch):
    """With the mixed kernel's gate forced false, "auto" on a (faked)
    TPU resolves decode to pallas and mixed to the FOLD — and that,
    per step kind, is what /api/v1/steps and /api/v1/health report; an
    explicit "pallas" that cannot be served raises instead."""
    import cake_tpu.autotune.space as space
    import cake_tpu.ops.ragged_paged_attention as rpa
    from cake_tpu.api.server import ApiServer
    from cake_tpu.args import Args
    from cake_tpu.master import Master

    monkeypatch.setattr(rpa, "ragged_paged_mixed_supported",
                        lambda *a, **k: False)
    with pytest.raises(ValueError, match="cannot serve the .'mixed'."):
        _paged_engine(tiny_config, tiny_params, paged_attn="pallas")

    # "auto" as a TPU resolves it (the kernels still interpret here)
    monkeypatch.setattr(
        space, "resolve_paged_attn",
        lambda name: "pallas" if name in (None, "auto") else name)
    eng = _paged_engine(tiny_config, tiny_params, paged_attn="auto")
    assert eng.attn_impl == {"decode": "pallas", "mixed": "fold"}
    api = ApiServer(Master(Args()), "m", engine=eng)   # starts the engine
    try:
        h = eng.submit([5] * 9, max_new_tokens=4)
        assert h.wait(timeout=300)
        by_kind = {}
        for rec in api.steps()["steps"]:
            by_kind.setdefault(rec["kind"], set()).add(rec["impl"])
        assert by_kind == {"mixed": {"paged-fold"},
                           "decode": {"paged-pallas"}}
        config = api.health()["engine_config"]
        assert config["attn_impl"] == {"decode": "pallas",
                                       "mixed": "fold"}
        assert config["paged_attn"] == "pallas"
    finally:
        eng.stop()
