"""Profiling subsystem: trace capture, step stats, memory reporting."""

import os

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.utils.profiling import (
    device_memory_stats, human_bytes, log_memory, trace,
)


def test_human_bytes():
    assert human_bytes(512) == "512 B"
    assert human_bytes(1536) == "1.5 KiB"
    assert human_bytes(3 * 1024 ** 3) == "3.0 GiB"


def test_trace_noop_when_disabled():
    with trace(None):
        pass
    with trace(""):
        pass


def test_trace_writes_profile(tmp_path):
    d = str(tmp_path / "prof")
    with trace(d):
        jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    found = []
    for root, _dirs, files in os.walk(d):
        found.extend(files)
    assert found, "profiler produced no output files"


def test_memory_stats_shape():
    stats = device_memory_stats()
    assert len(stats) == len(jax.local_devices())
    for s in stats:
        assert "device" in s and "bytes_in_use" in s
    log_memory("test")  # must not raise on CPU


def test_sd_tracing_flag_wires(tmp_path, monkeypatch):
    """--sd-tracing routes generation through the profiler context."""
    import cake_tpu.models.sd.sd as sd_mod
    from cake_tpu.args import ImageGenerationArgs

    calls = []

    class FakeSD(sd_mod.SDGenerator):
        def __init__(self):  # bypass heavy init
            pass

        def _generate_image(self, args, callback):
            calls.append("ran")

    monkeypatch.chdir(tmp_path)
    FakeSD().generate_image(
        ImageGenerationArgs(sd_tracing=True), lambda p: None)
    assert calls == ["ran"]
    assert os.path.isdir(tmp_path / "sd-trace")


# -- capture_trace: light by default ------------------------------------------


@pytest.mark.parametrize("perfetto", [False, True])
def test_capture_trace_converts_to_perfetto_only_when_asked(tmp_path,
                                                            perfetto):
    """The `.xplane.pb` is what readers want; the Perfetto JSON is
    re-read and re-written inside the serving process at stop, so it
    is made only when asked for."""
    from cake_tpu.utils.profiling import capture_trace
    out = capture_trace(0.1, str(tmp_path), perfetto=perfetto)
    assert out["dir"] == str(tmp_path) and out["seconds"] >= 0.1
    assert out["xplane"].endswith(".xplane.pb")
    assert os.path.isfile(out["xplane"])
    made = [f for _r, _d, fs in os.walk(tmp_path) for f in fs
            if f == "perfetto_trace.json.gz"]
    if perfetto:
        assert os.path.isfile(out["perfetto_trace"]) and made
    else:
        assert out["perfetto_trace"] is None and not made


# -- names in the device trace -----------------------------------------------
#
# jax.named_scope reaches the trace as the HLO op_name (the TPU
# profiler's `tf_op` stat); pallas_call's name= is the custom call's
# instruction name. Both are checked on the lowered programs here.

BLOCK_SCOPES = ("attn_norm", "qkv", "attn", "o_proj", "ffn")
PROGRAM_SCOPES = ("embed", "layers", "kv", "head")


def _tiny_paged(slots=2, width=8):
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.model import RopeTables
    from cake_tpu.models.llama.paged import PagedKVCache
    from cake_tpu.models.llama.params import init_params
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache = PagedKVCache.create(cfg, slots, 8, 16, 64, dtype=jnp.float32)
    rope = RopeTables.create(cfg, 64)
    pos = jnp.zeros(slots, jnp.int32)
    active = jnp.ones(slots, bool)
    return cfg, params, cache, rope, pos, active


def _step_args(which):
    cfg, params, cache, rope, pos, active = _tiny_paged()
    if which == "decode":
        from cake_tpu.models.llama.paged import forward_ragged_paged
        toks = jnp.zeros((2, 1), jnp.int32)

        def fn(params, toks, pos, active, cache, rope):
            return forward_ragged_paged(params, toks, cache, pos, active,
                                        rope, cfg)
        return fn, (params, toks, pos, active, cache, rope)
    from cake_tpu.models.llama.paged import mixed_step_paged
    toks = jnp.zeros((2, 8), jnp.int32)
    qlen = jnp.full(2, 8, jnp.int32)

    def fn(params, toks, pos, qlen, active, cache, rope):
        return mixed_step_paged.__wrapped__(params, toks, pos, qlen,
                                            active, cache, rope, cfg)
    return fn, (params, toks, pos, qlen, active, cache, rope)


def _op_names(lowered_text):
    import re
    return set(re.findall(r'loc\("([^"]*)"', lowered_text))


@pytest.mark.parametrize("which", ["decode", "mixed"])
def test_step_programs_name_their_scopes(which):
    fn, args = _step_args(which)
    names = _op_names(jax.jit(fn).lower(*args).as_text(debug_info=True))
    for scope in PROGRAM_SCOPES[:2] + PROGRAM_SCOPES[3:]:
        assert any(f"/{scope}/" in n for n in names), scope
    # the scan's body is a function of its own in the lowered text,
    # and its op names start at the block's scopes
    assert any(n.endswith("/layers/while/body/closed_call")
               for n in names)
    for scope in BLOCK_SCOPES:
        assert any(n.startswith(scope + "/") for n in names), scope
    # the page write is named inside the attention it belongs to
    assert any(n.startswith("attn/kv/scatter") for n in names)


def test_sampling_program_names_its_scope():
    from cake_tpu.serve.engine import _masked_sample
    B, V = 2, 32
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    args = (jnp.ones(B, bool), keys, jnp.zeros((B, V)),
            jnp.full((B, 8), -1, jnp.int32), jnp.zeros(B, jnp.int32),
            jnp.zeros(B), jnp.ones(B), jnp.ones(B))
    from cake_tpu.ops.sampling import SamplingConfig
    text = jax.jit(lambda *a: _masked_sample(
        *a, top_k=SamplingConfig().top_k)).lower(*args).as_text(
            debug_info=True)
    assert any("/sample/" in n for n in _op_names(text))


def _kernel_calls():
    from cake_tpu.ops import flash_attention as fa
    from cake_tpu.ops import int4_matmul as i4
    from cake_tpu.ops import ragged_paged_attention as rpa
    B, H, KV, hd, P = 2, 4, 2, 16, 8
    pool = jnp.zeros((2, 4, P, KV * hd), jnp.float32)
    table = jnp.zeros((B, 2), jnp.int32)
    pos = jnp.zeros(B, jnp.int32)
    q1 = jnp.zeros((B, 1, H, hd), jnp.float32)
    qc = jnp.zeros((B, 8, H, hd), jnp.float32)
    qs = jnp.zeros((1, 128, H, hd), jnp.float32)
    ks = jnp.zeros((1, 128, KV, hd), jnp.float32)
    g = 32
    packed = i4.pack_int4(jnp.zeros((64, 128), jnp.int8), g)
    return {
        "cake_decode_attn": lambda: rpa.ragged_paged_attention(
            q1, pool, pool, 1, table, pos, interpret=True),
        "cake_mixed_attn": lambda: rpa.ragged_paged_attention_mixed(
            qc, pool, pool, 1, table, pos, jnp.full(B, 8, jnp.int32),
            interpret=True),
        "cake_flash_prefill": lambda: fa.flash_attention(
            qs, ks, ks, interpret=True),
        "cake_flash_prefill_cached": lambda: fa.flash_attention_cached(
            qs, ks, ks, jnp.int32(0), interpret=True),
        "cake_int4_matmul": lambda: i4.int4_matmul(
            jnp.zeros((8, 64), jnp.float32), packed,
            jnp.ones((64 // g, 128), jnp.float32), g=g, interpret=True),
        "cake_moe_gmm": _moe_gmm_call,
        "cake_mla_attn": _mla_calls()[0],
        "cake_mla_window_attn": _mla_calls()[1],
        "cake_mla_decode_attn": _mla_calls()[2],
        "cake_kda_step": _kda_step_call,
        "cake_ssm_step": _ssm_step_call,
    }


def _kda_step_call():
    from cake_tpu.ops import kda
    row = jnp.zeros((2, 2, 8), jnp.float32)
    return kda.step(jnp.zeros((1, 2, 2, 8, 8), jnp.float32), 0,
                    jnp.ones(2, jnp.int32), row, row, row, row,
                    jnp.zeros((2, 2), jnp.float32), interpret=True)


def _ssm_step_call():
    from cake_tpu.ops import ssm
    rows = jnp.zeros((2, 1, 8), jnp.float32)
    heads = jnp.zeros((2, 2), jnp.float32)
    return ssm.step(jnp.zeros((1, 2, 2, 8, 8), jnp.float32), 0,
                    jnp.ones(2, jnp.int32), jnp.zeros((2, 2, 8), jnp.float32),
                    rows, rows, heads, heads, jnp.zeros(2, jnp.float32),
                    interpret=True)


def _mla_calls():
    from cake_tpu.ops import mla_attention as mla
    q = jnp.zeros((8, 4, 24), jnp.float32)
    pool = jnp.zeros((2, 4, 8, 24), jnp.float32)
    return (
        lambda: mla.attend_selected(
            q, jnp.zeros((8, 16, 24), jnp.float32),
            jnp.full(8, 16, jnp.int32), 16, 0.2, impl="pallas",
            interpret=True),
        lambda: mla.attend_window(
            q, pool, jnp.int32(1), jnp.zeros(2, jnp.int32),
            jnp.zeros((8, 16), jnp.float32), jnp.int32(7), 16, 0.2,
            impl="pallas", interpret=True),
        lambda: mla.attend_pages(
            q, pool, jnp.int32(1), jnp.zeros((8, 2), jnp.int32),
            jnp.full(8, 9, jnp.int32), 16, 0.2, impl="pallas",
            interpret=True))


def _moe_gmm_call():
    from cake_tpu.ops import moe
    plan = moe.dispatch_plan(jnp.zeros((16, 2), jnp.int32), 4, tm=16)
    return moe.grouped_matmul(
        jnp.zeros((32, 8), jnp.float32), jnp.zeros((1, 4, 8, 128)),
        jnp.int32(0), plan.visit_tile, plan.visit_expert, plan.visit_lo,
        plan.visit_hi, tm=16, interpret=True)


@pytest.mark.parametrize("name", [
    "cake_decode_attn", "cake_mixed_attn", "cake_flash_prefill",
    "cake_flash_prefill_cached", "cake_int4_matmul", "cake_moe_gmm",
    "cake_mla_attn", "cake_mla_window_attn", "cake_mla_decode_attn",
    "cake_kda_step", "cake_ssm_step"])
def test_pallas_calls_carry_their_names(name):
    """A kernel event is recognised by name, not by the rank of its
    result: every pl.pallas_call in ops/ passes name=."""
    jaxpr = str(jax.make_jaxpr(_kernel_calls()[name])())
    assert "pallas_call" in jaxpr
    assert f"name={name}" in jaxpr, jaxpr[:2000]


def test_every_pallas_call_site_is_named():
    import pathlib
    import re
    ops = pathlib.Path(__file__).resolve().parents[1] / "cake_tpu" / "ops"
    sites = named = 0
    for path in ops.glob("*.py"):
        text = path.read_text()
        for m in re.finditer(r"pl\.pallas_call\(", text):
            sites += 1
            # the call's own argument list, up to the operands' call
            named += 'name="cake_' in text[m.end():m.end() + 1200].split(
                ")(")[0]
    assert sites == named == 15


def test_scopes_leave_the_compiled_program_unchanged(monkeypatch):
    """Scopes are metadata: with them switched off the optimised HLO
    of the paged decode step has the same instructions."""
    import contextlib
    import re
    from jax._src import source_info_util

    def optimised(fn, args):
        text = jax.jit(fn).lower(*args).compile().as_text()
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        ops = [ln.split(" = ", 1)[1] for ln in text.splitlines()
               if " = " in ln]
        return [re.match(r"\S+ ([\w\-]+)\(", op).group(1) for op in ops
                if re.match(r"\S+ ([\w\-]+)\(", op)]

    fn, args = _step_args("decode")
    with_scopes = optimised(fn, args)
    class NoScope(contextlib.ContextDecorator):
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(source_info_util, "extend_name_stack",
                        lambda name: NoScope())
    fn2, args2 = _step_args("decode")
    text = jax.jit(fn2).lower(*args2).as_text(debug_info=True)
    assert "/attn/" not in text and "/layers/" not in text
    without = optimised(fn2, args2)
    assert len(with_scopes) == len(without) > 50
    assert sorted(with_scopes) == sorted(without)
