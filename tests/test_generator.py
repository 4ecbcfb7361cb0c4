"""Generator + Master: streaming loop, EOS, reset, on-device scan parity."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.models.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import (
    ByteTokenizer, LlamaGenerator, bucket_length, trim_at_eos,
)
from cake_tpu.models.llama.params import init_params
from cake_tpu.ops.sampling import SamplingConfig


@pytest.fixture(scope="module")
def gen():
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    g = LlamaGenerator(
        cfg, params, ByteTokenizer(cfg.vocab_size),
        max_seq_len=256, sampling=SamplingConfig(temperature=0.0),
        cache_dtype=jnp.float32,
    )
    return g


def test_bucket_length():
    assert bucket_length(5, 4096) == 32
    assert bucket_length(33, 4096) == 64
    assert bucket_length(5000, 4096) == 4096


def test_streaming_generation(gen):
    gen.reset()
    gen.add_message(Message.system("s"))
    gen.add_message(Message.user("hello"))
    toks = [gen.next_token(i) for i in range(8)]
    assert gen.generated_tokens() == 8
    assert all(t.id >= 0 for t in toks)
    # greedy determinism across reset
    ids1 = [t.id for t in toks]
    gen.reset()
    gen.add_message(Message.system("s"))
    gen.add_message(Message.user("hello"))
    ids2 = [gen.next_token(i).id for i in range(8)]
    assert ids1 == ids2


def test_eos_detection():
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    g = LlamaGenerator(cfg, params, ByteTokenizer(cfg.vocab_size),
                       max_seq_len=256, sampling=SamplingConfig(temperature=0.0),
                       cache_dtype=jnp.float32)
    g.add_message(Message.user("x"))
    for i in range(100):
        t = g.next_token(i)
        if t.is_end_of_stream:
            assert t.id in cfg.eos_token_ids
            assert t.text == ""
            break


@pytest.mark.parametrize("end", ["budget", "eos"])
def test_token_texts_are_the_decode_of_the_ids(gen, end):
    """The texts of a session's tokens concatenate to the decode of its
    ids (PR 47: `StreamDetokenizer`, fed the new id only): short of an
    incomplete tail while the stream runs, whole once EOS flushed it,
    and EOS itself never reaches the tokenizer."""
    def session(g, n):
        g.reset()
        g.add_message(Message.user("hello"))
        return [g.next_token(i) for i in range(n)]

    toks = session(gen, 24)
    ids = [t.id for t in toks]
    whole = gen.tokenizer.decode(ids)
    text = "".join(t.text for t in toks)
    if end == "budget":
        assert whole.startswith(text) and text
        assert not any(t.text.endswith("\ufffd") for t in toks)
        assert not whole[len(text):].strip("\ufffd")
        # a reset starts the text again
        assert "".join(t.text for t in session(gen, 24)) == text
        return
    # the same stream with its 9th distinct-so-far token as EOS
    eos = next(t for i, t in enumerate(ids) if i >= 8 and t not in ids[:i])
    cfg = LlamaConfig.tiny(num_hidden_layers=2, eos_token_ids=(eos,))
    seen = []

    class Spy(ByteTokenizer):
        def decode(self, ids):
            seen.append(list(ids))
            return super().decode(ids)

    g = LlamaGenerator(cfg, gen.params, Spy(cfg.vocab_size),
                       max_seq_len=256, cache_dtype=jnp.float32,
                       sampling=SamplingConfig(temperature=0.0))
    toks = session(g, ids.index(eos) + 1)
    assert [t.id for t in toks] == ids[:ids.index(eos) + 1]
    assert toks[-1].is_end_of_stream
    assert "".join(t.text for t in toks) == gen.tokenizer.decode(
        ids[:ids.index(eos)])
    assert seen and not any(eos in s for s in seen)


def test_prompt_too_long_raises(gen):
    gen.reset()
    gen.add_message(Message.user("y" * 500))
    with pytest.raises(ValueError, match="exceeds limit"):
        gen.next_token(0)
    gen.reset()


def test_on_device_scan_matches_host_loop(gen):
    gen.reset()
    gen.add_message(Message.user("abc"))
    host_ids = [gen.next_token(i).id for i in range(6)]

    gen.reset()
    gen.add_message(Message.user("abc"))
    ids = gen._encode_prompt()
    padded = ids + [0] * (32 - len(ids))
    out = gen.generate_on_device(
        np.asarray([padded], np.int32), np.asarray([len(ids)]), 6
    )
    assert out.shape == (1, 6)
    assert out[0].tolist() == host_ids
    gen.reset()


def test_trim_at_eos():
    ids = np.asarray([[4, 5, 2, 9], [7, 7, 7, 7]])
    assert trim_at_eos(ids, (2,)) == [[4, 5], [7, 7, 7, 7]]


def test_master_generate_text():
    from cake_tpu.args import Args
    from cake_tpu.master import Master
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    g = LlamaGenerator(cfg, params, ByteTokenizer(cfg.vocab_size),
                       max_seq_len=256, sampling=SamplingConfig(temperature=0.0),
                       cache_dtype=jnp.float32)
    m = Master(Args(sample_len=5), text_generator=g)
    m.add_message(Message.system("s"))
    m.add_message(Message.user("hi"))
    seen = []
    text = m.generate_text(lambda t: seen.append(t))
    assert len(seen) <= 5
    assert m.tokens_per_s >= 0.0
    assert isinstance(text, str)


def test_prefill_chunk_must_divide_max_seq(tiny_config, tiny_params):
    """A padded final chunk window must stay inside the cache —
    dynamic_update_slice clamps out-of-range starts and would silently
    corrupt live entries, so the constraint is enforced at construction."""
    from cake_tpu.models.llama.generator import ByteTokenizer, LlamaGenerator

    with pytest.raises(ValueError, match="prefill_chunk"):
        LlamaGenerator(tiny_config, tiny_params,
                       ByteTokenizer(tiny_config.vocab_size),
                       max_seq_len=250, prefill_chunk=64)


@pytest.mark.parametrize("kv", ["f8_e4m3", "f8_e5m2"])
def test_fp8_kv_cache_generates(kv):
    """fp8 KV storage (--kv-dtype): values upcast into attention on read;
    generation stays finite and deterministic, and the cache really is
    1 byte/element."""
    from cake_tpu.utils.devices import resolve_kv_dtype

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    dt = resolve_kv_dtype(kv)
    g = LlamaGenerator(
        cfg, params, ByteTokenizer(cfg.vocab_size), max_seq_len=256,
        sampling=SamplingConfig(temperature=0.0), cache_dtype=dt)
    assert g.cache.k.dtype == dt
    assert g.cache.k.dtype.itemsize == 1
    g.add_message(Message.user("hello"))
    ids1 = [g.next_token(i).id for i in range(6)]
    g.reset()
    g.add_message(Message.user("hello"))
    ids2 = [g.next_token(i).id for i in range(6)]
    assert ids1 == ids2
    assert all(i >= 0 for i in ids1)


def test_fp8_kv_close_to_f32_kv():
    """Tiny-model sanity: fp8-stored KV produces logits close to the f32
    cache (per-step quantization error only, no accumulation blowup)."""
    from cake_tpu.models.llama.cache import KVCache
    from cake_tpu.models.llama.model import RopeTables, prefill

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rope = RopeTables.create(cfg, 64)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 3,
                              cfg.vocab_size)
    plen = jnp.full((1,), 16, jnp.int32)

    lo, _ = prefill(params, toks, plen,
                    KVCache.create(cfg, 1, 64, dtype=jnp.float32),
                    rope, cfg)
    l8, _ = prefill(params, toks, plen,
                    KVCache.create(cfg, 1, 64, dtype=jnp.float8_e4m3fn),
                    rope, cfg)
    # prefill attends the freshly-written (quantized) cache entries, so
    # differences are bounded by fp8 resolution on k/v
    np.testing.assert_allclose(np.asarray(l8), np.asarray(lo),
                               atol=0.5, rtol=0.2)
