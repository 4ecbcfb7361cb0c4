"""Args parsing and LlamaConfig loading."""

import json

import pytest

from cake_tpu.args import Args, ModelType, parse_args
from cake_tpu.models.llama.config import LlamaConfig


def test_defaults_match_reference():
    a = Args()
    assert a.seed == 299792458          # lib.rs default
    assert a.sample_len == 100
    # repeat_penalty is a None sentinel so explicit values are
    # distinguishable; the EFFECTIVE default is the reference's 1.1
    assert a.repeat_penalty is None
    assert a.repeat_last_n == 128
    assert a.address == "127.0.0.1:10128"
    assert a.dtype == "bf16"            # TPU-native default (ref uses f16)


def test_repeat_penalty_effective_defaults(tiny_config):
    """Unset --repeat-penalty resolves to 1.1 (the reference's), whatever
    else is asked for; explicit values flow as-is."""
    from cake_tpu.context import Context

    def sampling_for(**kw):
        args = Args(model="", max_seq_len=256, temperature=0.0,
                    flash_attention=False, **kw).validate()
        return Context.from_args(args).load_text_model().sampling

    assert sampling_for().repeat_penalty == 1.1
    assert sampling_for(spec_draft="d", kv_pages=16).repeat_penalty == 1.1
    assert sampling_for(repeat_penalty=1.3).repeat_penalty == 1.3


@pytest.mark.parametrize("gone", [["--draft-model", "x"],
                                  ["--spec-rounds", "2"]],
                         ids=lambda gone: gone[0])
def test_the_dense_speculative_engines_options_are_gone(gone):
    """Speculation is a kind of row of the paged engine (--spec-draft);
    the dense draft-and-verify engine's two options are unknown to the
    parser, like any other word it never heard of."""
    with pytest.raises(SystemExit) as e:
        parse_args(["--model", "/tmp/m", *gone])
    assert e.value.code == 2


def test_parse_args_roundtrip():
    args, sd, img = parse_args([
        "--model", "/tmp/m", "--model-type", "text",
        "--temperature", "0.7", "--top-k", "40",
        "--sd-version", "xl", "--sd-n-steps", "20",
    ])
    assert args.model == "/tmp/m"
    assert args.model_type == ModelType.TEXT
    assert args.temperature == 0.7
    assert args.top_k == 40
    assert sd.sd_version.value == "xl"
    assert img.sd_n_steps == 20


def test_mixed_batch_option_is_gone(capsys):
    """--mixed-batch chose between the mixed step and the paged phase
    loop until PR 28; a paged engine has one prefill path now, and a
    command line that still carries the flag is refused by name."""
    with pytest.raises(SystemExit) as e:
        parse_args(["--model", "/tmp/m", "--kv-pages", "16",
                    "--mixed-batch", "off"])
    assert e.value.code == 2
    assert "unrecognized arguments: --mixed-batch off" \
        in capsys.readouterr().err
    assert not hasattr(Args(), "mixed_batch")


def test_args_validate_dtype():
    with pytest.raises(ValueError):
        Args(dtype="f8").validate()


def test_config_from_hf_json(tmp_path):
    raw = {
        "vocab_size": 128256, "hidden_size": 4096,
        "intermediate_size": 14336, "num_hidden_layers": 32,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "rms_norm_eps": 1e-5, "rope_theta": 500000.0,
        "eos_token_id": [128001, 128009],
    }
    (tmp_path / "config.json").write_text(json.dumps(raw))
    cfg = LlamaConfig.from_path(str(tmp_path))
    assert cfg.head_dim == 128
    assert cfg.eos_token_ids == (128001, 128009)


def test_gqa_fallback():
    # num_key_value_heads defaults to num_attention_heads (config.rs:40-42)
    cfg = LlamaConfig.from_hf_dict({
        "vocab_size": 100, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
    })
    assert cfg.num_key_value_heads == 4
