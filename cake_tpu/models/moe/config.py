"""MoE model hyperparameters (HF Mixtral and OLMoE `config.json` layouts).

Extends LlamaConfig — everything but the FFN is the Llama-family block
(GQA attention, RoPE, RMSNorm). `model_type: "mixtral"` and `"olmoe"`
select this family (models/llama/config.load_config_dict); what tells
them apart is data, not code:

  * mixtral: `num_local_experts`, top-k weights renormalised
    (`norm_topk_prob` true), weights under `block_sparse_moe`;
  * olmoe: `num_experts`, `norm_topk_prob` as published (false), an
    RMSNorm over the whole query and key projections before the split
    into heads (`qk_norm`), weights under `mlp`, the Tülu chat format.
"""

from __future__ import annotations

from dataclasses import dataclass

from cake_tpu.models.llama.config import LlamaConfig


@dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    # divide the k routing weights by their sum (Mixtral) or keep the
    # softmax-over-all-experts probabilities as they are (OLMoE)
    norm_topk_prob: bool = True
    # RMSNorm over the whole q and k projections (leaves q_norm [H*hd],
    # k_norm [KV*hd]), before the split into heads and before RoPE
    qk_norm: bool = False
    # HF weight names: ("block_sparse_moe", w1/w3/w2) or ("mlp",
    # gate_proj/up_proj/down_proj)
    hf_layout: str = "mixtral"

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "MoEConfig":
        base = LlamaConfig.from_hf_dict(raw)
        olmoe = raw.get("model_type") == "olmoe"
        return cls(
            **{f: getattr(base, f) for f in base.__dataclass_fields__},
            num_local_experts=raw.get(
                "num_experts" if olmoe else "num_local_experts",
                64 if olmoe else 8),
            num_experts_per_tok=raw.get("num_experts_per_tok",
                                        8 if olmoe else 2),
            norm_topk_prob=raw.get("norm_topk_prob", not olmoe),
            qk_norm=olmoe,
            hf_layout="olmoe" if olmoe else "mixtral",
        )

    @classmethod
    def tiny(cls, **overrides) -> "MoEConfig":
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, rms_norm_eps=1e-5, rope_theta=10000.0,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(2,), tie_word_embeddings=False,
            num_local_experts=4, num_experts_per_tok=2,
        )
        base.update(overrides)
        return cls(**base)

    @classmethod
    def mixtral_8x7b(cls) -> "MoEConfig":
        return cls(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, rms_norm_eps=1e-5, rope_theta=1e6,
            max_position_embeddings=32768, bos_token_id=1,
            eos_token_ids=(2,), num_local_experts=8, num_experts_per_tok=2,
            chat_template="mistral",
        )

    @classmethod
    def tiny_olmoe(cls, **overrides) -> "MoEConfig":
        """OLMoE's block at a test's size: QK norm on, raw top-k
        probabilities, 8 experts of 64, top-2, 3 layers."""
        base = dict(
            vocab_size=256, hidden_size=128, intermediate_size=64,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, rms_norm_eps=1e-5, rope_theta=10000.0,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(2,), tie_word_embeddings=False,
            num_local_experts=8, num_experts_per_tok=2,
            norm_topk_prob=False, qk_norm=True, hf_layout="olmoe",
            chat_template="tulu",
        )
        base.update(overrides)
        return cls(**base)

    @classmethod
    def olmoe_1b_7b(cls) -> "MoEConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct `config.json`."""
        return cls(
            vocab_size=50304, hidden_size=2048, intermediate_size=1024,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=16, rms_norm_eps=1e-5, rope_theta=10000.0,
            max_position_embeddings=4096, bos_token_id=1,
            eos_token_ids=(50279,), num_local_experts=64,
            num_experts_per_tok=8, norm_topk_prob=False, qk_norm=True,
            hf_layout="olmoe", chat_template="tulu",
        )
