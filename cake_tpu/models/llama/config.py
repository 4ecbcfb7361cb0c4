"""Llama model hyperparameters, deserialised from HF `config.json`.

Reference: `LlamaConfig`/`Config` (cake-core/src/models/llama3/config.rs):
rope_theta defaults to 10k (config.rs:8-10), GQA kv-head fallback to the
full head count (config.rs:40-42). The reference hardcodes
MAX_SEQ_LEN = 4096 (config.rs:6); here the runtime context window is a
separate knob (`Args.max_seq_len`) so long-context serving isn't capped by
a constant.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass, field
from typing import Optional, Tuple


def _read_config(model_dir: str) -> dict:
    with open(os.path.join(model_dir, "config.json")) as f:
        return json.load(f)


# config.json `model_type` -> what the family adds to the Llama block
# (README "Models served"). An absent key is read as "llama".
MODEL_TYPES = {
    "llama": "the block as it is: GQA, RoPE, RMSNorm, SwiGLU",
    "mistral": "a sliding window where the config gives one; [INST] chat",
    "qwen2": "a bias on the q, k and v projections; ChatML",
    "mixtral": "sparse experts, top-k weights renormalised",
    "olmoe": "sparse experts with the raw top-k probabilities, and an "
             "RMSNorm over the query and key projections",
    "glm_moe_dsa": "latent attention over one cache row a token, a learned "
                   "sparse indexer whose key sets layers share, "
                   "sigmoid-routed experts with a shared one (paged engine)",
    "dots3_note": "two kinds of latent attention layer: full layers with "
                  "an indexer each, sliding-window layers with a latent "
                  "geometry of their own in a ring of pages a row; a "
                  "sigmoid gate a head, rescaled latents (paged engine)",
    "deepseek_v2": "latent attention over every visible key (no indexer), "
                   "YaRN on its rope part, softmax routing limited to the "
                   "best groups of experts, two shared experts (paged "
                   "engine)",
    "nemotron_h": "one mixer a block: Mamba-2 with a recurrent state a row "
                  "beside the page pool, relu² experts in a latent with a "
                  "shared one, GQA without positions (paged engine)",
    "zaya": "compressed convolutional attention with a conv tail a row "
            "beside the page pool, half of each head rotated, an MLP "
            "router whose state runs down the layers, one expert a token "
            "(paged engine)",
    "bailing_hybrid": "Kimi Delta Attention (a gated delta rule over a "
                      "matrix state a row and head beside the page pool) "
                      "with gated latent attention every few layers, "
                      "sigmoid routing with a choice bias limited to "
                      "groups by their two best (paged engine)",
    "exaone_moe": "GQA in two kinds of layer: sliding-window layers that "
                  "rotate and keep their K/V in a ring of pages a row, "
                  "full layers with no positions on the allocator's "
                  "pages; q and k normed a head; sigmoid-routed experts "
                  "with a shared one (paged engine)",
    "granitemoehybrid": "a mixer AND a dense SwiGLU a layer, each branch "
                        "scaled into the stream: Mamba-2 with a recurrent "
                        "state a row beside the page pool, or GQA without "
                        "positions at a softmax scale of its own; embedding, "
                        "residual and logit multipliers; a tied head (paged "
                        "engine)",
    "KeyeVL2": "Keye-VL-2.0's language model alone: GQA with q and k "
               "normed a head, a learned sparse indexer that selects the "
               "keys a query attends over ordinary K/V pages (its keys in "
               "a third pool over the same page table), softmax-routed "
               "experts, none shared (paged engine)",
    "brumby": "power retention of degree 2 in every layer: q and k normed "
              "a head and rotated, a gate a K/V head, a matrix state a row "
              "and layer that a GQA group shares beside a page pool of NO "
              "layers; a dense SwiGLU, an untied head (paged engine)",
    "longcat_flash": "shortcut-connected layers: two latent attentions "
                     "over every visible key and two dense SwiGLUs a "
                     "layer, and one routed MoE that reads the first "
                     "FFN's input and returns after the second; identity "
                     "(zero-compute) experts in the router's width, a "
                     "choice bias, weights not renormalised (paged "
                     "engine)",
}
_MOE_TYPES = ("mixtral", "olmoe")


def load_config_dict(raw: dict) -> "LlamaConfig":
    """Dispatch a parsed config.json on `model_type`: "mixtral" and
    "olmoe" -> MoEConfig (sparse experts), the dense names and an
    absent key -> LlamaConfig. A name this program has no block for is
    refused: served as a Llama it would answer, wrongly, under that
    model's name."""
    model_type = raw.get("model_type", "llama")
    if model_type not in MODEL_TYPES:
        raise ValueError(
            f"unknown model_type {model_type!r} in config.json: this "
            "program serves " + ", ".join(MODEL_TYPES))
    if model_type == "glm_moe_dsa":
        from cake_tpu.models.moe.config import GlmMoeDsaConfig
        return GlmMoeDsaConfig.from_hf_dict(raw)
    if model_type == "dots3_note":
        from cake_tpu.models.moe.config import Dots3NoteConfig
        return Dots3NoteConfig.from_hf_dict(raw)
    if model_type == "deepseek_v2":
        from cake_tpu.models.moe.config import DeepseekV2Config
        return DeepseekV2Config.from_hf_dict(raw)
    if model_type == "nemotron_h":
        from cake_tpu.models.moe.config import NemotronHConfig
        return NemotronHConfig.from_hf_dict(raw)
    if model_type == "zaya":
        from cake_tpu.models.moe.config import ZayaConfig
        return ZayaConfig.from_hf_dict(raw)
    if model_type == "bailing_hybrid":
        from cake_tpu.models.moe.config import BailingHybridConfig
        return BailingHybridConfig.from_hf_dict(raw)
    if model_type == "exaone_moe":
        from cake_tpu.models.moe.config import ExaoneMoeConfig
        return ExaoneMoeConfig.from_hf_dict(raw)
    if model_type == "granitemoehybrid":
        from cake_tpu.models.moe.config import GraniteHybridConfig
        return GraniteHybridConfig.from_hf_dict(raw)
    if model_type == "KeyeVL2":
        from cake_tpu.models.moe.config import KeyeVL2Config
        return KeyeVL2Config.from_hf_dict(raw)
    if model_type == "brumby":
        from cake_tpu.models.moe.config import BrumbyConfig
        return BrumbyConfig.from_hf_dict(raw)
    if model_type == "longcat_flash":
        from cake_tpu.models.moe.config import LongcatFlashConfig
        return LongcatFlashConfig.from_hf_dict(raw)
    if model_type in _MOE_TYPES:
        from cake_tpu.models.moe import MoEConfig
        return MoEConfig.from_hf_dict(raw)
    return LlamaConfig.from_hf_dict(raw)


def load_config(model_dir: str) -> "LlamaConfig":
    """Load `<model_dir>/config.json` with model_type dispatch — the single
    entry point every config.json consumer should use."""
    return load_config_dict(_read_config(model_dir))


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 8192
    bos_token_id: int = 128000
    eos_token_ids: Tuple[int, ...] = (128001, 128009)
    tie_word_embeddings: bool = False
    # Sliding-window attention (Mistral-family, HF "sliding_window"):
    # each query attends at most this many most-recent positions. None =
    # full causal attention (Llama). The KV cache stays full-length
    # (correct; a ring buffer is a memory optimization, not semantics).
    sliding_window: Optional[int] = None
    # prompt template for the chat paths (models/chat.TEMPLATES);
    # from_hf_dict sets "mistral" for model_type mistral/mixtral,
    # "chatml" for qwen2 and "tulu" for olmoe
    chat_template: str = "llama3"
    # QKV projection bias (Qwen2-family; HF "attention_bias" / implied by
    # model_type qwen2) — adds bq/bk/bv leaves to every block
    attention_bias: bool = False
    # Use the Pallas flash-attention kernel for prefill windows whose shapes
    # tile (ops/flash_attention.py). Off by default so CPU test runs don't
    # pay interpret-mode cost; the TPU Context enables it.
    use_flash_attention: bool = False

    # what the paged engine reads of this family of models
    # (models/family.Family), as "module:NAME"; a config class of a
    # family with a trunk of its own names its own
    _family = "cake_tpu.models.llama.paged:FAMILY"

    @property
    def family(self):
        module, name = self._family.split(":")
        return getattr(importlib.import_module(module), name)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rope_dim(self) -> int:
        """Width of the rotated part of a query or key head (the RoPE
        tables' width): the whole head but for latent attention."""
        return self.head_dim

    @property
    def is_moe(self) -> bool:
        """Single source of truth for family dispatch (MoEConfig carries
        num_local_experts; dense configs don't)."""
        return bool(getattr(self, "num_local_experts", 0))

    @classmethod
    def from_path(cls, model_dir: str) -> "LlamaConfig":
        """Load from `<model_dir>/config.json` (reference config.rs:30-37),
        dispatching on model_type — a Mixtral checkpoint yields MoEConfig.
        Called on a subclass, that subclass is guaranteed (so e.g.
        MoEConfig.from_path on a checkpoint without model_type still reads
        the expert fields)."""
        raw = _read_config(model_dir)
        cfg = load_config_dict(raw)
        return cfg if isinstance(cfg, cls) else cls.from_hf_dict(raw)

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "LlamaConfig":
        eos = raw.get("eos_token_id", 128001)
        if isinstance(eos, int):
            eos = (eos,)
        else:
            eos = tuple(eos)
        return cls(
            vocab_size=raw["vocab_size"],
            hidden_size=raw["hidden_size"],
            intermediate_size=raw["intermediate_size"],
            num_hidden_layers=raw["num_hidden_layers"],
            num_attention_heads=raw["num_attention_heads"],
            num_key_value_heads=raw.get(
                "num_key_value_heads", raw["num_attention_heads"]
            ),
            rms_norm_eps=raw.get("rms_norm_eps", 1e-5),
            rope_theta=raw.get("rope_theta", 10000.0),
            max_position_embeddings=raw.get("max_position_embeddings", 8192),
            bos_token_id=raw.get("bos_token_id", 128000),
            eos_token_ids=eos,
            tie_word_embeddings=raw.get("tie_word_embeddings", False),
            # Qwen2/2.5 checkpoints ship sliding_window alongside
            # use_sliding_window: false (full attention) — honor the gate
            sliding_window=(raw.get("sliding_window")
                            if raw.get("use_sliding_window", True)
                            else None),
            # Mixtral shares Mistral's [INST] instruct format and
            # SentencePiece vocab — Llama-3 header tokens don't exist
            # there; Qwen2 uses ChatML
            chat_template={"mistral": "mistral", "mixtral": "mistral",
                           "qwen2": "chatml", "olmoe": "tulu",
                           "glm_moe_dsa": "chatml",
                           "dots3_note": "chatml",
                           "deepseek_v2": "chatml",
                           "nemotron_h": "chatml", "zaya": "chatml"}.get(
                               raw.get("model_type", ""), "llama3"),
            attention_bias=raw.get("attention_bias",
                                   raw.get("model_type") == "qwen2"),
        )

    @classmethod
    def tiny(cls, **overrides) -> "LlamaConfig":
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, rms_norm_eps=1e-5, rope_theta=10000.0,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(2,), tie_word_embeddings=False,
        )
        base.update(overrides)
        return cls(**base)

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, rms_norm_eps=1e-5, rope_theta=500000.0,
            max_position_embeddings=8192,
        )

    @classmethod
    def mistral_7b(cls) -> "LlamaConfig":
        """Mistral-7B-v0.1: Llama architecture + 4096-token sliding
        window (HF mistralai/Mistral-7B-v0.1 config.json; weight names
        are identical, so loading/sharding/quantization all apply)."""
        return cls(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, rms_norm_eps=1e-5, rope_theta=10000.0,
            max_position_embeddings=32768, bos_token_id=1,
            eos_token_ids=(2,), sliding_window=4096,
            chat_template="mistral",
        )

    @classmethod
    def qwen2_7b(cls) -> "LlamaConfig":
        """Qwen2-7B-Instruct: Llama architecture + QKV bias + ChatML
        (HF Qwen/Qwen2-7B-Instruct config.json)."""
        return cls(
            vocab_size=152064, hidden_size=3584, intermediate_size=18944,
            num_hidden_layers=28, num_attention_heads=28,
            num_key_value_heads=4, rms_norm_eps=1e-6, rope_theta=1e6,
            max_position_embeddings=32768, bos_token_id=151643,
            eos_token_ids=(151645, 151643), attention_bias=True,
            chat_template="chatml",
        )

    @classmethod
    def llama3_70b(cls) -> "LlamaConfig":
        return cls(
            vocab_size=128256, hidden_size=8192, intermediate_size=28672,
            num_hidden_layers=80, num_attention_heads=64,
            num_key_value_heads=8, rms_norm_eps=1e-5, rope_theta=500000.0,
            max_position_embeddings=8192,
        )
