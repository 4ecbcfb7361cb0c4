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
from typing import NamedTuple, Optional, Tuple

from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.ops.rope import Yarn


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

    _family = "cake_tpu.models.llama.paged:SPARSE"

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


def _stored_row(width: int) -> int:
    """The width a latent row of `width` numbers is STORED at: padded
    with zeros to whole 128-lane tiles (576 -> 640, 1,088 -> 1,152).
    The TPU keeps an array whose minor dimension is not a multiple of
    128 in a transposed tiled layout, and a step program converted the
    whole pool on the way in and on the way out (two copies of 15 ms
    each at 9 x 800 x 128 x 576; compiler, PR 30). A test-sized latent
    (under one tile) is stored as it is."""
    return width if width <= 128 else -(-width // 128) * 128


class LatentGeometry(NamedTuple):
    """The sizes of ONE kind of latent attention layer: what
    models/moe/glm_dsa's trunk reads in place of the config, so that a
    model may have two (dots3_note: full layers and sliding-window
    layers, each with its own heads, ranks, head dims and theta).

    scope: the prefix of the layer's named scopes and kernel names
    ("mla" | "swa"); rope: the RopeTables fields the layer rotates by;
    q_scale / kv_scale: what the normed query and kv latents are
    multiplied by (1.0 = not at all); gated: each head's output is
    multiplied by a sigmoid of the layer's normed input (the leaf
    `w_attn_gate` [D, heads]); window: keys a query attends, its own
    included (None = a full layer: the indexer's selection over
    everything visible, or every visible key where the layer has no
    indexer)."""

    scope: str
    heads: int
    # None: a full-rank query projection (leaf `wq`; no `wq_a`,
    # `q_a_norm`, `wq_b`)
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    row: int
    rope: Tuple[str, str] = ("cos", "sin")
    q_scale: float = 1.0
    kv_scale: float = 1.0
    gated: bool = False
    window: Optional[int] = None
    # what head_dim^-0.5 is multiplied by (YaRN's mscale^2; 1.0 = plain)
    scale_factor: float = 1.0

    @property
    def softmax_scale(self) -> float:
        return ((self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
                * self.scale_factor)


@dataclass(frozen=True)
class GlmMoeDsaConfig(MoEConfig):
    """GLM-5.2 (`model_type: glm_moe_dsa`): latent attention (MLA), the
    learned sparse indexer (DSA) whose key sets a "full" layer computes
    and the "shared" layers after it reuse, leading dense layers, then
    sigmoid-routed experts with a shared one. The equations are in
    models/reference/glm_moe_dsa.py; the served path in
    models/moe/glm_dsa.py.

    `intermediate_size` is the dense layers' FFN width,
    `moe_intermediate_size` an expert's. `num_local_experts` counts the
    routed experts HELD here (config.json `n_routed_experts`);
    `n_routed_experts_total` is the router's width (the published
    count, config.json key of the same name, absent = all held) and
    `first_routed_expert` the first held expert's index: one chip's
    share of an expert-parallel deployment routes over all of them and
    computes its own (ops/moe.moe_mlp)."""

    _family = "cake_tpu.models.moe.glm_dsa:FAMILY"

    # None: a full-rank query (bailing_hybrid)
    q_lora_rank: Optional[int] = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    # per layer: "dense" | "sparse", and "full" | "shared" (Dots3Note-
    # Config: "full" | "sliding"; DeepseekV2Config: "dense", no indexer)
    mlp_layer_types: Tuple[str, ...] = ()
    indexer_types: Tuple[str, ...] = ()
    moe_intermediate_size: int = 2048
    n_routed_experts_total: int = 256
    first_routed_expert: int = 0
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    # group-limited routing (ops/moe.choose; 1 / 1 = not at all: the
    # parser here refuses anything else, DeepseekV2Config's takes it)
    n_group: int = 1
    topk_group: int = 1
    # a group's score: the sum of its `group_top` best scores (1: its
    # best, DeepseekV2Config's; 2: BailingHybridConfig's)
    group_top: int = 1

    @property
    def rope_dim(self) -> int:
        return self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A latent pool row: the normed c_kv and the rotated shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The width a latent row is STORED at (_stored_row)."""
        return _stored_row(self.latent_width)

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.indexer_types)
                     if t == "full")

    @property
    def sliding_layers(self) -> Tuple[int, ...]:
        """Layers that attend a window of their own latent pool (none
        in this family: Dots3NoteConfig)."""
        return tuple(i for i, t in enumerate(self.indexer_types)
                     if t == "sliding")

    @property
    def latent_layers(self) -> Tuple[int, ...]:
        """Layers whose rows lie in the latent pool the page table
        maps: every layer that is not a sliding one."""
        return tuple(i for i, t in enumerate(self.indexer_types)
                     if t != "sliding")

    def geometry(self, layer: int) -> LatentGeometry:
        """The sizes of `layer`'s latent attention."""
        return LatentGeometry(
            "mla", self.num_attention_heads, self.q_lora_rank,
            self.kv_lora_rank, self.qk_nope_head_dim,
            self.qk_rope_head_dim, self.v_head_dim, self.latent_row)

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.mlp_layer_types)
                     if t == "sparse")

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "GlmMoeDsaConfig":
        L = raw["num_hidden_layers"]
        rope = raw.get("rope_parameters") or {}
        base = LlamaConfig.from_hf_dict(dict(
            raw, rope_theta=rope.get("rope_theta",
                                     raw.get("rope_theta", 10000.0))))
        dense = raw.get("first_k_dense_replace", 0)
        mlp = tuple(raw.get("mlp_layer_types")
                    or ["dense"] * dense + ["sparse"] * (L - dense))
        freq = raw.get("index_topk_freq", 1)
        idx = tuple(raw.get("indexer_types")
                    or ["full" if i < dense or (i - dense) % freq == freq - 1
                        else "shared" for i in range(L)])
        if len(mlp) != L or len(idx) != L:
            raise ValueError(
                f"mlp_layer_types ({len(mlp)}) and indexer_types "
                f"({len(idx)}) must name num_hidden_layers = {L} layers")
        if idx[0] != "full":
            raise ValueError("indexer_types must start with a full layer: "
                             "a shared layer reuses the set below it")
        for name in ("n_group", "topk_group"):
            if raw.get(name, 1) != 1:
                raise ValueError(f"{name} = {raw[name]}: group-limited "
                                 "routing is not implemented")
        if raw.get("n_shared_experts", 1) != 1:
            raise ValueError("n_shared_experts must be 1")
        if raw.get("rope_scaling"):
            raise ValueError(
                "rope_scaling is not implemented for this model_type "
                "(deepseek_v2 takes type yarn)")
        if raw.get("num_nextn_predict_layers", 0):
            raise ValueError(
                "num_nextn_predict_layers > 0: the multi-token-prediction "
                "module is not served (it drafts for speculation and adds "
                "nothing to the next-token logits); set it to 0")
        held = raw["n_routed_experts"]
        total = raw.get("n_routed_experts_total", held)
        first = raw.get("first_routed_expert", 0)
        if not 0 <= first <= total - held:
            raise ValueError(
                f"experts {first}..{first + held - 1} are not among the "
                f"router's {total}")
        return cls(
            **{f: getattr(base, f) for f in base.__dataclass_fields__},
            num_local_experts=held,
            num_experts_per_tok=raw["num_experts_per_tok"],
            norm_topk_prob=raw.get("norm_topk_prob", True),
            hf_layout="glm_moe_dsa",
            q_lora_rank=raw["q_lora_rank"],
            kv_lora_rank=raw["kv_lora_rank"],
            qk_nope_head_dim=raw["qk_nope_head_dim"],
            qk_rope_head_dim=raw["qk_rope_head_dim"],
            v_head_dim=raw["v_head_dim"],
            index_n_heads=raw["index_n_heads"],
            index_head_dim=raw["index_head_dim"],
            index_topk=raw["index_topk"],
            mlp_layer_types=mlp, indexer_types=idx,
            moe_intermediate_size=raw["moe_intermediate_size"],
            n_routed_experts_total=total, first_routed_expert=first,
            routed_scaling_factor=raw.get("routed_scaling_factor", 1.0),
            scoring_func=raw.get("scoring_func", "sigmoid"),
        )

    @classmethod
    def tiny_glm(cls, **overrides) -> "GlmMoeDsaConfig":
        """GLM-5.2's block at a test's size: one dense layer with a full
        indexer, then sparse layers shared x2, full, shared; index_topk
        8, so that a context of a few dozen tokens passes it several
        times over; 8 routed experts, all held."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_hidden_layers=5, num_attention_heads=4,
            num_key_value_heads=4, rms_norm_eps=1e-5, rope_theta=10000.0,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(256,), tie_word_embeddings=False,
            chat_template="chatml",
            num_local_experts=8, num_experts_per_tok=2,
            norm_topk_prob=True, hf_layout="glm_moe_dsa",
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2,
            index_head_dim=16, index_topk=8,
            mlp_layer_types=("dense",) + ("sparse",) * 4,
            indexer_types=("full", "shared", "shared", "full", "shared"),
            moe_intermediate_size=32, n_routed_experts_total=8,
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class Dots3NoteConfig(GlmMoeDsaConfig):
    """dots3-note (`model_type: dots3_note`): TWO kinds of latent
    attention layer in one model, by `layer_types`. A `full_attention`
    layer is GlmMoeDsaConfig's "full" layer (MLA over the keys its OWN
    indexer selects: nothing is shared); a `sliding_attention` layer is
    latent attention with its own geometry (the `swa_*` keys) over the
    last `sliding_window_size` keys, the query's own included, and no
    indexer. Both gate each head's output by a sigmoid of the layer's
    normed input and multiply the two normed latents by
    sqrt(hidden / rank): the published config says so
    (`attention_gate_type`, `apply_mla_qkv_lora_rescale`) and a config
    that says otherwise is refused. The FFNs are
    GlmMoeDsaConfig's. The equations are in
    models/reference/dots3_note.py; the served path is
    models/moe/glm_dsa.py's trunk with a geometry per kind of layer,
    and the sliding layers' rows lie in a pool of their own, a ring of
    `window_ring_pages` pages a row (models/llama/paged.WindowedPagedCache).

    `indexer_types` holds "full" | "sliding" here (from `layer_types`)."""

    _family = "cake_tpu.models.moe.glm_dsa:WINDOWED"

    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    sliding_window_size: int = 513

    @property
    def swa_latent_row(self) -> int:
        """A sliding layer's stored row: its normed c_kv and rotated
        shared key, padded as latent_row is (1,088 -> 1,152)."""
        return _stored_row(self.swa_kv_lora_rank + self.swa_qk_rope_head_dim)

    def geometry(self, layer: int) -> LatentGeometry:
        def scale(rank: int) -> float:
            return (self.hidden_size / rank) ** 0.5

        if self.indexer_types[layer] == "sliding":
            return LatentGeometry(
                "swa", self.swa_num_attention_heads, self.swa_q_lora_rank,
                self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                self.swa_latent_row, ("swa_cos", "swa_sin"),
                scale(self.swa_q_lora_rank), scale(self.swa_kv_lora_rank),
                True, self.sliding_window_size)
        return super().geometry(layer)._replace(
            q_scale=scale(self.q_lora_rank),
            kv_scale=scale(self.kv_lora_rank), gated=True)

    def window_ring_pages(self, page_size: int, width: int) -> int:
        """R: the pages of the sliding layers' pool a ROW holds, whatever
        its context: logical page j of the row lies in its physical page
        j mod R. A dispatch writes at most `width` tokens of a row (a
        prompt's window) before its queries attend, and the first of
        them reaches sliding_window_size - 1 keys back, so the keys a
        dispatch needs span at most (sliding_window_size - 1) + width
        positions: ceil of that over the page, plus one for a span that
        starts inside a page. The write of logical page p lands on
        page p - R's place, and p - R is below every page the span
        touches (models/llama/paged.ring_holds states the inequality)."""
        return -(-(self.sliding_window_size - 1 + width) // page_size) + 1

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "Dots3NoteConfig":
        L = raw["num_hidden_layers"]
        kinds = {"full_attention": "full", "sliding_attention": "sliding"}
        types = raw.get("layer_types") or ["full_attention"] * L
        if len(types) != L or set(types) - set(kinds):
            raise ValueError(
                f"layer_types must name num_hidden_layers = {L} layers, "
                "each full_attention or sliding_attention; got "
                + ", ".join(sorted(set(types))))
        for name in ("index_topk_freq", "indexer_types"):
            if raw.get(name) not in (None, 1):
                raise ValueError(
                    f"{name}: every full_attention layer of model_type "
                    "dots3_note computes its own key sets (layer_types "
                    "says which layers those are)")
        for name in ("attention_gate_type", "swa_attention_gate_type"):
            if raw.get(name) != "headwise":
                raise ValueError(f"{name} = {raw.get(name)!r}: model_type "
                                 "dots3_note is served with a 'headwise' "
                                 "gate in both kinds of layer")
        if raw.get("apply_mla_qkv_lora_rescale") is not True:
            raise ValueError(
                "apply_mla_qkv_lora_rescale = "
                f"{raw.get('apply_mla_qkv_lora_rescale')!r}: model_type "
                "dots3_note is served with the rescale of the two normed "
                "latents (true)")
        for name in ("vision_config", "audio_config"):
            if raw.get(name):
                raise ValueError(
                    f"{name}: the vision and audio towers are not served "
                    "(the language model alone); take it out")
        if raw.get("rope_scaling"):
            raise ValueError("rope_scaling is not implemented")
        if raw.get("attention_bias", False):
            raise ValueError("attention_bias = true: projection biases "
                             "are not implemented")
        if raw.get("moe_layer_freq", 1) != 1:
            raise ValueError("moe_layer_freq must be 1")
        if raw.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act = {raw['hidden_act']!r}: only "
                             "'silu' is implemented")
        for name, full in (("swa_num_key_value_heads",
                            "swa_num_attention_heads"),
                           ("num_key_value_heads", "num_attention_heads")):
            if raw.get(name, raw[full]) != raw[full]:
                raise ValueError(f"{name} must equal {full}: latent "
                                 "attention has a key a head")
        if raw["sliding_window_size"] < 1:
            raise ValueError("sliding_window_size must be at least 1")
        # the refusals GLM's parser makes (n_group, shared experts, MTP,
        # the held experts' range) and its fields
        base = GlmMoeDsaConfig.from_hf_dict(dict(
            raw, indexer_types=["full"] * L, index_topk_freq=1))
        fields = {f: getattr(base, f) for f in base.__dataclass_fields__}
        fields.update(
            hf_layout="dots3_note", chat_template="chatml",
            sliding_window=None,
            indexer_types=tuple(kinds[t] for t in types),
            swa_num_attention_heads=raw["swa_num_attention_heads"],
            swa_q_lora_rank=raw["swa_q_lora_rank"],
            swa_kv_lora_rank=raw["swa_kv_lora_rank"],
            swa_qk_nope_head_dim=raw["swa_qk_nope_head_dim"],
            swa_qk_rope_head_dim=raw["swa_qk_rope_head_dim"],
            swa_v_head_dim=raw["swa_v_head_dim"],
            swa_rope_theta=raw.get("swa_rope_theta", base.rope_theta),
            sliding_window_size=raw["sliding_window_size"])
        return cls(**fields)

    @classmethod
    def tiny_dots3(cls, **overrides) -> "Dots3NoteConfig":
        """dots3-note's layers at a test's size: a dense full layer,
        then the published period (sliding x3, full) and the start of
        another; window 6, index_topk 8, so that a context of a few
        dozen tokens passes both several times over; two geometries
        that differ in every size; 8 routed experts, all held."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_hidden_layers=6, num_attention_heads=4,
            num_key_value_heads=4, rms_norm_eps=1e-5, rope_theta=8e7,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(256,), tie_word_embeddings=False,
            chat_template="chatml",
            num_local_experts=8, num_experts_per_tok=2,
            norm_topk_prob=True, hf_layout="dots3_note",
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2,
            index_head_dim=16, index_topk=8,
            mlp_layer_types=("dense",) + ("sparse",) * 5,
            indexer_types=("full", "sliding", "sliding", "sliding", "full",
                           "sliding"),
            moe_intermediate_size=32, n_routed_experts_total=8,
            routed_scaling_factor=1.0,
            swa_num_attention_heads=2, swa_q_lora_rank=24,
            swa_kv_lora_rank=24, swa_qk_nope_head_dim=24,
            swa_qk_rope_head_dim=4, swa_v_head_dim=8,
            swa_rope_theta=5e4, sliding_window_size=6,
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class DeepseekV2Config(GlmMoeDsaConfig):
    """DeepSeek-V2 (`model_type: deepseek_v2`): latent attention over
    EVERY visible key (no indexer: `indexer_types` holds "dense" in every
    layer, the latent pool has no index-key pool beside it), YaRN on the
    rope part (`rope_scaling`: the frequencies in the RoPE tables, the
    softmax scale times mscale^2 in the layer's LatentGeometry), and
    group-limited routing: softmax over all the router's experts, the
    `topk_group` best of `n_group` groups by each group's best score,
    the top k inside them, not renormalised, times
    `routed_scaling_factor` (ops/moe.choose); the `n_shared_experts`
    shared experts are one MLP of n_shared_experts *
    moe_intermediate_size. With a layer shared by n_group chips one
    group is one chip's experts (device-limited routing), so
    `first_routed_expert .. + n_routed_experts - 1` is a group here. The
    equations are in models/reference/deepseek_v2.py; the served path is
    models/moe/glm_dsa.py's trunk, its dense kind of layer."""

    _family = "cake_tpu.models.moe.glm_dsa:DENSE"

    n_group: int = 8
    topk_group: int = 3
    # config.json rope_scaling of type yarn (ops/rope.Yarn), or None
    rope_scaling: Optional[Yarn] = None

    def geometry(self, layer: int) -> LatentGeometry:
        factor = (self.rope_scaling.softmax_factor if self.rope_scaling
                  else 1.0)
        return super().geometry(layer)._replace(scale_factor=factor)

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "DeepseekV2Config":
        L = raw["num_hidden_layers"]
        for name in ("index_topk", "index_n_heads", "index_head_dim",
                     "indexer_types", "index_topk_freq"):
            if raw.get(name) is not None:
                raise ValueError(
                    f"{name}: model_type deepseek_v2 attends every visible "
                    "key; a model with a sparse indexer is glm_moe_dsa")
        method = raw.get("topk_method", "group_limited_greedy")
        if method != "group_limited_greedy":
            raise ValueError(f"topk_method = {method!r}: only "
                             "'group_limited_greedy' is implemented")
        if raw.get("scoring_func", "softmax") != "softmax":
            raise ValueError(f"scoring_func = {raw['scoring_func']!r}: "
                             "model_type deepseek_v2 scores by softmax")
        n_group, topk_group = raw.get("n_group", 1), raw.get("topk_group", 1)
        total = raw.get("n_routed_experts_total", raw["n_routed_experts"])
        if total % n_group or not 1 <= topk_group <= n_group:
            raise ValueError(
                f"n_group = {n_group}, topk_group = {topk_group}: the "
                f"router's {total} experts fall into n_group equal groups "
                "of which 1..n_group are taken")
        if (raw["num_experts_per_tok"]
                > topk_group * (total // n_group)):
            raise ValueError(
                f"num_experts_per_tok = {raw['num_experts_per_tok']} "
                f"experts do not fit in {topk_group} groups of "
                f"{total // n_group}")
        if raw.get("q_lora_rank") is None:
            # the shared trunk takes a full-rank query (project_latent's
            # `wq`: bailing_hybrid's MLA layers); what is missing is the
            # reference this model_type is held to
            raise ValueError(
                "q_lora_rank is null (the DeepSeek-V2-Lite layout, a full-"
                "rank query projection): not implemented for model_type "
                "deepseek_v2 (models/reference/deepseek_v2.py, the copy "
                "its benchmark cell holds, has no full-rank query to "
                "compare with)")
        if raw.get("attention_bias", False):
            raise ValueError("attention_bias = true: projection biases "
                             "are not implemented")
        if raw.get("moe_layer_freq", 1) != 1:
            raise ValueError("moe_layer_freq must be 1")
        if raw.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act = {raw['hidden_act']!r}: only "
                             "'silu' is implemented")
        if (raw.get("num_key_value_heads", raw["num_attention_heads"])
                != raw["num_attention_heads"]):
            raise ValueError("num_key_value_heads must equal "
                             "num_attention_heads: latent attention has a "
                             "key a head")
        if raw.get("n_shared_experts", 0) < 1:
            raise ValueError("n_shared_experts must be at least 1")
        yarn = None
        scaling = raw.get("rope_scaling")
        if scaling:
            kind = scaling.get("type", scaling.get("rope_type"))
            if kind != "yarn":
                raise ValueError(f"rope_scaling type {kind!r}: only "
                                 "'yarn' is implemented")
            yarn = Yarn(
                float(scaling["factor"]),
                int(scaling["original_max_position_embeddings"]),
                float(scaling.get("beta_fast", 32)),
                float(scaling.get("beta_slow", 1)),
                float(scaling.get("mscale", 1)),
                float(scaling.get("mscale_all_dim", 0)))
        # GLM's parser for what the two share (the dense / sparse split,
        # MTP's refusal, the held experts' range): the keys it would
        # refuse or lacks are handed over in its own terms
        base = GlmMoeDsaConfig.from_hf_dict(dict(
            raw, n_group=1, topk_group=1, n_shared_experts=1,
            rope_scaling=None, indexer_types=["full"] * L,
            index_n_heads=0, index_head_dim=0, index_topk=0,
            scoring_func="softmax",
            norm_topk_prob=raw.get("norm_topk_prob", False)))
        fields = {f: getattr(base, f) for f in base.__dataclass_fields__}
        fields.update(
            hf_layout="deepseek_v2", chat_template="chatml",
            indexer_types=("dense",) * L,
            n_shared_experts=raw["n_shared_experts"],
            n_group=n_group, topk_group=topk_group, rope_scaling=yarn)
        return cls(**fields)

    @classmethod
    def tiny_dsv2(cls, **overrides) -> "DeepseekV2Config":
        """DeepSeek-V2's layers at a test's size: one dense layer, then
        three sparse ones; 16 routed experts in 4 groups of which 2 are
        taken, 3 a token, ALL held (a test of the share holds one
        group); two shared experts; YaRN over a trained length of 16,
        so that a context of a few dozen tokens is several times past
        it."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=4, rms_norm_eps=1e-6, rope_theta=10000.0,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(256,), tie_word_embeddings=False,
            chat_template="chatml",
            num_local_experts=16, num_experts_per_tok=3,
            norm_topk_prob=False, hf_layout="deepseek_v2",
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, index_n_heads=0,
            index_head_dim=0, index_topk=0,
            mlp_layer_types=("dense",) + ("sparse",) * 3,
            indexer_types=("dense",) * 4,
            moe_intermediate_size=32, n_routed_experts_total=16,
            n_shared_experts=2, routed_scaling_factor=16.0,
            scoring_func="softmax", n_group=4, topk_group=2,
            rope_scaling=Yarn(4.0, 16, 8.0, 1.0, 0.707, 0.707),
        )
        base.update(overrides)
        return cls(**base)


# config.json keys of model_type longcat_flash whose one served value is
# the published one: anything else is refused by the key's name
_LONGCAT_ONLY = {
    "zero_expert_type": "identity", "attention_method": "MLA",
    "attention_bias": False, "rope_scaling": None, "router_bias": False,
    "norm_topk_prob": False, "hidden_act": "silu",
}


@dataclass(frozen=True)
class LongcatFlashConfig(GlmMoeDsaConfig):
    """LongCat-Flash (`model_type: longcat_flash`): a published layer is
    TWO sublayers, each latent attention over every visible key (the two
    normed latents rescaled where `mla_scale_q_lora` / `mla_scale_kv_lora`
    say so) and a dense SwiGLU, and ONE routed MoE that reads the first
    sublayer's FFN input and is added after the second sublayer's FFN
    (the shortcut). The router is `n_routed_experts_total +
    zero_expert_num` wide: an index past the routed experts is an
    identity expert, which returns the MoE's input and holds no matrix.
    Softmax over the router's whole width, a choice bias, `moe_topk` a
    token, the weights not renormalised, times `routed_scaling_factor`.
    The equations are in models/reference/longcat_flash.py; the served
    path is models/moe/glm_dsa.py's trunk.

    Here `num_hidden_layers` counts SUBLAYERS (2 x config.json
    `num_layers`): each has its attention leaves, its latent pool layer
    and its dense FFN, `indexer_types` holds "dense" in every one and
    `mlp_layer_types` ("shortcut", "dense") in turn: a "shortcut"
    sublayer also holds its layer's router and experts.
    `intermediate_size` is `ffn_hidden_size`, `moe_intermediate_size`
    `expert_ffn_hidden_size`, `num_experts_per_tok` `moe_topk`."""

    _family = "cake_tpu.models.moe.glm_dsa:SHORTCUT"

    n_shared_experts: int = 0
    zero_expert_num: int = 256
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True

    @property
    def num_layers(self) -> int:
        """Published layers: two sublayers each."""
        return self.num_hidden_layers // 2

    @property
    def shortcut_layers(self) -> Tuple[int, ...]:
        """Sublayers that hold their layer's router and experts."""
        return tuple(i for i, t in enumerate(self.mlp_layer_types)
                     if t == "shortcut")

    def geometry(self, layer: int) -> LatentGeometry:
        def scale(on: bool, rank: int) -> float:
            return (self.hidden_size / rank) ** 0.5 if on else 1.0

        return super().geometry(layer)._replace(
            q_scale=scale(self.mla_scale_q_lora, self.q_lora_rank),
            kv_scale=scale(self.mla_scale_kv_lora, self.kv_lora_rank))

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "LongcatFlashConfig":
        for name, want in _LONGCAT_ONLY.items():
            if raw.get(name, want) != want:
                raise ValueError(
                    f"{name} = {raw[name]!r}: model_type longcat_flash "
                    f"serves {want!r} only (not implemented)")
        if raw.get("q_lora_rank") is None:
            raise ValueError(
                "q_lora_rank is null (a full-rank query projection): not "
                "implemented for model_type longcat_flash "
                "(models/reference/longcat_flash.py has no full-rank "
                "query to compare with)")
        for name in ("mtp_num_layers", "num_nextn_predict_layers"):
            if raw.get(name, 0):
                raise ValueError(
                    f"{name} > 0: the multi-token-prediction module is not "
                    "served (it drafts for speculation and adds nothing to "
                    "the next-token logits); set it to 0")
        if (raw.get("num_key_value_heads") or raw["num_attention_heads"]
                ) != raw["num_attention_heads"]:
            raise ValueError("num_key_value_heads must equal "
                             "num_attention_heads: latent attention has a "
                             "key a head")
        L = raw["num_layers"]
        held = raw["n_routed_experts"]
        total = raw.get("n_routed_experts_total", held)
        first = raw.get("first_routed_expert", 0)
        if not 0 <= first <= total - held:
            raise ValueError(
                f"first_routed_expert = {first}: experts {first}.."
                f"{first + held - 1} are not among the router's {total} "
                "routed experts")
        zero, k = raw.get("zero_expert_num", 0), raw["moe_topk"]
        if zero < 0 or k > total + zero:
            raise ValueError(
                f"moe_topk = {k} of a router {total} + zero_expert_num = "
                f"{zero} wide")
        base = LlamaConfig.from_hf_dict(dict(
            raw, num_hidden_layers=2 * L,
            intermediate_size=raw["ffn_hidden_size"],
            num_key_value_heads=raw["num_attention_heads"]))
        fields = {f: getattr(base, f) for f in base.__dataclass_fields__}
        fields.update(chat_template="chatml", sliding_window=None)
        return cls(
            **fields,
            num_local_experts=held, num_experts_per_tok=k,
            norm_topk_prob=False, hf_layout="longcat_flash",
            q_lora_rank=raw["q_lora_rank"],
            kv_lora_rank=raw["kv_lora_rank"],
            qk_nope_head_dim=raw["qk_nope_head_dim"],
            qk_rope_head_dim=raw["qk_rope_head_dim"],
            v_head_dim=raw["v_head_dim"],
            index_n_heads=0, index_head_dim=0, index_topk=0,
            mlp_layer_types=("shortcut", "dense") * L,
            indexer_types=("dense",) * (2 * L),
            moe_intermediate_size=raw["expert_ffn_hidden_size"],
            n_routed_experts_total=total, first_routed_expert=first,
            routed_scaling_factor=raw.get("routed_scaling_factor", 1.0),
            scoring_func="softmax", zero_expert_num=zero,
            mla_scale_q_lora=bool(raw.get("mla_scale_q_lora", False)),
            mla_scale_kv_lora=bool(raw.get("mla_scale_kv_lora", False)),
        )

    @classmethod
    def tiny_longcat(cls, **overrides) -> "LongcatFlashConfig":
        """LongCat-Flash's layers at a test's size: 2 layers = 4
        sublayers, 4 heads, 16 routed experts ALL held (a test of the
        share holds four), 8 zero experts, 3 a token."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=4, rms_norm_eps=1e-5, rope_theta=1e7,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(512,), tie_word_embeddings=False,
            chat_template="chatml",
            num_local_experts=16, num_experts_per_tok=3,
            norm_topk_prob=False, hf_layout="longcat_flash",
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, index_n_heads=0,
            index_head_dim=0, index_topk=0,
            mlp_layer_types=("shortcut", "dense") * 2,
            indexer_types=("dense",) * 4,
            moe_intermediate_size=32, n_routed_experts_total=16,
            routed_scaling_factor=6.0, scoring_func="softmax",
            zero_expert_num=8,
        )
        base.update(overrides)
        return cls(**base)


# config.json keys of model_type bailing_hybrid whose one served value is
# the published one: anything else is refused by the key's name
_BAILING_ONLY = {
    "hidden_act": "silu", "score_function": "sigmoid",
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "num_shared_experts": 1, "linear_silu": True,
    "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
    "use_qk_norm": True, "rope_interleave": True, "rope_scaling": None,
    "scale_router_input": False, "up_proj_norm": False, "use_bias": False,
    "use_qkv_bias": False, "use_mla_nope": False, "use_nGPT": False,
    "value_norm": False, "num_kv_heads_for_linear_attn": 0,
    "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
}


@dataclass(frozen=True)
class BailingHybridConfig(GlmMoeDsaConfig):
    """Ling-3.0 (`model_type: bailing_hybrid`): layer i is latent
    attention (MLA over every visible key, a full-rank query where
    `q_lora_rank` is null, a sigmoid gate a head) where (i + 1) mod
    `layer_group_size` == 0, and Kimi Delta Attention elsewhere: a
    float32 MATRIX state a row and head beside the page pool, updated by
    a gated delta rule with a decay a key channel
    (models/llama/paged.HybridPagedCache: `ssm` the state, `conv` the
    q | k | v tails of the short causal conv, `k` the latent pool of the
    MLA layers alone). `first_k_dense_replace` dense SwiGLU layers, then
    sigmoid-routed experts with a choice bias, limited to the
    `topk_group` groups of `n_group` whose TWO best biased scores sum
    highest (ops/moe.choose: group_top 2), and a shared expert. The
    equations are in models/reference/bailing_hybrid.py; the served path
    in models/moe/bailing_hybrid.py.

    `indexer_types` holds "kda" | "dense" (an MLA layer: glm_dsa's
    dense kind). `num_local_experts` counts the routed experts HELD
    here (config.json `num_experts`), `n_routed_experts_total` the
    router's width (`num_experts_total`, absent = all held),
    `first_routed_expert` the first held expert's index."""

    _family = "cake_tpu.models.moe.bailing_hybrid:FAMILY"

    # heads are num_attention_heads in both kinds of layer; a KDA
    # head's key and value width (config.json `head_dim`)
    kda_head_dim: int = 128
    conv_kernel: int = 4
    # the bounded gate: g = kda_lower_bound * sigmoid(exp(A_log) (a + dt_bias))
    kda_lower_bound: float = -5.0
    group_top: int = 2

    @property
    def kda_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.indexer_types)
                     if t == "kda")

    @property
    def latent_layers(self) -> Tuple[int, ...]:
        """The MLA layers: the page pool holds their rows alone."""
        return tuple(i for i, t in enumerate(self.indexer_types)
                     if t == "dense")

    @property
    def kda_width(self) -> int:
        """Channels of each of q, k, v, the decay and the output gate."""
        return self.num_attention_heads * self.kda_head_dim

    def geometry(self, layer: int) -> LatentGeometry:
        return super().geometry(layer)._replace(gated=True)

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "BailingHybridConfig":
        L = raw["num_hidden_layers"]
        for name, want in _BAILING_ONLY.items():
            if raw.get(name, want) != want:
                raise ValueError(
                    f"{name} = {raw[name]!r}: model_type bailing_hybrid "
                    f"serves {want!r} only (not implemented)")
        for name in ("expert_swiglu_limit_list",
                     "share_expert_swiglu_limit_list"):
            limits = list(raw.get(name) or [0] * L)
            if len(limits) < L:
                raise ValueError(f"{name} names {len(limits)} layers of "
                                 f"num_hidden_layers = {L}")
            clamped = [i for i in range(L) if limits[i]]
            if clamped:
                raise ValueError(
                    f"{name}: a non-zero limit in served layers {clamped} "
                    "(the clamp on the SwiGLU is not implemented: the "
                    "published config does not give its form)")
        if raw.get("num_nextn_predict_layers", 0):
            raise ValueError(
                "num_nextn_predict_layers > 0: the multi-token-prediction "
                "module is not served (it drafts for speculation and adds "
                "nothing to the next-token logits); set it to 0")
        dn, dr = raw["qk_nope_head_dim"], raw["qk_rope_head_dim"]
        for name, want in (("qk_head_dim", dn + dr), ("rotary_dim", dr)):
            if raw.get(name, want) != want:
                raise ValueError(
                    f"{name} = {raw[name]}: qk_nope_head_dim + "
                    f"qk_rope_head_dim = {dn} + {dr}, of which the rope "
                    "part is rotated")
        if (raw.get("num_key_value_heads", raw["num_attention_heads"])
                != raw["num_attention_heads"]):
            raise ValueError("num_key_value_heads must equal "
                             "num_attention_heads: a key a head in both "
                             "kinds of layer")
        period = raw["layer_group_size"]
        dense = raw.get("first_k_dense_replace", 0)
        held = raw["num_experts"]
        total = raw.get("num_experts_total", held)
        first = raw.get("first_routed_expert", 0)
        if not 0 <= first <= total - held:
            raise ValueError(
                f"experts {first}..{first + held - 1} are not among the "
                f"router's {total}")
        n_group, topk_group = raw.get("n_group", 1), raw.get("topk_group", 1)
        k = raw["num_experts_per_tok"]
        if (total % n_group or not 1 <= topk_group <= n_group
                or k > topk_group * (total // n_group)):
            raise ValueError(
                f"n_group = {n_group}, topk_group = {topk_group}: the "
                f"router's {total} experts fall into n_group equal groups "
                f"of which 1..n_group are taken and hold the {k} a token")
        base = LlamaConfig.from_hf_dict(raw)
        fields = {f: getattr(base, f) for f in base.__dataclass_fields__}
        fields["chat_template"] = "chatml"
        return cls(
            **fields,
            num_local_experts=held, num_experts_per_tok=k,
            norm_topk_prob=True, hf_layout="bailing_hybrid",
            q_lora_rank=raw.get("q_lora_rank"),
            kv_lora_rank=raw["kv_lora_rank"],
            qk_nope_head_dim=dn, qk_rope_head_dim=dr,
            v_head_dim=raw["v_head_dim"],
            index_n_heads=0, index_head_dim=0, index_topk=0,
            mlp_layer_types=("dense",) * dense + ("sparse",) * (L - dense),
            indexer_types=tuple("dense" if (i + 1) % period == 0 else "kda"
                                for i in range(L)),
            moe_intermediate_size=raw["moe_intermediate_size"],
            n_routed_experts_total=total, first_routed_expert=first,
            n_shared_experts=(
                raw.get("moe_shared_expert_intermediate_size",
                        raw["moe_intermediate_size"])
                // raw["moe_intermediate_size"]),
            routed_scaling_factor=raw.get("routed_scaling_factor", 1.0),
            scoring_func="sigmoid", n_group=n_group, topk_group=topk_group,
            kda_head_dim=raw["head_dim"],
            conv_kernel=raw.get("short_conv_kernel_size", 4),
            kda_lower_bound=float(raw.get("kda_lower_bound", -5)),
        )

    @classmethod
    def tiny_ling(cls, **overrides) -> "BailingHybridConfig":
        """Ling-3.0's layers at a test's size: two periods of three
        (`K K M K K M`), one dense layer, then sparse ones; 4 heads of
        8; 16 routed experts in 4 groups of which 2 are taken, 3 a
        token, ALL held (a test of the share holds a group)."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_hidden_layers=6, num_attention_heads=4,
            num_key_value_heads=4, rms_norm_eps=1e-6, rope_theta=10000.0,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(256,), tie_word_embeddings=False,
            chat_template="chatml",
            num_local_experts=16, num_experts_per_tok=3,
            norm_topk_prob=True, hf_layout="bailing_hybrid",
            q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, index_n_heads=0,
            index_head_dim=0, index_topk=0,
            mlp_layer_types=("dense",) + ("sparse",) * 5,
            indexer_types=("kda", "kda", "dense") * 2,
            moe_intermediate_size=32, n_routed_experts_total=16,
            n_shared_experts=1, routed_scaling_factor=2.5,
            scoring_func="sigmoid", n_group=4, topk_group=2,
            kda_head_dim=8, conv_kernel=4,
        )
        base.update(overrides)
        return cls(**base)


# config.json keys of model_type exaone_moe whose one served value is
# the published one: anything else is refused by the key's name
_EXAONE_ONLY = {
    "hidden_act": "silu", "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "attention_bias": False, "rope_scaling": None,
}
_EXAONE_KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


@dataclass(frozen=True)
class ExaoneMoeConfig(MoEConfig):
    """K-EXAONE (`model_type: exaone_moe`): plain GQA in every layer,
    in TWO kinds by `layer_types`. A `sliding_attention` layer rotates q
    and k and a query attends its last `sliding_window` keys, its own
    included; a `full_attention` layer attends every key at or before
    the query and has NO positional rotation. Both RMS-norm q and k a
    head before that. `first_k_dense_replace` dense SwiGLU layers, then
    sigmoid-routed experts (a choice bias, the k best renormalised,
    times `routed_scaling_factor`) and a shared expert: GLM-5.2's FFN
    (models/moe/glm_dsa.ffn). The equations are in
    models/reference/exaone_moe.py; the served path in
    models/moe/exaone_moe.py, and its cache is K and V pages by kind of
    layer: the full layers' on the allocator's pages, the sliding
    layers' in a ring of `window_ring_pages` pages a row
    (models/llama/paged.WindowedKVCache).

    `indexer_types` holds "sliding" | "full" (from `layer_types`), as
    Dots3NoteConfig's. `num_local_experts` counts the routed experts
    HELD here (config.json `num_experts`), `n_routed_experts_total` the
    router's width (`num_experts_total`, absent = all held),
    `first_routed_expert` the first held expert's index. The base
    class's `sliding_window` stays None (it asks the DENSE engine for a
    ring cache: serve/engine.py); the window is `sliding_window_size`."""

    _family = "cake_tpu.models.moe.exaone_moe:FAMILY"

    # config.json `head_dim`: not hidden_size / heads here (6,144 / 64)
    attn_head_dim: int = 128
    sliding_window_size: int = 128
    mlp_layer_types: Tuple[str, ...] = ()
    indexer_types: Tuple[str, ...] = ()
    moe_intermediate_size: int = 2048
    n_routed_experts_total: int = 128
    first_routed_expert: int = 0
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    group_top: int = 1

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    # the layers by kind, as the latent families read the same two
    # tuples; R, and why a ring of R pages holds every key a dispatch
    # needs: the same count as the latent ring's
    # (models/llama/paged.ring_holds)
    full_layers = GlmMoeDsaConfig.full_layers
    sliding_layers = GlmMoeDsaConfig.sliding_layers
    sparse_layers = GlmMoeDsaConfig.sparse_layers
    window_ring_pages = Dots3NoteConfig.window_ring_pages

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "ExaoneMoeConfig":
        L = raw["num_hidden_layers"]
        for name, want in _EXAONE_ONLY.items():
            if raw.get(name, want) != want:
                raise ValueError(
                    f"{name} = {raw[name]!r}: model_type exaone_moe "
                    f"serves {want!r} only (not implemented)")
        if raw.get("num_nextn_predict_layers", 0):
            raise ValueError(
                "num_nextn_predict_layers > 0: the multi-token-prediction "
                "module is not served (it drafts for speculation and adds "
                "nothing to the next-token logits); set it to 0 (and "
                "drop mtp_layer_types / mtp_sliding_windows)")
        rope = raw.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise ValueError(
                f"rope_parameters.rope_type = {rope['rope_type']!r}: only "
                "'default' (plain RoPE, one theta) is implemented")
        W = raw["sliding_window"]
        if W < 1:
            raise ValueError("sliding_window must be at least 1")
        types = list(raw.get("layer_types") or [])
        if len(types) != L or set(types) - set(_EXAONE_KINDS):
            raise ValueError(
                f"layer_types must name num_hidden_layers = {L} layers, "
                "each sliding_attention or full_attention; got "
                f"{len(types)}: " + ", ".join(sorted(set(types))))
        windows = raw.get("sliding_windows")
        if windows is not None and list(windows) != [
                W if t == "sliding_attention" else 0 for t in types]:
            raise ValueError(
                "sliding_windows must give sliding_window for each "
                "sliding_attention layer of layer_types and 0 for each "
                "full_attention layer: one window is served")
        dense = raw.get("first_k_dense_replace", 0)
        mlp = list(raw.get("mlp_layer_types")
                   or ["dense"] * dense + ["sparse"] * (L - dense))
        if len(mlp) != L or set(mlp) - {"dense", "sparse"}:
            raise ValueError(
                f"mlp_layer_types must name num_hidden_layers = {L} "
                "layers, each dense or sparse")
        if raw.get("num_shared_experts", 1) < 1:
            raise ValueError("num_shared_experts must be at least 1")
        held = raw["num_experts"]
        total = raw.get("num_experts_total", held)
        first = raw.get("first_routed_expert", 0)
        if not 0 <= first <= total - held:
            raise ValueError(
                f"experts {first}..{first + held - 1} are not among the "
                f"router's {total}")
        base = LlamaConfig.from_hf_dict(dict(
            raw, rope_theta=rope.get("rope_theta",
                                     raw.get("rope_theta", 10000.0))))
        fields = {f: getattr(base, f) for f in base.__dataclass_fields__}
        fields.update(chat_template="chatml", sliding_window=None)
        return cls(
            **fields,
            num_local_experts=held,
            num_experts_per_tok=raw["num_experts_per_tok"],
            norm_topk_prob=raw.get("norm_topk_prob", True),
            hf_layout="exaone_moe",
            attn_head_dim=raw.get(
                "head_dim",
                raw["hidden_size"] // raw["num_attention_heads"]),
            sliding_window_size=W,
            mlp_layer_types=tuple(mlp),
            indexer_types=tuple(_EXAONE_KINDS[t] for t in types),
            moe_intermediate_size=raw["moe_intermediate_size"],
            n_routed_experts_total=total, first_routed_expert=first,
            n_shared_experts=raw.get("num_shared_experts", 1),
            routed_scaling_factor=raw.get("routed_scaling_factor", 1.0),
        )

    @classmethod
    def tiny_exaone(cls, **overrides) -> "ExaoneMoeConfig":
        """K-EXAONE's layers at a test's size: two published periods
        (`S S S F` twice), layer 0 dense and seven sparse; 8 query
        heads of 16 over 2 K/V heads (head_dim is NOT hidden / heads);
        a window of 6 keys; 8 routed experts, 2 a token, all held."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=2, rms_norm_eps=1e-5, rope_theta=1e6,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(256,), tie_word_embeddings=False,
            chat_template="chatml",
            num_local_experts=8, num_experts_per_tok=2,
            norm_topk_prob=True, hf_layout="exaone_moe",
            attn_head_dim=16, sliding_window_size=6,
            mlp_layer_types=("dense",) + ("sparse",) * 7,
            indexer_types=("sliding", "sliding", "sliding", "full") * 2,
            moe_intermediate_size=32, n_routed_experts_total=8,
            n_shared_experts=1, routed_scaling_factor=2.5,
        )
        base.update(overrides)
        return cls(**base)


# config.json keys of model_type KeyeVL2 whose one served value is the
# published one: anything else is refused by the key's name
_KEYE_ONLY = {
    "hidden_act": "silu", "use_sliding_window": False,
    "sliding_window": None, "decoder_sparse_step": 1,
    "attention_bias": False, "tie_word_embeddings": False,
}


@dataclass(frozen=True)
class KeyeVL2Config(MoEConfig):
    """Keye-VL-2.0's LANGUAGE MODEL (`model_type: KeyeVL2`): every layer
    alike. GQA with an RMSNorm a head on q and k before the rotation, a
    learned sparse indexer beside it (the nested `sa_config`:
    `index_n_heads` heads of `index_head_dim` over ONE key head; a query
    attends the `index_topk` keys it scores highest, every visible key
    while there are no more than that), softmax-routed experts with the
    k best renormalised and none shared. The equations are in
    models/reference/keye_vl2.py; the served path in
    models/moe/keye_vl2.py, and its cache is ordinary K and V pages plus
    the indexer's keys in a third pool over the same page table
    (models/llama/paged.PagedKVCache.idx).

    `mrope_section` deals the rotation's head_dim / 2 frequencies to
    three position streams (temporal, height, width). For text the three
    are equal and the rotation is the ordinary one at every frequency,
    which is what is served: the vision tower that would make them
    differ is refused by its key (`vision_config`)."""

    _family = "cake_tpu.models.moe.keye_vl2:FAMILY"

    attn_head_dim: int = 128
    moe_intermediate_size: int = 768
    index_n_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    # (the published kernel's score-pass tiles: what is selected does
    # not depend on them)
    q_chunk_size: int = 512
    kv_chunk_size: int = 512
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    # what glm_dsa.ffn reads of a config: every expert is held, the
    # rule is softmax with no groups and no scale
    scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    group_top: int = 1

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    @property
    def n_routed_experts_total(self) -> int:
        return self.num_local_experts

    @property
    def index_rope_dim(self) -> int:
        """The indexer's heads are rotated whole (RopeTables.create)."""
        return self.index_head_dim

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "KeyeVL2Config":
        for tower in ("vision_config", "audio_config"):
            if raw.get(tower) is not None:
                raise ValueError(
                    f"{tower}: model_type KeyeVL2 is served as its "
                    "language model alone (no tower's layers are "
                    "implemented, and no request carries an image or a "
                    f"sound); take {tower} out of config.json")
        for name, want in _KEYE_ONLY.items():
            if raw.get(name, want) != want:
                raise ValueError(
                    f"{name} = {raw[name]!r}: model_type KeyeVL2 serves "
                    f"{want!r} only (not implemented)")
        if raw.get("mlp_only_layers"):
            raise ValueError(
                f"mlp_only_layers = {raw['mlp_only_layers']!r}: every "
                "layer of model_type KeyeVL2 is served with experts (a "
                "dense layer is not implemented)")
        sa = raw.get("sa_config")
        if not isinstance(sa, dict):
            raise ValueError(
                "sa_config: model_type KeyeVL2 needs the sparse "
                "indexer's settings (indexer_head_dim, indexer_num_heads, "
                "indexer_num_kv_heads, topk)")
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError(
                f"sa_config.indexer_num_kv_heads = "
                f"{sa['indexer_num_kv_heads']!r}: one index key a token "
                "is served (not implemented)")
        hd = raw.get("head_dim",
                     raw["hidden_size"] // raw["num_attention_heads"])
        rope = raw.get("rope_scaling") or {}
        kind = rope.get("rope_type", rope.get("type", "default"))
        if kind != "default" or rope.get("type", kind) != kind:
            raise ValueError(
                f"rope_scaling.rope_type = {kind!r}: only 'default' "
                "(plain frequencies, dealt to three position streams by "
                "mrope_section) is implemented")
        section = tuple(rope.get("mrope_section") or (hd // 2,))
        if sum(section) != hd // 2:
            raise ValueError(
                f"rope_scaling.mrope_section = {list(section)}: must sum "
                f"to head_dim / 2 = {hd // 2}")
        held = raw["num_experts"]
        if raw.get("num_local_experts", held) != held:
            raise ValueError(
                f"num_local_experts = {raw['num_local_experts']!r}: every "
                f"one of num_experts = {held} is held (a share of a "
                "layer's experts is not implemented for model_type "
                "KeyeVL2)")
        # (sliding_window is None by the table above)
        base = LlamaConfig.from_hf_dict(raw)
        fields = {f: getattr(base, f) for f in base.__dataclass_fields__}
        fields.update(chat_template="chatml")
        return cls(
            **fields,
            num_local_experts=held,
            num_experts_per_tok=raw["num_experts_per_tok"],
            norm_topk_prob=raw.get("norm_topk_prob", True),
            hf_layout="KeyeVL2",
            attn_head_dim=hd,
            moe_intermediate_size=raw["moe_intermediate_size"],
            index_n_heads=sa["indexer_num_heads"],
            index_head_dim=sa["indexer_head_dim"],
            index_topk=sa["topk"],
            q_chunk_size=sa.get("q_chunk_size", 512),
            kv_chunk_size=sa.get("kv_chunk_size", 512),
            mrope_section=section,
        )

    @classmethod
    def tiny_keye(cls, **overrides) -> "KeyeVL2Config":
        """Keye's layer at a test's size: 3 layers, 4 query heads over 2
        K/V heads of 16, an indexer of 2 heads of 8 that selects 48
        keys, 8 experts of 32, 2 a token."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, rms_norm_eps=1e-6, rope_theta=1e7,
            max_position_embeddings=512, bos_token_id=1,
            eos_token_ids=(512,), tie_word_embeddings=False,
            chat_template="chatml",
            num_local_experts=8, num_experts_per_tok=2,
            norm_topk_prob=True, hf_layout="KeyeVL2",
            attn_head_dim=16, moe_intermediate_size=32,
            index_n_heads=2, index_head_dim=8, index_topk=48,
            mrope_section=(2, 3, 3),
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class NemotronHConfig(MoEConfig):
    """Nemotron-3 (`model_type: nemotron_h`): every block is ONE mixer
    behind one RMS norm and one residual, its kind read from
    `hybrid_override_pattern`: `M` a Mamba-2 block (a per-ROW recurrent
    state, not per-token rows: models/llama/paged.HybridPagedCache), `E`
    a LatentMoE block (sigmoid-routed relu² experts inside a
    `moe_latent_size`-wide latent, a shared expert on the full width),
    `*` GQA attention without a positional embedding. The equations are
    in models/reference/nemotron_h.py; the served path in
    models/moe/nemotron_h.py.

    `intermediate_size` is unused by the blocks (config.json carries the
    expert width there too). `num_local_experts` counts the routed
    experts HELD here (config.json `n_routed_experts`),
    `n_routed_experts_total` the router's width, `first_routed_expert`
    the first held expert's index, as GlmMoeDsaConfig has them."""

    _family = "cake_tpu.models.moe.nemotron_h:FAMILY"

    pattern: Tuple[str, ...] = ()
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_routed_experts_total: int = 512
    first_routed_expert: int = 0
    routed_scaling_factor: float = 5.0
    scoring_func: str = "sigmoid"

    @property
    def mamba_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.pattern) if t == "M")

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.pattern) if t == "E")

    @property
    def attn_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.pattern) if t == "*")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the causal conv runs over: xs | B | C."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def in_proj_dim(self) -> int:
        """z | xBC | dt."""
        return self.d_inner + self.conv_dim + self.mamba_num_heads

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "NemotronHConfig":
        L = raw["num_hidden_layers"]
        pattern = tuple(raw["hybrid_override_pattern"])
        if len(pattern) != L or set(pattern) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {''.join(pattern)!r} must name "
                f"num_hidden_layers = {L} blocks out of M, E and *")
        base = LlamaConfig.from_hf_dict(dict(
            raw, rms_norm_eps=raw.get("layer_norm_epsilon",
                                      raw.get("norm_eps", 1e-5)),
            intermediate_size=raw.get("intermediate_size",
                                      raw["moe_intermediate_size"])))
        if raw.get("head_dim", base.head_dim) != base.head_dim:
            raise ValueError(
                f"head_dim {raw['head_dim']} != hidden_size / "
                f"num_attention_heads = {base.head_dim}")
        for name in ("n_group", "topk_group"):
            if raw.get(name, 1) != 1:
                raise ValueError(f"{name} = {raw[name]}: group-limited "
                                 "routing is not implemented")
        if raw.get("n_shared_experts", 1) != 1:
            raise ValueError("n_shared_experts must be 1")
        if raw.get("rope_scaling"):
            raise ValueError(
                "rope_scaling is not implemented for model_type nemotron_h "
                "(its attention blocks have no positional embedding)")
        if raw.get("num_nextn_predict_layers", 0):
            raise ValueError(
                "num_nextn_predict_layers > 0: the multi-token-prediction "
                "module is not served (it drafts for speculation and adds "
                "nothing to the next-token logits); set it to 0")
        for name, want in (("mlp_hidden_act", "relu2"),
                           ("mamba_hidden_act", "silu")):
            if raw.get(name, want) != want:
                raise ValueError(f"{name} = {raw[name]!r}: only {want!r} "
                                 "is implemented")
        for name in ("attention_bias", "mamba_proj_bias", "mlp_bias",
                     "use_bias"):
            if raw.get(name, False):
                raise ValueError(f"{name} = true: projection biases are "
                                 "not implemented")
        if not raw.get("use_conv_bias", True):
            raise ValueError("use_conv_bias = false is not implemented")
        if raw["mamba_num_heads"] % raw["n_groups"]:
            raise ValueError("n_groups must divide mamba_num_heads")
        held = raw["n_routed_experts"]
        total = raw.get("n_routed_experts_total", held)
        first = raw.get("first_routed_expert", 0)
        if not 0 <= first <= total - held:
            raise ValueError(
                f"experts {first}..{first + held - 1} are not among the "
                f"router's {total}")
        fields = {f: getattr(base, f) for f in base.__dataclass_fields__}
        fields["chat_template"] = "chatml"
        return cls(
            **fields,
            num_local_experts=held,
            num_experts_per_tok=raw["num_experts_per_tok"],
            norm_topk_prob=raw.get("norm_topk_prob", True),
            hf_layout="nemotron_h", pattern=pattern,
            mamba_num_heads=raw["mamba_num_heads"],
            mamba_head_dim=raw["mamba_head_dim"],
            n_groups=raw["n_groups"],
            ssm_state_size=raw["ssm_state_size"],
            conv_kernel=raw.get("conv_kernel", 4),
            chunk_size=raw.get("chunk_size", 128),
            time_step_min=raw.get("time_step_min", 0.001),
            time_step_max=raw.get("time_step_max", 0.1),
            time_step_floor=raw.get("time_step_floor", 1e-4),
            moe_intermediate_size=raw["moe_intermediate_size"],
            moe_latent_size=raw["moe_latent_size"],
            moe_shared_expert_intermediate_size=raw[
                "moe_shared_expert_intermediate_size"],
            n_routed_experts_total=total, first_routed_expert=first,
            routed_scaling_factor=raw.get("routed_scaling_factor", 1.0),
        )

    @classmethod
    def tiny_nemotron(cls, **overrides) -> "NemotronHConfig":
        """Nemotron-3's blocks at a test's size: every kind of block,
        4 Mamba heads of 8 in 2 groups, state 16, chunk 8, 16 routed
        experts of which the first 4 are held, 3 a token."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=24,
            num_hidden_layers=5, num_attention_heads=4,
            num_key_value_heads=2, rms_norm_eps=1e-5, rope_theta=10000.0,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(256,), tie_word_embeddings=False,
            chat_template="chatml",
            num_local_experts=4, num_experts_per_tok=3,
            norm_topk_prob=True, hf_layout="nemotron_h",
            pattern=tuple("EM*EM"), mamba_num_heads=4, mamba_head_dim=8,
            n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8,
            moe_intermediate_size=24, moe_latent_size=32,
            moe_shared_expert_intermediate_size=48,
            n_routed_experts_total=16, first_routed_expert=0,
            routed_scaling_factor=5.0,
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class ZayaConfig(MoEConfig):
    """ZAYA1 (`model_type: zaya`, the published ZAYA1-8B layout): every
    layer is one attention sublayer and one expert sublayer, each behind
    a learned residual scaling. Attention is compressed convolutional
    attention (CCA): queries and keys live in a latent of
    num_attention_heads * head_dim (half the hidden size at the
    published widths), mixed along the sequence by two short causal
    convolutions (a per-ROW tail of the last token's latents beside the
    page pool: models/llama/paged.HybridPagedCache), half of the values
    shifted by a token, half of each head rotated. The router is an MLP
    over a `router_hidden_size`-wide state that runs down the layers;
    every token takes ONE expert, weighed by its unrenormalised
    probability. The equations are in models/reference/zaya.py; the
    served path in models/moe/zaya.py.

    `intermediate_size` carries `moe_intermediate_size` (an expert's
    width: what models/moe/params and ops/moe read)."""

    _family = "cake_tpu.models.moe.zaya:FAMILY"

    attn_head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    router_hidden_size: int = 256

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    @property
    def rope_dim(self) -> int:
        """The rotated part of a head: its first
        partial_rotary_factor * head_dim dims."""
        return int(self.attn_head_dim * self.partial_rotary_factor)

    @property
    def cca_channels(self) -> int:
        """Channels the convolutions run over: the query latent and the
        key latent, one group of head_dim a head."""
        return ((self.num_attention_heads + self.num_key_value_heads)
                * self.attn_head_dim)

    @property
    def cca_tail_width(self) -> int:
        """A row's tail in one layer: the last token's latents before
        the convolutions and after the first, and the half of its
        values the next token takes (c | a | W_v2 u)."""
        return (2 * self.cca_channels
                + self.num_key_value_heads * self.attn_head_dim // 2)

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "ZayaConfig":
        L = raw["num_hidden_layers"]
        missing = [k for k in ("cca_time0", "cca_time1", "num_experts",
                               "moe_intermediate_size", "router_hidden_size",
                               "head_dim") if k not in raw]
        if missing:
            raise ValueError(
                "model_type zaya: config.json lacks " + ", ".join(missing)
                + " (the ZAYA1-8B layout is the one served; the "
                "Megatron-style layout of ZAYA1-base, with zaya_layers and "
                "ffn_hidden_size_list, is not)")
        for name in ("cca_time0", "cca_time1"):
            if raw[name] != 2:
                raise ValueError(
                    f"{name} = {raw[name]}: a row's tail holds one token "
                    "(kernel 2); longer kernels are not implemented")
        if raw.get("sliding_window") is not None:
            raise ValueError(
                f"sliding_window = {raw['sliding_window']}: windowed CCA "
                "layers (hybrid_sliding) are not implemented")
        types = raw.get("layer_types") or ["hybrid"] * L
        if len(types) != L or set(types) != {"hybrid"}:
            raise ValueError(
                f"layer_types must name num_hidden_layers = {L} layers, "
                "each `hybrid` (CCA over the whole context, then "
                "experts); got " + ", ".join(sorted(set(types))))
        for name in ("attention_bias", "lm_head_bias"):
            if raw.get(name, False):
                raise ValueError(f"{name} = true: projection biases are "
                                 "not implemented")
        if raw.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act = {raw['hidden_act']!r}: only "
                             "'silu' is implemented")
        if raw["num_attention_heads"] % raw["num_key_value_heads"]:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if raw["num_key_value_heads"] * raw["head_dim"] % 2:
            raise ValueError("the value shift halves num_key_value_heads "
                             "* head_dim: it must be even")
        rope = raw.get("rope_parameters") or {}
        rope = rope.get("hybrid", rope)
        factor = rope.get("partial_rotary_factor",
                          raw.get("partial_rotary_factor", 1.0))
        if rope.get("rope_type", "default") != "default":
            raise ValueError(f"rope_type = {rope['rope_type']!r}: only "
                             "'default' is implemented")
        if int(raw["head_dim"] * factor) % 2 or not 0 < factor <= 1:
            raise ValueError(
                f"partial_rotary_factor = {factor}: the rotated part of "
                f"a head of {raw['head_dim']} must be an even number of "
                "dims, at most the head")
        base = LlamaConfig.from_hf_dict(dict(
            raw, intermediate_size=raw["moe_intermediate_size"],
            rope_theta=rope.get("rope_theta",
                                raw.get("rope_theta", 10000.0)),
            sliding_window=None,
            eos_token_id=raw.get("eos_token_id", raw["vocab_size"])))
        fields = {f: getattr(base, f) for f in base.__dataclass_fields__}
        fields["chat_template"] = "chatml"
        return cls(
            **fields,
            num_local_experts=raw["num_experts"],
            num_experts_per_tok=raw["num_experts_per_tok"],
            norm_topk_prob=raw.get("norm_topk_prob", False),
            hf_layout="zaya",
            attn_head_dim=raw["head_dim"],
            cca_time0=raw["cca_time0"], cca_time1=raw["cca_time1"],
            partial_rotary_factor=factor,
            router_hidden_size=raw["router_hidden_size"],
        )

    @classmethod
    def tiny_zaya(cls, **overrides) -> "ZayaConfig":
        """ZAYA1's layer at a test's size: 4 layers, 4 query heads over
        2 key heads of 16 (group 2; a test of the q-k mean passes
        num_attention_heads=8 for the published group of 4), 4 experts
        of 32, one a token, a 16-wide router."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=32,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, rms_norm_eps=1e-5, rope_theta=5e6,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(512,), tie_word_embeddings=True,
            chat_template="chatml",
            num_local_experts=4, num_experts_per_tok=1,
            norm_topk_prob=False, hf_layout="zaya",
            attn_head_dim=16, cca_time0=2, cca_time1=2,
            partial_rotary_factor=0.5, router_hidden_size=16,
        )
        base.update(overrides)
        return cls(**base)


# config.json keys of model_type granitemoehybrid whose one served value
# is refused by the key's name otherwise
_GRANITE_ONLY = {
    "position_embedding_type": "nope", "hidden_act": "silu",
    "normalization_function": "rmsnorm", "mamba_proj_bias": False,
    "attention_bias": False, "mamba_conv_bias": True,
    "tie_word_embeddings": True,
}
_GRANITE_KINDS = ("mamba", "attention")


@dataclass(frozen=True)
class GraniteHybridConfig(MoEConfig):
    """Granite-4.0-H (`model_type: granitemoehybrid`), its dense
    members: every layer is a mixer AND a dense SwiGLU of
    `shared_intermediate_size`, each behind an RMS norm, each branch
    scaled by `residual_multiplier` on its way into the stream. The
    mixer is named by `layer_types`: `mamba`, a Mamba-2 mixer (a
    per-ROW recurrent state beside the page pool:
    models/llama/paged.HybridPagedCache), or `attention`, GQA without a
    positional embedding whose softmax scale is `attention_multiplier`,
    not 1/sqrt(head_dim). The embedding is multiplied by
    `embedding_multiplier`, the logits divided by `logits_scaling`; the
    head is the embedding transposed. The four scalars are static
    floats of the config. The equations are in
    models/reference/granite_hybrid.py; the served path in
    models/moe/granite_hybrid.py.

    The Mamba fields carry the published names; the properties under
    them are the names models/moe/nemotron_h.mamba_block reads (the
    mixer is that one, called as it is). `num_local_experts` is 0 (the
    family's sparse siblings are refused), so `is_moe` says by itself
    that the seeded draw and the trunk live under models/moe."""

    _family = "cake_tpu.models.moe.granite_hybrid:FAMILY"

    layer_types: Tuple[str, ...] = ()
    shared_intermediate_size: int = 8192
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256

    is_moe = True

    @property
    def mamba_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "mamba")

    @property
    def attn_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "attention")

    mamba_num_heads = property(lambda self: self.mamba_n_heads)
    mamba_head_dim = property(lambda self: self.mamba_d_head)
    n_groups = property(lambda self: self.mamba_n_groups)
    ssm_state_size = property(lambda self: self.mamba_d_state)
    conv_kernel = property(lambda self: self.mamba_d_conv)
    chunk_size = property(lambda self: self.mamba_chunk_size)
    d_inner = NemotronHConfig.d_inner
    conv_dim = NemotronHConfig.conv_dim
    in_proj_dim = NemotronHConfig.in_proj_dim

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "GraniteHybridConfig":
        L = raw["num_hidden_layers"]
        if raw.get("num_local_experts", 0) or raw.get(
                "num_experts_per_tok", 0):
            raise ValueError(
                f"num_local_experts = {raw.get('num_local_experts')!r}, "
                f"num_experts_per_tok = {raw.get('num_experts_per_tok')!r}: "
                "model_type granitemoehybrid is served with a dense SwiGLU "
                "in every layer (num_local_experts 0); its sparse siblings "
                "are not implemented")
        for name, want in _GRANITE_ONLY.items():
            if raw.get(name, want) != want:
                raise ValueError(
                    f"{name} = {raw[name]!r}: model_type granitemoehybrid "
                    f"is served with {name} = {want!r} only")
        types = tuple(raw.get("layer_types") or ())
        unknown = sorted(set(types) - set(_GRANITE_KINDS))
        if len(types) != L or unknown:
            raise ValueError(
                f"layer_types must name num_hidden_layers = {L} layers, "
                "each `mamba` or `attention`; got " + (
                    ", ".join(unknown) if unknown else f"{len(types)} "
                    "entries"))
        if raw.get("rope_scaling"):
            raise ValueError(
                "rope_scaling is not implemented for model_type "
                "granitemoehybrid (position_embedding_type nope: its "
                "attention layers have no positional embedding)")
        heads, d_head = raw["mamba_n_heads"], raw["mamba_d_head"]
        if raw.get("mamba_expand", 2) * raw["hidden_size"] != heads * d_head:
            raise ValueError(
                f"mamba_expand {raw.get('mamba_expand', 2)} x hidden_size "
                f"{raw['hidden_size']} != mamba_n_heads {heads} x "
                f"mamba_d_head {d_head}")
        if heads % raw.get("mamba_n_groups", 1):
            raise ValueError("mamba_n_groups must divide mamba_n_heads")
        base = LlamaConfig.from_hf_dict(dict(
            raw, eos_token_id=raw.get("eos_token_id", raw["vocab_size"])))
        fields = {f: getattr(base, f) for f in base.__dataclass_fields__}
        fields["chat_template"] = "chatml"
        return cls(
            **fields, num_local_experts=0, num_experts_per_tok=0,
            hf_layout="granitemoehybrid", layer_types=types,
            shared_intermediate_size=raw["shared_intermediate_size"],
            embedding_multiplier=float(raw.get("embedding_multiplier", 1.0)),
            attention_multiplier=float(raw.get(
                "attention_multiplier", base.head_dim ** -0.5)),
            residual_multiplier=float(raw.get("residual_multiplier", 1.0)),
            logits_scaling=float(raw.get("logits_scaling", 1.0)),
            mamba_n_heads=heads, mamba_d_head=d_head,
            mamba_n_groups=raw.get("mamba_n_groups", 1),
            mamba_d_state=raw["mamba_d_state"],
            mamba_d_conv=raw.get("mamba_d_conv", 4),
            mamba_expand=raw.get("mamba_expand", 2),
            mamba_chunk_size=raw.get("mamba_chunk_size", 256),
        )

    @classmethod
    def tiny_granite(cls, **overrides) -> "GraniteHybridConfig":
        """Granite-4.0-H's layer at a test's size: both kinds of mixer,
        4 Mamba heads of 16 in ONE group, state 16, chunk 8, 4 query
        heads over 2 of 16 with a softmax scale that is NOT
        1/sqrt(head_dim), the four multipliers away from 1."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96,
            num_hidden_layers=5, num_attention_heads=4,
            num_key_value_heads=2, rms_norm_eps=1e-5, rope_theta=10000.0,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(512,), tie_word_embeddings=True,
            chat_template="chatml",
            num_local_experts=0, num_experts_per_tok=0,
            hf_layout="granitemoehybrid",
            layer_types=("mamba", "mamba", "attention", "mamba", "mamba"),
            shared_intermediate_size=96, embedding_multiplier=12.0,
            attention_multiplier=1.0 / 16, residual_multiplier=0.22,
            logits_scaling=8.0, mamba_n_heads=8, mamba_d_head=16,
            mamba_n_groups=1, mamba_d_state=16, mamba_d_conv=4,
            mamba_expand=2, mamba_chunk_size=8,
        )
        base.update(overrides)
        return cls(**base)


# config.json keys of model_type brumby whose one served value is the
# published one: anything else is refused by the key's name
_BRUMBY_ONLY = {
    "use_sliding_window": False, "sliding_window": None,
    "rope_scaling": None, "attention_bias": False,
    "tie_word_embeddings": False, "hidden_act": "silu",
}


@dataclass(frozen=True)
class BrumbyConfig(MoEConfig):
    """Brumby (`model_type: brumby`): Qwen3's dense block with the
    softmax replaced by power retention of degree 2 in EVERY layer: q
    and k normed a head and rotated whole, a gate a K/V head and token,
    a matrix state a row, layer and K/V head that the query heads of a
    GQA group share, no K/V page anywhere (the rows' state beside a page
    pool of zero layers: models/llama/paged.HybridPagedCache), a dense
    SwiGLU, an untied head. The config is Qwen3's key for key but
    `model_type`; it has NO key for the mixer, whose constants (degree
    2, the gate's form, the normaliser) are models/moe/brumby.py's,
    named in its docstring. The equations are in
    models/reference/brumby.py; the served path in models/moe/brumby.py.

    `max_window_layers` is not read at all (Qwen's sliding-window
    switch, meaningless where `use_sliding_window` must be false).
    `num_local_experts` is 0, so `is_moe` says by itself that the seeded
    draw and the trunk live under models/moe."""

    _family = "cake_tpu.models.moe.brumby:FAMILY"

    attn_head_dim: int = 128

    is_moe = True

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    @property
    def group_size(self) -> int:
        """Query heads that share a K/V head's state."""
        return self.num_attention_heads // self.num_key_value_heads

    @classmethod
    def from_hf_dict(cls, raw: dict) -> "BrumbyConfig":
        for name, want in _BRUMBY_ONLY.items():
            if raw.get(name, want) != want:
                raise ValueError(
                    f"{name} = {raw[name]!r}: model_type brumby serves "
                    f"{want!r} only (not implemented)")
        hd = raw.get("head_dim",
                     raw["hidden_size"] // raw["num_attention_heads"])
        if hd % 16:
            raise ValueError(
                f"head_dim = {hd}: the retention state keeps a head's "
                "symmetric square in tiles of 16 x 16 (ops/retention.py); "
                "head_dim must be a multiple of 16")
        if raw["num_attention_heads"] % raw.get(
                "num_key_value_heads", raw["num_attention_heads"]):
            raise ValueError(
                "num_key_value_heads must divide num_attention_heads")
        base = LlamaConfig.from_hf_dict(dict(
            raw, eos_token_id=raw.get("eos_token_id", raw["vocab_size"])))
        fields = {f: getattr(base, f) for f in base.__dataclass_fields__}
        fields["chat_template"] = "chatml"
        return cls(**fields, num_local_experts=0, num_experts_per_tok=0,
                   hf_layout="brumby", attn_head_dim=hd)

    @classmethod
    def tiny_brumby(cls, **overrides) -> "BrumbyConfig":
        """Brumby's layer at a test's size: 3 layers, 4 query heads over
        2 K/V heads of 16 (a group of 2 shares a state of 256 x 16)."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, rms_norm_eps=1e-6, rope_theta=1e6,
            max_position_embeddings=256, bos_token_id=1,
            eos_token_ids=(512,), tie_word_embeddings=False,
            chat_template="chatml",
            num_local_experts=0, num_experts_per_tok=0,
            hf_layout="brumby", attn_head_dim=16,
        )
        base.update(overrides)
        return cls(**base)
