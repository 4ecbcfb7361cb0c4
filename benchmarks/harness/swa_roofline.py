"""What a model with sliding-window latent layers beside its full ones
needs for its attention, from counts alone (dots3_note: a sliding
layer's query attends the last `sliding_window_size` keys of its row,
its own included, and a row there is one latent of `swa_kv_lora_rank +
swa_qk_rope_head_dim` numbers that all `swa_num_attention_heads` heads
share).

Sliding layers. Operations: per (query, attended key) and head, one dot
over the whole row for the score and one multiply-add over the row's
first `swa_kv_lora_rank` numbers for the value: heads * (row + value) *
2 (64 x (1,088 + 1,024) x 2 at the published widths). Bytes: each
DISTINCT cache row a dispatch attended, read once; queries and results
are not counted, nor a row's stored padding (1,152 kept for 1,088), so
the need is a floor and a share of it cannot pass 100 %.

Full layers. `mla_roofline`'s arithmetic (selected pairs x heads x (row
+ value) x 2, distinct rows x row x bytes) at THIS model's full
geometry; `mla_roofline.mla_dims` reads `indexer_types`, which a
`dots3_note` config does not have (it has `layer_types`):
`as_full_config` is that mapping and nothing else.
"""

from __future__ import annotations

from . import mla_roofline


def swa_dims(model_config: dict) -> dict:
    return {"L_sliding": sum(t == "sliding_attention"
                             for t in model_config["layer_types"]),
            "L_full": sum(t == "full_attention"
                          for t in model_config["layer_types"]),
            "H": model_config["swa_num_attention_heads"],
            "row": (model_config["swa_kv_lora_rank"]
                    + model_config["swa_qk_rope_head_dim"]),
            "value": model_config["swa_kv_lora_rank"],
            "window": model_config["sliding_window_size"]}


def swa_need(model_config: dict, attended: float, distinct: float,
             cache_bytes: float = 2.0) -> tuple:
    """(bytes, operations) of ONE sliding layer's attention over
    `attended` (query, key) pairs that touch `distinct` cache rows."""
    d = swa_dims(model_config)
    ops = attended * d["H"] * (d["row"] + d["value"]) * 2.0
    return distinct * d["row"] * cache_bytes, ops


def swa_least_s(model_config: dict, attended: float, distinct: float,
                peak: dict, cache_bytes: float = 2.0) -> float:
    nbytes, ops = swa_need(model_config, attended, distinct, cache_bytes)
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["bf16_flops"])


def as_full_config(model_config: dict) -> dict:
    """The full layers alone as `mla_roofline` reads a config: every
    one of them computes its own key sets."""
    n = swa_dims(model_config)["L_full"]
    return dict(model_config, num_hidden_layers=n,
                indexer_types=["full"] * n)


def full_least_s(model_config: dict, selected: float, distinct: float,
                 peak: dict, cache_bytes: float = 2.0) -> float:
    """Least seconds of ONE full layer's attention over `selected`
    pairs that touch `distinct` rows."""
    return mla_roofline.attn_least_s(as_full_config(model_config), selected,
                                     distinct, peak, cache_bytes)
