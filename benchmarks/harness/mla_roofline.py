"""What latent attention over SELECTED cache rows needs, from counts
alone (glm_moe_dsa: every head of a query attends the same list of
rows, and a row is one latent of `kv_lora_rank + qk_rope_head_dim`
numbers).

Operations: per (query, selected key) and head, one dot over the whole
row for the score and one multiply-add over the row's first
`kv_lora_rank` numbers for the value: heads * (row + value) * 2.
Bytes: each DISTINCT cache row a dispatch selected, read once; the
queries and results are small beside them and are not counted (so the
need is a floor, and a share of it cannot pass 100 %). A row's stored
padding (the program keeps 640 numbers for 576) is NOT needed.
"""

from __future__ import annotations


def mla_dims(model_config: dict) -> dict:
    return {"L": model_config["num_hidden_layers"],
            "L_full": sum(t == "full"
                          for t in model_config["indexer_types"]),
            "H": model_config["num_attention_heads"],
            "row": (model_config["kv_lora_rank"]
                    + model_config["qk_rope_head_dim"]),
            "value": model_config["kv_lora_rank"]}


def attn_need(model_config: dict, selected: float, distinct: float,
              cache_bytes: float = 2.0) -> tuple:
    """(bytes, operations) of ONE layer's attention over `selected`
    (query, key) pairs that touch `distinct` cache rows."""
    d = mla_dims(model_config)
    ops = selected * d["H"] * (d["row"] + d["value"]) * 2.0
    return distinct * d["row"] * cache_bytes, ops


def attn_least_s(model_config: dict, selected: float, distinct: float,
                 peak: dict, cache_bytes: float = 2.0) -> float:
    nbytes, ops = attn_need(model_config, selected, distinct, cache_bytes)
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["bf16_flops"])
