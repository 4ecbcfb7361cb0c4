"""What latent attention over EVERY visible key needs, from counts alone
(deepseek_v2: no indexer; every head of a query attends every key of
its row up to its own position, and a row is one latent of
`kv_lora_rank + qk_rope_head_dim` numbers that all heads share).

Operations: per (query, key) and head, one dot over the whole row for
the score and one multiply-add over the row's first `kv_lora_rank`
numbers for the value: heads * (row + value) * 2 (128 x (576 + 512) x 2
= 278,528 at the published widths). Bytes: each key's row read once by
the query (or the window of queries) that attends it: row * cache bytes
(576 x 2 = 1,152); queries and results are not counted, nor a row's
stored padding (640 kept for 576), so the need is a floor and a share
of it cannot pass 100 %. At those widths a decode row's key costs 1.41
ns by the bf16 peak and 1.41 ns by HBM on a v5e: the kernel sits on the
ridge, and the need is the greater of the two.

`L` is the number of LATENT layers, which is what a step record's
`mla_keys_attended` sums over: every layer of a DeepSeek-V2 config; in a
config with `layer_group_size` (bailing_hybrid: Ling-3.0) layer i is
latent where (i + 1) mod `layer_group_size` = 0 and the others are KDA,
2 of 12 in the Ling cell. At Ling's 32 heads a key costs 0.35 ns by the
bf16 peak and the same 1.41 ns by HBM: bytes bound.
"""

from __future__ import annotations


def latent_layers(model_config: dict) -> int:
    layers = model_config["num_hidden_layers"]
    return layers // model_config.get("layer_group_size", 1)


def dims(model_config: dict) -> dict:
    return {"L": latent_layers(model_config),
            "H": model_config["num_attention_heads"],
            "row": (model_config["kv_lora_rank"]
                    + model_config["qk_rope_head_dim"]),
            "value": model_config["kv_lora_rank"]}


def ops_per_pair(model_config: dict) -> float:
    d = dims(model_config)
    return d["H"] * (d["row"] + d["value"]) * 2.0


def bytes_per_key(model_config: dict, cache_bytes: float = 2.0) -> float:
    return dims(model_config)["row"] * cache_bytes


def least_s(model_config: dict, pairs: float, keys: float, peak: dict,
            cache_bytes: float = 2.0) -> float:
    """Least seconds of ONE layer's attention over `pairs` (query, key)
    pairs that read `keys` cache rows (a decode row: keys == pairs; a
    window's queries share their row's keys)."""
    return max(keys * bytes_per_key(model_config, cache_bytes)
               / peak["hbm_bytes_per_s"],
               pairs * ops_per_pair(model_config) / peak["bf16_flops"])
