"""What plain GQA needs for its attention in a model whose layers are of
two kinds (exaone_moe: `layer_types` of `sliding_attention`, where a
query attends its last `sliding_window` keys, its own included, and
`full_attention`, every key at or before it), from counts alone.

One (query, key) pair costs every query head one dot over head_dim for
the score and one multiply-add over head_dim for the value: heads *
head_dim * 4 operations. Bytes: each DISTINCT key's K and V row read
once (kv_heads * head_dim numbers each), and each query's heads in and
out once; pages' unused slots, the re-reads of a window handed over in
several entries and the scratch are not counted, so the need is a floor
and a share of it cannot pass 100 %. A banded call is charged for the
keys its band ATTENDS, not for the keys visible.
"""

from __future__ import annotations


def gqa_dims(model_config: dict) -> dict:
    types = model_config["layer_types"]
    heads = model_config["num_attention_heads"]
    return {"L_sliding": sum(t == "sliding_attention" for t in types),
            "L_full": sum(t == "full_attention" for t in types),
            "H": heads,
            "KV": model_config.get("num_key_value_heads", heads),
            "hd": model_config.get(
                "head_dim", model_config["hidden_size"] // heads),
            "window": model_config["sliding_window"]}


def attn_need(model_config: dict, pairs: float, distinct_keys: float,
              queries: float, kv_bytes: float = 2.0,
              act_bytes: float = 2.0) -> tuple:
    """(bytes, operations) of attention over `pairs` (query, key) pairs
    that touch `distinct_keys` cache positions for `queries` query
    tokens, summed over whatever layers the counts are summed over."""
    d = gqa_dims(model_config)
    ops = 4.0 * d["H"] * d["hd"] * pairs
    nbytes = (2.0 * distinct_keys * d["KV"] * d["hd"] * kv_bytes
              + 2.0 * queries * d["H"] * d["hd"] * act_bytes)
    return nbytes, ops


def attn_least_s(model_config: dict, pairs: float, distinct_keys: float,
                 queries: float, peak: dict, kv_bytes: float = 2.0,
                 act_bytes: float = 2.0) -> float:
    nbytes, ops = attn_need(model_config, pairs, distinct_keys, queries,
                            kv_bytes, act_bytes)
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["bf16_flops"])


def window_context(pairs: float, queries: float) -> float:
    """The context a window of `queries` tokens ends at in a FULL layer,
    from its pairs: a window of n queries that ends at context c has
    n * c - n (n - 1) / 2 pairs under causality."""
    return pairs / queries + (queries - 1) / 2.0
