"""What attention over SELECTED keys of ordinary K/V pages needs (a
learned sparse indexer over GQA: `sa_config` beside the GQA keys), from
counts alone.

A query of a layer attends n = min(t + 1, topk) keys. One (query, key)
pair costs every query head one dot over head_dim for the score and one
multiply-add over head_dim for the value: heads * head_dim * 4
operations. Bytes: each DISTINCT selected key's K and V row read once
(kv_heads * head_dim numbers each: a window's queries share their row's
keys, so a row two queries chose is counted once), and each query's
heads in and out once. What an implementation reads beside that (whole
pages under a mask, a gather's copy, the selection itself) is not
counted, so the need is a floor and a share of it cannot pass 100 %.

The mapping is this file's own: it asks for every key before it reads
it and returns None on a config it does not know.
"""

from __future__ import annotations


def dsa_gqa_dims(model_config: dict):
    """The sizes, or None where the config is not of this kind."""
    sa = model_config.get("sa_config")
    if not isinstance(sa, dict) or "topk" not in sa:
        return None
    for key in ("num_hidden_layers", "num_attention_heads", "hidden_size"):
        if key not in model_config:
            return None
    heads = model_config["num_attention_heads"]
    return {"L": model_config["num_hidden_layers"], "H": heads,
            "KV": model_config.get("num_key_value_heads", heads),
            "hd": model_config.get(
                "head_dim", model_config["hidden_size"] // heads),
            "topk": sa["topk"]}


def attn_need(dims: dict, pairs: float, distinct_keys: float,
              queries: float, kv_bytes: float = 2.0,
              act_bytes: float = 2.0) -> tuple:
    """(bytes, operations) of attention over `pairs` (query, selected
    key) pairs that touch `distinct_keys` cache rows for `queries`
    query tokens, summed over whatever layers the counts are summed
    over."""
    ops = 4.0 * dims["H"] * dims["hd"] * pairs
    nbytes = (2.0 * distinct_keys * dims["KV"] * dims["hd"] * kv_bytes
              + 2.0 * queries * dims["H"] * dims["hd"] * act_bytes)
    return nbytes, ops


def attn_least_s(dims: dict, pairs: float, distinct_keys: float,
                 queries: float, peak: dict, kv_bytes: float = 2.0,
                 act_bytes: float = 2.0) -> float:
    nbytes, ops = attn_need(dims, pairs, distinct_keys, queries, kv_bytes,
                            act_bytes)
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["bf16_flops"])
