"""What a step or a kernel needs, from shapes alone.

The least bytes and operations the algorithm must move and do: weights
read once, the live part of the KV cache read once, padding and copies
not counted. Divided by the peaks they give the least time the chip
could take; a measured time over that is the roofline share.
"""

from __future__ import annotations


def dims(model_config: dict) -> dict:
    h = model_config["hidden_size"]
    heads = model_config["num_attention_heads"]
    return {"L": model_config["num_hidden_layers"], "h": h,
            "heads": heads,
            "kv": model_config.get("num_key_value_heads", heads),
            "hd": model_config.get("head_dim", h // heads),
            "ffn": model_config["intermediate_size"],
            "vocab": model_config["vocab_size"],
            "tied": bool(model_config.get("tie_word_embeddings", False))}


def layer_weight_params(d: dict) -> int:
    """Matrix parameters of one decoder block (norms and biases are
    thousands against hundreds of millions, and are left out)."""
    attn = d["h"] * (d["heads"] * d["hd"]) * 2 \
        + d["h"] * (d["kv"] * d["hd"]) * 2
    return attn + 3 * d["h"] * d["ffn"]


def decode_weight_bytes(model_config: dict, weight_bytes: float = 1.0) -> float:
    """Bytes of weights one decode step must read: every block, and the
    output head (the embedding is a gather of a few rows)."""
    d = dims(model_config)
    return weight_bytes * (d["L"] * layer_weight_params(d)
                           + d["h"] * d["vocab"])


def kv_bytes_per_token(model_config: dict, kv_bytes: float = 2.0) -> float:
    """K and V of one position, all layers."""
    d = dims(model_config)
    return 2 * d["L"] * d["kv"] * d["hd"] * kv_bytes


def decode_step_least_s(model_config: dict, live_tokens: float, peak: dict,
                        weight_bytes: float = 1.0, kv_bytes: float = 2.0,
                        stages: int = 1, tp: int = 1) -> float:
    """Least time of one decode step, bandwidth bound: weights plus the
    live KV over the HBM rate. Across chips each of stages * tp chips
    holds an equal share of the bytes; the tp chips of a stage read at
    the same time and the stages run in turn, so the least time is the
    sum over the stages of one chip's bytes over one chip's rate."""
    total = (decode_weight_bytes(model_config, weight_bytes)
             + live_tokens * kv_bytes_per_token(model_config, kv_bytes))
    one_chip = total / (stages * tp)
    return stages * one_chip / peak["hbm_bytes_per_s"]


def attention_need(model_config: dict, rows: list, kv_bytes: float = 2.0,
                   act_bytes: float = 2.0) -> tuple:
    """(bytes, operations) one attention call of ONE layer needs for
    `rows` = [(query tokens, context length after them)]: K and V of
    each row's context once, q in and out once, and 4 * heads * hd
    operations per (query, key) pair under the causal mask."""
    d = dims(model_config)
    nbytes = ops = 0.0
    for q, ctx in rows:
        nbytes += 2 * ctx * d["kv"] * d["hd"] * kv_bytes
        nbytes += 2 * q * d["heads"] * d["hd"] * act_bytes
        # a query at offset i of its window sees ctx - q + i + 1 keys
        pairs = q * (ctx - q) + q * (q + 1) / 2.0
        ops += 4.0 * d["heads"] * d["hd"] * pairs
    return nbytes, ops


def least_s(nbytes: float, ops: float, peak: dict) -> tuple:
    """(seconds, which bound): the larger of bytes over the HBM rate and
    operations over the bf16 peak."""
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    by_ops = ops / peak["bf16_flops"]
    return (by_bytes, "bandwidth") if by_bytes >= by_ops else (by_ops, "compute")
