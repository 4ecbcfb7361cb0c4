"""What a `granitemoehybrid` model's Mamba-2 mixers and its decode step
need, from counts alone.

`ssm_roofline.ssm_dims` reads the recurrence's sizes under model_type
nemotron_h's key names; a `granitemoehybrid` config publishes the same
quantities as `mamba_n_heads`, `mamba_d_head`, `mamba_n_groups`,
`mamba_d_state`, `mamba_chunk_size`, and names a layer's kind in
`layer_types`. This file is that mapping (`as_ssm_config`), so that the
least bytes and operations of the one-step update and of the chunked
scan are `ssm_roofline`'s own, the same work whatever implements it,
and the decode step's need for this family:

THE DECODE STEP reads every matrix once (a Mamba layer's `in_proj` and
`out_proj`, an attention layer's q, k, v and o, the dense SwiGLU of
`shared_intermediate_size` in every layer, the tied head), reads and
writes the float32 state of every LIVE row in every Mamba layer, and
reads the live rows' K and V in the attention layers. Conv tails, norms,
the embedding's gathered rows and activations are not counted: a floor.
"""

from __future__ import annotations

from . import ssm_roofline

KEYS = ("mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state",
        "layer_types", "hidden_size", "shared_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "vocab_size")


def as_ssm_config(model_config: dict):
    """The config as `ssm_roofline` reads one, or None where a key this
    file reads is absent (another family's config)."""
    c = model_config
    if any(k not in c for k in KEYS):
        return None
    return {"mamba_num_heads": c["mamba_n_heads"],
            "mamba_head_dim": c["mamba_d_head"],
            "n_groups": c["mamba_n_groups"],
            "ssm_state_size": c["mamba_d_state"],
            "chunk_size": c.get("mamba_chunk_size", 256),
            "hybrid_override_pattern": "M" * c["layer_types"].count("mamba")}


def mamba_layers(model_config: dict):
    cfg = as_ssm_config(model_config)
    return None if cfg is None else ssm_roofline.ssm_dims(cfg)["L_M"]


def step_least_s(model_config: dict, row_layers: float, peak: dict):
    """Least seconds of the one-step update of `row_layers` (row, Mamba
    layer) pairs: `ssm_roofline.step_least_s`."""
    cfg = as_ssm_config(model_config)
    return None if cfg is None else ssm_roofline.step_least_s(
        cfg, row_layers, peak)


def scan_least_s(model_config: dict, token_layers: float, peak: dict,
                 act_bytes: float = 2.0):
    """Least seconds of the chunked scan of `token_layers` (token,
    Mamba layer) pairs: `ssm_roofline.scan_least_s`."""
    cfg = as_ssm_config(model_config)
    return None if cfg is None else ssm_roofline.scan_least_s(
        cfg, token_layers, peak, act_bytes)


def weight_params(model_config: dict) -> float:
    """Matrix parameters a decode step reads (module docstring)."""
    c = model_config
    d = ssm_roofline.ssm_dims(as_ssm_config(c))
    D, F = c["hidden_size"], c["shared_intermediate_size"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim", D // heads)
    mamba = (D * (d["d_inner"] + d["conv_dim"] + d["H"])
             + d["d_inner"] * D)
    attn = 2 * D * heads * hd + 2 * D * kv * hd
    n_attn = len(c["layer_types"]) - d["L_M"]
    return (d["L_M"] * mamba + n_attn * attn
            + len(c["layer_types"]) * 3 * D * F + D * c["vocab_size"])


def decode_step_need_bytes(model_config: dict, row_layers: float,
                           live_keys: float, weight_bytes: float = 1.0,
                           kv_bytes: float = 2.0) -> float:
    """Bytes of ONE decode step: the weights, 2 x the state of
    `row_layers` (live row, Mamba layer) pairs, and K and V of
    `live_keys` cache positions (summed over the rows) in every
    attention layer."""
    c = model_config
    cfg = as_ssm_config(c)
    n_attn = len(c["layer_types"]) - ssm_roofline.ssm_dims(cfg)["L_M"]
    hd = c.get("head_dim", c["hidden_size"] // c["num_attention_heads"])
    return (weight_bytes * weight_params(c)
            + ssm_roofline.step_need_bytes(cfg, row_layers)
            + n_attn * live_keys * 2 * c["num_key_value_heads"] * hd
            * kv_bytes)


def decode_step_least_s(model_config: dict, row_layers: float,
                        live_keys: float, peak: dict,
                        weight_bytes: float = 1.0, kv_bytes: float = 2.0):
    if as_ssm_config(model_config) is None:
        return None
    return decode_step_need_bytes(
        model_config, row_layers, live_keys, weight_bytes,
        kv_bytes) / peak["hbm_bytes_per_s"]
