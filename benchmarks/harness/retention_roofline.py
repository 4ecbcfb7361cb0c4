"""What a `brumby` model's power retention layers and its decode step
need, from counts alone.

Every layer keeps, a row and K/V head, the symmetric square of its keys
against their values: S [D, hd] and the normaliser z [D], float32. D is
counted at hd (hd + 1) / 2, 8,256 at heads of 128: the LEAST any layout
of the symmetric square holds, not what the served path keeps (9,216 in
16 x 16 tiles), so a change of layout moves the share and no share can
read over 100 for it.

THE ONE-STEP UPDATE (a row's single token) is bound by the state it
carries: each (row, layer) reads S and z of its KV heads once and writes
them once; the token's own q, k, v and gate are a few kilobytes beside
them and are not counted (a floor).

THE WINDOW FORM (a prompt's window of `width` tokens), per token and
layer, whatever implements it: every query head reads the carried state
(2 H D (hd + 1): the numerator's hd columns and the normaliser's one),
every K/V head's state takes the token's phi(k) v^T (2 KV D (hd + 1)),
and inside the window a token attends the tokens before it, (width + 1)
/ 2 on average (the scores 2 H hd and the weighted sum 2 H hd each).
Bytes: q, k, v read and y written in the activations' type, and the
window's state read once and written once, shared by its `width` tokens.
At the published widths a token and layer needs 107.5 MFLOP (0.55 us at
the bf16 peak of a v5e) and 158 KB (0.19 us at its HBM rate): the need
is the greater, the operations'.

THE DECODE STEP reads every matrix once (q, k, v, o, the gate in
float32, the SwiGLU, the untied head) and reads and writes the state of
every LIVE row in every layer. Norms, the embedding's gathered rows and
activations are not counted: a floor.
"""

from __future__ import annotations

KEYS = ("head_dim", "num_attention_heads", "num_key_value_heads",
        "hidden_size", "intermediate_size", "vocab_size",
        "num_hidden_layers")


def dims(model_config: dict):
    """The sizes this file reads, or None on a config of another family
    (model_type) or one that lacks a key."""
    c = model_config
    if c.get("model_type") != "brumby" or any(k not in c for k in KEYS):
        return None
    hd = c["head_dim"]
    return {"H": c["num_attention_heads"], "KV": c["num_key_value_heads"],
            "hd": hd, "D": hd * (hd + 1) // 2, "L": c["num_hidden_layers"],
            "hidden": c["hidden_size"], "F": c["intermediate_size"],
            "V": c["vocab_size"]}


def state_bytes(d: dict) -> float:
    """One row's S and z in one layer, float32."""
    return d["KV"] * d["D"] * (d["hd"] + 1) * 4.0


def step_least_s(model_config: dict, row_layers: float, peak: dict):
    """Least seconds of the one-step update of `row_layers` (row, layer)
    pairs: the state once each way."""
    d = dims(model_config)
    if d is None:
        return None
    return row_layers * 2.0 * state_bytes(d) / peak["hbm_bytes_per_s"]


def window_need(model_config: dict, token_layers: float, width: int,
                act_bytes: float = 2.0):
    """(bytes, operations) of the window form of `token_layers` (token,
    layer) pairs at windows of `width` tokens."""
    d = dims(model_config)
    if d is None:
        return None
    H, KV, hd, D = d["H"], d["KV"], d["hd"], d["D"]
    ops = token_layers * (2.0 * (H + KV) * D * (hd + 1)
                          + H * 4.0 * hd * (width + 1) / 2.0)
    nbytes = token_layers * ((2 * H + 2 * KV) * hd * act_bytes
                             + 2.0 * state_bytes(d) / width)
    return nbytes, ops


def window_least_s(model_config: dict, token_layers: float, width: int,
                   peak: dict, act_bytes: float = 2.0):
    need = window_need(model_config, token_layers, width, act_bytes)
    if need is None:
        return None
    return max(need[0] / peak["hbm_bytes_per_s"],
               need[1] / peak["bf16_flops"])


def weight_bytes_of(model_config: dict, weight_bytes: float = 1.0):
    """Bytes of the matrices a decode step reads (module docstring)."""
    d = dims(model_config)
    if d is None:
        return None
    Dm, H, KV, hd = d["hidden"], d["H"], d["KV"], d["hd"]
    layer = (2 * Dm * H * hd + 2 * Dm * KV * hd + 3 * Dm * d["F"])
    return (weight_bytes * (d["L"] * layer + Dm * d["V"])
            + 4.0 * d["L"] * Dm * KV)


def decode_step_least_s(model_config: dict, row_layers: float, peak: dict,
                        weight_bytes: float = 1.0):
    """Least seconds of ONE decode step: the weights and 2 x the state
    of `row_layers` (live row, layer) pairs."""
    d = dims(model_config)
    if d is None:
        return None
    return ((weight_bytes_of(model_config, weight_bytes)
             + row_layers * 2.0 * state_bytes(d))
            / peak["hbm_bytes_per_s"])
