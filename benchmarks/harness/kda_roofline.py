"""What Kimi Delta Attention's delta rule needs, from counts alone
(bailing_hybrid: H heads, a float32 state [d_k, d_v] a row, layer and
head, d_k = d_v = `head_dim`).

THE ONE-STEP UPDATE (a row's single token) is bound by the state it
carries: each (row, layer) reads its state [H, d_k, d_v] once and writes
it once; the token's own q, k, v, decay and beta are a few kilobytes
beside it and are not counted (so the need is a floor).

THE CHUNKED FORM (a prompt's window) at a STATED chunk of Q = 64 tokens,
whatever chunk the program picks, per token, layer and head:
the decayed scores inside the chunk, K+ K-^T and Q+ K-^T (2 Q d_k each);
the unit-lower-triangular solve (2 Q^2 / 3: a chunk's Q^3 / 3
multiply-adds over its Q tokens); T applied to beta V and beta K+
(2 Q d_v, 2 Q d_k); the scores times U (2 Q d_v); and the three products
with the state the chunk started from and leaves (W_k S, Q+ S, K_end^T U:
2 d_k d_v each). The counts are the chunked form's OWN: an
implementation that recurs token by token, or chunks finer, does fewer
operations and cannot read over 100 % for that. Bytes: the token's q, k,
v read once and its output written once in the activations' type, its
decay (d_k a head) and beta read in float32; the window's state read and
write are left out (a floor). At the published widths a token and layer
needs 5.85 MFLOP (30 ns at the bf16 peak of a v5e) and 49 KB (60 ns at
its HBM rate): the need is the greater, the bytes'.
"""

from __future__ import annotations

CHUNK = 64


def kda_dims(model_config: dict) -> dict:
    c = model_config
    period = c["layer_group_size"]
    return {"H": c["num_attention_heads"], "dk": c["head_dim"],
            "dv": c["head_dim"],
            "L_kda": sum((i + 1) % period != 0
                         for i in range(c["num_hidden_layers"]))}


def state_bytes(model_config: dict, state_itemsize: float = 4.0) -> float:
    """One row's KDA state in one layer."""
    d = kda_dims(model_config)
    return d["H"] * d["dk"] * d["dv"] * state_itemsize


def step_need_bytes(model_config: dict, row_layers: float,
                    state_itemsize: float = 4.0) -> float:
    """Bytes the one-step update of `row_layers` (row, layer) pairs
    needs: the state read once and written once."""
    return row_layers * 2.0 * state_bytes(model_config, state_itemsize)


def step_least_s(model_config: dict, row_layers: float, peak: dict,
                 state_itemsize: float = 4.0) -> float:
    return (step_need_bytes(model_config, row_layers, state_itemsize)
            / peak["hbm_bytes_per_s"])


def chunk_need(model_config: dict, token_layers: float,
               act_bytes: float = 2.0, chunk: int = CHUNK) -> tuple:
    """(bytes, operations) the chunked form of `token_layers` (token,
    layer) pairs needs at a chunk of `chunk` tokens."""
    d = kda_dims(model_config)
    H, dk, dv, Q = d["H"], d["dk"], d["dv"], float(chunk)
    ops = token_layers * H * (3 * 2.0 * Q * dk + 2 * 2.0 * Q * dv
                              + 2.0 * Q * Q / 3.0 + 3 * 2.0 * dk * dv)
    nbytes = token_layers * H * ((2 * dk + 2 * dv) * act_bytes
                                 + (dk + 1) * 4.0)
    return nbytes, ops


def chunk_least_s(model_config: dict, token_layers: float, peak: dict,
                  act_bytes: float = 2.0) -> float:
    nbytes, ops = chunk_need(model_config, token_layers, act_bytes)
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["bf16_flops"])
