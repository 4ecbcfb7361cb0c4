"""What a Mamba-2 block's recurrence needs, from counts alone
(nemotron_h: H heads of P over a state of N a head, G groups).

THE ONE-STEP UPDATE (a row's single token) is bound by the state it
carries: each (row, block) reads its float32 state [H, P, N] once and
writes it once; the token's own inputs are a few kilobytes beside it and
are not counted (so the need is a floor).

THE CHUNKED SCAN (a prompt's window) in chunks of Q tokens, per token
and block: the scores C.B inside the chunk (2 G Q N operations), the
masked scores times the inputs (2 H Q P), what the token adds to the
chunk's state and what it reads from the state the chunk started from
(2 H P N each). The counts are the chunked form's OWN: an implementation
that recurs token by token does fewer operations and cannot read over
100 % for that. Bytes: the token's conv output read once (xs, B, C) and
its result written once, in the activations' type; the window's state
read and write are left out (a floor).
"""

from __future__ import annotations


def ssm_dims(model_config: dict) -> dict:
    c = model_config
    H, P = c["mamba_num_heads"], c["mamba_head_dim"]
    G, N = c["n_groups"], c["ssm_state_size"]
    return {"H": H, "P": P, "G": G, "N": N, "Q": c.get("chunk_size", 128),
            "d_inner": H * P, "conv_dim": H * P + 2 * G * N,
            "L_M": c["hybrid_override_pattern"].count("M")}


def state_bytes(model_config: dict, state_itemsize: float = 4.0) -> float:
    """One row's SSM state in one block."""
    d = ssm_dims(model_config)
    return d["H"] * d["P"] * d["N"] * state_itemsize


def step_need_bytes(model_config: dict, row_blocks: float,
                    state_itemsize: float = 4.0) -> float:
    """Bytes the one-step update of `row_blocks` (row, block) pairs
    needs: the state read once and written once."""
    return row_blocks * 2.0 * state_bytes(model_config, state_itemsize)


def step_least_s(model_config: dict, row_blocks: float, peak: dict,
                 state_itemsize: float = 4.0) -> float:
    return (step_need_bytes(model_config, row_blocks, state_itemsize)
            / peak["hbm_bytes_per_s"])


def scan_need(model_config: dict, token_blocks: float,
              act_bytes: float = 2.0) -> tuple:
    """(bytes, operations) the chunked scan of `token_blocks` (token,
    block) pairs needs."""
    d = ssm_dims(model_config)
    ops = token_blocks * (2.0 * d["G"] * d["Q"] * d["N"]
                          + 2.0 * d["H"] * d["Q"] * d["P"]
                          + 4.0 * d["H"] * d["P"] * d["N"])
    nbytes = token_blocks * (d["conv_dim"] + d["d_inner"]) * act_bytes
    return nbytes, ops


def scan_least_s(model_config: dict, token_blocks: float, peak: dict,
                 act_bytes: float = 2.0) -> float:
    nbytes, ops = scan_need(model_config, token_blocks, act_bytes)
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["bf16_flops"])
