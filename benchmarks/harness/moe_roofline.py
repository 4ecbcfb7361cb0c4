"""What a sparse-expert FFN's grouped matmuls need, from counts alone.

`roofline.py` reads `intermediate_size` as one dense FFN; in a
mixture-of-experts config it is the width of ONE expert, and what a step
needs depends on how many (token, expert) rows it routed and how many
experts those rows touched. The least bytes and operations: each touched
expert's three weight matrices read once, each routed row read once and
written once, 2 * D * F operations per row and projection. Rows that
only pad a tile, and experts no row touched, are NOT needed. Divided by
the peaks they give the least time the chip could take.
"""

from __future__ import annotations


def moe_dims(model_config: dict) -> dict:
    return {"L": model_config["num_hidden_layers"],
            "D": model_config["hidden_size"],
            "F": model_config["intermediate_size"],
            "E": model_config.get("num_experts",
                                  model_config.get("num_local_experts")),
            "k": model_config["num_experts_per_tok"]}


def expert_params(model_config: dict) -> int:
    """Matrix parameters of one expert: gate, up, down."""
    d = moe_dims(model_config)
    return 3 * d["D"] * d["F"]


def experts_need(model_config: dict, rows: float, touched: float,
                 weight_bytes: float = 1.0, act_bytes: float = 2.0) -> tuple:
    """(bytes, operations) the grouped matmuls of ONE layer need for
    `rows` routed (token, expert) rows over `touched` distinct experts."""
    d = moe_dims(model_config)
    nbytes = (touched * expert_params(model_config) * weight_bytes
              + rows * 2 * d["D"] * act_bytes)
    ops = rows * 2.0 * expert_params(model_config)
    return nbytes, ops


def experts_least_s(model_config: dict, rows: float, touched: float,
                    peak: dict, weight_bytes: float = 1.0,
                    act_bytes: float = 2.0) -> float:
    """Least seconds of one layer's grouped matmuls: the larger of the
    bytes over the HBM rate and the operations over the bf16 peak (the
    int8 weights are widened to bf16 before the MXU sees them)."""
    nbytes, ops = experts_need(model_config, rows, touched, weight_bytes,
                               act_bytes)
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["bf16_flops"])
