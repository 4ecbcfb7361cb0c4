"""What LatentMoE's grouped matmuls need, from counts alone (nemotron_h:
the routed experts live in a `moe_latent_size`-wide latent, two matrices
an expert, `up` [R, F] and `down` [F, R], relu² between them, no gate).

The least bytes and operations of ONE block: each touched expert's two
matrices read once, each routed row read once and written once in the
latent, 2 * R * F operations per row and projection. Rows that only pad
a tile, experts no row touched, and the pairs routed to experts another
chip holds are NOT needed. The latent projections and the shared expert
are dense matmuls outside the kernel and are not counted here.
"""

from __future__ import annotations


def expert_params(model_config: dict) -> int:
    """Matrix parameters of one routed expert: up, down."""
    return 2 * model_config["moe_latent_size"] * \
        model_config["moe_intermediate_size"]


def experts_need(model_config: dict, rows: float, touched: float,
                 weight_bytes: float = 1.0, act_bytes: float = 2.0) -> tuple:
    """(bytes, operations) the grouped matmuls of ONE block need for
    `rows` (token, held expert) rows over `touched` distinct experts."""
    nbytes = (touched * expert_params(model_config) * weight_bytes
              + rows * 2 * model_config["moe_latent_size"] * act_bytes)
    return nbytes, rows * 2.0 * expert_params(model_config)


def experts_least_s(model_config: dict, rows: float, touched: float,
                    peak: dict, weight_bytes: float = 1.0,
                    act_bytes: float = 2.0) -> float:
    nbytes, ops = experts_need(model_config, rows, touched, weight_bytes,
                               act_bytes)
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["bf16_flops"])
