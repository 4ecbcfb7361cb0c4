"""What ZAYA1's top-1 expert sublayer needs, from counts alone.

`moe_roofline.moe_dims` reads `intermediate_size`; a `zaya` config
states an expert's width as `moe_intermediate_size` and has no other
FFN. This file is that mapping and nothing else: the least bytes and
operations are `moe_roofline`'s (each touched expert's gate, up and
down read once, each routed row read once and written once, 2 * D * F
operations per row and projection), with one row a token.
"""

from __future__ import annotations

from . import moe_roofline


def as_moe_config(model_config: dict) -> dict:
    """The config as `moe_roofline` reads one: an expert's width under
    `intermediate_size`."""
    return dict(model_config,
                intermediate_size=model_config["moe_intermediate_size"])


def expert_params(model_config: dict) -> int:
    """Matrix parameters of one expert: gate, up, down."""
    return moe_roofline.expert_params(as_moe_config(model_config))


def experts_least_s(model_config: dict, rows: float, touched: float,
                    peak: dict, weight_bytes: float = 1.0,
                    act_bytes: float = 2.0) -> float:
    """Least seconds of ONE layer's grouped matmuls for `rows` routed
    tokens over `touched` distinct experts."""
    return moe_roofline.experts_least_s(
        as_moe_config(model_config), rows, touched, peak,
        weight_bytes=weight_bytes, act_bytes=act_bytes)
