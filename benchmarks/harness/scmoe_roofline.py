"""A shortcut-connected MoE model's config (longcat_flash) as
`mla_dense_roofline` reads one.

The published config spells its depth `num_layers`, and a layer holds
TWO latent attentions (two sublayers, each with a layer of the latent
pool), so a step record's `mla_keys_attended` sums over 2 x `num_layers`
latent layers. This file is that mapping and nothing else: the
operations and bytes of a (query, key) pair stay `mla_dense_roofline`'s
(heads x (row + value) x 2 operations, row x cache bytes: 139,264 and
1,152 B at 64 heads over a 576-wide row), so a kernel's share reads the
same work whatever implements it.
"""

from __future__ import annotations

KEYS = ("num_layers", "num_attention_heads", "kv_lora_rank",
        "qk_rope_head_dim", "zero_expert_num")


def known(model_config: dict) -> bool:
    """Whether the config is one this file maps: it asks for the keys
    before anything reads them."""
    return all(k in model_config for k in KEYS)


def as_mla_dense_config(model_config: dict) -> dict | None:
    """The config with its 2 x `num_layers` latent layers under
    `num_hidden_layers`; None for a config this file does not know."""
    if not known(model_config):
        return None
    return dict(model_config,
                num_hidden_layers=2 * model_config["num_layers"])
