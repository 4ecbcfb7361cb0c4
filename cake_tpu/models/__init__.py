"""Model-level abstractions: generator protocols, Token, chat types.

Reference: `Generator` / `TextGenerator` / `ImageGenerator` traits and
`Token` (cake-core/src/models/mod.rs:14-71).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Protocol, runtime_checkable


@dataclass
class Token:
    """One generated token (reference models/mod.rs:14-36)."""

    id: int
    text: str
    is_end_of_stream: bool = False

    def __str__(self) -> str:
        return "" if self.is_end_of_stream else self.text


@runtime_checkable
class TextGenerator(Protocol):
    """Reference models/mod.rs:52-64."""

    def add_message(self, message) -> None: ...
    def reset(self) -> None: ...
    def next_token(self, index: int) -> Token: ...
    def generated_tokens(self) -> int: ...


@runtime_checkable
class ImageGenerator(Protocol):
    """Reference models/mod.rs:66-71."""

    def generate_image(self, args, callback: Callable[[List[bytes]], None]) -> None: ...


from cake_tpu.models.chat import Message, MessageRole, History  # noqa: E402,F401


def load_text_params(config, model_dir: Optional[str], dtype, rng=None,
                     quant: Optional[str] = None):
    """Parameter pytree for any text-model family, keyed by the config.

    HF safetensors when present under model_dir, else random init (with a
    warning). Family dispatch (dense Llama vs MoE) lives here, next to
    load_config's model_type dispatch, so app layers never branch on it.

    quant ("int8" | "int4", --quant): the tree comes back as
    ops/quant.quantize_params would leave it, WITHOUT the full-precision
    tree ever existing on the device — weights on disk quantize leaf by
    leaf as each tensor lands, and a weightless model draws its
    quantized leaves directly (the dense family's init_params_quantized,
    the MoE family's init_params). An 8B bf16 tree is ~15 GiB, most of a
    v5e's HBM; load-then-quantize cannot start there.
    """
    import logging

    import jax

    from cake_tpu.utils.loading import has_weights

    is_moe = config.is_moe
    if is_moe:
        from cake_tpu.models.moe.params import (
            init_params, load_params_from_hf,
        )
    else:
        from cake_tpu.models.llama.params import (
            init_params, load_params_from_hf,
        )
    bits = {"int8": 8, "int4": 4}.get(quant)   # None / "none": as is
    if has_weights(model_dir):
        finish = None
        if bits:
            from cake_tpu.ops.quant import make_leaf_quantizer
            finish = make_leaf_quantizer(bits)
        return load_params_from_hf(model_dir, config, dtype=dtype,
                                   finish=finish)
    logging.getLogger(__name__).warning(
        "no weights at %r; using random init", model_dir)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if not bits:
        return init_params(config, rng, dtype=dtype)
    if is_moe:
        from cake_tpu.models.moe.params import init_params_jit
        return init_params_jit(config, rng, dtype=dtype, bits=bits)
    from cake_tpu.models.llama.params import init_params_quantized_jit
    return init_params_quantized_jit(config, rng, dtype=dtype, bits=bits)
