"""Dtype policy and device inventory.

The reference probes cuda -> metal -> cpu for one inference device
(utils/mod.rs:15-30); here the program runs on whatever devices JAX's
default backend has (JAX_PLATFORMS decides, and a backend that fails
to start is an error, never a quiet CPU). The dtype parse defaults to
f16 in the reference (cake/mod.rs:54-60); on TPU the default compute
dtype is bfloat16 (the MXU-native type), f16 is honored if requested.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_DTYPES = {
    "f16": jnp.float16,
    "bf16": jnp.bfloat16,
    "f32": jnp.float32,
}

# storage-only dtypes: valid for the KV cache (--kv-dtype), where values
# are written once and upcast into the attention matmul on read — halves
# KV HBM traffic/footprint — but not for weights/activations
_KV_DTYPES = {
    **_DTYPES,
    "f8_e4m3": jnp.float8_e4m3fn,
    "f8_e5m2": jnp.float8_e5m2,
}


def resolve_dtype(name: str):
    """Map a CLI dtype name to a jnp dtype (reference cake/mod.rs:54-60)."""
    try:
        return _DTYPES[name]
    except KeyError:
        raise ValueError(
            f"unsupported dtype '{name}' (expected one of {sorted(_DTYPES)})"
        ) from None


def resolve_kv_dtype(name: str):
    """Map a --kv-dtype name (compute dtypes + fp8 storage variants)."""
    try:
        return _KV_DTYPES[name]
    except KeyError:
        raise ValueError(
            f"unsupported kv dtype '{name}' "
            f"(expected one of {sorted(_KV_DTYPES)})"
        ) from None


def device_kind_summary() -> str:
    """Human-readable device inventory (WorkerInfo-style introspection).

    Replaces the reference's `WorkerInfo` message fields
    (proto/message.rs:42-58) with local JAX device/topology queries.
    """
    lines = []
    for d in jax.devices():
        lines.append(
            f"{d.id}: {d.platform}/{d.device_kind} process={d.process_index}"
        )
    return "\n".join(lines)
