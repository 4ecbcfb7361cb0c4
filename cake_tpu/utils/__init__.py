"""Utilities: device/dtype policy, weight loading, debug helpers.

Capability parity with the reference's `cake-core/src/utils/mod.rs`.
"""

from cake_tpu.utils.devices import resolve_dtype  # noqa: F401
from cake_tpu.utils.loading import (  # noqa: F401
    load_safetensors_paths_from_index,
    load_weights,
    load_weight_index,
)
from cake_tpu.utils.debug import panic_on_nan  # noqa: F401
from cake_tpu.utils.profiling import (  # noqa: F401
    device_memory_stats, human_bytes, log_memory, trace,
)
