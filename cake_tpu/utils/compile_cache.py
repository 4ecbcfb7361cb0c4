"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles (cli.main): where JAX_COMPILATION_CACHE_DIR is set, JAX itself
keeps the cache there and this module sets no other path; where it is
not, the cache goes to `<checkout>/.jax_cache` — a fixed path, because
the path is part of how a later process finds the entries, so a
temporary name, a pid or a time in it would never hit.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def checkout_cache_dir() -> str:
    """`<checkout>/.jax_cache` (git-ignored): beside the cake_tpu
    package this module was imported from."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile and return
    its directory. The engine compiles many programs that take well
    under JAX's default one-second floor (sampling, table updates, tiny
    models in tests), so that floor drops to zero: every program is
    cached."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = checkout_cache_dir()
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
