"""Where JAX's persistent compilation cache lives.

Entry points call `enable_compile_cache()` once, before their first
compile (chip_smoke.py, bench.py, `python -m paddle_tpu.trainer`);
nothing does on import. Whoever runs the program places the cache from
outside with `JAX_COMPILATION_CACHE_DIR`, which JAX reads itself — then
this sets nothing. Otherwise the cache goes to one fixed directory in
the checkout: the path is part of every entry's key, so a directory
that moved with the process (a temporary directory, a pid or a time in
its name) would never hit.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
