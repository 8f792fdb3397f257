"""Where JAX's persistent compilation cache lives.

A cold compile of a full-width train step is a minute or more, and a
chip-tool call keeps nothing but its output directory, so every entry
point that compiles for the device calls :func:`use_compile_cache`
first. The directory is part of the cache key: it is either where the
environment says (``JAX_COMPILATION_CACHE_DIR``, which jax reads by
itself — then nothing is set in code) or one fixed place in the
checkout. Never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Place the compilation cache and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
