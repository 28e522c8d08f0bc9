"""Where JAX's persistent compilation cache lives.

Entry points call ``configure_compile_cache()`` before their first
compile; nothing calls it at package import or from the tests. The
directory is part of every cache key, so it has to be the same path in
every run: a temporary name, a pid or a time in it would never hit.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Return the cache directory in use. ``JAX_COMPILATION_CACHE_DIR``,
    when set, is the placement from outside: JAX reads it itself and no
    directory is set in code. Otherwise the cache goes to the fixed,
    git-ignored ``<checkout>/.jax_cache``.

    Either way the Python call stack is kept out of the MLIR locations
    JAX emits. A Pallas kernel reaches XLA as serialized MLIR, locations
    included, inside a custom call's payload, and the cache key hashes
    that payload: with tracebacks in it, the same train step lowered from
    two call sites (``fit`` and an ahead-of-time ``lower().compile()``,
    or two lines of one script) gets two keys and never hits."""
    import jax

    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
