"""CLI entry points, and the one place that says where XLA's persistent
compile cache lives."""

from __future__ import annotations

import os

# <checkout>/.jax_cache, from this file's own location: the path is part
# of the cache key's directory lookup, so it must not follow cwd, a temp
# name, a pid or a time — a directory that moves never hits.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Decide the compile cache directory for this process and return it.

    Where JAX_COMPILATION_CACHE_DIR is set jax reads it itself and this
    sets nothing; where it is not, every entry point of the repo shares
    the one fixed directory inside the checkout (listed in .gitignore).
    The minimum compile time worth caching stays at jax's default."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
