"""JAX's persistent compilation cache, at one fixed place.

Every entry point that jits (``chip_smoke.py``, ``__graft_entry__.py``,
``benchmark/harness.py``) calls :func:`enable` before its first compilation,
so a second cold process finds what the first compiled.  The directory is
part of the cache key, hence never a temp name, a pid or a time.  The
native tier's PJRT compilations (``DeviceClient.compile``) do not pass
through JAX and are not cached; they report as set-up seconds.
"""

from __future__ import annotations

import os

#: Inside the checkout and git-ignored.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable() -> str | None:
    """Turns the persistent cache on and returns the directory in use.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set in code.  On the CPU backend nothing is cached (None):
    those compilations are tests and dry runs, and XLA:CPU logs a
    machine-feature error for every entry it loads back."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
