"""Where compiled XLA programs persist — one rule for every entry point.

If ``JAX_COMPILATION_CACHE_DIR`` is set, that is the cache and nothing
here touches it. If it is not, the cache is ``<checkout>/.jax_cache``
(git-ignored). The directory is part of the cache key's home: a path
built from a temp name, a pid, the time or the storage base dir moves
every run and never hits, so none is ever used. ``pio`` (tools/console),
``bench.py`` and ``chip_smoke.py`` all call :func:`configure`, and child
processes inherit the variable, so a deploy reuses what its train
compiled. To run without a persistent cache set JAX's own
``JAX_ENABLE_COMPILATION_CACHE=false``.

jax-free on import: safe for the parents that must not open a device.
"""

from __future__ import annotations

import os
import sys

__all__ = ["ENV_VAR", "default_cache_dir", "configure"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — beside the ``predictionio_tpu`` package."""
    package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_dir), ".jax_cache")


def configure() -> str:
    """Apply the rule and return the directory in force. Call it before
    the first compile; before jax is imported the variable alone does
    it, and an already-imported jax (which read its environment at
    import) is pointed at the same default."""
    cache_dir = os.environ.get(ENV_VAR)
    if cache_dir:
        return cache_dir
    cache_dir = default_cache_dir()
    os.environ[ENV_VAR] = cache_dir
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
