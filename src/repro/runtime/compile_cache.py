"""JAX's persistent compilation cache, placed the same way by every entry
point (``repro.apps.run``, ``repro.serving.serve``, ``repro.launch.train``,
``repro.launch.serve`` and ``chip_smoke.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at start-up and
the cache lives there: nothing here overrides it. Otherwise the cache
lives in ``<checkout>/.jax_cache`` (listed in ``.gitignore``). The path is
fixed because a later process finds a compiled program only under the
same directory, so it never depends on a temp directory, a pid or the
time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent cache on and return its directory.

    Every program is cached, however small or quick to compile: the
    pricing engine compiles many programs that each take well under the
    default one-second threshold, and together they are its start-up.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
        # Re-initialize, so the directory applies even after this
        # process has compiled something.
        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return Path(path)
