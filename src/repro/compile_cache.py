"""The persistent JAX compilation cache, at one fixed place.

``enable_compile_cache()`` is the one switch every entry point that wants
the cache calls (``chip_smoke.py``, ``benchmarks/bench_simx.py``) before
its first compile.  Where the cache lives:

  * ``$JAX_COMPILATION_CACHE_DIR``, when set — JAX reads that variable
    itself, so the directory is left exactly as the environment placed it
    and nothing here overrides it;
  * otherwise ``<checkout>/.jax_compile_cache``, resolved from this file's
    location, so every run from any working directory reads and writes the
    same directory (the path is part of what makes a cache entry hit).

The admission thresholds are zeroed because the simulator's programs are
many small scans that JAX's default one-second / minimum-size gates would
never admit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The in-checkout fallback directory (``.gitignore`` lists it).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    The setting is process-global: a caller that must undo it (a test)
    restores ``jax_compilation_cache_dir`` and the two
    ``jax_persistent_cache_min_*`` thresholds, then calls
    ``jax.experimental.compilation_cache.compilation_cache.reset_cache()``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # JAX latches whether the cache is on at the process's first compile;
    # reset the latch so the next compile reads the settings above
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
    return jax.config.jax_compilation_cache_dir
