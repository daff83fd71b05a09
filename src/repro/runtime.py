"""Process set-up shared by the entry points: where on-disk caches live.

Everything the program caches on disk stays inside the checkout, at fixed
paths, so a run reads and writes nothing around it and a second run of the
same checkout finds what the first one wrote:

* JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR`` when it
  is set (JAX reads the variable itself; nothing else is set in code), else
  ``.jax_cache/`` at the checkout root;
* the autotuner's tuned launch configs: ``$REPRO_AUTOTUNE_CACHE`` when set,
  else ``.autotune_cache/`` at the checkout root (kernels/autotune/cache.py).

Both directories are listed in ``.gitignore``.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Call before the first compile.  Every compiled program is cached,
    whatever its compile time: a chain kernel compiles in well under the
    default one-second threshold, and a release plan has hundreds of them.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
