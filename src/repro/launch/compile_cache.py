"""Where the chip entry points keep JAX's persistent compilation cache.

A cold chip process compiles every kernel and every jitted solve again;
the persistent cache lets the next process on the same machine skip that.
The cache's directory is part of what it is keyed on, so it must not move
between runs: it is either the directory `JAX_COMPILATION_CACHE_DIR`
names, or `.jax_cache/` at the checkout root, never a temporary name.

Call `use_compile_cache()` at the start of an entry point's `main()`,
never at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.core.autotune import CACHE_ENV

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache(root: Path = CHECKOUT_ROOT) -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache goes to
    ``<root>/.jax_cache``.  The `ensemble="auto"` profile cache is kept in
    the same directory unless `REPRO_AUTOTUNE_CACHE` names another file, so
    tuned winners live exactly as long as the compiled programs."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root) / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    os.environ.setdefault(CACHE_ENV, os.path.join(path, "autotune.json"))
    return path
