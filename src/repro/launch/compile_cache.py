"""JAX's persistent compilation cache for the launchers.

A cold start compiles every program again: for a 24-layer model that is
every prefill bucket, the decode program and the sampler, or the whole
train step.  The launchers call `enable_compile_cache()` once at start-up
(never at import), so a second run on the same machine reads the compiled
programs back.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this sets
nothing.  Otherwise the cache goes to `<checkout>/.jax_cache`: a fixed path
derived from this package's location, because the directory is part of the
cache's key and a path that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[Path]:
    """Point JAX's compilation cache at `DEFAULT_DIR` unless the environment
    already names one.  Returns the directory set here, or None."""
    if os.environ.get(ENV_VAR):
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
