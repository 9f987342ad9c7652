"""JAX's persistent compilation cache, one rule for every entry point.

Each entry point (`launch.mc`, `launch.serve`, `launch.train`,
`examples/train_detector.py`, `benchmarks/run.py`, `chip_smoke.py`) calls
`enable_compile_cache()` before it compiles anything:

  * `JAX_COMPILATION_CACHE_DIR` set: JAX already reads it; nothing else is
    configured in code, so the cache lives only there.
  * otherwise: `<checkout>/.jax_cache` (git-ignored).  The directory is part
    of the cache key, so it is fixed — never derived from a temporary name,
    a pid or the time — and a later run in the same checkout hits it.

Either way the key includes the programs' metadata, their `jax.named_scope`
paths and source locations among it: without them a build whose scopes
differ loads an executable compiled from another build, whose ops carry
that build's op names into a profiler trace.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
