"""Persistent XLA compilation cache — compile once per checkout, not once
per process.

Big programs (the Word2Vec epoch scan, the transformer step with its flash
kernels, the decode warm grid) recompile from scratch in every fresh
process. JAX ships a persistent on-disk cache that keys compiled
executables by HLO fingerprint; pointing every entry point at ONE fixed
directory makes the second process's compiles cache reads.

Global config mutation never happens on library import: the entry points
(``chip_smoke.py``, ``python -m deeplearning4j_tpu.train``,
``python -m deeplearning4j_tpu.serve``) call :func:`enable_compilation_cache`
first thing.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache (git-ignored), derived from the package's own
# location: the directory is part of the cache key, so it must not move
# between processes — never ~, a temp name, a pid or a time
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it: this
    touches nothing and returns that directory. Otherwise the cache goes
    to ``<checkout>/.jax_cache``."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    os.makedirs(CHECKOUT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
