"""Where the persistent XLA compilation cache lives.

A cold ``llama_350m`` train step plus the serving engine's programs is
most of a short chip call, so every entry point that compiles (the
launcher, the serving CLIs and workers, the bench tools, the chip smoke,
the test harness) calls ``place_compile_cache`` once before its first
compile.  The directory is part of the cache key, so it must never move:

- ``JAX_COMPILATION_CACHE_DIR`` set from outside: JAX already reads it;
  this module sets nothing, and no other code sets a directory either.
- unset: one fixed path inside the checkout (``.jax_cache``, ignored by
  git) — never a temp name, a pid or a time.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def place_compile_cache() -> str:
    """Make sure a persistent compile cache is configured; returns its
    directory.  Idempotent, and touches no backend."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
