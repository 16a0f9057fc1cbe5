"""Placement of JAX's persistent compilation cache, for entry points only.

A cold run compiles every bucket's estimate, graph and brute executables
and then the Pallas kernels; the persistent cache lets the next process on
the same machine skip that.  Library code never calls this (importing
``repro`` must not touch global JAX state); ``chip_smoke.py`` and
``benchmarks/run.py`` call it before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: str | os.PathLike) -> str:
    """Turn on the persistent compile cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that directory
    from the environment and nothing else is configured here.  Otherwise the
    cache goes to the fixed path ``<checkout>/.jax_cache`` (git-ignored): the
    path is part of what makes a later run find the entries, so it never
    depends on a temp name, a pid or the time."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
