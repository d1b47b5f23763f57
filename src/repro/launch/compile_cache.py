"""Where JAX's persistent compilation cache lives, for every entry point.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set, and no other directory
is configured then.  Otherwise the cache is the fixed in-repo path
``<repo>/.jax_cache`` (git-ignored): the path is part of the cache's key, so
it never depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `compile_cache_dir`;
    call before anything compiles.  Returns the directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
