"""Where JAX keeps its persistent compilation cache for this repo's
entry points.

A cache directory is part of the cache key, so a run that compiles the
same programs as an earlier one reuses them only if both used the same
directory.  `JAX_COMPILATION_CACHE_DIR`, when set, wins (JAX reads it
itself and nothing here overrides it); otherwise the entry points use
one fixed directory, `.jax_cache/` at the repository root (gitignored).
Call `enable_compile_cache()` from an entry point's main, before the
first compilation — never at import, so importing the library changes
no global JAX state.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
