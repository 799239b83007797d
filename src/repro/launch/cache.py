"""Persistent XLA compilation cache, placed from outside the program.

``enable_compile_cache()`` is called once from the entry points
(``chip_smoke.py``, ``launch.serve.main``, ``launch.train.main``)
before their first compile, never on import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
here overrides it; otherwise the cache lives at a fixed path inside the
checkout (``<repo>/.jax_cache``, gitignored).  The path is part of the
cache's key, so it never comes from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    # the step programs' ``jax.named_scope``s live in op metadata, which
    # the key leaves out by default: a cache shared with a build whose
    # programs differ only in their scopes would hand back that build's
    # executables, and a profile of this one would name their ops
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
