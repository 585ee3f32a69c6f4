"""Where JAX's persistent compilation cache lives for the repo's entry points.

:func:`enable_compile_cache` is called by ``chip_smoke.py`` and the
benchmark CLIs, never by tests and never on import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing else
is set. Otherwise the cache goes to the fixed ``<repo>/.jax_cache``
(listed in ``.gitignore``): never a temporary, per-process or timestamped
name, so a later run in the same checkout finds what an earlier one
compiled. Child processes started afterwards inherit the same directory
through the environment variable.

Either way the cache key includes the programs' metadata: the named
scopes (``repro.obs.profile.scope``) live there, and a key blind to them
would serve an executable compiled from other source, whose ``op_name``
metadata names other scopes, so a profile of the loaded program would
attribute its device time to the wrong layers.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    os.environ[ENV_VAR] = str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
