"""Where JAX keeps its persistent compilation cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing here
overrides it.  Otherwise the cache lives at one fixed path inside the
checkout, ``<repo>/.jax_cache``: the path is part of what a later run
must find again, so it never holds a temporary directory, a pid or a
timestamp.

The cache key takes in the programs' metadata: the step's named scopes
(``forward``, ``optimizer``, ``exchange``) live only there, and a key
without it would load an executable compiled from another version of
the code, whose profile then names the ops by that version's scopes.
Each op's location keeps one Python frame, its own, not the stack that
traced it, so the key does not change with the caller; its ``op_name``
(the scopes) is kept whole.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (the
    environment's, else :data:`DEFAULT_DIR`) and return that directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
