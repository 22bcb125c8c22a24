"""JAX's persistent compilation cache, kept at one fixed place.

Every cold process compiles each program again; a full-width campaign
engine takes minutes. With the persistent cache on, a later process on
the same machine loads the executables an earlier one wrote.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there and
this module sets no other path. Otherwise the cache goes to
:data:`DEFAULT_DIR`, a fixed git-ignored directory of the checkout. The
path is part of what makes an entry found again, so it is never built
from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["ENV_VAR", "DEFAULT_DIR", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/...``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Switch the persistent compilation cache on; return its directory.

    Call it before the process compiles anything: JAX fixes the cache
    directory at its first compile.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
