"""Where JAX keeps its persistent compilation cache.

Every command-line entry point calls `enable()` from its main(), so a second
run of the same command loads its compiled programs instead of compiling
them again.  Nothing calls it at import or from the tests: a library import
must not redirect the caller's cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout-local default: a fixed path, because the directory is part
#: of what a later run must find again (never a temp name, pid or time)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    If JAX_COMPILATION_CACHE_DIR is set, JAX already uses it and nothing
    else is set here; otherwise the cache goes to <repo>/.jax_cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
