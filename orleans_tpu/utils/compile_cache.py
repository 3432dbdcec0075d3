"""Persistent XLA compile cache placement for the entry points.

Called at the start of ``chip_smoke.py`` and
``python -m orleans_tpu.host`` — never on package import, so tests and
library users write nothing to it.  The cache's path is part of JAX's
cache key, so it is a fixed directory of the checkout: never derived
from a temporary name, a process id or the clock."""

from __future__ import annotations

import os
from typing import Optional

#: ``<repo root>/.jax_cache`` (listed in .gitignore)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at ``REPO_CACHE_DIR``
    unless ``JAX_COMPILATION_CACHE_DIR`` places it from outside, in
    which case JAX reads that variable itself and nothing is set here.
    Returns the directory this call set, or None."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
