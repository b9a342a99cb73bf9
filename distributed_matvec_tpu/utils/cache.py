"""Persistent XLA compilation cache, placeable from outside.

Compiling the engine programs is a large part of a cold run, so every
process keeps JAX's persistent compilation cache.  Where it lives follows one
rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
code sets no directory at all; otherwise the cache is one fixed directory
inside the checkout (``<repo>/.cache/xla``, git-ignored).  The path is part
of the cache's key, so it never depends on ``HOME``, the artifact root, a
pid or the time: two processes of one checkout agree on it.
"""

from __future__ import annotations

import os
from typing import Optional

from .logging import log_warn

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compilation_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache", "xla")


def enable_compilation_cache() -> Optional[str]:
    """Turn JAX's persistent compilation cache on and return its directory
    (``None`` when the in-checkout directory cannot be created — the run
    then compiles everything, it does not fail).  Safe to call repeatedly.
    """
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = CHECKOUT_CACHE_DIR
        try:
            os.makedirs(directory, exist_ok=True)
        except OSError as e:
            log_warn(f"compilation cache disabled: cannot create "
                     f"{directory}: {e!r}")
            return None
        if jax.config.jax_compilation_cache_dir != directory:
            jax.config.update("jax_compilation_cache_dir", directory)
    # cache everything that took meaningful compile time — unless the user
    # already chose a threshold via the standard env var
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return directory
