"""Persistent XLA compile cache for the entry points.

Called by the programs a user starts (``chip_smoke.py``, ``bench.py``,
``python -m paddle_tpu.serving.server``, ``tools/serve_bench.py``) — never
at ``import paddle_tpu``: a library import must not decide where a
process writes.
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Returns the directory jax's persistent compilation cache uses.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set in code.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (git-ignored): the path is part of the
    cache key, so a temporary name, pid or time would never hit."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
