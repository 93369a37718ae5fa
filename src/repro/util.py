"""Shared utilities.

``uscan`` wraps ``lax.scan`` with a process-global unroll switch: the
dry-run sets ``set_unroll(True)`` when extracting roofline terms, because
XLA's HloCostAnalysis counts while-loop bodies ONCE (verified empirically)
— flops/bytes of scanned layers/local-steps would otherwise be
undercounted by the trip count. Normal execution keeps rolled loops for
compact HLO and fast compiles.

``use_repo_compile_cache`` places JAX's persistent compilation cache for
the entry points (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``); importing ``repro`` never touches it.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Callable, Optional

import jax
from jax import lax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_UNROLL = False


def set_unroll(value: bool) -> None:
    global _UNROLL
    _UNROLL = bool(value)


def get_unroll() -> bool:
    return _UNROLL


def uscan(f: Callable, init: Any, xs: Any, length: Optional[int] = None):
    return lax.scan(f, init, xs, length=length, unroll=True if _UNROLL else 1)


def umap(f: Callable, xs: Any):
    def body(_, x):
        return None, f(x)

    _, ys = uscan(body, None, xs)
    return ys


def use_repo_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is changed. Otherwise the cache goes to the fixed
    ``<repo>/.jax_cache`` (listed in ``.gitignore``): the directory is
    part of each entry's key, so a temporary or per-process path would
    never be hit again. Call from ``main()`` only.
    """
    from jax.experimental.compilation_cache import compilation_cache

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    # JAX decides once, at the process's first compile, whether a cache
    # is in use; an import may already have compiled without one
    compilation_cache.reset_cache()
    return str(REPO_CACHE_DIR)
