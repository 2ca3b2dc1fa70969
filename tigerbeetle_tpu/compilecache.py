"""Where JAX's persistent compilation cache lives.

A cold start of the served path compiles for minutes on a TPU (the
commit kernels, the query-index sort, each merge shape); the persistent
cache turns every later start of the same checkout into a read. The
path is part of the cache key, so it must never move: it is either
where `JAX_COMPILATION_CACHE_DIR` says — placed from outside, and then
this module sets nothing — or ONE fixed directory inside the checkout
(`.jax_cache/`, ignored by git), never a temporary name, pid or time.

Called by every entry point that is about to compile — `cli.py start`,
bench.py, profile_*.py, __graft_entry__.py — and never at package
import: `tigerbeetle_tpu/__init__.py` stays JAX-free, and the tests keep
JAX's own default (no persistent cache).
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure() -> str:
    """Place the cache before this process's first compile; returns the
    directory in use. Imports jax (callers are about to anyway)."""
    placed = os.environ.get(ENV)
    if placed:
        return placed  # jax read it at import; set no directory in code
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
