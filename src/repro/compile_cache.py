"""JAX's persistent compilation cache, placed where a run can find it again.

A cold run on the chip compiles every kernel at its serving shape (tens of
seconds each at Graph500 scale 22). The persistent cache keeps those
executables on disk so the next process skips the compile. Its directory
is part of what makes an entry findable, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` wins when set (the directory a
deployment mounts for this), and otherwise the cache lives at the fixed
path ``<checkout>/.jax_cache`` — never under a temporary name, a PID or
a time stamp.

Entry points that run on the chip (``chip_smoke.py``,
``benchmarks/run.py``) call `enable_compile_cache` once at start-up. The
library and the tests never turn it on.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def compile_cache_dir() -> pathlib.Path:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    return pathlib.Path(env) if env else CHECKOUT / ".jax_cache"


def enable_compile_cache() -> pathlib.Path:
    """Point JAX's persistent compilation cache at `compile_cache_dir`
    (and at no other directory); returns it."""
    import jax

    path = compile_cache_dir()
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
