"""Where the store's Pallas kernels run, which shapes they compile for,
and where JAX keeps compiled code.

:func:`interpret_mode` is the one place the interpret decision is made:
the kernels compile with Mosaic when JAX's default backend is a TPU and
run in the Pallas interpreter everywhere else (the CPU test tier).

:func:`bucket` is the size-class rule every kernel's host wrapper pads
its lengths with.

:func:`enable_compile_cache` turns on JAX's persistent compilation cache
for an entry point: the directory ``JAX_COMPILATION_CACHE_DIR`` names when
that is set (JAX reads it itself), else ``.jax_cache/`` at the checkout
root — a fixed path, because the path is part of the cache key.
"""

from __future__ import annotations

import functools
import os
from pathlib import Path

#: checkout-root cache directory used when JAX_COMPILATION_CACHE_DIR is unset
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def bucket(n: int, least: int) -> int:
    """The power-of-two size class (at least ``least``) that ``n`` pads
    to: one compiled kernel shape per class, so the number of compiles
    grows with log(n), not with the number of distinct lengths."""
    size = least
    while size < n:
        size *= 2
    return size


@functools.cache
def interpret_mode() -> bool:
    """True unless JAX's default backend is a TPU."""
    import jax
    return jax.default_backend() != "tpu"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and cache
    every compile, however short; returns the directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
