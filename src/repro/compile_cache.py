"""JAX's persistent compile cache, as the program's entry points set it.

The serving path compiles one executable per (shape signature, batch
tier), a second or two each for a TPU.  The entry points
(``chip_smoke.py``, ``examples/serve_search.py``) call
:func:`configure_compile_cache` before their first compile so that a
later run loads those executables instead of compiling them again.
Importing ``repro`` never calls it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout: the path is part of what a later run looks up
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache(*, multi_device: bool = False) -> str | None:
    """Set the persistent compile cache up; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and it stands.  Otherwise the cache goes to :data:`DEFAULT_DIR`.
    Every compile is cached, however short.

    Every process that runs programs across several devices (a z-sharded
    mesh or a 2-D topology) passes ``multi_device=True``: the persistent
    cache is then off, wherever it was set, and None is returned.  On a
    TPU v5e 2x2, a process that loaded the topology's programs from the
    cache halted the chip at its first mesh dispatch ("Invalid logical
    z"), while two processes before it on the same chips, which compiled
    the same programs (one with the cache off, one writing it), served
    correctly.

    Call it before the first compile: JAX reads these settings once per
    process.
    """
    if multi_device:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
