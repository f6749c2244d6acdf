"""Where jax's persistent compilation cache lives — one rule for every
entry point (``chip_smoke.py``, ``benchmark/run.py``, the engine's
``compile_cache_dir`` key).

The directory is part of the cache key, so a cache that moves never
hits: where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is placed
from outside and code sets no other directory (jax reads the variable
itself); where it is not, the cache sits at one fixed path inside the
checkout — never a temporary name, a process id or a time.
"""
from __future__ import annotations

import os
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_compile_cache (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def compile_cache_dir(configured: Optional[str] = None) -> str:
    """The directory the cache would use: the environment's, else
    ``configured`` (the engine's JSON key), else the fixed in-checkout
    path. Pure — touches neither jax nor the filesystem."""
    return os.environ.get(ENV) or configured or DEFAULT_DIR


def enable_compile_cache(configured: Optional[str] = None) -> str:
    """Switch the persistent cache on at :func:`compile_cache_dir` and
    return the directory in use. jax initialises its cache once per
    process (first compile wins), so a directory that differs from the
    one already in force is reported and left alone, never "updated"."""
    import jax

    path = compile_cache_dir(configured)
    current = jax.config.jax_compilation_cache_dir
    if current and current != path:
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            "compile cache %s ignored: this process already uses %s "
            "(jax initializes one cache per process, first compile wins)",
            path, current)
        return current
    os.makedirs(path, exist_ok=True)
    if not current:   # set from the environment: jax already holds it
        jax.config.update("jax_compilation_cache_dir", path)
    return path
