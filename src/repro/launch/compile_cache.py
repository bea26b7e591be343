"""Where compiled programs persist between processes.

A full-width model's step programs take tens of seconds to compile, and
every process that serves one compiles them again unless JAX's
persistent compilation cache is on. The cache key includes the cache
directory, so the directory must not move between runs: JAX's own
``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself),
and otherwise every entry point uses one fixed directory inside the
checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``).

The entry points that touch the chip call ``enable_compile_cache()``
before their first compile: ``python -m repro`` (``api.cli.main``),
``python -m repro.launch.serve`` and ``chip_smoke.py``.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (``src/repro/launch`` -> repo root)
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Sets ``jax_compilation_cache_dir`` only where the environment names
    none. Call before the process's first compile: JAX decides once per
    process whether the cache is in use.
    """
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
