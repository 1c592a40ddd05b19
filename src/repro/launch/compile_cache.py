"""JAX persistent compilation cache at a fixed place.

Entry points call :func:`enable_compile_cache` from their ``main()`` —
never at import, so tests and library users keep JAX's defaults.  A
cache entry is keyed on the path it lives at, so the directory must not
move between runs: the checkout's ``.jax_cache/`` (listed in
``.gitignore``), unless ``JAX_COMPILATION_CACHE_DIR`` already names one,
in which case JAX reads that variable itself and nothing is set here.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — this file is ``src/repro/launch/``.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
