"""The one place that decides where JAX's persistent compilation cache lives.

Every entry point (``chip_smoke.py``, the ``benchmarks/`` modules and the
test suite's ``conftest.py``) calls :func:`use_compile_cache` before its
first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory
is used and no other; otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (git-ignored).  The path is fixed, never a
temporary, per-process or timed one, so that a later run of the same
checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import sys

#: The checkout this package is imported from (``<checkout>/src/repro``).
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_
    CACHE_DIR`` if set, else at ``<checkout>/.jax_cache``, and return that
    path.  Works before and after ``import jax``: the environment variable
    covers a later import, the config update an earlier one."""
    path = os.environ.get(_ENV) or os.path.join(CHECKOUT, ".jax_cache")
    os.environ[_ENV] = path
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path
