"""Benchmarks of the scheduler; run each as ``python -m benchmarks.<name>``.

Every benchmark keeps JAX's persistent compilation cache where
``repro.compile_cache`` says, set here before any of them compiles.
"""
from repro.compile_cache import use_compile_cache

use_compile_cache()
