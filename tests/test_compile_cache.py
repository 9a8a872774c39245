"""Where the persistent compilation cache lives: ``$JAX_COMPILATION_CACHE_DIR``
when it is set, else ``<checkout>/.jax_cache``, and nowhere else.  Each case
runs in a fresh interpreter on the CPU, so this process's cache settings
are left alone."""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

_PROGRAM = """
import os
from repro.compile_cache import use_compile_cache
path = use_compile_cache()
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("env_set", (True, False))
def test_cache_goes_only_where_the_helper_says(tmp_path, env_set):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", _PROGRAM], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300, check=True).stdout.split()
    want = (tmp_path / "cache") if env_set else (ROOT / ".jax_cache")
    assert out[-2:] == [str(want), str(want)]
    assert any(want.iterdir())
    # Nothing was written beside the working directory's cache, if any.
    assert sorted(p.name for p in tmp_path.iterdir()) == (
        ["cache"] if env_set else [])
