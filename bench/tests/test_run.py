"""The harness refuses to run off the chip, and without the program."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "testbed-fb.drain",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _printed_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_refuses_off_the_chip():
    p = _run(ROOT)
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
    assert "no result" in p.stderr


def test_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
