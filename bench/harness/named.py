"""Files the harness finds by name under the benchmark's directory."""
from __future__ import annotations

import importlib.util
import json
import os


def module(path: str):
    """The Python file at ``path``, loaded as a module of its own."""
    if not os.path.exists(path):
        raise FileNotFoundError(f"no such file: {path}")
    name = os.path.relpath(path, os.path.dirname(os.path.dirname(path)))
    spec = importlib.util.spec_from_file_location(
        "bench_" + name[:-3].replace(os.sep, "_").replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def json_file(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
