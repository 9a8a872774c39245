"""A tiny cell the tests can run on the CPU: the testbed's type mix at 20
servers, dodoor at b = 10."""
import copy
import json
import os

from conftest import BENCH, ROOT


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def tiny_config() -> dict:
    cfg = copy.deepcopy(config("testbed-fb"))
    cfg["name"] = "tiny-fb"
    cfg["fleet"]["servers"] = 20
    cfg["policy"]["b"] = 10
    return cfg


def tiny_spec(traffic: str = "drain") -> dict:
    s = spec()
    s["configs"].append({"name": "tiny-fb", "source": "test",
                         "file": "bench/configs/tiny-fb.json",
                         "reduced": [], "why": "test"})
    s["workloads"].append({"name": "tiny-fb." + traffic,
                           "config": "tiny-fb", "traffic": traffic,
                           "chips": 1, "why": "test"})
    for m in s["end_to_end"]:
        m.pop("workloads", None)
    s["per_layer"] = []
    return s


def write_tiny(bench_dir: str) -> None:
    with open(os.path.join(bench_dir, "configs", "tiny-fb.json"), "w") as f:
        json.dump(tiny_config(), f)
