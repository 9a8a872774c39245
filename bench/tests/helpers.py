"""A tiny cell the tests can run on the CPU: the testbed's type mix at 20
servers, dodoor at b = 10."""
import copy
import json
import os

from conftest import BENCH, ROOT
from harness import fleet as fleets
from harness import named, tasks


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def task_mix(name: str) -> tasks.TaskMix:
    """The task mix of configuration ``name`` on its own fleet."""
    cfg = config(name)
    return tasks.load(BENCH, cfg["tasks"], fleets.build(cfg["fleet"]))


def functionbench():
    return named.module(os.path.join(BENCH, "tasks", "functionbench.py"))


def tiny_config() -> dict:
    cfg = copy.deepcopy(config("testbed-fb"))
    cfg["name"] = "tiny-fb"
    cfg["fleet"]["servers"] = 20
    cfg["policy"]["b"] = 10
    return cfg


def tiny_spec(traffic: str = "drain", config: str = "tiny-fb") -> dict:
    s = spec()
    s["configs"].append({"name": config, "source": "test",
                         "file": f"bench/configs/{config}.json",
                         "reduced": [], "why": "test"})
    s["workloads"].append({"name": config + "." + traffic,
                           "config": config, "traffic": traffic,
                           "chips": 1, "why": "test"})
    for m in s["end_to_end"]:
        m.pop("workloads", None)
    s["per_layer"] = []
    return s


def write_tiny(bench_dir: str) -> None:
    with open(os.path.join(bench_dir, "configs", "tiny-fb.json"), "w") as f:
        json.dump(tiny_config(), f)
