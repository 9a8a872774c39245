"""A configuration, a traffic mix, an arrival process, a loop, a per-layer
metric, a task mix, service keywords and a reference that takes them are
added as new files and entries, in a copy of the benchmark, and the harness
runs them without an edit to any file that was there."""
import hashlib
import json
import os
import shutil

import pytest

import run
from conftest import BENCH
from harness import system
from helpers import tiny_config, tiny_spec

EVENLY = '''"""Arrivals at exactly the mean rate."""
import numpy as np


def process(mix, rate):
    return lambda rng, k: np.full(k, 1.0 / rate)
'''

# A loop of its own: a burst of ``mix["burst"]`` tasks submitted at once,
# drained block by block; it reports the rate of decisions.
BURST = '''import time

from harness import traffic


class Plan:
    def __init__(self, stream, burst, b):
        self.stream, self.burst, self.capacity = stream, burst, burst + b


def prepare(rng, mix, fleet, draw, seconds, b, rate):
    return Plan(traffic.Stream(rng, mix.gaps(rate), draw, int(mix["burst"])),
                int(mix["burst"]), b)


def run(svc, plan, seconds, b, submit, tick=None):
    w = traffic.Window()
    w.compiles = (svc.compiles, None)
    submit(svc, plan.stream.take(plan.burst))
    w.t0 = time.perf_counter()
    while svc.available >= b:
        svc.step()
    placed = plan.burst - svc.available
    placed += svc.flush() if svc.available else 0
    w.t1 = time.perf_counter()
    w.compiles = (w.compiles[0], svc.compiles)
    w.placed, w.tasks = placed, plan.stream.consumed()
    return w


def end_to_end(w, log=print):
    return {"decisions_per_s": w.placed / (w.t1 - w.t0)}
'''

# A task mix of its own, alike on every node type: virtual machines of 1,
# 2, 4 or 8 cores with a fixed memory per core and exponential lifetimes.
VMS = '''import numpy as np

from harness.tasks import Tasks

CORES = np.array([1, 2, 4, 8], np.float32)


def core_seconds_per_task(fleet, params):
    return float(CORES.mean()) * float(params["mean_s"])


def draw(rng, fleet, m, params, submit_ms):
    cores = CORES[rng.integers(0, len(CORES), size=m)]
    res = np.stack([cores, cores * np.float32(params["mb_per_core"])], 1)
    ms = rng.exponential(1e3 * params["mean_s"], m).astype(np.float32)
    types = len(fleet.type_names)
    d = np.repeat(ms[:, None], types, 1)
    return Tasks(r_submit=res, r_exec=np.repeat(res[:, None], types, 1),
                 d_est=d, d_act=d.copy(),
                 submit_ms=np.asarray(submit_ms, np.float32))
'''

# A reference that takes the service keyword its configuration gives (it
# moves no placement) and otherwise is the committed one.
DODOOR_KW = '''import os

from harness import named

DODOOR = named.module(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "dodoor.py"))


def simulate(fleet, policy, tasks, seed, *, publish_snapshots, **kw):
    return DODOOR.simulate(fleet, policy, tasks, seed, **kw)
'''


def tiny_vms_config() -> dict:
    cfg = tiny_config()
    cfg["name"] = "tiny-vms"
    cfg["reference"] = "dodoor_kw"
    cfg["tasks"] = {"mix": "vms", "mb_per_core": 7000, "mean_s": 0.5}
    cfg["service"] = {"publish_snapshots": False}
    return cfg


# traffic mix -> (its file, the end-to-end metrics it reports, the
# configuration of its cell)
MIXES = {
    "even-open": ({"loop": "open", "arrivals": "evenly", "load": 1.0},
                  {"decision_p50_ms", "decision_p95_ms", "setup_s"},
                  "tiny-fb"),
    "burst": ({"loop": "burst", "arrivals": "evenly", "load": 1.0,
               "burst": 45}, {"decisions_per_s", "setup_s"}, "tiny-fb"),
    "vms-drain": ({"loop": "backlog", "arrivals": "poisson", "load": 0.8,
                   "prefill_per_s": 200}, {"decisions_per_s", "setup_s"},
                  "tiny-vms"),
}


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _copy(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    return bench


def _built(monkeypatch) -> list:
    """The keywords of every ``DecisionService`` the harness builds."""
    built, real = [], system.DecisionService

    def recording(*a, **kw):
        built.append(kw)
        return real(*a, **kw)
    monkeypatch.setattr(system, "DecisionService", recording)
    return built


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_new_files_only(tmp_path, monkeypatch, mix):
    params, reported, config = MIXES[mix]
    bench = _copy(tmp_path)
    before = _digests(bench)
    (bench / "configs" / "tiny-fb.json").write_text(json.dumps(tiny_config()))
    (bench / "configs" / "tiny-vms.json").write_text(
        json.dumps(tiny_vms_config()))
    (bench / "arrivals" / "evenly.py").write_text(EVENLY)
    (bench / "loops" / "burst.py").write_text(BURST)
    (bench / "tasks" / "vms.py").write_text(VMS)
    (bench / "reference" / "dodoor_kw.py").write_text(DODOOR_KW)
    (bench / "traffic" / (mix + ".json")).write_text(json.dumps(params))
    # One reader serves the metric under every cell's suffix.
    (bench / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.window.placed)\n")
    cell_name = config + "." + mix
    spec = tiny_spec(mix, config)
    spec["per_layer"].append({
        "name": "steps_seen." + mix, "unit": "decisions",
        "better": "higher", "source": "host_clock", "layer": "test",
        "moves": "setup_s", "workloads": [cell_name]})
    cell, e2e, layer = run.cell_spec(spec, cell_name)
    assert [m["name"] for m in layer] == ["steps_seen." + mix]
    built = _built(monkeypatch)
    out = run.run_cell(spec, cell_name, 3, 1.0, False,
                       allow_cpu=True, bench_dir=str(bench),
                       log=lambda *a, **k: None)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert set(out["metrics"]) == reported
    # The warm-up's service and the window's, each built with exactly the
    # configuration's service keywords.
    want = {"tiny-fb": {}, "tiny-vms": {"publish_snapshots": False}}[config]
    assert len(built) == 2
    for kw in built:
        assert {k: v for k, v in kw.items()
                if k not in ("seed", "capacity")} == want
    after = _digests(bench)
    assert {k: after[k] for k in before} == before
    ctx = run.Context(None, type("W", (), {"placed": 12})(), None, None,
                      None)
    assert run.reader(str(bench), "steps_seen." + mix).read(ctx) == 12.0


def test_unknown_service_keyword_fails_at_setup(tmp_path):
    """A keyword the service does not take fails with the program's own
    ``TypeError`` while the cell is set up, before the window opens."""
    bench = _copy(tmp_path)
    cfg = tiny_config()
    cfg["service"] = {"no_such_keyword": True}
    (bench / "configs" / "tiny-fb.json").write_text(json.dumps(cfg))
    lines = []
    with pytest.raises(TypeError, match="no_such_keyword"):
        run.run_cell(tiny_spec("drain"), "tiny-fb.drain", 3, 1.0, False,
                     allow_cpu=True, bench_dir=str(bench),
                     log=lambda *a, **k: lines.append(" ".join(a)))
    assert not any(line.startswith(("setup:", "window:")) for line in lines)
