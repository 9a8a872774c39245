"""A configuration, a traffic mix, an arrival process, a loop and a
per-layer metric are added as new files and entries, in a copy of the
benchmark, and the harness runs them without an edit to any file that was
there."""
import hashlib
import json
import os
import shutil

import pytest

import run
from conftest import BENCH
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


def prepare(rng, mix, fleet, sigma, seconds, b, rate):
    return Plan(traffic.Stream(rng, mix.gaps(rate), fleet.type_names, sigma,
                               int(mix["burst"])), int(mix["burst"]), b)


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

MIXES = {
    "even-open": ({"loop": "open", "arrivals": "evenly", "load": 1.0},
                  {"decision_p50_ms", "decision_p95_ms", "setup_s"}),
    "burst": ({"loop": "burst", "arrivals": "evenly", "load": 1.0,
               "burst": 45}, {"decisions_per_s", "setup_s"}),
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


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_new_files_only(tmp_path, mix):
    params, reported = MIXES[mix]
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    before = _digests(bench)
    (bench / "configs" / "tiny-fb.json").write_text(json.dumps(tiny_config()))
    (bench / "arrivals" / "evenly.py").write_text(EVENLY)
    (bench / "loops" / "burst.py").write_text(BURST)
    (bench / "traffic" / (mix + ".json")).write_text(json.dumps(params))
    # One reader serves the metric under every cell's suffix.
    (bench / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.window.placed)\n")
    spec = tiny_spec(mix)
    spec["per_layer"].append({
        "name": "steps_seen." + mix, "unit": "decisions",
        "better": "higher", "source": "host_clock", "layer": "test",
        "moves": "setup_s", "workloads": ["tiny-fb." + mix]})
    cell, e2e, layer = run.cell_spec(spec, "tiny-fb." + mix)
    assert [m["name"] for m in layer] == ["steps_seen." + mix]
    out = run.run_cell(spec, "tiny-fb." + mix, 3, 1.0, False,
                       allow_cpu=True, bench_dir=str(bench),
                       log=lambda *a, **k: None)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert set(out["metrics"]) == reported
    after = _digests(bench)
    assert {k: after[k] for k in before} == before
    ctx = run.Context(None, type("W", (), {"placed": 12})(), None, None,
                      None)
    assert run.reader(str(bench), "steps_seen." + mix).read(ctx) == 12.0
