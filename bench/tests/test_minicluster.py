"""The four-mini-cluster Azure cell's files: the VM mix, a tiny
mini-cluster configuration through the whole run on the CPU (correct, and
not correct under the bf16 control), and the two new readers on synthetic
several-chip traces."""
import copy
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from conftest import BENCH
from harness import fleet as fleets
from harness import named
from harness import tasks as mixes
from harness import trace as tr
from helpers import config, tiny_spec
from test_trace import _chip, _synthetic


def test_azure_vm_moments():
    cfg = config("cell100k-azure")
    mix = mixes.load(BENCH, cfg["tasks"], fleets.build(cfg["fleet"]))
    m = 100_000
    t = mix.draw(np.random.default_rng(2**31 + 5), m, np.arange(m))
    cores = t.r_submit[:, 0]
    life_s = t.d_est[:, 0] / 1e3
    assert abs(cores.mean() - 2.6) < 0.02
    assert abs(life_s.mean() - 248.0) < 3.0
    assert set(np.unique(cores).tolist()) <= {1.0, 2.0, 4.0, 8.0}
    assert life_s.min() >= 5.0 and life_s.max() <= 600.0
    assert np.array_equal(t.r_submit[:, 1], 7000.0 * cores)
    assert np.array_equal(t.d_act, t.d_est)
    for plane in (t.r_exec, t.d_est[..., None]):     # same on every type
        assert (plane == plane[:, :1]).all()
    assert np.array_equal(t.r_exec[:, 0], t.r_submit)
    per_task = mix.module.core_seconds_per_task(None, cfg["tasks"])
    assert per_task == pytest.approx(cfg["derived"]["core_seconds_per_task"],
                                     abs=1e-3)
    assert mix.rate_at_load(0.8) == pytest.approx(
        cfg["derived"]["tasks_per_s_at_load_0.8"], abs=0.01)


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    """A copy of the benchmark with a tiny mini-cluster configuration: the
    Azure cell's file at 40 servers, two mini-clusters, b = 20."""
    d = tmp_path_factory.mktemp("bench") / "bench"
    shutil.copytree(BENCH, d, ignore=shutil.ignore_patterns("tests"))
    cfg = copy.deepcopy(config("cell100k-azure"))
    cfg["name"] = "tiny-azure"
    cfg["fleet"]["servers"] = 40
    cfg["policy"]["b"] = 20
    cfg["service"]["mini_clusters"] = 2
    with open(d / "configs" / "tiny-azure.json", "w") as f:
        json.dump(cfg, f)
    return str(d)


def test_tiny_mini_clusters_correct_and_control_fails(bench_dir):
    spec = tiny_spec("drain", "tiny-azure")
    quiet = {"log": lambda *a, **k: None}
    out = run.run_cell(spec, "tiny-azure.drain", 2**31 + 7, 0.5, False,
                       allow_cpu=True, bench_dir=bench_dir, **quiet)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"decisions_per_s", "setup_s"}
    r = run.drive(spec, "tiny-azure.drain", 2**31 + 9, 0.5, False,
                  allow_cpu=True, bench_dir=bench_dir, **quiet)
    want = run.reference_run(r, **quiet)
    assert all(c["value"] == 0 for c in run.compare(r.got, want).values())
    control = run.reference_run(r, dtype=jnp.bfloat16, **quiet)
    checks = run.compare(control, want)
    assert checks["servers_differing"]["value"] > 0


def _view(tmp_path, chips):
    planes = {f"/device:TPU:{i}": _chip(ops) for i, ops in enumerate(chips)}
    planes["/host:CPU"] = {"python3": [("bench.step", 0, 200)]}
    path = _synthetic(tmp_path, planes)
    return tr.View(tr.read(path, len(chips)))


def _reader(name):
    return run.reader(BENCH, name)


def test_part_skew(tmp_path):
    # Two steps on each of two chips: 20 and 30 ns, then 40 and 40 ns; a
    # third execution on chip 0 alone has no partner and is left out.
    v = _view(tmp_path, [[(10, 30), (60, 100), (150, 160)],
                         [(12, 42), (61, 101)]])
    ctx = run.Context(v, None, None, None, None)
    got = _reader("part_skew_pct.azure").read(ctx)
    assert got == pytest.approx(100 * (10 / 25 + 0 / 40) / 2)
    one = _view(tmp_path, [[(10, 30)]])
    assert _reader("part_skew_pct.azure").read(
        run.Context(one, None, None, None, None)) is None


def test_part_roofline(tmp_path):
    """Per call, a part's bytes: ``b / k`` rows over ``n / k`` servers."""
    from harness.roofline import roofline_pct, sparse_kernel_bytes
    planes = {}
    for i in range(2):
        chip = _chip([(0, 100)])
        chip[tr.DEVICE_OP_LINE] = [
            ("%dodoor_fused_sparse.1 = (s32[8]) custom-call()", 10, 50)]
        planes[f"/device:TPU:{i}"] = chip
    planes["/host:CPU"] = {"python3": [("bench.step", 0, 200)]}
    v = tr.View(tr.read(_synthetic(tmp_path, planes), 2))
    fleet = fleets.Fleet(C=np.ones((1000, 2), np.float32),
                         node_type=np.zeros(1000, np.int32),
                         type_names=("a", "b", "c", "d"))
    peaks = {"hbm_bytes_per_s": 8.19e11}
    cfg = {"service": {"mini_clusters": 4}}
    ctx = run.Context(v, None, fleet, {"b": 400}, peaks, cfg)
    got = _reader("dodoor_fused_sparse_part_roofline.azure").read(ctx)
    assert got == pytest.approx(roofline_pct(sparse_kernel_bytes(100, 250),
                                             40e-9, 8.19e11))
    whole = _reader("dodoor_fused_sparse_roofline.drain").read(ctx)
    assert got < whole
    none = run.Context(v, None, fleet, {"b": 400}, None, cfg)
    assert _reader("dodoor_fused_sparse_part_roofline.azure").read(
        none) is None


def test_reference_scores_each_rl_as_written():
    """Two candidates whose RL ratio equals their duration ratio tie
    under LOADSCORE as written, and candidate A stays, compiled or not;
    XLA's fold of the two divisions into one would pick B here (an m510
    and an xl170 with Azure VMs, from a replay of the cell's stream)."""
    ref = named.module(os.path.join(BENCH, "reference",
                                    "dodoor_minicluster.py"))
    C = jnp.asarray([[8, 64000], [10, 64000]], jnp.float32)
    L = jnp.asarray([[105, 735000], [124, 868000]], jnp.float32)
    D = jnp.asarray([12172198.0, 10307101.0], jnp.float32)
    r = jnp.asarray([[1, 7000]], jnp.float32)
    cand = jnp.asarray([[0, 1]], jnp.int32)
    d_cand = jnp.zeros((1, 2), jnp.float32)

    def pick(choose, jit):
        def go():
            return choose(r, cand, d_cand, L, D, C, jnp.float32(0.5),
                          jnp.float32)
        return int((jax.jit(go) if jit else go)()[0])

    assert pick(ref._choose, False) == 0
    assert pick(ref._choose, True) == 0
    assert pick(ref.DODOOR._choose, True) == 0
