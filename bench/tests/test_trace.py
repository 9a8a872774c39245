"""The trace reduction, on small traces recorded on one TPU v5e chip (a
fraction of a second of each cell's window, with the benchmark's spans)."""
import os

import pytest

import run
from conftest import BENCH
from harness import trace as tr

DATA = os.path.join(BENCH, "tests", "data")


@pytest.fixture(scope="module", params=["cell10k-fb.open",
                                        "testbed-fb.drain"])
def view(request):
    path = os.path.join(DATA, request.param + ".xplane.pb")
    return request.param, tr.View(tr.read(path))


def _reader(name):
    return run.reader(BENCH, name)


def test_window_and_busy(view):
    _, v = view
    assert v.window_s > 0
    assert 0 < v.busy_s < v.window_s
    # Busy time is a union: never more than the ops' summed time, and at
    # least the longest single op.
    assert v.busy_s * 1e9 <= sum(e.dur for e in v.ops)
    assert v.busy_s * 1e9 >= max(e.dur for e in v.ops)


def test_one_kernel_call_per_step(view):
    _, v = view
    steps, calls = v.steps(), v.kernel_calls()
    assert len(steps) > 0 and len(calls) == len(steps)
    for k in calls:   # every kernel call lies inside a step program
        assert any(s.start <= k.start and k.end <= s.end for s in steps)
    assert len(v.spans("bench.step")) >= len(steps) - 1


def test_readers(view):
    cell, v = view
    sfx = cell.split(".")[1]
    ctx = run.Context(v, None, None, None, None)
    idle = _reader("device_idle_pct." + sfx).read(ctx)
    assert idle == pytest.approx(100 * (1 - v.busy_s / v.window_s))
    kernel = _reader("kernel_ms." + sfx).read(ctx)
    commit = _reader("commit_ms." + sfx).read(ctx)
    steps = v.steps()
    per_step = sum(e.dur for e in steps) / len(steps) / 1e6
    assert kernel + commit == pytest.approx(per_step)
    assert 0 < kernel < per_step
    gap = _reader("host_gap_ms." + sfx).read(ctx)
    spans = v.spans("bench.step")
    mean_span = sum(s.dur for s in spans) / len(spans) / 1e6
    assert 0 < gap < mean_span


def test_breakdown_lists(view):
    _, v = view
    ops = v.top_ops()
    assert 0 < len(ops) <= 10
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = v.idle_gaps()
    assert 0 < len(gaps) <= 10
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))
    idle_total = v.window_s - v.busy_s
    assert sum(g[1] for g in gaps) <= idle_total + 1e-9


def test_reader_finds_nothing_returns_nothing():
    empty = tr.View(tr.Trace([], [], [], {}))
    ctx = run.Context(empty, None, None, None, None)
    for name in ("kernel_ms.open", "commit_ms.drain", "host_gap_ms.open",
                 "device_idle_pct.drain",
                 "dodoor_fused_sparse_roofline.open"):
        assert _reader(name).read(ctx) is None
