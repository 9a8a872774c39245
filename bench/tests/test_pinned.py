"""The benchmark's accepted per-layer readers give the values they gave
when they were accepted, on the two traces recorded then: a change to the
program's tracing or to the harness beside them must not move them."""
import os
import types

import numpy as np
import pytest

import run
from conftest import BENCH, ROOT
from harness import fleet as fleets
from harness import named, roofline
from harness import trace as tr

DATA = os.path.join(BENCH, "tests", "data")

PINNED = {
    "cell10k-fb.open": {
        "host_gap_ms.open": 10.3707835,
        "device_idle_pct.open": 80.06442631171998,
        "commit_ms.open": 64.8683325,
        "kernel_ms.open": 1.618852,
        "dodoor_fused_sparse_roofline.open": 0.04401617791847411,
    },
    "testbed-fb.drain": {
        "host_gap_ms.drain": 8.144295,
        "device_idle_pct.drain": 94.90836149571898,
        "commit_ms.drain": 0.44653625,
        "kernel_ms.drain": 0.0023975,
        "dodoor_fused_sparse_roofline.drain": 0.34101456416367787,
    },
}


def _context(cell):
    spec = named.json_file(os.path.join(ROOT, "BENCHMARK.json"))
    c, _, _ = run.cell_spec(spec, cell)
    cfg = named.json_file(os.path.join(BENCH, "configs",
                                       c["config"] + ".json"))
    view = tr.View(tr.read(os.path.join(DATA, cell + ".xplane.pb")))
    peaks = roofline.peaks(os.path.join(BENCH, "peaks.json"), "TPU v5 lite")
    return run.Context(view, None, fleets.build(cfg["fleet"]),
                       cfg["policy"], peaks)


@pytest.mark.parametrize("cell,metric", [(c, m) for c in sorted(PINNED)
                                         for m in sorted(PINNED[c])])
def test_trace_reader_value_is_pinned(cell, metric):
    got = run.reader(BENCH, metric).read(_context(cell))
    assert got == pytest.approx(PINNED[cell][metric], rel=1e-12)


def test_fill_wait_reader_value_is_pinned():
    """``fill_wait_ms.open`` reads the window's host record, not the trace:
    the mean of dispatch - due over the tasks due in the last 3 s."""
    w = types.SimpleNamespace(
        t0=0.0, t1=10.0, placed=4,
        due=np.array([6.5, 7.5, 8.0, 9.9, 10.5]),
        dispatch=np.array([7.25, 8.0, 8.75, 10.0]))
    ctx = run.Context(None, w, None, None, None)
    got = run.reader(BENCH, "fill_wait_ms.open").read(ctx)
    assert got == pytest.approx((0.5 + 0.75 + 0.1) / 3 * 1e3, rel=1e-12)
