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


def _synthetic(tmp_path, planes: dict) -> str:
    """An ``.xplane.pb`` holding ``planes``: {plane: {line: [(name, start_ns,
    end_ns), ...]}}, every event on its own metadata id."""
    from jax.profiler import ProfileData
    out, meta = [], []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        text, meta = [], []
        for lid, (line, events) in enumerate(lines.items(), 1):
            evs = []
            for name, a, b in events:
                meta.append(name)
                evs.append(f"events {{ metadata_id: {len(meta)} "
                           f"offset_ps: {a * 1000} "
                           f"duration_ps: {(b - a) * 1000} }}")
            text.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 '
                        + " ".join(evs) + " }")
        text += [f'event_metadata {{ key: {i} value {{ id: {i} '
                 f'name: "{name}" }} }}' for i, name in enumerate(meta, 1)]
        out.append(f'planes {{ id: {pid} name: "{plane}" '
                   + " ".join(text) + " }")
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        "\n".join(out)))
    return str(path)


def _chip(ops):
    """A device plane: one step program and one op per interval."""
    return {tr.DEVICE_PROGRAM_LINE: [("jit__serve_step(1)", a, b)
                                     for a, b in ops],
            tr.DEVICE_OP_LINE: [(f"%while.{i} = s32[] while()", a, b)
                                for i, (a, b) in enumerate(ops)]}


PLANES = {
    "/device:TPU:0": _chip([(20, 40)]),
    "/device:TPU:1": _chip([(10, 30), (50, 60)]),
    "/device:TPU:2": _chip([(70, 80), (82, 84), (86, 88)]),
    "/host:CPU": {"python3": [("bench.step", 0, 100), ("other", 0, 5)]},
}


def test_read_keeps_the_busiest_chips(tmp_path):
    path = _synthetic(tmp_path, PLANES)
    two = tr.read(path, 2)
    assert [len(p) for _, p in two.devices] == [3, 2]
    assert [e.start for e in two.programs] == [10, 50, 70, 82, 86]
    assert sorted(two.ops) == sorted(e for ops, _ in two.devices
                                     for e in ops)
    assert [s.name for s in two.spans] == ["bench.step"]
    one = tr.read(path)
    assert one.devices == two.devices[:1]
    assert (one.ops, one.programs) == one.devices[0]
    # A chip the trace lacks is kept as one that ran nothing.
    four = tr.read(path, 4)
    assert four.devices[3] == ([], []) and four.devices[:3] == tr.read(
        path, 3).devices


def test_busy_is_the_union_and_busy_s_the_mean(tmp_path):
    path = _synthetic(tmp_path, {k: v for k, v in PLANES.items()
                                 if k != "/device:TPU:2"})
    v = tr.View(tr.read(path, 2))
    assert v.window_s == pytest.approx(100e-9)
    assert v.busy == [[10, 40], [50, 60]]                 # any chip working
    assert v.busy_s == pytest.approx((20 + 30) / 2 * 1e-9)   # per-chip mean
    ctx = run.Context(v, None, None, None, None)
    assert _reader("device_idle_pct.open").read(ctx) == pytest.approx(75.0)
    # The host's share of the step: the time in which no chip worked.
    assert _reader("host_gap_ms.drain").read(ctx) == pytest.approx(60e-6)
    assert [g[1] for g in v.idle_gaps()] == pytest.approx(
        [40e-9, 10e-9, 10e-9])
    assert len(v.steps()) == 3 and len(v.devices) == 2


def test_one_chip_reads_as_before(view):
    """With one chip, the busy union and ``busy_s`` are that chip's: the
    union of its ops in the window, as the reduction had it with a single
    device."""
    cell, v = view
    path = os.path.join(DATA, cell + ".xplane.pb")
    assert tr.read(path, 1) == tr.read(path)
    inside = [e for e in v.trace.ops if v.lo <= e.start and e.end <= v.hi]
    assert v.busy == tr.union(inside)
    assert v.busy_s == tr.covered(tr.union(inside), v.lo, v.hi) / 1e9
    # The same device counted as one of two chips, the other idle: the mean
    # halves, the union stands.
    two = tr.View(tr.read(path, 2))
    assert two.busy == v.busy
    assert two.busy_s == pytest.approx(v.busy_s / 2, rel=1e-12)


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("stats,peak", [
    ([{"peak_bytes_in_use": 7}], 7),
    ([{"peak_bytes_in_use": 5}, {"peak_bytes_in_use": 9},
      {"peak_bytes_in_use": 2}], 9),
    ([None], None),
    ([{}, {"peak_bytes_in_use": 3}], 3),
])
def test_memory_peak_is_the_fullest_chip(stats, peak):
    got, each = run.peak_bytes([_Device(s) for s in stats])
    assert got == peak
    assert each == [None if s is None else s.get("peak_bytes_in_use", 0)
                    for s in stats]
