"""The served path's own names on a trace (``harness/phases.py``): a traced
run of a tiny cell on the CPU, and traces with their scope maps recorded on
one TPU v5e chip (a few blocks of each cell's traced window)."""
import os
import shutil

import pytest

import run
from conftest import BENCH
from harness import named, phases
from harness import trace as tr
from helpers import tiny_spec, write_tiny

DATA = os.path.join(BENCH, "tests", "data")
CELLS = ("cell10k-fb.open", "testbed-fb.drain")


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench") / "bench"
    shutil.copytree(BENCH, d, ignore=shutil.ignore_patterns("tests"))
    write_tiny(str(d))
    return str(d)


@pytest.mark.parametrize("traffic", ["drain", "open"])
def test_traced_run_holds_every_phase_once_per_block(bench_dir, tmp_path,
                                                     traffic):
    out = run.run_cell(tiny_spec(traffic), "tiny-fb." + traffic, 2**31 + 9,
                       1.0, True, allow_cpu=True, bench_dir=bench_dir,
                       keep_trace=str(tmp_path), log=lambda *a, **k: None)
    assert out["correct"], out["checks"]
    path = str(tmp_path / ("tiny-fb." + traffic + ".xplane.pb"))
    spans = phases.read_spans(path)
    view = tr.View(tr.read(path))
    got = phases.check_blocks(spans, view.spans("bench.step"))
    assert got["blocks"] >= 2
    assert got["phases_in_order"] == got["blocks"]
    assert got["inside_bench_step"] == got["blocks"]
    assert got["distinct_block_ids"] == got["blocks"]
    assert got["consecutive_ids"]
    for p in phases.PHASES:
        assert phases.phase_ms(spans, p) > 0
    # The host spans are all there is on the CPU: no device op, no stage.
    assert phases.scope_ms(view, {}, "commit") is None


HLO = '''
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_serve_step)/select/jit(f)/mul" stack_frame_id=3}
  ROOT %while.11 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(_serve_step)/commit/while"}
  %dodoor_fused_sparse.1 = (s32[56,1]) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(_serve_step)/select/jit(dodoor_fused_sparse_pallas)/dodoor_fused_sparse/pallas_call"}
  %add.7 = f32[] add(%x, %y), metadata={op_name="jit(_serve_step)/while/body/add"}
  %copy.2 = f32[8]{0} copy(%z)
  %scatter.4 = f32[8]{0} scatter(%q), metadata={op_name="jit(_serve_step)/push/scatter"}
'''


def test_scope_map_reads_op_name_metadata():
    assert phases.scope_map(HLO) == {
        "%fusion.3": "select", "%while.11": "commit",
        "%dodoor_fused_sparse.1": "select", "%scatter.4": "push"}


def test_old_traces_have_no_phases_and_keep_the_old_names():
    """A trace of a program without the spans and scopes reads nothing new,
    and the breakdown falls back to the benchmark's own names."""
    for cell in CELLS:
        path = os.path.join(DATA, cell + ".xplane.pb")
        view = tr.View(tr.read(path))
        spans = phases.read_spans(path)
        assert spans == []
        assert phases.check_blocks(spans, view.spans("bench.step")) is None
        assert phases.phase_ms(spans, "serve.upload") is None
        assert phases.scope_ms(view, {}, "commit") is None
        assert phases.scope_shares(view, {}) is None
        assert phases.idle_gaps(view, spans) == view.idle_gaps()
        assert phases.top_ops(view, {}) == view.top_ops()


def test_commit_rounds_reader_finds_nothing_returns_nothing():
    empty = tr.View(tr.Trace([], [], [], {}))
    ctx = run.Context(empty, None, None, None, None)
    for sfx in ("open", "drain"):
        assert run.reader(BENCH, "commit_rounds_ms." + sfx).read(ctx) is None


@pytest.fixture(scope="module", params=CELLS)
def recorded(request):
    """A chip trace of a program with the spans and scopes, its view, its
    ``serve.*`` spans and the scope map compiled on that chip."""
    cell = request.param
    path = os.path.join(DATA, cell + ".serve.xplane.pb")
    smap = named.json_file(os.path.join(DATA, cell + ".scopes.json"))
    return (cell, tr.View(tr.read(path)), phases.read_spans(path), smap)


def test_recorded_blocks_meet_the_span_contract(recorded):
    _, view, spans, _ = recorded
    got = phases.check_blocks(spans, view.spans("bench.step"))
    assert got["blocks"] >= 2
    for key in ("phases_in_order", "inside_bench_step",
                "distinct_block_ids"):
        assert got[key] == got["blocks"], key
    assert got["consecutive_ids"]
    assert got["cover_min"] >= 0.95


def test_recorded_phase_means(recorded):
    cell, view, spans, _ = recorded
    means = {p: phases.phase_ms(spans, p) for p in phases.PHASES}
    assert all(v > 0 for v in means.values())
    parents = [b.parent.dur for b in phases.blocks(spans)]
    assert sum(means.values()) <= sum(parents) / len(parents) / 1e6
    # The phases' host-only parts and the rest add up to host_gap_ms.
    split = phases.host_split_ms(view, spans)
    gap = run.reader(BENCH, "host_gap_ms." + cell.split(".")[1]).read(
        run.Context(view, None, None, None, None))
    assert sum(split.values()) == pytest.approx(gap, rel=1e-9)


def test_recorded_stages(recorded):
    cell, view, _, smap = recorded
    shares = phases.scope_shares(view, smap)
    assert shares["staged"] >= 0.95
    assert shares["commit"] > shares["select"] + shares["push"]
    ctx = run.Context(view, None, None, None, None)
    commit_ms = run.reader(BENCH, "commit_ms." + cell.split(".")[1]).read(
        ctx)
    rounds = phases.scope_ms(view, smap, "commit")
    assert 0.9 * commit_ms <= rounds <= commit_ms
    assert phases.scope_ms(view, {}, "commit") is None


def test_recorded_breakdown_is_labelled(recorded):
    cell, view, spans, smap = recorded
    ops = phases.top_ops(view, smap)
    assert ops[0][0] == "commit %while.11"
    assert [o[1] for o in ops] == [o[1] for o in view.top_ops()]
    if cell == "cell10k-fb.open":
        assert ["select %dodoor_fused_sparse.1"] == [
            o[0] for o in ops if "dodoor_fused_sparse" in o[0]]
    gaps = phases.idle_gaps(view, spans)
    assert [g[1] for g in gaps] == [g[1] for g in view.idle_gaps()]
    names = {g[0] for g in gaps}
    if cell == "testbed-fb.drain":
        assert names and all(n.startswith("serve.") for n in names)
    else:
        assert gaps[0][0] == "bench.wait"
