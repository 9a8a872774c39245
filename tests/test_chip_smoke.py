"""CPU rehearsal of ``chip_smoke.py``: each phase at a tiny size, with the
Pallas kernel interpreted where a phase asks for it, and the script's
refusal to run off the chip."""
import importlib.util
import pathlib

import pytest

from repro.sim import make_testbed
from repro.workloads import functionbench as fb

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


@pytest.fixture(scope="module")
def tiny():
    return make_testbed(scale=0.2), fb.synthesize(m=120, qps=60.0, seed=0)


def test_refuses_to_run_off_the_chip():
    with pytest.raises(smoke.SmokeFailure, match="no tpu"):
        smoke.main([])


def test_phase_testbed_all_policies(tiny):
    cluster, wl = tiny
    smoke.phase_testbed(cluster, {"fb": wl}, b=10)


def test_phase_outages_masked_kernel(tiny):
    cluster, wl = tiny
    assert smoke.phase_outages(cluster, wl, b=10, count=4,
                               horizon_ms=2_000.0, use_kernel=True)


def test_phase_scale():
    smoke.phase_scale(n=300, m=1_000, qps=400.0, b=50)


def test_phase_four_chips_on_one_device():
    smoke.phase_four_chips(devices=1, n=400, m=800, b=50, shards=4)


def test_same_result_names_first_divergence(tiny):
    cluster, wl = tiny
    from repro.sim import EngineConfig, simulate
    res = simulate(wl, cluster, EngineConfig(policy="random", b=10), 0,
                   mode="batched")
    moved = res._replace(server=res.server.copy())
    moved.server[7] = (moved.server[7] + 1) % cluster.num_servers
    with pytest.raises(smoke.SmokeFailure, match="first at decision 7"):
        smoke.same_result(moved, res, "x")
