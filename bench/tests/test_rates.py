"""The offered loads the configurations state, from Table 4 and the fleets."""
import pytest

from harness import fleet as fleets
from conftest import BENCH
from harness import traffic
from helpers import config, functionbench, task_mix


@pytest.mark.parametrize("name,servers,cores,rate", [
    ("testbed-fb", 100, 1334, 84.08),
    ("cell10k-fb", 10_000, 133_400, 8407.9),
])
def test_rate_at_80pct_of_cores(name, servers, cores, rate):
    cfg = config(name)
    fl = fleets.build(cfg["fleet"])
    assert fl.n == servers and fl.cores == cores
    assert functionbench().core_seconds_per_task(
        fl, cfg["tasks"]) == pytest.approx(12.6929, abs=1e-3)
    assert task_mix(name).rate_at_load(0.8) == pytest.approx(rate, rel=1e-4)
    assert cfg["derived"]["tasks_per_s_at_load_0.8"] == pytest.approx(
        rate, rel=1e-3)
    assert cfg["derived"]["cores"] == cores


def test_per_type_means_match_table4():
    fl = fleets.build(config("testbed-fb")["fleet"])
    res, dur = functionbench().profiles(fl.type_names)
    per_type = (res[:, :, 0] * dur / 1e3).mean(axis=0)
    assert per_type == pytest.approx([12.9, 8.97, 10.8, 15.65], abs=0.01)


def test_fleets_equal_the_programs():
    """The copied fleet arithmetic gives the program's own fleets."""
    import numpy as np
    from repro.sim import make_scaled, make_testbed
    for name, prog in (("testbed-fb", make_testbed()),
                       ("cell10k-fb", make_scaled(10_000))):
        fl = fleets.build(config(name)["fleet"])
        np.testing.assert_array_equal(fl.C, prog.C)
        np.testing.assert_array_equal(fl.node_type, prog.node_type)


@pytest.mark.parametrize("mix,cfg,rate", [("open", "cell10k-fb", 8407.9),
                                          ("drain", "testbed-fb", 84.08)])
def test_committed_mixes_rate(mix, cfg, rate):
    m = traffic.load(BENCH, mix)
    assert traffic.rate_per_s(m, task_mix(cfg)) == pytest.approx(rate,
                                                                  rel=1e-4)
    assert callable(m.loop.run) and callable(m.loop.end_to_end)
