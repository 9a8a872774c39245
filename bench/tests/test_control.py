"""The control: the plain reference with its load scores in bfloat16, one
step below the float32 the configurations state, put in the program's
place, must fail the comparison that decides ``correct``."""
import jax.numpy as jnp
import numpy as np

import run
from harness import fleet as fleets
from harness import functionbench as fb
from helpers import config
from reference import dodoor


def test_bf16_scores_fail_the_comparison():
    cfg = config("testbed-fb")
    fl = fleets.build(cfg["fleet"])
    rng = np.random.default_rng(2**31 + 11)
    m = 20_000
    due_ms = np.cumsum(rng.exponential(1000 / fb.rate_at_load(fl, 0.8), m))
    tasks = fb.draw(rng, fl.type_names, m, 0.1, due_ms)
    want = dodoor.simulate(fl, cfg["policy"], tasks, 11)
    control = dodoor.simulate(fl, cfg["policy"], tasks, 11,
                              dtype=jnp.bfloat16)
    checks = run.compare(control, want)
    assert checks["servers_differing"]["value"] > 0
    assert checks["starts_differing"]["value"] > 0
    same = run.compare(want, want)
    assert all(c["value"] == 0 for c in same.values())
