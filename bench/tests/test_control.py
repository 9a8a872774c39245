"""The control: the plain reference with its load scores in bfloat16, one
step below the float32 the configurations state, put in the program's
place, must fail the comparison that decides ``correct``."""
import jax.numpy as jnp
import numpy as np

import run
from harness import fleet as fleets
from helpers import config, task_mix
from reference import dodoor


def test_bf16_scores_fail_the_comparison():
    cfg = config("testbed-fb")
    fl = fleets.build(cfg["fleet"])
    rng = np.random.default_rng(2**31 + 11)
    m = 20_000
    mix = task_mix("testbed-fb")
    due_ms = np.cumsum(rng.exponential(1000 / mix.rate_at_load(0.8), m))
    tasks = mix.draw(rng, m, due_ms)
    want = dodoor.simulate(fl, cfg["policy"], tasks, 11)
    control = dodoor.simulate(fl, cfg["policy"], tasks, 11,
                              dtype=jnp.bfloat16)
    checks = run.compare(control, want)
    assert checks["servers_differing"]["value"] > 0
    assert checks["starts_differing"]["value"] > 0
    same = run.compare(want, want)
    assert all(c["value"] == 0 for c in same.values())
