"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see the single real CPU device; only launch/dryrun.py forces 512 devices.

Suite-speed plumbing:
* the persistent XLA compilation cache (compiles dominate the wall clock;
  re-runs skip them), placed by ``repro.compile_cache`` — set via env
  *before* the first ``import jax`` anywhere in the session;
* ``sim_cache`` — session-scope memoization of ``simulate()`` results so
  modules sharing a (workload, cluster, config) triple simulate once.
"""
import os

import numpy as np
import pytest

from repro.compile_cache import use_compile_cache

use_compile_cache()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")


@pytest.fixture(scope="session")
def testbed():
    from repro.sim import make_testbed
    return make_testbed()


@pytest.fixture(scope="session")
def small_testbed():
    """A 20-node heterogeneous fleet (scale=0.2) for fast engine tests."""
    from repro.sim import make_testbed
    return make_testbed(scale=0.2)


@pytest.fixture(scope="session")
def fb_small():
    from repro.workloads import functionbench as fb
    return fb.synthesize(m=600, qps=60.0, seed=0)


@pytest.fixture(scope="session")
def azure_small():
    from repro.workloads import azure
    return azure.synthesize(m=400, qps=4.0, seed=0)


@pytest.fixture(scope="session")
def sim_cache():
    """Memoized ``simulate``: ``sim_cache(wl, cluster, cfg, seed=0,
    mode=..., use_kernel=..., key=...)``.

    ``key`` names the workload/cluster pair (defaults to their ``id``s —
    stable within a session for session-scope fixtures); everything else in
    the cache key is the hashable ``EngineConfig`` itself.
    """
    from repro.sim import simulate

    cache = {}

    def run(wl, cluster, cfg, seed=0, *, mode="sequential",
            use_kernel=False, key=None):
        k = (key, id(wl), id(cluster), cfg, seed, mode, use_kernel)
        if k not in cache:
            # Pin wl/cluster so their ids stay unique for the session.
            cache[k] = (wl, cluster,
                        simulate(wl, cluster, cfg, seed, mode=mode,
                                 use_kernel=use_kernel))
        return cache[k][2]

    return run
