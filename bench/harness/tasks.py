"""Task mixes, found by name, and the stream of tasks they draw.

A configuration's ``tasks`` block names its mix (``"mix": <name>``) and
gives the mix's parameters.  The mix is the module
``bench/tasks/<name>.py``, which exposes

``draw(rng, fleet, m, params, submit_ms) -> Tasks``
    ``m`` tasks with the given virtual arrivals, drawn from ``rng`` alone;
``core_seconds_per_task(fleet, params) -> float``
    the mean core-seconds a task of the mix holds on ``fleet``, from which
    a traffic mix's ``load`` becomes a rate.

``params`` is the configuration's whole ``tasks`` block.  Every mix
returns the one :class:`Tasks` tuple below, which is what the served path
and the plain references take.  A new mix is a new file beside the others.
"""
from __future__ import annotations

import os
from typing import NamedTuple

import numpy as np

from . import named


class Tasks(NamedTuple):
    """A stream of tasks, as the served path takes them."""
    r_submit: np.ndarray   # [m, 2] declared demand (mean over node types)
    r_exec: np.ndarray     # [m, T, 2] per-node-type cores and MB
    d_est: np.ndarray      # [m, T] profiled duration per node type (ms)
    d_act: np.ndarray      # [m, T] actual duration per node type (ms)
    submit_ms: np.ndarray  # [m] virtual arrival time (ms)

    def __len__(self) -> int:
        return int(self.submit_ms.shape[0])

    def rows(self, lo: int, hi: int) -> "Tasks":
        return Tasks(*(a[lo:hi] for a in self))


class TaskMix:
    """A configuration's task mix on one fleet."""

    def __init__(self, module, params: dict, fleet):
        self.module, self.params, self.fleet = module, params, fleet

    def draw(self, rng: np.random.Generator, m: int,
             submit_ms: np.ndarray) -> Tasks:
        """``m`` tasks of the mix with the given virtual arrivals."""
        return self.module.draw(rng, self.fleet, m, self.params, submit_ms)

    def rate_at_load(self, load: float) -> float:
        """Tasks per second that hold ``load`` of the fleet's cores busy."""
        return load * self.fleet.cores / self.module.core_seconds_per_task(
            self.fleet, self.params)


def load(bench_dir: str, params: dict, fleet) -> TaskMix:
    """The mix a configuration's ``tasks`` block names, on ``fleet``."""
    return TaskMix(named.module(os.path.join(bench_dir, "tasks",
                                             params["mix"] + ".py")),
                   params, fleet)
