"""The one general traffic generator.

A traffic mix is a data file ``bench/traffic/<mix>.json``.  It names the
pieces this module finds by name, each in a file of its own, and gives
their parameters:

``loop``
    The loop ``bench/loops/<loop>.py`` that drives the service through the
    window and yields the cell's end-to-end values.  It exposes
    ``prepare(rng, mix, fleet, draw, seconds, b, rate)`` (the stream, drawn
    from the seed by the configuration's task mix, ``draw(rng, m,
    submit_ms)``, with the ring ``capacity`` it needs), ``run(svc, plan,
    seconds, b, submit, tick)`` (a :class:`Window`) and ``end_to_end(window,
    log)`` (the values of the end-to-end metrics it measures, by name).
``arrivals``
    The arrival process ``bench/arrivals/<arrivals>.py``: ``process(mix,
    rate)`` returns ``gaps(rng, k)``, the next ``k`` gaps in seconds between
    arrivals at the mean rate ``rate``.
``load``
    The offered load as a share of the fleet's cores, for the
    configuration's task mix (tasks per second = load x cores / mean
    core-seconds per task, as :mod:`harness.tasks` has the mix give them).

Any further key is the loop's or the process's own.  What the tasks are is
the configuration's: its task mix (:mod:`harness.tasks`).  A new traffic
mix is a new data file; a new loop, arrival process or task mix is a new
file beside the others, and no file that is there changes.  Every task
stream is drawn from the run's seed alone.
"""
from __future__ import annotations

import os
import time

import numpy as np
from jax.profiler import TraceAnnotation

from . import named
from .tasks import Tasks

CHUNK = 1 << 15     # tasks drawn per generator call of an endless stream


class Mix:
    """A traffic mix: its parameters, its loop and its arrival process."""

    def __init__(self, params: dict, loop, arrivals):
        self.params, self.loop, self.arrivals = params, loop, arrivals

    def __getitem__(self, key):
        return self.params[key]

    def gaps(self, rate: float):
        return self.arrivals.process(self.params, rate)


def load(bench_dir: str, name: str) -> Mix:
    """The mix ``name``, with the loop and the arrival process it names."""
    path = os.path.join(bench_dir, "traffic", name + ".json")
    params = named.json_file(path)
    if not 0.0 < float(params["load"]) <= 1.0:
        raise ValueError(f"{path}: load must be in (0, 1]")
    loop = named.module(os.path.join(bench_dir, "loops",
                                     params["loop"] + ".py"))
    arrivals = named.module(os.path.join(bench_dir, "arrivals",
                                         params["arrivals"] + ".py"))
    return Mix(params, loop, arrivals)


def rate_per_s(mix: Mix, tasks) -> float:
    """Tasks per second: the mix's load on the fleet, for the
    configuration's task mix ``tasks`` (a :class:`harness.tasks.TaskMix`)."""
    return tasks.rate_at_load(float(mix["load"]))


def schedule(gaps, rng: np.random.Generator, rate: float,
             seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of ``round(rate *
    seconds)`` arrivals inside the window: the process's first arrivals,
    with time scaled so that the next one would fall at the window's
    close.  Every seed gets the same number of tasks, so the same full
    blocks and the same ragged tail, in other arrival times; for Poisson
    arrivals this is the Poisson process given its count."""
    k = max(1, int(round(rate * seconds)))
    t = np.cumsum(gaps(rng, k + 1))
    return t[:k] * (seconds / t[k])


class Stream:
    """An endless task stream in chunks of ``CHUNK``, whose virtual arrivals
    follow ``gaps``; the same seed gives the same stream however much of it
    a run consumes."""

    def __init__(self, rng: np.random.Generator, gaps, draw, prefill: int):
        self._rng, self._gaps, self._draw = rng, gaps, draw
        self._t_ms = 0.0
        self._parts: list = []
        self._have = 0
        self.used = 0
        while self._have < prefill:
            self._grow()

    def _grow(self) -> None:
        submit = self._t_ms + np.cumsum(self._gaps(self._rng, CHUNK) * 1e3)
        self._t_ms = float(submit[-1])
        self._parts.append(self._draw(self._rng, CHUNK, submit))
        self._have += CHUNK

    def take(self, k: int):
        while self.used + k > self._have:
            with TraceAnnotation("bench.generate"):
                self._grow()
        lo = self.used
        self.used += k
        return self._rows(lo, lo + k)

    def _rows(self, lo: int, hi: int):
        """Rows ``[lo, hi)``, joining only the chunks they span."""
        c0, c1 = lo // CHUNK, (hi - 1) // CHUNK
        parts = self._parts[c0:c1 + 1]
        joined = parts[0] if len(parts) == 1 else Tasks(
            *(np.concatenate(cols) for cols in zip(*parts)))
        return joined.rows(lo - c0 * CHUNK, hi - c0 * CHUNK)

    def consumed(self):
        """Every row taken so far, in order."""
        return self._rows(0, self.used) if self.used else \
            self._parts[0].rows(0, 0)


def sleep_until(t: float) -> float:
    """Wait for ``perf_counter() >= t``; returns how late it woke (s)."""
    left = t - time.perf_counter()
    if left > 4e-4:
        time.sleep(left - 3e-4)
    while time.perf_counter() < t:
        pass
    return time.perf_counter() - t


class Window:
    """What one loop measured.  Times are ``perf_counter`` seconds."""

    def __init__(self):
        self.t0 = self.t1 = 0.0
        self.placed = 0
        self.refused = 0
        self.due = np.zeros(0)        # open loop: due time per task
        self.dispatch = np.zeros(0)   # open loop: start of the task's step()
        self.done = np.zeros(0)       # open loop: end of the task's step()
        self.lateness = []            # open loop: generator wake lateness
        self.steps = []               # (start, end) of every step() call
        self.tasks = None             # every task submitted, in order
        self.compiles = (0, 0)        # service's compile count before/after
