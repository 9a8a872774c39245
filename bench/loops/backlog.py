"""The backlog loop: a standing backlog, a burst of invocations or a replay.

Before each ``step()`` the ring is topped up to one full block from an
endless stream whose virtual arrivals follow the mix's arrival process;
the window ends with its last step, and the rate of decisions is measured.
The mix's own parameter: ``prefill_per_s``, the tasks drawn per second of
window before the window opens (a run that consumes more draws further
chunks inside the window).
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

from harness import traffic


class Plan:
    def __init__(self, stream, b: int):
        self.stream = stream
        self.capacity = 2 * b


def prepare(rng, mix, fleet, draw, seconds: float, b: int,
            rate: float) -> Plan:
    return Plan(traffic.Stream(rng, mix.gaps(rate), draw,
                               int(mix["prefill_per_s"] * seconds)), b)


def run(svc, plan: Plan, seconds: float, b: int, submit,
        tick=None) -> traffic.Window:
    """Top the ring up to a block, step, until the window closes."""
    stream = plan.stream
    w = traffic.Window()
    w.compiles = (svc.compiles, None)
    t0 = time.perf_counter()
    end = t0 + seconds
    placed = 0
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if tick is not None:
            tick(now - t0)
        if svc.available < b:
            with TraceAnnotation("bench.submit"):
                submit(svc, stream.take(b - svc.available))
        t_d = time.perf_counter()
        with TraceAnnotation("bench.step"):
            svc.step()
        w.steps.append((t_d, time.perf_counter()))
        placed += b
    w.t1 = time.perf_counter()
    if svc.available:
        placed += svc.flush()
    w.compiles = (w.compiles[0], svc.compiles)
    w.t0, w.placed = t0, placed
    w.tasks = stream.consumed()
    return w


def end_to_end(w: traffic.Window, log=print) -> dict:
    """Decisions placed in the window over its whole length."""
    return {"decisions_per_s": w.placed / (w.t1 - w.t0)}
