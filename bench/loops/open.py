"""The open loop: tasks arrive on a wall-clock schedule whether or not the
service keeps up, and each task's latency runs from when it was due.

The due times follow the mix's arrival process at the mix's rate; a task's
virtual ``submit_ms`` equals its due time, counted from the window's start,
so the simulated fleet carries exactly the offered load.  The ring holds
every arrival of the window.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from harness import traffic
from harness.latency import Recorder
from harness.tasks import Tasks


class Plan:
    def __init__(self, tasks, due_s: np.ndarray, b: int):
        self.tasks, self.due_s = tasks, due_s
        self.capacity = len(due_s) + b


def prepare(rng, mix, fleet, draw, seconds: float, b: int,
            rate: float) -> Plan:
    due = traffic.schedule(mix.gaps(rate), rng, rate, seconds)
    return Plan(draw(rng, len(due), due * 1e3), due, b)


def run(svc, plan: Plan, seconds: float, b: int, submit,
        tick=None) -> traffic.Window:
    """A step runs whenever a full block is buffered; otherwise the loop
    sleeps until the task that fills the block is due.  When the window
    closes the generator stops, and the buffered tasks are placed by
    ``step()`` and the ragged tail by ``flush()``.  ``tick(elapsed)`` is
    called once per pass (the traced run starts its profiler there)."""
    tasks, due_s = plan.tasks, plan.due_s
    n = len(tasks)
    w = traffic.Window()
    accepted = np.ones(n, bool)
    dispatch = np.full(n, np.nan)
    done = np.full(n, np.nan)
    sent = placed = 0
    w.compiles = (svc.compiles, None)
    t0 = time.perf_counter()
    end = t0 + seconds

    def place(count, call):
        nonlocal placed
        t_d = time.perf_counter()
        with TraceAnnotation("bench.step"):
            call()
        t_e = time.perf_counter()
        w.steps.append((t_d, t_e))
        dispatch[placed:placed + count] = t_d
        done[placed:placed + count] = t_e
        placed += count

    def send(upto):
        nonlocal sent
        if upto > sent:
            with TraceAnnotation("bench.submit"):
                try:
                    submit(svc, tasks.rows(sent, upto))
                except RuntimeError:          # the ring refused the chunk
                    w.refused += upto - sent
                    accepted[sent:upto] = False
            sent = upto

    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if tick is not None:
            tick(now - t0)
        send(int(np.searchsorted(due_s, now - t0, side="right")))
        if svc.available >= b:
            place(b, svc.step)
            continue
        fill = placed + w.refused + b - 1
        target = t0 + (due_s[fill] if fill < n else seconds)
        with TraceAnnotation("bench.wait"):
            w.lateness.append(traffic.sleep_until(min(target, end)))
    w.t1 = time.perf_counter()
    send(n)
    while svc.available >= b:
        place(b, svc.step)
    if svc.available:
        place(svc.available, svc.flush)
    w.compiles = (w.compiles[0], svc.compiles)
    w.t0, w.placed = t0, placed
    w.tasks = Tasks(*(a[accepted] for a in tasks))
    w.due = t0 + due_s[accepted]
    w.dispatch, w.done = dispatch[:placed], done[:placed]
    return w


def latency(w: traffic.Window) -> Recorder:
    """Every placed task's wait, in ms, from its due time to the end of
    the ``step()`` or ``flush()`` that placed it."""
    lat = Recorder()
    lat.record((w.done - w.due[:w.placed]) * 1e3)
    return lat


def end_to_end(w: traffic.Window, log=print) -> dict:
    """The median and the 95th percentile over every task, and how late the
    generator ran, on an earlier line."""
    lat = latency(w)
    values = {"decision_p50_ms": lat.percentile(50),
              "decision_p95_ms": lat.percentile(95)}
    log(f"decision latency over {lat.count} tasks: p50 "
        f"{values['decision_p50_ms']:.4f} ms, p95 "
        f"{values['decision_p95_ms']:.4f} ms, p99 "
        f"{lat.percentile(99):.4f} ms", flush=True)
    if w.lateness:
        late = np.asarray(w.lateness) * 1e3
        log(f"generator lateness: mean {late.mean():.4f} ms, p99 "
            f"{np.percentile(late, 99):.4f} ms, max {late.max():.4f} ms "
            f"over {late.size} waits", flush=True)
    return values
