"""The open-loop generator: due times, and latency measured from the due
time against a fake service that stalls."""
import os
import time

import numpy as np
import pytest

from conftest import BENCH
from harness import named, traffic
from helpers import task_mix

OPEN = named.module(os.path.join(BENCH, "loops", "open.py"))


def poisson(rate):
    return named.module(os.path.join(BENCH, "arrivals",
                                     "poisson.py")).process({}, rate)


def test_due_times_are_a_poisson_schedule_inside_the_window():
    rng = np.random.default_rng(2**31 + 5)
    due = traffic.schedule(poisson(8000.0), rng, 8000.0, 2.0)
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 2.0
    assert len(due) == 16_000
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1 / 8000, rel=0.05)
    assert gaps.std() == pytest.approx(1 / 8000, rel=0.1)    # exponential
    again = traffic.schedule(poisson(8000.0),
                             np.random.default_rng(2**31 + 5), 8000.0, 2.0)
    np.testing.assert_array_equal(due, again)
    # Another seed: other times, the same count (the same blocks and tail).
    other = traffic.schedule(poisson(8000.0), np.random.default_rng(9),
                             8000.0, 2.0)
    assert len(other) == len(due) and not np.array_equal(other, due)


def test_backlog_stream_is_the_same_however_it_is_taken():
    def stream():
        return traffic.Stream(np.random.default_rng(7), poisson(84.0),
                              task_mix("testbed-fb").draw, 10)
    a, b = stream(), stream()
    a.take(traffic.CHUNK + 100)
    for k in (7, traffic.CHUNK - 7, 100):
        b.take(k)
    np.testing.assert_array_equal(a.consumed().submit_ms,
                                  b.consumed().submit_ms)
    np.testing.assert_array_equal(a.consumed().d_act, b.consumed().d_act)
    sub = a.consumed().submit_ms
    assert np.all(np.diff(sub) >= 0)
    assert 1000 / np.diff(sub.astype(np.float64)).mean() == pytest.approx(
        84.0, rel=0.05)


class StallingService:
    """A stand-in for the served path: each step takes ``step_s``, and the
    step that starts first after ``stall_at`` takes ``stall_s`` more."""

    def __init__(self, b, step_s, stall_at, stall_s):
        self.b, self.step_s = b, step_s
        self.stall_at, self.stall_s = stall_at, stall_s
        self.available = 0
        self.compiles = 1
        self.t0 = None
        self.stalled = None

    def step(self):
        now = time.perf_counter()
        extra = 0.0
        if self.stalled is None and now - self.t0 >= self.stall_at:
            self.stalled = now
            extra = self.stall_s
        time.sleep(self.step_s + extra)
        self.available -= self.b

    def flush(self):
        self.available = 0


def test_latency_runs_from_the_due_time_through_a_stall():
    b, rate, seconds = 10, 1000.0, 1.0
    due = traffic.schedule(poisson(rate), np.random.default_rng(3), rate,
                           seconds)
    tasks = task_mix("testbed-fb").draw(np.random.default_rng(4), len(due),
                                        due * 1e3)
    svc = StallingService(b, 0.001, 0.5, 0.2)

    def submit(s, rows):
        if s.t0 is None:
            s.t0 = time.perf_counter()
        s.available += len(rows)

    svc.t0 = time.perf_counter()
    w = OPEN.run(svc, OPEN.Plan(tasks, due, b), seconds, b, submit)
    assert w.placed == len(due) and w.refused == 0
    lat = w.done - w.due
    assert np.all(lat >= 0)
    # Tasks due during the stall wait for it: from the due time, not from
    # when the loop got round to submitting them.
    stall = svc.stalled
    hit = (w.due > stall) & (w.due < stall + 0.15)
    assert hit.sum() > 50
    assert np.all(lat[hit] >= (stall + 0.2 - w.due[hit]) - 1e-3)
    # Away from the stall a task waits at most for its block to fill plus
    # a step and some scheduling slack.
    calm = w.due < w.t0 + 0.4
    assert np.median(lat[calm]) < 0.03
    # Every task waits for its block's dispatch, which is after it is due.
    assert np.all(w.dispatch >= w.due - 1e-6)
