"""Where a slow ``step()`` spent its time, as far as the host can tell.

:class:`Watched` stands in front of the service and samples, around every
``step()`` and ``flush()``, the host clock, the CPU time of the process
and of the calling thread, and the calling thread's voluntary and
involuntary context switches; a ``gc`` callback records every collection.
After the window, :meth:`Watched.report` names the slowest calls over a
threshold with what happened inside them: thread CPU time that kept pace
with the clock points at work in the process (a collection, Python), CPU
time that stood still with involuntary switches at the host taking the
CPU away, and voluntary switches at a wait on the device or the runtime.
A call that took none of these, with no collection in it, was paused from
outside the guest, which the kernel's steal time (read before and after)
may show.  A sample costs a few microseconds; nothing else runs in the
window.
"""
from __future__ import annotations

import gc
import os
import resource
import time

SLOW_S = 0.1        # a call slower than this is reported


def _sample() -> tuple:
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return (time.perf_counter(), time.process_time(), time.thread_time(),
            ru.ru_nvcsw, ru.ru_nivcsw)


def _steal_s() -> float | None:
    """Steal time so far, summed over the guest's CPUs, in seconds, where
    the kernel reports it."""
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


class Watched:
    """The service, with each ``step()`` and ``flush()`` sampled."""

    def __init__(self, svc):
        self.svc = svc
        self.calls: list = []
        self.collections: list = []
        self._gc_t0 = None
        self._steal0 = _steal_s()
        gc.callbacks.append(self._on_gc)

    def __getattr__(self, name):
        return getattr(self.svc, name)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.collections.append((self._gc_t0, time.perf_counter(),
                                     info.get("generation")))
            self._gc_t0 = None

    def _timed(self, call):
        a = _sample()
        try:
            return call()
        finally:
            self.calls.append((a, _sample()))

    def step(self):
        return self._timed(self.svc.step)

    def flush(self):
        return self._timed(self.svc.flush)

    def close(self):
        """Stop listening to the collector and let the service go."""
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self.svc = None

    def report(self, t0: float, k: int = 5) -> list[str]:
        """One line per slow call (the ``k`` slowest), and a summary."""
        slow = sorted((c for c in self.calls if c[1][0] - c[0][0] > SLOW_S),
                      key=lambda c: c[0][0] - c[1][0])
        steal = _steal_s()
        stolen = "not reported" if steal is None or self._steal0 is None \
            else f"{steal - self._steal0:.2f} s"
        lines = [f"slow calls (> {SLOW_S * 1e3:.0f} ms): {len(slow)} of "
                 f"{len(self.calls)}; collections in the window: "
                 f"{sum(1 for c in self.collections if c[0] >= t0)}; steal "
                 f"time since set-up: {stolen}"]
        for a, z in slow[:k]:
            gc_ms = sum(max(0.0, min(e, z[0]) - max(s, a[0]))
                        for s, e, _ in self.collections) * 1e3
            lines.append(
                f"slow call at {a[0] - t0:.3f} s: {(z[0] - a[0]) * 1e3:.3f} "
                f"ms wall, {(z[1] - a[1]) * 1e3:.3f} ms process CPU, "
                f"{(z[2] - a[2]) * 1e3:.3f} ms thread CPU, {z[3] - a[3]} "
                f"voluntary / {z[4] - a[4]} involuntary switches, "
                f"{gc_ms:.3f} ms in collections")
        return lines
