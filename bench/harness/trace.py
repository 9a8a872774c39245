"""Reduction of a profiler trace to the benchmark's per-layer numbers.

A traced run records the benchmark's own host spans (``bench.step``,
``bench.submit``, ``bench.wait``, ``bench.generate``: ``TraceAnnotation``s
around its calls into the service) and the device's operations on one
clock.  This module reads the ``.xplane.pb`` with ``jax.profiler`` alone
and offers the primitives the per-layer readers in ``bench/metrics/`` use:
the devices' operations, the step programs and kernel calls among them, the
host spans, busy time as a union of intervals, and the traced window.

A cell on ``chips`` chips keeps the ``chips`` devices that ran the most
programs, each one's events apart and all of them merged: ``busy`` is the
time in which any of them worked, and ``busy_s`` the mean over the chips of
each one's busy time.  With one chip both are that chip's.

Names on the device are matched by substring, as read off a chip trace:
``DEVICE_OP_LINE`` is the line that holds one event per operation,
``STEP_PROGRAM`` names the served step's compiled program (``jit__serve_step``
on the program line), and ``KERNEL`` the sparse Pallas kernel's operation
(an op event's name is its HLO instruction, ``%<name> = <shape> <opcode>(...)``;
the kernel's is ``%dodoor_fused_sparse_pallas.<k> = ... custom-call(...)``).
Op events nest (a ``while`` spans its body's ops), so times are unions of
intervals or whole program executions, never sums over all op events.
"""
from __future__ import annotations

import glob
import os
from typing import NamedTuple


DEVICE_PLANE = "/device:TPU:"
DEVICE_OP_LINE = "XLA Ops"
DEVICE_PROGRAM_LINE = "XLA Modules"
STEP_PROGRAM = "_serve_step"
KERNEL = "%dodoor_fused_sparse"
SPAN_PREFIX = "bench."


class Event(NamedTuple):
    name: str
    start: int   # ns
    end: int     # ns

    @property
    def dur(self) -> int:
        return self.end - self.start

    @property
    def op(self) -> str:
        """An op event's instruction name (``%while.11``)."""
        return self.name.split(" = ", 1)[0]


class Trace(NamedTuple):
    ops: list         # device operations, every kept device's, in order
    programs: list    # device program executions, every kept device's
    spans: list       # the benchmark's host spans
    lines: dict       # every (plane, line) name -> event count (diagnostic)
    devices: tuple = ()   # per kept device, busiest first: (ops, programs)


def find(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def read(path: str, chips: int = 1) -> Trace:
    """The trace's host spans, and the op and program events of the
    ``chips`` devices that ran the most programs (a device the trace lacks
    counts as one that ran nothing)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict = {}
    spans, lines = [], {}

    def events(line):
        return [Event(e.name, int(e.start_ns), int(e.end_ns))
                for e in line.events]

    for plane in data.planes:
        for line in plane.lines:
            evs = events(line)
            lines[f"{plane.name} | {line.name}"] = len(evs)
            if plane.name.startswith(DEVICE_PLANE):
                dev = devices.setdefault(plane.name, {})
                if line.name in (DEVICE_OP_LINE, DEVICE_PROGRAM_LINE):
                    dev[line.name] = evs
            elif plane.name.startswith("/host:"):
                spans += [e for e in evs if e.name.startswith(SPAN_PREFIX)]
    kept = sorted(devices.values(), key=lambda d: -len(d.get(
        DEVICE_PROGRAM_LINE, [])))[:chips]
    kept += [{}] * (chips - len(kept))
    key = lambda e: e.start                                 # noqa: E731
    per = tuple((sorted(d.get(DEVICE_OP_LINE, []), key=key),
                 sorted(d.get(DEVICE_PROGRAM_LINE, []), key=key))
                for d in kept)
    return Trace(sorted((e for ops, _ in per for e in ops), key=key),
                 sorted((e for _, pr in per for e in pr), key=key),
                 sorted(spans, key=key), lines, per)


def union(events) -> list:
    """Disjoint, sorted [start, end) intervals covering ``events``."""
    out: list = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return out


def covered(intervals, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) that ``intervals`` (disjoint) cover."""
    return int(sum(max(0, min(b, hi) - max(a, lo)) for a, b in intervals))


class View:
    """The traced window of one run: from the first to the last host span
    the benchmark recorded while the profiler ran."""

    def __init__(self, trace: Trace):
        self.trace = trace
        spans = trace.spans
        self.lo = spans[0].start if spans else 0
        self.hi = max((s.end for s in spans), default=0)
        inside = lambda e: self.lo <= e.start and e.end <= self.hi  # noqa
        self.ops = [e for e in trace.ops if inside(e)]
        self.programs = [e for e in trace.programs if inside(e)]
        # Each kept device's busy intervals (its ops, else its programs),
        # and their union: the time in which any chip worked.
        self.devices = [([e for e in ops if inside(e)],
                         [e for e in programs if inside(e)])
                        for ops, programs in trace.devices
                        or ((trace.ops, trace.programs),)]
        self.device_busy = [union(ops or programs)
                            for ops, programs in self.devices]
        self.busy = union([e for ops, programs in self.devices
                           for e in ops or programs])

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Mean over the kept chips of each chip's busy time in the
        window (s)."""
        return sum(covered(iv, self.lo, self.hi) for iv in
                   self.device_busy) / len(self.device_busy) / 1e9

    def spans(self, name: str) -> list:
        return [s for s in self.trace.spans if s.name == name]

    def steps(self) -> list:
        """Executions of the served step's program."""
        return [e for e in self.programs if STEP_PROGRAM in e.name]

    def kernel_calls(self) -> list:
        return [e for e in self.ops if e.op.startswith(KERNEL)]

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` instructions with the most device time (inclusive: a
        loop's time includes its body's)."""
        total: dict = {}
        for e in self.ops:
            total[e.op] = total.get(e.op, 0) + e.dur
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> list:
        """The ``k`` longest device-idle gaps, each named by the host span
        that overlaps it most (``idle`` where none does)."""
        edges = [self.lo] + [x for iv in self.busy for x in iv] + [self.hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
        out = []
        for a, b in gaps:
            best = max(self.trace.spans,
                       key=lambda s: min(s.end, b) - max(s.start, a),
                       default=None)
            name = "idle"
            if best is not None and min(best.end, b) > max(best.start, a):
                name = best.name
            if name == "bench.step":
                name = "bench.step (host side)"
            out.append([name, (b - a) / 1e9])
        return out
