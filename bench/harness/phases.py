"""The served path's own names on a trace: host phases and step stages.

The program names each phase of a block's host round trip with a
``TraceAnnotation`` ``serve.<phase>`` tagged ``block=k``: one
``serve.step`` (``serve.flush`` for the ragged tail) per block, holding
``PHASES`` in order, and ``serve.submit`` around each ring push.  Its step
program runs in three named scopes, ``SCOPES``, which reach the compiled
program's ``op_name`` metadata beside each instruction's name; a device
trace names its op events by those instructions (``%while.11 = ...``).

This module reads both off a kept ``.xplane.pb`` with ``jax.profiler``
alone: the ``serve.*`` events (:func:`read_spans`), the instruction ->
scope map of the compiled step (:func:`scope_map`, :func:`step_scopes`),
and the readings built on them.  It works beside :mod:`harness.trace` and
changes nothing that module or its readers read; on a trace or a program
without these names every reading is ``None``.
"""
from __future__ import annotations

import re
from bisect import bisect_right
from statistics import median
from typing import NamedTuple

from harness.trace import covered, union

SPAN_PREFIX = "serve."
PARENTS = ("serve.step", "serve.flush")
PHASES = ("serve.ring_pop", "serve.upload", "serve.dispatch",
          "serve.device_wait", "serve.readback", "serve.publish")
SCOPES = ("select", "commit", "push")

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?(%[\w.\-]+) = .*?op_name="([^"]*)"')


class Span(NamedTuple):
    name: str
    block: int | None
    start: int   # ns
    end: int     # ns

    @property
    def dur(self) -> int:
        return self.end - self.start


def read_spans(path: str) -> list:
    """Every ``serve.*`` host event of the trace, in start order."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    block = dict(e.stats).get("block")
                    out.append(Span(e.name, None if block is None
                                    else int(block), int(e.start_ns),
                                    int(e.end_ns)))
    return sorted(out, key=lambda s: s.start)


def scope_map(hlo_text: str) -> dict:
    """Instruction name (``%while.11``) -> the stage scope its ``op_name``
    names, for every instruction of the compiled program under one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        scope = next((p for p in m.group(2).split("/") if p in SCOPES), None)
        if scope is not None:
            out[m.group(1)] = scope
    return out


def step_scopes(fleet, policy: dict, keywords: dict | None = None) -> dict:
    """The scope map of the served step as the cell runs it, compiled for
    the default device (the chip the trace came from): a throwaway service
    on the cell's fleet, policy and ``service`` keywords lowers its step
    for its block shapes.

    It is compiled afresh, past JAX's caches in memory and on disk: they
    key a program without its ``op_name`` metadata, so an entry made by a
    build without the scopes would come back without them.  The
    instructions, and so their names on the trace, are the same either
    way.  Call it after the cell's last compile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from harness import system
    svc = system.service(fleet, policy, seed=0, capacity=int(policy["b"]),
                         keywords=keywords)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        text = svc.lower_step().compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return scope_map(text)


class Block(NamedTuple):
    parent: Span
    children: list   # the serve.* spans inside the parent, in start order


def _inside(events: list, outer: list) -> list:
    """For each of ``outer`` (disjoint, in start order), the ``events`` (in
    start order) that lie inside it."""
    starts = [e.start for e in events]
    out = []
    for o in outer:
        i = bisect_right(starts, o.start - 1)
        j = bisect_right(starts, o.end)
        out.append([e for e in events[i:j] if e is not o and e.end <= o.end])
    return out


def blocks(spans: list) -> list:
    """One entry per ``serve.step`` / ``serve.flush`` span, with the spans
    that lie inside it."""
    parents = [s for s in spans if s.name in PARENTS]
    return [Block(p, kids) for p, kids in zip(parents,
                                              _inside(spans, parents))]


def phase_ms(spans: list, phase: str) -> float | None:
    """Mean per block, in ms, of ``phase`` inside the blocks the trace holds
    whole."""
    durs = [c.dur for b in blocks(spans) for c in b.children
            if c.name == phase]
    return sum(durs) / len(durs) / 1e6 if durs else None


def check_blocks(spans: list, bench_steps: list) -> dict | None:
    """How the trace's blocks meet the span contract: each parent holds
    every phase once, in order, with its own block id, inside a
    ``bench.step``; and how much of the parent its phases cover."""
    bl = blocks(spans)
    if not bl:
        return None
    ordered = nested = 0
    cover = []
    for b in bl:
        kids = [c for c in b.children if c.name in PHASES]
        if (tuple(c.name for c in kids) == PHASES
                and {c.block for c in kids} == {b.parent.block}):
            ordered += 1
        if any(s.start <= b.parent.start and b.parent.end <= s.end
               for s in bench_steps):
            nested += 1
        iv = union(kids)
        cover.append(covered(iv, b.parent.start, b.parent.end)
                     / max(b.parent.dur, 1))
    ids = [b.parent.block for b in bl]
    return {"blocks": len(bl), "phases_in_order": ordered,
            "inside_bench_step": nested,
            "distinct_block_ids": len(set(ids)),
            "consecutive_ids": ids == list(range(ids[0], ids[0] + len(ids))),
            "cover_min": min(cover), "cover_median": median(cover)}


def _step_ops(view) -> list:
    """The op events inside the step program's executions."""
    return [e for ops in _inside(view.ops, view.steps()) for e in ops]


def scope_ms(view, smap: dict, scope: str) -> float | None:
    """Per block, in ms, the busy union of the step's ops under
    ``scope``."""
    steps = view.steps()
    ops = [e for e in _step_ops(view) if smap.get(e.op) == scope]
    if not steps or not ops:
        return None
    busy = union(ops)
    return covered(busy, view.lo, view.hi) / len(steps) / 1e6


def scope_shares(view, smap: dict) -> dict | None:
    """Share of the step program's busy device time under each scope, and
    under any of them (``staged``)."""
    ops = _step_ops(view)
    if not ops or not any(smap.get(e.op) in SCOPES for e in ops):
        return None
    whole = covered(union(ops), view.lo, view.hi)

    def share(keep):
        return covered(union([e for e in ops if keep(smap.get(e.op))]),
                       view.lo, view.hi) / whole

    out = {s: share(lambda x, s=s: x == s) for s in SCOPES}
    out["staged"] = share(lambda x: x in SCOPES)
    return out


def top_ops(view, smap: dict, k: int = 10) -> list:
    """``View.top_ops`` with each instruction prefixed by its scope."""
    return [[f"{smap[op]} {op}" if op in smap else op, s]
            for op, s in view.top_ops(k)]


def idle_gaps(view, spans: list, k: int = 10) -> list:
    """``View.idle_gaps``, each gap named by the innermost host span that
    overlaps it most: of the ``serve.*`` and ``bench.*`` spans over the gap
    with no such span inside them, the one with the most overlap (so a
    long ``bench.wait`` keeps its name); ``View.idle_gaps``' name where
    none overlaps."""
    def overlap(s, a, b):
        return min(s.end, b) - max(s.start, a)

    host = list(spans) + list(view.trace.spans)
    out = []
    for (name, seconds), (a, b) in zip(view.idle_gaps(k),
                                       _gap_bounds(view, k)):
        over = [s for s in host if overlap(s, a, b) > 0]
        leaves = [s for s in over if not any(
            t is not s and s.start <= t.start and t.end <= s.end
            for t in over)]
        if leaves:
            name = max(leaves, key=lambda s: overlap(s, a, b)).name
        if name == "bench.step":
            name = "bench.step (host side)"
        out.append([name, seconds])
    return out


def host_split_ms(view, spans: list) -> dict | None:
    """``host_gap_ms`` split by phase: per block, in ms, the part of each
    phase in which the device ran nothing, and ``other``, the rest of the
    enclosing ``bench.step`` span's host gap (outside every phase)."""
    steps = view.spans("bench.step")
    if not view.busy:
        return None
    total = dict.fromkeys(PHASES + ("other",), 0)
    n = 0
    for b in blocks(spans):
        outer = next((s for s in steps if s.start <= b.parent.start
                      and b.parent.end <= s.end), None)
        if outer is None:
            continue
        rest = outer.dur - covered(view.busy, outer.start, outer.end)
        for c in b.children:
            if c.name in total:
                host = c.dur - covered(view.busy, c.start, c.end)
                total[c.name] += host
                rest -= host
        total["other"] += rest
        n += 1
    return {p: v / n / 1e6 for p, v in total.items()} if n else None


def _gap_bounds(view, k: int) -> list:
    """The ``k`` longest gaps' bounds, in ``View.idle_gaps``' order."""
    edges = [view.lo] + [x for iv in view.busy for x in iv] + [view.hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return sorted(gaps, key=lambda g: g[0] - g[1])[:k]
