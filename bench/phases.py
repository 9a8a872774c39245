#!/usr/bin/env python3
"""Where a kept trace's time went, by the served path's own names.

    python3 bench/phases.py --workload <cell> --trace <run>.xplane.pb \
        [--map <scopes>.json | --map-out <scopes>.json]

Reads a trace that ``bench/run.py --trace 1 --keep-trace <dir>`` kept and
prints one JSON object: per block, the mean of each ``serve.*`` host phase
and the device time of each step stage (``select``, ``commit``, ``push``);
how the blocks meet the span contract; each stage's share of the step
program's device time; the longest device ops labelled by stage and the
longest idle gaps named by host phase; and, beside them, the benchmark's
own readings of the same trace.  The instruction -> stage map is read from
``--map``, else compiled from the cell's step for the default device, so
without ``--map`` run it on the chip the trace came from; ``--map-out``
keeps the compiled map.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from statistics import median

import run
from harness import fleet as fleets
from harness import named, phases
from harness import trace as tr


def read(view, spans: list, smap: dict, readers: dict, ctx) -> dict:
    """Every reading of one trace (``None`` where it finds nothing)."""
    bench_steps = view.spans("bench.step")
    submits = [s for s in spans if s.name == "serve.submit"]
    return {
        "phases_ms": {p: phases.phase_ms(spans, p) for p in phases.PHASES},
        "submit_ms": (sum(s.dur for s in submits) / len(submits) / 1e6
                      if submits else None),
        "blocks": phases.check_blocks(spans, bench_steps),
        "host_split_ms": phases.host_split_ms(view, spans),
        "stages_ms": {s: phases.scope_ms(view, smap, s)
                      for s in phases.SCOPES},
        "stage_shares": phases.scope_shares(view, smap),
        "bench_step_ms_median": (median(s.dur for s in bench_steps) / 1e6
                                 if bench_steps else None),
        "benchmark": {name: mod.read(ctx) for name, mod in readers.items()},
        "device_ops": phases.top_ops(view, smap),
        "idle_gaps": phases.idle_gaps(view, spans),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", required=True)
    ap.add_argument("--map", default=None)
    ap.add_argument("--map-out", default=None)
    args = ap.parse_args(argv)
    spec = named.json_file(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, _, layer = run.cell_spec(spec, args.workload)
    cfg = named.json_file(os.path.join(run.BENCH, "configs",
                                       cell["config"] + ".json"))
    fleet, policy = fleets.build(cfg["fleet"]), cfg["policy"]
    if args.map:
        smap = named.json_file(args.map)
    else:
        smap = phases.step_scopes(fleet, policy, cfg.get("service"))
        if args.map_out:
            with open(args.map_out, "w") as f:
                json.dump(smap, f, sort_keys=True)
    view = tr.View(tr.read(args.trace, int(cell["chips"])))
    ctx = run.Context(view, None, fleet, policy, None, cfg)
    readers = {m["name"]: run.reader(run.BENCH, m["name"]) for m in layer
               if m["name"].split(".")[0] in ("host_gap_ms", "commit_ms",
                                              "kernel_ms",
                                              "device_idle_pct")}
    print(json.dumps(read(view, phases.read_spans(args.trace), smap,
                          readers, ctx)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
