#!/usr/bin/env python3
"""Rate sweep of an open-loop cell, to find the served path's knee once.

    python3 bench/sweep.py --workload cell10k-fb.open --seed 1 \
        --seconds 10 --rates 4000,8000,12000

One process; for each offered rate one run of the cell as ``run.py`` sets
it up and drives it, with the mix's rate replaced.  Per rate it prints the
decisions placed in the window, the p50/p95/p99 latency from the due time,
and the backlog when the window closed (tasks due but not yet placed).
The knee is the highest rate whose backlog stays within a block or two
and whose p95 has not left the fill time plus a few steps behind.  No
correctness check: a sweep is not a measurement the ledger keeps.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

import run  # sets up paths, the compile cache and JAX
from harness import named


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = named.json_file(os.path.join(run.ROOT, "BENCHMARK.json"))
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        r = run.drive(spec, args.workload, args.seed, args.seconds, False,
                      rate=rate, log=lambda *a, **k: None)
        w = r.window
        lat = r.mix.loop.latency(w)
        placed_by_close = int(np.sum(w.done <= w.t1))
        due_by_close = int(np.sum(w.due <= w.t1))
        row = {"rate_per_s": rate, "due": len(w.due),
               "placed_in_window": placed_by_close,
               "backlog_at_close": due_by_close - placed_by_close,
               "p50_ms": lat.percentile(50), "p95_ms": lat.percentile(95),
               "p99_ms": lat.percentile(99),
               "steps_per_s": placed_by_close / int(r.policy["b"])
               / (w.t1 - w.t0)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
