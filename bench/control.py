#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's and the
control's, on several seeds, at a cell's own size and load.

    python3 bench/control.py --workload cell10k-fb.open --seconds 20 \
        --seeds 11,12,13

For each seed: one run of the cell as ``run.py`` makes it, then the plain
reference in float32 (what the configuration states) and the control, the
same reference with its load scores in bfloat16 (the next precision down),
put in the program's place.  It prints each compared number for the
program (the lower reading) and for the control (the upper reading).  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax.numpy as jnp

import run
from harness import named


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = named.json_file(os.path.join(run.ROOT, "BENCHMARK.json"))
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run.drive(spec, args.workload, seed, args.seconds, False)
        want = run.reference_run(r)
        control = run.reference_run(r, dtype=jnp.bfloat16)
        row = {"seed": seed, "decisions": len(r.window.tasks),
               "program": {k: c["value"] for k, c in
                           run.compare(r.got, want).items()},
               "control": {k: c["value"] for k, c in
                           run.compare(control, want).items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "readings": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
