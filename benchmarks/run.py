"""Benchmark aggregator: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]

Two sections persist machine-readable perf results so the trajectory is
tracked across PRs: the hot path writes ``BENCH_engine.json`` (per-policy
sequential/batched ms, speedup, decisions/s, git SHA) and the scale-sweep
section writes ``BENCH_scale.json`` (sweep-vs-loop speedup on the
acceptance grid, big-fleet sweep points).
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time


def _run_bench_scale(smoke: bool, json_path: str):
    """bench_scale runs as a child process, launched before this process
    imports JAX: on an accelerator the child must be the only process
    holding it, and on the CPU its one-host-device-per-core XLA flag must
    exist before JAX initializes and must not leak into the other
    sections' single-device numbers.  An empty ``json_path`` passes
    through and disables the file, matching ``--json``."""
    cmd = [sys.executable, "-m", "benchmarks.bench_scale",
           "--json", json_path] + (["--smoke"] if smoke else [])
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    subprocess.run(cmd, cwd=root, env=env, check=True)


def _section(title: str, fn) -> None:
    print(f"\n===== {title} =====", flush=True)
    t0 = time.time()
    fn()
    print(f"# section time: {time.time() - t0:.1f}s", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller task counts (CI-sized)")
    ap.add_argument("--json", default="BENCH_engine.json",
                    help="hot-path results file ('' disables)")
    ap.add_argument("--json-scale", default="BENCH_scale.json",
                    help="scale-sweep results file ('' disables)")
    ap.add_argument("--json-scenarios", default="BENCH_scenarios.json",
                    help="scenario-grid results file ('' disables)")
    ap.add_argument("--json-study", default="BENCH_study.json",
                    help="combined-study results file ('' disables)")
    ap.add_argument("--json-faults", default="BENCH_faults.json",
                    help="failure/recovery results file ('' disables)")
    ap.add_argument("--json-dags", default="BENCH_dags.json",
                    help="task-graph results file ('' disables)")
    ap.add_argument("--json-obs", default="BENCH_obs.json",
                    help="observability results file ('' disables)")
    ap.add_argument("--json-serve", default="BENCH_serve.json",
                    help="streaming-service results file ('' disables)")
    args = ap.parse_args()
    q = args.quick

    t_all = time.time()
    # First, while this process has not touched JAX (see _run_bench_scale).
    _section("Scale studies — vmapped sweep engine (simulate_many)",
             lambda: _run_bench_scale(smoke=q, json_path=args.json_scale))

    from . import (bench_azure, bench_dags, bench_faults,
                   bench_functionbench, bench_gap, bench_kernels,
                   bench_obs, bench_reliability, bench_roofline,
                   bench_router, bench_scenarios, bench_sensitivity,
                   bench_serve, bench_study)

    sections = [
        ("Fig 3/4/5 — Azure VM placement (§6.2)",
         lambda: bench_azure.main(m=1000 if q else 2000,
                                  qps_list=(5, 10) if q else (2, 5, 10, 20))),
        ("Fig 6/7 — FunctionBench serverless (§6.3)",
         lambda: bench_functionbench.main(
             m=2000 if q else 5000,
             qps_list=(100, 300) if q else (100, 200, 300, 400))),
        ("Fig 8 — parameter sensitivity (§6.4)",
         lambda: bench_sensitivity.main(m=1500 if q else 4000)),
        ("§2.1 — balls-into-bins gaps vs theory",
         lambda: bench_gap.main(m=8000 if q else 20000)),
        ("§5 — scheduling hot-path implementations",
         # smoke=True overrides the shapes internally (T=128, m=120)
         lambda: bench_kernels.main(smoke=q, json_path=args.json or None)),
        ("Scenario engine — bursty/diurnal/outage/churn grid",
         lambda: bench_scenarios.main(smoke=q,
                                      json_path=args.json_scenarios
                                      or None)),
        ("Unified study planner — seeds × configs × scenarios, one compile",
         lambda: bench_study.main(smoke=q,
                                  json_path=args.json_study or None)),
        ("§2.4 — Dodoor as LLM-serving router",
         lambda: bench_router.main(m=1000 if q else 2000,
                                   qps_list=(40,) if q else (20, 40, 80))),
        ("§4.2/§4.3 — store outage + hierarchical mini-clusters",
         lambda: bench_reliability.main(m=2000 if q else 4000)),
        ("Failure & recovery — kill/retry, cache loss, goodput",
         lambda: bench_faults.main(smoke=q,
                                   json_path=args.json_faults or None)),
        ("Task graphs — frontier loop × locality weight",
         lambda: bench_dags.main(smoke=q,
                                 json_path=args.json_dags or None)),
        ("Observability — trace overhead, §3.2 staleness, message ledger",
         lambda: bench_obs.main(smoke=q,
                                json_path=args.json_obs or None)),
        ("Streaming service — per-decision/step latency, donated steps",
         lambda: bench_serve.main(smoke=q,
                                  json_path=args.json_serve or None)),
        ("§Roofline — fused-kernel bytes-touched model vs measurement",
         lambda: bench_roofline.main(smoke=q)),
    ]
    for title, fn in sections:
        _section(title, fn)
    print(f"\n# total benchmark time: {time.time() - t_all:.1f}s")


if __name__ == "__main__":
    main()
