"""Scale-study benchmark — the sweep engine at paper scale and beyond.

Three sections, all persisted machine-readably to ``BENCH_scale.json``:

* **sweep-vs-loop** — the acceptance grid: 4 seeds × 3 α-configs of the
  dodoor batched driver on the fb_small trace, ``repro.sim.simulate_many``
  (one compiled grid, fanned across devices) against the per-run Python
  loop of ``simulate()`` calls it replaces.  Placement/ledger parity is
  asserted before timing.
* **scale points** — n ∈ {101, 10³, 10⁴, 10⁵} heterogeneous fleets
  (``make_scaled``) under synthesized Azure traces with m up to 2·10⁵,
  multi-seed, reporting per-point wall ms and decisions/s.  Points with a
  ``shards`` key run through the sharded-table planner
  (``server_shards=k`` — ISSUE 6): the replicated-``[n, …]`` operands
  become k mini-cluster shards, which is what breaks the 10⁴ decisions/s
  collapse (5,288 → tens of thousands) and makes 10⁵ reachable at all.
* **meanfield points** — n ∈ {10⁴, 10⁵} validated against the
  ``repro.sim.meanfield`` tolerance bands instead of per-run parity
  (infeasible at this scale): het=0 fleets under the full-capacity
  service workload, per-type mean queue inside the JSQ(2) fixed-point
  band for PoT and for dodoor at α=0 (queue-count sampling — the policy
  the predictor speaks about; duration-aware α>0 places better than
  classical JSQ(2) and exits the band from below).

CPU note: JAX exposes one host device by default, which would serialize the
grid; when ``JAX_PLATFORMS`` selects the CPU, this benchmark (and only it —
the other benchmarks' numbers must not see a partitioned host) sets
``--xla_force_host_platform_device_count=<cores>`` before JAX initializes,
so the grid spreads over cores as it would over accelerator devices.  On
an accelerator it runs on the real devices and sets nothing.

    PYTHONPATH=src python -m benchmarks.bench_scale [--smoke] [--json PATH]
                                                    [--single-device]
"""
from __future__ import annotations

import os
import sys

# Must precede the first `import jax` in this process: on the CPU, expose
# one host device per core so the sweep engine's multi-device fan-out has
# devices to fan over.  `--single-device` (or an inherited XLA_FLAGS
# already pinning a device count, or an already-imported jax, or any
# platform but the CPU) leaves things alone.
if (os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"
        and "--single-device" not in sys.argv and "jax" not in sys.modules
        and "xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    _ndev = min(os.cpu_count() or 1, 16)
    if _ndev > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_ndev}").strip()

import argparse
import time

import jax
import numpy as np

from benchmarks.common import write_bench_json
from repro.sim import (EngineConfig, make_scaled, make_service_workload,
                       make_testbed, measured_mean_queue, pod_mean_queue,
                       simulate, simulate_many, summarize_sweep,
                       tolerance_band)
from repro.workloads import azure
from repro.workloads import functionbench as fb


def _best_of(fn, reps: int = 5) -> float:
    """Min-of-reps wall clock (ms) after a warmup call."""
    fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def bench_sweep_vs_loop(seeds=(0, 1, 2, 3), alphas=(0.3, 0.5, 0.7),
                        m: int = 600, qps: float = 60.0, b: int = 10,
                        scale: float = 0.2, reps: int = 9) -> dict:
    """The acceptance grid: simulate_many vs a per-run loop on fb_small.

    Parity is asserted per grid point before timing — the speedup only
    counts because the sweep returns exactly what the loop returns.
    """
    cluster = make_testbed(scale=scale)
    wl = fb.synthesize(m=m, qps=qps, seed=0)
    configs = [EngineConfig(policy="dodoor", b=b, alpha=a) for a in alphas]

    def run_loop():
        return [simulate(wl, cluster, c, seed=s, mode="batched")
                for s in seeds for c in configs]

    def run_sweep():
        return simulate_many(wl, cluster, configs, seeds)

    sw = run_sweep()
    for si, s in enumerate(seeds):
        for gi, c in enumerate(configs):
            ref = simulate(wl, cluster, c, seed=s, mode="batched")
            pt = sw.point(si, gi)
            assert (ref.server == pt.server).all(), "sweep parity violated"
            assert ref.msgs_total == pt.msgs_total, "sweep ledger violated"

    # Same protocol as bench_kernels.bench_engine: each candidate timed
    # separately, min-of-reps after a warmup call.
    t_loop = _best_of(run_loop, reps)
    t_sweep = _best_of(run_sweep, reps)
    row = {"trace": "fb_small" if m == 600 else f"fb(m={m})",
           "m": m, "b": b, "num_seeds": len(seeds),
           "num_configs": len(configs), "points": len(seeds) * len(configs),
           "devices": jax.device_count(),
           "loop_ms": round(t_loop, 3), "sweep_ms": round(t_sweep, 3),
           "speedup": round(t_loop / t_sweep, 2)}
    print("bench,trace,points,devices,loop_ms,sweep_ms,speedup")
    print(f"scale,{row['trace']},{row['points']},{row['devices']},"
          f"{t_loop:.1f},{t_sweep:.1f},{row['speedup']:.2f}", flush=True)
    return row


def bench_scale_points(points, reps: int = 2) -> list:
    """Big-fleet sweeps: one simulate_many per (n, m) point, multi-seed.

    A point's optional ``shards`` runs the sharded-table planner
    (``server_shards``): k mini-clusters of n/k servers, ``b`` the
    per-mini-cluster batch — bit-identical to ``simulate_hierarchical``'s
    §4.2 decomposition, merged host-side."""
    rows = []
    print("bench,n,m,b,shards,seeds,sweep_ms,ms_per_point,decisions_per_s")
    for p in points:
        n, m, qps, b, seeds = (p["n"], p["m"], p["qps"], p["b"],
                               tuple(p["seeds"]))
        shards = p.get("shards")
        cluster = make_scaled(n, het=p.get("het", 1.0))
        wl = azure.synthesize(m=m, qps=qps, seed=0)
        cfg = EngineConfig(policy="dodoor", b=b)

        t = _best_of(lambda: simulate_many(wl, cluster, cfg, seeds,
                                           server_shards=shards), reps)
        npts = len(seeds)
        row = {"n": n, "m": m, "b": b, "qps": qps, "num_seeds": npts,
               "server_shards": shards,
               "sweep_ms": round(t, 3),
               "ms_per_point": round(t / npts, 3),
               "decisions_per_s": round(npts * m / (t * 1e-3))}
        rows.append(row)
        print(f"scale,{n},{m},{b},{shards or 1},{npts},{t:.0f},"
              f"{row['ms_per_point']:.0f},{row['decisions_per_s']}",
              flush=True)
    return rows


def _per_type_mean_queue(res, cluster, t0: float, t1: float) -> list:
    """Time-averaged queue length per node type over the window — the
    per-class quantity the heterogeneous mean-field ODE predicts."""
    out = []
    server_type = cluster.node_type[np.asarray(res.server)]
    for c in range(cluster.num_types):
        on_c = server_type == c
        n_c = int((cluster.node_type == c).sum())
        ov = np.clip(np.minimum(res.finish_ms[on_c], t1)
                     - np.maximum(res.enqueue_ms[on_c], t0), 0, None)
        out.append(float(ov.sum()) / (t1 - t0) / max(n_c, 1))
    return out


def bench_meanfield_points(points) -> list:
    """n ∈ {10⁴, 10⁵} validation rows: per-run parity is infeasible here,
    so each point is accepted against the mean-field tolerance band — the
    per-type mean queue of the sharded run must land inside the JSQ(2)
    fixed-point band (computed at the mini-cluster size n_c, the unit
    undergoing mean-field dynamics; dodoor's band adds the b-batch
    staleness term)."""
    rows = []
    print("bench,n,shards,m,policy,alpha,mean_queue,band_lo,band_hi,"
          "in_band,wall_ms,decisions_per_s")
    for p in points:
        n, k, m, lam, b = p["n"], p["shards"], p["m"], p["lam"], p["b"]
        n_c = n // k
        cluster = make_scaled(n, het=0.0)
        wl = make_service_workload(cluster, lam, m, seed=0)
        horizon = float(wl.submit_ms[-1])
        t0, t1 = 0.25 * horizon, 0.95 * horizon
        pred = pod_mean_queue(lam, d=2)
        for policy, alpha, band_b in (("pot", None, None),
                                      ("dodoor", 0.0, b)):
            kw = {} if alpha is None else {"alpha": alpha}
            cfg = EngineConfig(policy=policy, b=b, interference=0.0,
                               rbuf_slots=64, mem_units=8, **kw)
            wall = time.perf_counter()
            sw = simulate_many(wl, cluster, cfg, seeds=(0,),
                               server_shards=k)
            wall = (time.perf_counter() - wall) * 1e3
            res = sw.point(0, 0)
            q = measured_mean_queue(res, n, t0, t1)
            per_type = _per_type_mean_queue(res, cluster, t0, t1)
            lo, hi = tolerance_band(pred, n_c, b=band_b)
            in_band = all(lo <= qt <= hi for qt in per_type)
            row = {"n": n, "server_shards": k, "m": m, "lam": lam, "b": b,
                   "policy": policy, "alpha": alpha,
                   "mean_queue": round(q, 4),
                   "per_type_mean_queue": [round(x, 4) for x in per_type],
                   "predicted": round(pred, 4),
                   "tolerance_band": [round(lo, 4), round(hi, 4)],
                   "in_band": bool(in_band),
                   "wall_ms": round(wall, 1),
                   "decisions_per_s": round(m / (wall * 1e-3))}
            rows.append(row)
            print(f"meanfield,{n},{k},{m},{policy},{alpha},{q:.4f},"
                  f"{lo:.4f},{hi:.4f},{in_band},{wall:.0f},"
                  f"{row['decisions_per_s']}", flush=True)
    return rows


def main(*, smoke: bool = False,
         json_path: str | None = "BENCH_scale.json"):
    if smoke:
        # CI-sized: the acceptance grid stays intact (it *is* the headline
        # number) but fewer timing reps; scale points shrink to seconds.
        # The sharded n=10³ point doubles as the CI perf-regression probe
        # (tools/check_perf_regression.py); the meanfield section is
        # full-mode only — steady-state windows don't shrink to CI time.
        svl = bench_sweep_vs_loop(reps=3)
        points = [
            {"n": 101, "m": 4000, "qps": 10.0, "b": 50, "seeds": (0, 1)},
            {"n": 1000, "m": 20000, "qps": 100.0, "b": 500, "seeds": (0,)},
            {"n": 1000, "m": 20000, "qps": 100.0, "b": 100, "seeds": (0,),
             "shards": 4},
        ]
        rows = bench_scale_points(points, reps=1)
        mf = []
    else:
        svl = bench_sweep_vs_loop()
        points = [
            {"n": 101, "m": 20000, "qps": 20.0, "b": 50,
             "seeds": (0, 1, 2, 3)},
            {"n": 1000, "m": 100000, "qps": 100.0, "b": 500,
             "seeds": (0, 1)},
            # the old ceiling: replicated table at n=10⁴ (kept as the
            # baseline the sharded point is measured against)...
            {"n": 10000, "m": 200000, "qps": 400.0, "b": 500,
             "seeds": (0, 1)},
            # ...and the ISSUE 6 fix: the same point sharded (10 × 10³
            # mini-clusters), plus n=10⁵ — unreachable before.
            {"n": 10000, "m": 200000, "qps": 400.0, "b": 500,
             "seeds": (0, 1), "shards": 10},
            {"n": 100000, "m": 200000, "qps": 400.0, "b": 500,
             "seeds": (0, 1), "shards": 100},
        ]
        rows = bench_scale_points(points, reps=1)
        mf = bench_meanfield_points([
            {"n": 10_000, "shards": 5, "m": 100_000, "lam": 0.7, "b": 50},
            {"n": 100_000, "shards": 100, "m": 1_000_000, "lam": 0.7,
             "b": 50},
        ])
    if json_path:
        write_bench_json(json_path,
                         {"sweep_vs_loop": svl, "scale_points": rows,
                          "meanfield_points": mf}, bench="scale")
    return svl, rows, mf


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized shapes")
    ap.add_argument("--json", default="BENCH_scale.json",
                    help="output path for machine-readable results "
                         "('' disables)")
    ap.add_argument("--single-device", action="store_true",
                    help="do not force one host device per core "
                         "(exercises the chunked-vmap fallback)")
    args = ap.parse_args()
    main(smoke=args.smoke, json_path=args.json or None)
