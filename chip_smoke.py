#!/usr/bin/env python3
"""Chip smoke test: the served scheduling path on a TPU, end to end.

    python3 chip_smoke.py               # one chip: phases 1-4
    python3 chip_smoke.py --four-chips  # four chips: the sharded study only

One process drives the system through the entry points a user calls,
``repro.serve.serve_workload`` (the ``DecisionService``) and
``repro.sim.simulate`` / ``simulate_many``, and stops with a non-zero exit
at the first result that is wrong.  Off the chip it says so and exits
non-zero; it never falls back to the CPU.

1. Device: the platform is ``tpu``, ``use_kernel="auto"`` resolves to the
   compiled (not interpreted) Pallas kernel, and the compiled ``dodoor``
   serve step contains the Mosaic kernel (``tpu_custom_call``).
2. Paper testbed (100 servers, b = 50) under the FunctionBench and Azure
   traces: every policy served through the service equals
   ``simulate(mode="sequential")`` on the same chip (placements and all
   four message counters), and for dodoor and (1+β) the kernel and the
   two-stage path place identically.
3. Down windows: dodoor under random outages through the masked kernel,
   against the sequential driver under the same dynamics.
4. Production size: ``make_scaled(10_000)`` under an Azure trace of
   200,000 requests at b = 500, dodoor and PoT served against
   ``simulate(mode="batched", use_kernel=False)``.

``--four-chips`` runs the n = 10⁵ sharded study (100 mini-clusters, two
seeds) fanned over four chips and compares every point with the same
study on one chip.

Timings printed along the way are chip readings of a smoke run, not
benchmark numbers.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu writes its logs under /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from repro.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.dodoor_choice.kernel import _resolve_interpret  # noqa: E402
from repro.serve import DecisionService, serve_workload  # noqa: E402
from repro.sim import (EngineConfig, make_scaled, make_testbed,  # noqa: E402
                       random_outages, resolve_use_kernel, simulate,
                       simulate_many)
from repro.workloads import azure  # noqa: E402
from repro.workloads import functionbench as fb  # noqa: E402

POLICIES = ("random", "pot", "prequal", "dodoor", "one_plus_beta")
KERNEL_POLICIES = ("dodoor", "one_plus_beta")
MSG_FIELDS = ("msgs_base", "msgs_probe", "msgs_push", "msgs_flush")


class SmokeFailure(AssertionError):
    """A phase found a wrong result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def same_result(got, want, what: str) -> None:
    """Placements and the four message counters must be equal; on a
    difference, name the first diverging decision."""
    g, w = np.asarray(got.server), np.asarray(want.server)
    check(g.shape == w.shape, f"{what}: {g.shape[0]} vs {w.shape[0]} "
                              f"decisions")
    diff = np.flatnonzero(g != w)
    if diff.size:
        i = int(diff[0])
        raise SmokeFailure(
            f"{what}: {diff.size} of {g.size} placements differ; first at "
            f"decision {i}: server {int(g[i])} vs {int(w[i])}")
    for f in MSG_FIELDS:
        check(getattr(got, f) == getattr(want, f),
              f"{what}: {f} {getattr(got, f)} vs {getattr(want, f)}")


def compile_clock() -> dict:
    """A running total, under ``"seconds"``, of the time JAX spends in the
    backend compiler (XLA and Mosaic), fed by JAX's monitoring events."""
    total = {"seconds": 0.0}

    def listen(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total["seconds"] += seconds

    jax.monitoring.register_event_duration_secs_listener(listen)
    return total


def served(wl, cluster, cfg, **kw):
    """``serve_workload`` with its wall time; returns (svc, res, seconds)."""
    t0 = time.perf_counter()
    svc, res = serve_workload(wl, cluster, cfg, **kw)
    return svc, res, time.perf_counter() - t0


def reading(label: str, svc, m: int, seconds: float) -> None:
    step = svc.latency_summary()["step"]
    print(f"  {label}: {m / seconds:,.0f} decisions/s, step p50 "
          f"{step['p50_ms']} ms, p99 {step['p99_ms']} ms, "
          f"compiles {svc.compiles} (chip reading of a smoke run, not a "
          f"benchmark number)", flush=True)


def phase_device(platform: str = "tpu") -> dict:
    """Phase 1: the device, and the compiled kernel on the served path."""
    d = jax.devices()[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={info['count']} jax={jax.__version__}", flush=True)
    check(d.platform == platform,
          f"JAX found no {platform}: platform is {d.platform!r}")
    check(resolve_use_kernel("auto") is True,
          "use_kernel='auto' does not pick the kernel")
    check(_resolve_interpret(None) is False,
          "the Pallas kernel would run interpreted")
    svc = DecisionService(make_testbed(), EngineConfig(policy="dodoor", b=50))
    t0 = time.perf_counter()
    text = svc.lower_step().compile().as_text()
    print(f"  dodoor serve step compiled in {time.perf_counter() - t0:.1f} s",
          flush=True)
    check("tpu_custom_call" in text,
          "the compiled dodoor serve step holds no Mosaic kernel")
    return info


def phase_testbed(cluster, traces: dict, b: int = 50, policies=POLICIES,
                  use_kernel="auto") -> None:
    """Phase 2: every policy served vs the sequential driver, and the
    kernel vs the two-stage path for the policies that have a kernel."""
    for name, wl in traces.items():
        m = wl.r_submit.shape[0]
        for policy in policies:
            cfg = EngineConfig(policy=policy, b=b)
            ref = simulate(wl, cluster, cfg, 0, mode="sequential")
            svc, res, sec = served(wl, cluster, cfg, use_kernel=use_kernel)
            label = f"{name}/{policy}"
            if policy in KERNEL_POLICIES:
                label += " (kernel)" if svc._use_kernel else " (two-stage)"
            same_result(res, ref, f"{label} served vs sequential")
            reading(label, svc, m, sec)
            if policy in KERNEL_POLICIES:
                other = not svc._use_kernel
                _, res2, _ = served(wl, cluster, cfg, use_kernel=other)
                same_result(res2, res, f"{name}/{policy} use_kernel="
                                       f"{other} vs {not other}")
        print(f"phase 2 {name}: {len(policies)} policies equal the "
              f"sequential driver", flush=True)


def phase_outages(cluster, wl, b: int = 50, count: int = 20,
                  horizon_ms: float = 60_000.0, use_kernel="auto") -> bool:
    """Phase 3: dodoor under random outages (the masked kernel on the
    chip) vs the sequential driver under the same dynamics.  Returns
    whether the masked kernel ran."""
    dyn = random_outages(cluster.num_servers, count, horizon_ms)
    cfg = EngineConfig(policy="dodoor", b=b)
    ref = simulate(wl, cluster, cfg, 0, mode="sequential", dynamics=dyn)
    svc, res, sec = served(wl, cluster, cfg, dynamics=dyn,
                           use_kernel=use_kernel)
    same_result(res, ref, "outages/dodoor served vs sequential")
    reading("outages/dodoor" + (" (masked kernel)" if svc._masked else ""),
            svc, wl.r_submit.shape[0], sec)
    print("phase 3: outage run equals the sequential driver", flush=True)
    return svc._masked


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def phase_scale(n: int = 10_000, m: int = 200_000, qps: float = 400.0,
                b: int = 500, policies=("dodoor", "pot"),
                use_kernel="auto") -> None:
    """Phase 4: the n = 10⁴ scale point served vs the batched driver."""
    cluster = make_scaled(n)
    wl = azure.synthesize(m=m, qps=qps, seed=0)
    for policy in policies:
        cfg = EngineConfig(policy=policy, b=b)
        ref = simulate(wl, cluster, cfg, 0, mode="batched", use_kernel=False)
        svc, res, sec = served(wl, cluster, cfg, use_kernel=use_kernel)
        same_result(res, ref, f"n={n}/{policy} served vs batched")
        reading(f"n={n}/{policy}", svc, m, sec)
    print(f"phase 4: n={n}, m={m} served equals the batched driver; peak "
          f"device memory {peak_bytes(jax.devices()[0])} bytes", flush=True)


def phase_four_chips(devices: int = 4, n: int = 100_000, m: int = 200_000,
                     qps: float = 400.0, b: int = 500, shards: int = 100,
                     seeds=(0, 1)) -> None:
    """The sharded n = 10⁵ study fanned over ``devices`` chips vs the same
    study on one, point by point; every chip must have held work."""
    check(jax.device_count() == devices,
          f"{jax.device_count()} devices, not {devices}")
    cluster = make_scaled(n)
    wl = azure.synthesize(m=m, qps=qps, seed=0)
    cfg = EngineConfig(policy="dodoor", b=b)
    before = [peak_bytes(d) for d in jax.devices()]
    t0 = time.perf_counter()
    fanned = simulate_many(wl, cluster, cfg, seeds, server_shards=shards)
    t1 = time.perf_counter()
    after = [peak_bytes(d) for d in jax.devices()]
    print(f"  fanned over {devices} devices in {t1 - t0:.1f} s; peak bytes "
          f"per device {after}", flush=True)
    # Work that landed on a device raised its peak memory.  The CPU
    # backend reports no memory statistics, so there the check is skipped.
    if devices > 1 and jax.devices()[0].platform != "cpu":
        idle = [i for i, (x, y) in enumerate(zip(before, after))
                if y is None or y <= (x or 0)]
        check(not idle, f"devices {idle} held no work in the fanned study")
    one = simulate_many(wl, cluster, cfg, seeds, server_shards=shards,
                        shard=False)
    print(f"  one device in {time.perf_counter() - t1:.1f} s", flush=True)
    for si, seed in enumerate(seeds):
        same_result(fanned.point(si, 0), one.point(si, 0),
                    f"seed {seed}: {devices}-device study vs one device")
    print(f"four-chip phase: every point of the {devices}-device study "
          f"equals the one-device study", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded study across four chips")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    compiling = compile_clock()

    def timed(label, fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        print(f"{label} took {time.perf_counter() - t0:.1f} s (compiles "
              f"included)", flush=True)
        return out

    info = timed("phase 1", phase_device)
    if args.four_chips:
        timed("four-chip phase", phase_four_chips)
    else:
        testbed = make_testbed()
        fb_wl = fb.synthesize(m=5000, qps=300, seed=0)
        traces = {"functionbench": fb_wl,
                  "azure": azure.synthesize(m=4000, qps=20, seed=0)}
        timed("phase 2", phase_testbed, testbed, traces)
        check(timed("phase 3", phase_outages, testbed, fb_wl),
              "the outage run did not take the masked kernel")
        timed("phase 4", phase_scale)
    print(f"all phases passed in {time.perf_counter() - t_start:.1f} s, "
          f"{compiling['seconds']:.1f} s of it in the backend compiler",
          flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
