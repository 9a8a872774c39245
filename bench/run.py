#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's root:
the cell names a configuration (``bench/configs/<config>.json``, whose
``reference`` names ``bench/reference/<reference>.py``, whose ``tasks.mix``
names its task mix in ``bench/tasks/``, and whose optional ``service``
block gives the keywords that build the service and go to the reference)
and a traffic mix (``bench/traffic/<traffic>.json``, which names its loop
in ``bench/loops/`` and its arrival process in ``bench/arrivals/``); each
per-layer metric is a reader in ``bench/metrics/<metric>.py``, or in the
file of its base name (``host_gap_ms.py`` for ``host_gap_ms.open``).  The
cell's ``chips`` are all counted: the traced busy time is the mean over
them, the memory peak the largest.  Adding a configuration, a task mix, a
service keyword, a traffic mix, a loop, an arrival process, a metric or a
cell on several chips adds files and entries, and edits none.

One run: synthesize the fleet and the task stream from ``--seed``, warm up
the served path (set-up), drive ``DecisionService`` for ``--seconds``
under the mix, then compare every decision the window made with the plain
reference (placement, start time, the four message counters).  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of the window's last seconds gives its
per-layer metrics.  The last line of standard output is one JSON object;
the last lines of standard error are each compared number beside its
limit.  Off the chip, or on fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
# libtpu logs under /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# JAX's persistent compile cache: the directory the environment gives, else
# a fixed one inside the checkout; every program is cached, however quick.
os.environ["JAX_COMPILATION_CACHE_DIR"] = (
    os.environ.get("JAX_COMPILATION_CACHE_DIR")
    or os.path.join(ROOT, ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import jax  # noqa: E402
import numpy as np  # noqa: E402

T_IMPORTED = time.perf_counter()

from harness import fleet as fleets  # noqa: E402
from harness import named, roofline, stalls, system  # noqa: E402
from harness import tasks as task_mixes  # noqa: E402
from harness import trace as tr, traffic  # noqa: E402

TRACE_SECONDS = 3.0     # the traced sub-window: the window's last seconds
SEED_BITS = 31          # the service's seed is the run's seed mod 2**31


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def reader(bench_dir: str, metric: str):
    """The reader of a per-layer metric: ``metrics/<metric>.py``, else the
    file of its base name, the part before the first dot."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    if not os.path.exists(path):
        path = os.path.join(bench_dir, "metrics",
                            metric.split(".")[0] + ".py")
    return named.module(path)


def compile_clock() -> dict:
    """Running totals of backend compiles (count and seconds), fed by JAX's
    monitoring events; a copy of the bring-up smoke's clock."""
    total = {"seconds": 0.0, "count": 0}

    def listen(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total["seconds"] += seconds
            total["count"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return total


def cell_spec(spec: dict, name: str) -> tuple[dict, list, list]:
    """The cell, its end-to-end metrics and its per-layer metrics."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if mine(m) and m["moves"] in moved]
    return cells[name], e2e, layer


def check_device(chips: int, allow_cpu: bool) -> dict:
    devices = jax.devices()
    d = devices[0]
    if not allow_cpu and d.platform != "tpu":
        raise NoChip(f"JAX found no TPU: platform is {d.platform!r}")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} devices, the cell needs {chips}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


def peak_bytes(devices) -> tuple[int | None, list]:
    """The largest ``peak_bytes_in_use`` over ``devices``, and each
    device's (``None`` where the backend reports no memory)."""
    each = []
    for d in devices:
        stats = d.memory_stats()
        each.append(None if stats is None
                     else int(stats.get("peak_bytes_in_use", 0)))
    known = [v for v in each if v is not None]
    return (max(known) if known else None), each


class Profiler:
    """Starts JAX's profiler once the window has ``TRACE_SECONDS`` left."""

    def __init__(self, seconds: float, log_dir: str):
        self.at = max(0.0, seconds - TRACE_SECONDS)
        self.log_dir = log_dir
        self.on = False

    def tick(self, elapsed: float) -> None:
        if not self.on and elapsed >= self.at:
            jax.profiler.start_trace(self.log_dir)
            self.on = True

    def stop(self) -> None:
        if self.on:
            jax.profiler.stop_trace()


def compare(got: dict, want: dict) -> dict:
    """Each compared number beside its limit (all exact: limit 0)."""
    g_srv, w_srv = got["server"], want["server"]
    m = min(len(g_srv), len(w_srv))
    missing = abs(len(g_srv) - len(w_srv))
    servers = int(np.sum(g_srv[:m] != w_srv[:m])) + missing
    g_st = np.asarray(got["start_ms"], np.float32)[:m]
    w_st = np.asarray(want["start_ms"], np.float32)[:m]
    starts = int(np.sum(g_st.view(np.int32) != w_st.view(np.int32))) \
        + missing
    msgs = int(np.sum(np.abs(np.asarray(got["msgs"], np.int64)
                             - np.asarray(want["msgs"], np.int64))))
    return {"servers_differing": {"value": servers, "limit": 0},
            "starts_differing": {"value": starts, "limit": 0},
            "messages_differing": {"value": msgs, "limit": 0}}


class Drive:
    """One cell's set-up and measured window, up to the program's answers."""


def drive(spec: dict, cell_name: str, seed: int, seconds: float,
          trace: bool, *, allow_cpu: bool = False, bench_dir: str = BENCH,
          rate: float | None = None, log=print) -> Drive:
    """Set up one cell from ``--seed``, warm the served path, run the
    window (traced when ``trace``), and collect what the timed path
    produced.  ``rate`` overrides the mix's rate (the knee sweep's)."""
    r = Drive()
    r.cell, r.e2e, r.layer = cell_spec(spec, cell_name)
    r.device = check_device(int(r.cell["chips"]), allow_cpu)
    r.peaks = None if allow_cpu else roofline.peaks(
        os.path.join(bench_dir, "peaks.json"), r.device["kind"])
    clock = compile_clock()
    t_device = time.perf_counter()

    cfg = named.json_file(os.path.join(bench_dir, "configs",
                                       r.cell["config"] + ".json"))
    r.mix = traffic.load(bench_dir, r.cell["traffic"])
    r.reference = named.module(os.path.join(bench_dir, "reference",
                                            cfg["reference"] + ".py"))
    r.readers = {m["name"]: reader(bench_dir, m["name"])
                 for m in r.layer} if trace else {}
    r.config = cfg
    r.fleet = fleet = fleets.build(cfg["fleet"])
    r.policy = policy = cfg["policy"]
    r.keywords = cfg.get("service", {})
    b = int(policy["b"])
    tasks = task_mixes.load(bench_dir, cfg["tasks"], fleet)
    r.svc_seed = seed % (1 << SEED_BITS)
    rate = rate or traffic.rate_per_s(r.mix, tasks)
    plan = r.mix.loop.prepare(np.random.default_rng(seed), r.mix, fleet,
                              tasks.draw, seconds, b, rate)
    warm = tasks.draw(np.random.default_rng(0), 2 * b,
                      np.arange(2 * b, dtype=np.float32))
    t_drawn = time.perf_counter()
    system.warm_up(fleet, policy, warm, r.keywords)
    svc = stalls.Watched(system.service(fleet, policy, r.svc_seed,
                                        plan.capacity, r.keywords))
    # Everything set-up made is kept out of the collector's later passes, so
    # that a full collection in the window scans only what the window made.
    gc.collect()
    gc.freeze()
    r.setup_s = time.perf_counter() - T_START
    compiled = dict(clock)
    log(f"setup: {r.setup_s:.3f} s (imports {T_IMPORTED - T_START:.3f} s, "
        f"device {t_device - T_IMPORTED:.3f} s, fleet and stream "
        f"{t_drawn - t_device:.3f} s, warm-up and service "
        f"{T_START + r.setup_s - t_drawn:.3f} s), {compiled['seconds']:.3f} "
        f"s of it in {compiled['count']} backend compiles; n={fleet.n} b={b} "
        f"rate={rate:.1f}/s loop={r.mix['loop']}", flush=True)

    r.log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    prof = Profiler(seconds, r.log_dir) if trace else None
    tick = prof.tick if trace else None
    try:
        w = r.mix.loop.run(svc, plan, seconds, b, system.submit, tick)
    finally:
        if trace:
            prof.stop()
    r.window = w
    r.window_compiles = clock["count"] - compiled["count"]
    log(f"window: {w.t1 - w.t0:.3f} s, {w.placed} decisions, "
        f"{w.refused} refused; compiles in the window: "
        f"{r.window_compiles} (service count {w.compiles[0]} -> "
        f"{w.compiles[1]})", flush=True)
    if w.steps:
        st = np.asarray(w.steps)
        dur = (st[:, 1] - st[:, 0]) * 1e3
        worst = int(np.argmax(dur))
        log(f"steps: {dur.size}, median {np.median(dur):.3f} ms, p99 "
            f"{np.percentile(dur, 99):.3f} ms, slowest {dur[worst]:.3f} ms "
            f"at {st[worst, 0] - w.t0:.3f} s into the window", flush=True)
    for line in svc.report(w.t0):
        log(line, flush=True)
    peak, each = peak_bytes(jax.devices()[:r.device["count"]])
    r.device["memory_peak_bytes"] = peak
    r.device["memory_peak_bytes_by_device"] = each
    r.got = system.placements(svc.svc)
    svc.close()
    del svc
    gc.collect()
    return r


def reference_run(r: Drive, dtype=None, log=print) -> dict:
    """The plain reference over every task the window submitted, given
    the configuration's ``service`` keywords as the service was."""
    t0 = time.perf_counter()
    kw = dict(r.keywords) if dtype is None else dict(r.keywords, dtype=dtype)
    want = r.reference.simulate(r.fleet, r.policy, r.window.tasks,
                                r.svc_seed, **kw)
    log(f"reference: {len(r.window.tasks)} decisions in "
        f"{time.perf_counter() - t0:.3f} s", flush=True)
    return want


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, *, allow_cpu: bool = False, keep_trace=None,
             bench_dir: str = BENCH, log=print) -> dict:
    """One run of one cell; returns the result object."""
    r = drive(spec, cell_name, seed, seconds, trace, allow_cpu=allow_cpu,
              bench_dir=bench_dir, log=log)
    w = r.window
    checks = compare(r.got, reference_run(r, log=log))
    attempted = len(w.tasks) + w.refused
    failed = attempted - w.placed
    correct = (failed == 0 and r.window_compiles == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    metrics = {}
    breakdown = None
    if not trace:
        values = dict(r.mix.loop.end_to_end(w, log), setup_s=r.setup_s)
        for m in r.e2e:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        path = tr.find(r.log_dir)
        if keep_trace and path:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, os.path.join(keep_trace, cell_name
                                           + ".xplane.pb"))
        view = tr.View(tr.read(path, r.device["count"])) if path else None
        shutil.rmtree(r.log_dir, ignore_errors=True)
        if view is not None:
            ctx = Context(view, w, r.fleet, r.policy, r.peaks, r.config)
            units = {m["name"]: m["unit"] for m in r.layer}
            for name, mod in r.readers.items():
                v = mod.read(ctx)
                if v is not None:
                    metrics[name] = {"value": float(v), "unit": units[name]}
            r.device["busy_s"] = view.busy_s
            r.device["window_s"] = view.window_s
            breakdown = {"device_ops": view.top_ops(),
                         "idle_gaps": view.idle_gaps()}
            log("trace lines: " + json.dumps(view.trace.lines), flush=True)
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": r.device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


class Context:
    """What a per-layer reader may read: the traced window's view, the
    window's host-side record, the fleet, the policy, the peaks and the
    whole configuration (``service`` is its service keywords)."""

    def __init__(self, view, window, fleet, policy, peaks, config=None):
        self.view, self.window, self.fleet = view, window, fleet
        self.policy, self.peaks, self.config = policy, peaks, config

    @property
    def service(self) -> dict:
        return (self.config or {}).get("service", {})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this dir")
    args = ap.parse_args(argv)
    spec = named.json_file(os.path.join(ROOT, "BENCHMARK.json"))
    try:
        out = run_cell(spec, args.workload, args.seed, args.seconds,
                       bool(args.trace), keep_trace=args.keep_trace)
    except (NoChip, roofline.UnknownDevice) as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    print(f"correct: {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
