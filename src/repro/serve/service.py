"""The streaming decision service: donated-buffer step engine.

:class:`DecisionService` ingests arrival chunks through a host-side ring
buffer (:class:`~repro.serve.ring.ArrivalRing`), re-blocks them into
``b``-task decision blocks, and drives one compiled
``step(carry, block)`` per block.  The step body is the factored-out
single-block body of the offline batched scan
(:func:`repro.sim.engine._make_block_step`), jitted here with
``donate_argnums`` on the carry — ring buffers, unit clocks, cached
views, Prequal pools, and the message ledger are donated back to XLA
every step, so steady-state steps allocate nothing and never recompile
(block shapes are fixed by ``b``; the ragged tail rides a validity mask,
not a new shape).

Bit-exactness contract: feeding the service the same arrival plane as
``simulate(mode="batched")`` — same order, any chunking — yields
bit-identical placements and message ledger for all five policies.  The
service replicates the offline driver's block decomposition exactly:
global decision indices are a running ``arange``, full blocks carry an
all-true validity mask, and :meth:`DecisionService.flush` edge-pads the
ragged tail with the last task's row (``np.pad(mode="edge")``
semantics).

Cache snapshots are double-buffered per §3.2: each block boundary
publishes the post-push cached view into the non-live host buffer and
flips the pointer, so :meth:`DecisionService.snapshot` readers always
see a complete snapshot while the next block writes the other one.

Every phase of the host round trip is a ``jax.profiler.TraceAnnotation``
named ``serve.*`` and tagged ``block=k`` (block ``k`` holds decision ids
``[k·b, (k+1)·b)``): ``serve.submit`` around each ring push, and per
block one ``serve.step`` (or ``serve.flush`` for the ragged tail) holding
``serve.ring_pop``, ``serve.upload``, ``serve.dispatch``,
``serve.device_wait``, ``serve.readback`` and ``serve.publish``, in that
order.  They land on a profiler trace beside the device's operations and
cost about a microsecond each when no profiler runs.  Each block moves
its data in one transfer each way: ``serve.upload`` stages the ids, the
five planes and the validity mask in one int32 buffer, sends it, and
splits it on the device (:func:`_unpack_block`, a program of its own);
``serve.readback`` is one ``jax.device_get`` of the seven output planes
and, when snapshots are published, the three post-step views, whose
copies were queued behind the step when it was dispatched.  Both spans
carry ``arrays`` and ``bytes`` tags: the arrays and bytes moved.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..sim.cluster import ClusterSpec
from ..sim.engine import (Dynamics, EngineConfig, SimResult, _Carry,
                          _cluster_arrays, _init_carry, _lower_dynamics,
                          _make_block_step, _make_dyn, _make_dyn_ints,
                          _static_cfg, _validate_config, resolve_use_kernel)
from .latency import LatencyRecorder
from .ring import ArrivalRing, ArrivalRows

#: Host-side carry field order for checkpoints (must match _Carry).
_CARRY_FIELDS = _Carry._fields


@partial(jax.jit, static_argnames=("tt", "k"))
def _unpack_block(buf, tt: int, k: int):
    """The step's block operand from one staged int32 buffer
    (:meth:`DecisionService._stage`): ids, the five planes bit-cast back
    to float32, the ids again as task ids, and the validity mask."""
    b = buf.shape[0]
    widths = (1, k, tt * k, tt, tt, 1)
    lo = np.cumsum((0,) + widths)
    ids, r_submit, r_exec, d_est, d_act, submit_ms = (
        buf[:, a:z] for a, z in zip(lo[:-1], lo[1:]))
    f32 = partial(jax.lax.bitcast_convert_type, new_dtype=jnp.float32)
    return (ids[:, 0], f32(r_submit), f32(r_exec).reshape(b, tt, k),
            f32(d_est), f32(d_act), f32(submit_ms)[:, 0], ids[:, 0],
            buf[:, -1] != 0)


@partial(jax.jit, donate_argnums=(0,),
         static_argnames=("cfg", "n", "use_kernel", "kernel_masked",
                          "cache_faulted"))
def _serve_step(carry, blk, C, node_type, mem_unit, cores_per, dyn_vec,
                dyn_ints, win, base_key, cfg: EngineConfig, n: int,
                use_kernel: bool, kernel_masked: bool,
                cache_faulted: bool):
    """One decision block through the scan body, with the carry donated.

    Shared across service instances (one compile per static
    configuration); operands are traced arguments exactly as in
    ``_simulate_batched_jax``, so the one-block jaxpr is identical to
    the offline scan body's."""
    step = _make_block_step(C, node_type, mem_unit, cores_per, dyn_vec,
                            dyn_ints, win, base_key, cfg, n, use_kernel,
                            kernel_masked, cache_faulted, False)
    return step(carry, blk)


class DecisionService:
    """Online scheduling over the offline engine's exact arithmetic.

    Usage::

        svc = DecisionService(cluster, EngineConfig(policy="dodoor", b=50))
        svc.submit_workload(wl)          # or submit(...) per chunk
        svc.drain()                      # run every full decision block
        svc.flush()                      # edge-padded ragged tail
        res = svc.result()               # SimResult, bit-exact vs offline

    Supported knobs mirror ``simulate(mode="batched")`` for independent
    tasks: all five policies, ``dynamics`` timelines including
    ``cache_faults``, ``use_kernel``.  ``cfg.retry``, ``cfg.trace``,
    ``cfg.locality`` and DAG workloads run host-side wave loops around
    the scan and are not streamable — they raise ``NotImplementedError``.
    """

    def __init__(self, cluster: ClusterSpec, cfg: EngineConfig, *,
                 seed: int = 0, dynamics=None,
                 use_kernel: bool | str = "auto",
                 capacity: int = 1 << 16,
                 publish_snapshots: bool = True):
        _validate_config(cfg)
        if cfg.retry is not None:
            raise NotImplementedError(
                "DecisionService with a RetryPolicy: the re-entry queue "
                "is a host-side wave loop over the whole stream — run "
                "retries offline via simulate().")
        if cfg.trace:
            raise NotImplementedError(
                "DecisionService with cfg.trace: the decision-trace "
                "ground truth is an offline post-pass — trace via "
                "simulate(mode='batched').")
        if cfg.locality is not None:
            raise NotImplementedError(
                "DecisionService with a LocalityModel: the locality "
                "gather needs parent placements, which only the offline "
                "DAG frontier loop carries.")
        if cfg.outage_ms:
            raise ValueError(
                "EngineConfig.outage_ms is deprecated — pass "
                "Dynamics(store_outages=...) as dynamics.")
        if dynamics is not None and not isinstance(dynamics, Dynamics):
            raise TypeError(f"dynamics must be a Dynamics spec, got "
                            f"{type(dynamics).__name__}")
        use_kernel = resolve_use_kernel(use_kernel, cfg.interpret)
        faulted = dynamics is not None and dynamics.cache_faults is not None
        if faulted:
            use_kernel = False    # megakernel reads only the shared view
        masked = (use_kernel and dynamics is not None
                  and dynamics.has_down_windows)

        n = cluster.num_servers
        self.cluster = cluster
        self.cfg = cfg
        self._n = n
        self._b = cfg.b
        self._seed = int(seed)
        self._use_kernel = use_kernel
        self._masked = masked
        self._faulted = faulted
        self._scfg = _static_cfg(cfg, for_kernel=use_kernel, keep_b=True)
        self._C, self._node_type, self._cores_per, self._mem_unit = \
            _cluster_arrays(cluster, cfg.mem_units)
        self._dyn = _make_dyn(cfg)
        self._dyn_ints = _make_dyn_ints(cfg)
        self._win = _lower_dynamics(dynamics, n)
        self._base_key = jax.random.PRNGKey(self._seed)
        self._carry = _init_carry(self._scfg, n, self._cores_per, faulted)

        self._ring = ArrivalRing(capacity, cluster.num_types)
        self._next_idx = 0
        self._ring_pad = 0    # pad decisions consumed by flush() tails
        self._steps = 0
        self._outs: list[list[np.ndarray]] = [[] for _ in range(8)]
        self.decision_latency = LatencyRecorder()
        self.ring_wait = LatencyRecorder()
        self.step_wall = LatencyRecorder()
        self._publish = publish_snapshots
        self._snaps: list[dict | None] = [None, None]
        self._live = -1           # index of the published snapshot buffer

    # -- ingestion --------------------------------------------------------

    @property
    def available(self) -> int:
        """Buffered (submitted, not yet scheduled) tasks."""
        return self._ring.count

    @property
    def scheduled(self) -> int:
        """Decisions made so far (valid tasks through step/flush)."""
        return self._next_idx - self._ring_pad

    @property
    def compiles(self) -> int:
        """Compiled-program count of the shared step — steady-state
        steps must not grow this (asserted in tests)."""
        return _serve_step._cache_size()

    def submit(self, r_submit, r_exec, d_est, d_act, submit_ms) -> int:
        """Enqueue an arrival chunk (numpy planes, any length ≥ 0).
        Records one host enqueue timestamp for the chunk — the start of
        each task's enqueue→placement latency.  The ``serve.submit`` span's
        ``block`` is the block the chunk's first task joins."""
        with TraceAnnotation("serve.submit", block=(
                self._next_idx + self._ring.count) // self._b):
            return self._ring.push(r_submit, r_exec, d_est, d_act,
                                   submit_ms, time.perf_counter())

    def submit_workload(self, workload, start: int = 0,
                        stop: int | None = None) -> int:
        """Enqueue a slice of a workload trace (``FBWorkload``-shaped:
        r_submit/r_exec/d_est/d_act/submit_ms)."""
        sl = slice(start, stop)
        return self.submit(workload.r_submit[sl], workload.r_exec[sl],
                           workload.d_est[sl], workload.d_act[sl],
                           workload.submit_ms[sl])

    # -- the step ---------------------------------------------------------

    def step(self) -> int:
        """Run one full decision block (requires ``available ≥ b``).
        Returns the number of tasks placed (= b)."""
        b = self._b
        if self._ring.count < b:
            raise ValueError(
                f"step() needs a full block: {self._ring.count} buffered "
                f"< b={b}; submit more, or flush() the ragged tail")
        block = self._steps
        with TraceAnnotation("serve.step", block=block):
            with TraceAnnotation("serve.ring_pop", block=block):
                rows = self._ring.pop(b)
            return self._run_block(rows, b, block)

    def drain(self) -> int:
        """Step every full block currently buffered; returns tasks
        placed."""
        done = 0
        while self._ring.count >= self._b:
            done += self.step()
        return done

    def flush(self) -> int:
        """Drain, then run the ragged tail (< b tasks) as one edge-padded
        block — identical to the offline driver's ``np.pad(mode="edge")``
        tail, so placements and ledger stay bit-exact.  Returns tasks
        placed."""
        done = self.drain()
        k = self._ring.count
        if k == 0:
            return done
        pad = self._b - k

        def edge(a):
            return np.concatenate(
                [a, np.repeat(a[-1:], pad, axis=0)], axis=0)

        block = self._steps
        with TraceAnnotation("serve.flush", block=block):
            with TraceAnnotation("serve.ring_pop", block=block):
                rows = self._ring.pop(k)
                padded = ArrivalRows(*(edge(np.asarray(p)) for p in rows))
            self._ring_pad += pad
            return done + self._run_block(padded, k, block)

    def _stage(self, rows: ArrivalRows, valid_count: int) -> np.ndarray:
        """Block ``rows`` as the one int32 buffer it is uploaded in, a row
        per task: its global decision id, the five planes' float32 bits,
        and whether it is valid (``valid_count`` leading rows are; the
        rest edge-pad a ragged tail)."""
        b = self._b
        ids = np.arange(self._next_idx, self._next_idx + b, dtype=np.int32)
        valid = (np.arange(b) < valid_count).astype(np.int32)
        cols = (ids[:, None], rows.r_submit, rows.r_exec.reshape(b, -1),
                rows.d_est, rows.d_act, rows.submit_ms[:, None],
                valid[:, None])
        return np.concatenate([c.view(np.int32) for c in cols], axis=1)

    def _upload(self, buf: np.ndarray) -> tuple:
        """Send a staged block in one transfer and split it on the device
        into the step's block operand."""
        return _unpack_block(buf, tt=self.cluster.num_types,
                             k=self._C.shape[1])

    def _step_operands(self, blk) -> tuple:
        return (self._carry, blk, self._C, self._node_type, self._mem_unit,
                self._cores_per, self._dyn, self._dyn_ints, self._win,
                self._base_key)

    def _step_statics(self) -> dict:
        return dict(cfg=self._scfg, n=self._n, use_kernel=self._use_kernel,
                    kernel_masked=self._masked, cache_faulted=self._faulted)

    def lower_step(self, sharding=None):
        """Lower the step program for this service's block shapes, to
        inspect what the compiler emits (``.compile().as_text()``).  With
        a ``sharding`` the operands are abstract shapes placed there — a
        device of a described topology compiles for a chip that is not
        attached."""
        operands = self._step_operands(self._upload(
            self._stage(self._ring.zeros(self._b), self._b)))
        if sharding is not None:
            operands = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                               sharding=sharding), operands)
        return _serve_step.lower(*operands, **self._step_statics())

    def _run_block(self, rows: ArrivalRows, valid_count: int, k: int) -> int:
        """Block ``k``'s round trip, one ``serve.*`` span per phase: one
        upload of the staged block, the step, and one readback of the
        output planes and the published views, whose copies are queued
        behind the step as soon as it is dispatched."""
        b = self._b
        t0 = time.perf_counter()
        with TraceAnnotation("serve.upload", block=k) as span:
            buf = self._stage(rows, valid_count)
            span.set_metadata(arrays=1, bytes=buf.nbytes)
            blk = self._upload(buf)
        t_dispatch = time.perf_counter()
        with TraceAnnotation("serve.dispatch", block=k):
            self._carry, out = _serve_step(*self._step_operands(blk),
                                           **self._step_statics())
            fetch = tuple(out[:7])
            if self._publish:
                fetch += (self._carry.view_L, self._carry.view_D,
                          self._carry.view_rif)
            for a in fetch:
                a.copy_to_host_async()
        with TraceAnnotation("serve.device_wait", block=k):
            jax.block_until_ready(out)
        t1 = time.perf_counter()
        self.step_wall.record((t1 - t0) * 1e3)
        t_enq = rows.t_enq[:valid_count]
        self.ring_wait.record((t_dispatch - t_enq) * 1e3)
        self.decision_latency.record((t1 - t_enq) * 1e3)
        with TraceAnnotation("serve.readback", block=k, arrays=len(fetch),
                             bytes=sum(a.nbytes for a in fetch)):
            got = jax.device_get(fetch)
            for acc, plane in zip(self._outs[:7], got):
                acc.append(plane[:valid_count])
        self._outs[7].append(rows.submit_ms[:valid_count])
        self._next_idx += b
        self._steps += 1
        if self._publish:
            with TraceAnnotation("serve.publish", block=k):
                idx = self._steps % 2
                self._snaps[idx] = {
                    "step": self._steps,
                    "virtual_ms": float(rows.submit_ms[valid_count - 1]),
                    "view_L": got[7], "view_D": got[8], "view_rif": got[9],
                }
                self._live = idx
        return valid_count

    # -- results ----------------------------------------------------------

    def snapshot(self) -> dict | None:
        """The most recently *published* cache snapshot (double-buffered:
        never the one the in-flight block is writing), or ``None`` before
        the first step."""
        return self._snaps[self._live] if self._live >= 0 else None

    def result(self) -> SimResult:
        """Everything scheduled so far as a :class:`SimResult` —
        bit-exact vs ``simulate(mode="batched")`` over the same stream.
        Requires an empty ring (``flush()`` first)."""
        if self._ring.count:
            raise ValueError(
                f"{self._ring.count} buffered arrivals not yet scheduled "
                f"— flush() before result()")
        if not self._outs[0]:
            raise ValueError("no decisions yet")
        j, start, finish, enq, sched_ms, cores, mem_mb, submit = (
            np.concatenate(acc) for acc in self._outs)
        msgs = np.asarray(self._carry.msgs)
        return SimResult(
            server=j.astype(np.int32), submit_ms=submit,
            enqueue_ms=enq, start_ms=start, finish_ms=finish,
            sched_ms=sched_ms, cores=cores, mem_mb=mem_mb,
            msgs_base=int(msgs[0]), msgs_probe=int(msgs[1]),
            msgs_push=int(msgs[2]), msgs_flush=int(msgs[3]),
            policy=self.cfg.policy)

    def latency_summary(self) -> dict:
        """Histograms + percentiles for the instrumented clocks."""
        return {
            "decision": {**self.decision_latency.summary(),
                         "histogram": self.decision_latency.histogram()},
            "ring_wait": {**self.ring_wait.summary(),
                          "histogram": self.ring_wait.histogram()},
            "step": {**self.step_wall.summary(),
                     "histogram": self.step_wall.histogram()},
        }

    # -- checkpoint / resume ----------------------------------------------

    def export_checkpoint(self) -> dict:
        """Snapshot the full scheduling state at a block boundary.  The
        ring must be empty (buffered arrivals belong to the client — they
        are not part of cluster state); resuming a fresh service from the
        returned dict and replaying the remaining stream is bit-exact
        with never having stopped."""
        if self._ring.count:
            raise ValueError(
                f"{self._ring.count} buffered arrivals — drain()/flush() "
                f"before checkpointing (the ring is client state)")
        carry = {f: (None if leaf is None else np.asarray(leaf))
                 for f, leaf in zip(_CARRY_FIELDS, self._carry)}
        return {"carry": carry, "next_idx": int(self._next_idx),
                "ring_pad": int(self._ring_pad), "steps": int(self._steps),
                "seed": self._seed, "policy": self.cfg.policy,
                "b": self._b, "faulted": self._faulted}

    @classmethod
    def from_checkpoint(cls, cluster: ClusterSpec, cfg: EngineConfig,
                        ckpt: dict, **kwargs) -> "DecisionService":
        """Rebuild a service mid-stream from :meth:`export_checkpoint`.
        ``cluster``/``cfg``/``seed``/``dynamics`` must match the
        exporting service (the checkpoint pins the identity-shaping
        ones)."""
        svc = cls(cluster, cfg, seed=ckpt["seed"], **kwargs)
        for key, have in (("policy", cfg.policy), ("b", cfg.b),
                          ("faulted", svc._faulted)):
            if ckpt[key] != have:
                raise ValueError(
                    f"checkpoint {key}={ckpt[key]!r} does not match the "
                    f"restoring service's {have!r}")
        svc._carry = _Carry(**{
            f: (None if v is None else jnp.asarray(v))
            for f, v in ckpt["carry"].items()})
        svc._next_idx = int(ckpt["next_idx"])
        svc._ring_pad = int(ckpt["ring_pad"])
        svc._steps = int(ckpt["steps"])
        return svc


def serve_workload(workload, cluster: ClusterSpec, cfg: EngineConfig, *,
                   seed: int = 0, dynamics=None,
                   use_kernel: bool | str = "auto",
                   chunk: int | None = None, open_loop: bool = False,
                   publish_snapshots: bool = True):
    """Stream a whole workload trace through a fresh service and return
    ``(service, SimResult)``.

    ``open_loop`` submits every chunk up front and then drains (queueing
    pressure: later tasks wait on earlier blocks — tail latency grows);
    the default closed loop alternates submit/step so each block is
    scheduled as soon as it forms.  ``chunk`` is the submission chunk
    size (default ``cfg.b``).  Placements are independent of both knobs
    — only the measured latencies differ."""
    m = workload.r_submit.shape[0]
    chunk = chunk or cfg.b
    svc = DecisionService(cluster, cfg, seed=seed, dynamics=dynamics,
                          use_kernel=use_kernel,
                          capacity=max(m, cfg.b),
                          publish_snapshots=publish_snapshots)
    for lo in range(0, m, chunk):
        svc.submit_workload(workload, lo, min(lo + chunk, m))
        if not open_loop:
            svc.drain()
    svc.flush()
    return svc, svc.result()
