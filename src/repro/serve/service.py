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

Mini-clusters (§4.2): ``DecisionService(..., mini_clusters=k)`` splits the
fleet into ``k`` interleaved parts (server ``j`` to part ``j mod k``,
:func:`repro.sim.hierarchy.split_cluster`), each with its own schedulers,
data store, batch counter and PRNG key ``PRNGKey(seed + c)``, and deals
each ``b``-task block round-robin (decision ``i`` to part ``i mod k``), so
part ``c`` steps ``b/k`` of its own decisions at a time.  One program
(:func:`_serve_step_parts`) runs every part, each part's state on its own
device where there are enough; the results are merged back into
submission order with global server ids and the parts' message ledgers
summed, bit-exact with ``simulate_hierarchical(..., mode="batched",
b=b // k)``.  A block still moves in one transfer each way:
``serve.upload`` holds ``serve.deal`` (the rows dealt into one
``[k, b/k, W]`` buffer) and ``serve.readback`` holds ``serve.merge``, all
four tagged ``parts=k``.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..sim.cluster import ClusterSpec
from ..sim.engine import (Dynamics, EngineConfig, SimResult, _Carry,
                          _cluster_arrays, _init_carry, _lower_dynamics,
                          _make_block_step, _make_dyn, _make_dyn_ints,
                          _static_cfg, _validate_config, resolve_use_kernel)
from ..sim.hierarchy import split_cluster
from .latency import LatencyRecorder
from .ring import ArrivalRing, ArrivalRows

#: Host-side carry field order for checkpoints (must match _Carry).
_CARRY_FIELDS = _Carry._fields

#: Operands stacked on a leading axis of mini-clusters, sharded over the
#: mesh's one axis; and operands every mini-cluster shares.
_STACKED = PartitionSpec("parts")
_SHARED = PartitionSpec()
#: The step's operands (:meth:`DecisionService._step_operands`) under
#: mini-clusters: all stacked but ``dyn_vec``, ``dyn_ints`` and ``win``.
_STEP_SPECS = (_STACKED,) * 6 + (_SHARED,) * 3 + (_STACKED,)


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


def _pack(ids, rows: ArrivalRows, valid) -> np.ndarray:
    """Rows as the int32 buffer a block is uploaded in, one row per task:
    its decision id, the five planes' float32 bits and its validity.
    ``ids`` and ``valid`` shape the leading axes."""
    cols = (ids[..., None], rows.r_submit,
            rows.r_exec.reshape(ids.shape + (-1,)), rows.d_est, rows.d_act,
            rows.submit_ms[..., None], valid.astype(np.int32)[..., None])
    return np.concatenate([c.view(np.int32) for c in cols], axis=-1)


def _over_parts(fn, mesh: Mesh, specs: tuple):
    """``fn`` applied to every mini-cluster of operands stacked on a leading
    axis of parts, that axis sharded over ``mesh``.  ``specs`` marks each
    operand ``_STACKED`` or ``_SHARED`` (the same for every part).  A device
    that holds one part runs ``fn`` on it alone, so the program on it is
    the one-part program; a device that holds several maps ``fn`` over
    them with ``vmap``."""
    stacked = tuple(s == _STACKED for s in specs)

    def local(*args):
        held = next(jax.tree.leaves(a)[0].shape[0]
                    for a, s in zip(args, stacked) if s)
        if held > 1:
            return jax.vmap(fn, in_axes=tuple(0 if s else None
                                              for s in stacked))(*args)
        one = [jax.tree.map(lambda x: x[0], a) if s else a
               for a, s in zip(args, stacked)]
        return jax.tree.map(lambda x: x[None], fn(*one))

    return jax.shard_map(local, mesh=mesh, in_specs=specs,
                         out_specs=_STACKED, check_vma=False)


@partial(jax.jit, static_argnames=("tt", "k", "mesh"))
def _unpack_parts(buf, tt: int, k: int, mesh: Mesh):
    """:func:`_unpack_block` on every part of a dealt ``[parts, b/parts,
    W]`` buffer, on the devices that hold the parts."""
    return _over_parts(partial(_unpack_block, tt=tt, k=k), mesh,
                       (_STACKED,))(buf)


@partial(jax.jit, static_argnames=("cfg", "n", "mesh"))
def _init_carry_parts(cores_per, cfg: EngineConfig, n: int,
                      mesh: Mesh) -> _Carry:
    """Every part's t=0 carry, stacked, each made on its part's device and
    laid out as the step returns it."""
    return _over_parts(partial(_init_carry, cfg, n, faulted=False), mesh,
                       (_STACKED,))(cores_per)


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


@partial(jax.jit, donate_argnums=(0,),
         static_argnames=("cfg", "n", "use_kernel", "kernel_masked",
                          "cache_faulted", "mesh"))
def _serve_step_parts(carry, blk, C, node_type, mem_unit, cores_per, dyn_vec,
                      dyn_ints, win, base_key, cfg: EngineConfig, n: int,
                      use_kernel: bool, kernel_masked: bool,
                      cache_faulted: bool, mesh: Mesh):
    """:func:`_serve_step` for every mini-cluster in one program, with the
    stacked carry donated: the operands but ``dyn_vec``, ``dyn_ints`` and
    ``win`` carry a leading axis of parts sharded over ``mesh``, and ``n``
    and ``cfg.b`` are one part's."""
    def part(carry, blk, C, node_type, mem_unit, cores_per, dyn_vec,
             dyn_ints, win, base_key):
        step = _make_block_step(C, node_type, mem_unit, cores_per, dyn_vec,
                                dyn_ints, win, base_key, cfg, n, use_kernel,
                                kernel_masked, cache_faulted, False)
        return step(carry, blk)

    return _over_parts(part, mesh, _STEP_SPECS)(
        carry, blk, C, node_type, mem_unit, cores_per, dyn_vec, dyn_ints,
        win, base_key)


def _parts_mesh(k: int) -> Mesh:
    """A 1-D mesh over ``jax.devices()[:d]``, ``d`` the largest count of
    devices that divides ``k``."""
    have = len(jax.devices())
    d = max(x for x in range(1, min(k, have) + 1) if k % x == 0)
    return Mesh(np.array(jax.devices()[:d]), ("parts",))


class _MiniClusters:
    """The §4.2 split of one service's fleet and stream: ``count`` parts,
    server ``j`` in part ``j mod count`` and decision ``i`` in part
    ``i mod count``, each part's operands stacked on a leading axis that
    ``mesh`` shards."""

    def __init__(self, cluster: ClusterSpec, count: int, b: int,
                 mesh: Mesh):
        self.count, self.block = count, b // count
        self.parts = split_cluster(cluster, count)
        self.idx = np.stack([idx for _, idx in self.parts])    # [k, n/k]
        self.mesh = mesh
        self.stacked = NamedSharding(mesh, _STACKED)
        self.shared = NamedSharding(mesh, _SHARED)

    def valid(self, valid_count: int) -> np.ndarray:
        """Each part's share of ``valid_count`` dealt decisions."""
        k = self.count
        return (valid_count - np.arange(k) + k - 1) // k

    def deal(self, rows: ArrivalRows, valid_count: int,
             first: int) -> np.ndarray:
        """The block's first ``valid_count`` rows dealt round-robin into one
        ``[count, block, W]`` buffer, part ``c`` taking rows ``c, c + count,
        …`` with local decision ids from ``first``.  A part with fewer
        valid rows than ``block`` repeats its last (the offline driver's
        edge padding); one with none repeats the block's last row, all
        invalid."""
        k, lane = self.count, np.arange(self.block)
        c = np.arange(k)[:, None]
        own = self.valid(valid_count)[:, None]
        src = np.where(own > 0, c + k * np.minimum(lane, own - 1),
                       valid_count - 1)
        ids = np.broadcast_to(first + lane, src.shape).astype(np.int32)
        return _pack(ids, ArrivalRows(*(p[src] for p in rows)), lane < own)

    def merge(self, got) -> list:
        """Fetched outputs in submission order and global server ids: the
        seven ``[count, block]`` planes interleaved back by decision, then
        any ``[count, n/count, …]`` views scattered to their servers."""
        j = np.take_along_axis(self.idx, got[0], axis=1)

        def by_task(a):
            return np.swapaxes(a, 0, 1).reshape((-1,) + a.shape[2:])

        def by_server(a):
            out = np.empty((self.idx.size,) + a.shape[2:], a.dtype)
            out[self.idx] = a
            return out

        return ([by_task(j)] + [by_task(a) for a in got[1:7]]
                + [by_server(a) for a in got[7:]])


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

    ``mini_clusters=k`` serves the fleet as the §4.2 mini-clusters (see the
    module docstring): ``k`` must divide the fleet size and ``cfg.b``,
    which stays the block the service steps, ``b/k`` decisions of each
    part at a time; results are bit-exact with ``simulate_hierarchical(wl,
    cluster, cfg._replace(b=b // k), k, seed, mode="batched", b=b // k)``.
    It takes no ``dynamics`` and no checkpoints (``NotImplementedError``).
    ``part_decisions`` counts each part's valid decisions.
    """

    def __init__(self, cluster: ClusterSpec, cfg: EngineConfig, *,
                 seed: int = 0, dynamics=None,
                 use_kernel: bool | str = "auto",
                 capacity: int = 1 << 16,
                 publish_snapshots: bool = True,
                 mini_clusters: int = 1):
        _validate_config(cfg)
        k = int(mini_clusters)
        if k < 1 or cluster.num_servers % k or cfg.b % k:
            raise ValueError(
                f"mini_clusters={mini_clusters} must be ≥ 1 and divide the "
                f"fleet's {cluster.num_servers} servers and b={cfg.b}")
        if k > 1 and dynamics is not None:
            raise NotImplementedError(
                "DecisionService with mini_clusters and dynamics: a "
                "timeline would have to be split over the parts — run it "
                "offline via simulate_hierarchical().")
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
        self._parts = None
        if k == 1:
            self._scfg = _static_cfg(cfg, for_kernel=use_kernel,
                                     keep_b=True)
            self._C, self._node_type, self._cores_per, self._mem_unit = \
                _cluster_arrays(cluster, cfg.mem_units)
            self._dyn = _make_dyn(cfg)
            self._dyn_ints = _make_dyn_ints(cfg)
            self._win = _lower_dynamics(dynamics, n)
            self._base_key = jax.random.PRNGKey(self._seed)
            self._carry = _init_carry(self._scfg, n, self._cores_per,
                                      faulted)
        else:
            self._init_parts(cluster, cfg, k)
        self._part_decisions = np.zeros(k, np.int64)

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

    def _init_parts(self, cluster: ClusterSpec, cfg: EngineConfig,
                    k: int) -> None:
        """Each part's operands, stacked and placed one part (or an equal
        share of the parts) a device; per-part ``n`` and ``b``."""
        part_cfg = cfg._replace(b=cfg.b // k)
        _validate_config(part_cfg)
        parts = self._parts = _MiniClusters(cluster, k, cfg.b,
                                            _parts_mesh(k))
        self._n = cluster.num_servers // k
        self._scfg = _static_cfg(part_cfg, for_kernel=self._use_kernel,
                                 keep_b=True)
        arrays = [_cluster_arrays(spec, cfg.mem_units)
                  for spec, _ in parts.parts]
        self._C, self._node_type, self._cores_per, self._mem_unit = (
            jax.device_put(np.stack([np.asarray(a[i]) for a in arrays]),
                           parts.stacked) for i in range(4))
        self._dyn, self._dyn_ints, self._win = jax.device_put(
            (_make_dyn(cfg), _make_dyn_ints(part_cfg),
             _lower_dynamics(None, self._n)), parts.shared)
        self._base_key = jax.device_put(
            np.stack([np.asarray(jax.random.PRNGKey(self._seed + c))
                      for c in range(k)]), parts.stacked)
        self._carry = _init_carry_parts(self._cores_per, self._scfg,
                                        self._n, parts.mesh)
        if parts.mesh.size == 1:
            # One device returns every leaf of the step's carry unsharded;
            # laid out so from the start, the first step compiles the one
            # program every later step runs.
            self._carry = jax.device_put(self._carry, parts.shared)

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
        """Compiled-program count of the shared steps, one-part and
        mini-cluster — steady-state steps must not grow this (asserted in
        tests)."""
        return _serve_step._cache_size() + _serve_step_parts._cache_size()

    @property
    def part_decisions(self) -> np.ndarray:
        """Valid decisions made so far in each mini-cluster (``[k]``; one
        entry without mini-clusters); sums to :attr:`scheduled`."""
        return self._part_decisions.copy()

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
                if self._parts is None:    # mini-clusters pad each part
                    rows = ArrivalRows(*(edge(np.asarray(p)) for p in rows))
            self._ring_pad += pad
            return done + self._run_block(rows, k, block)

    def _stage(self, rows: ArrivalRows, valid_count: int) -> np.ndarray:
        """Block ``rows`` as the one int32 buffer it is uploaded in, a row
        per task: its global decision id, the five planes' float32 bits,
        and whether it is valid (``valid_count`` leading rows are; the
        rest edge-pad a ragged tail)."""
        b = self._b
        ids = np.arange(self._next_idx, self._next_idx + b, dtype=np.int32)
        return _pack(ids, rows, np.arange(b) < valid_count)

    def _upload(self, buf: np.ndarray) -> tuple:
        """Send a staged block in one transfer and split it on the device
        (each part's on its own) into the step's block operand."""
        tt, k = self.cluster.num_types, self._C.shape[-1]
        if self._parts is None:
            return _unpack_block(buf, tt=tt, k=k)
        return _unpack_parts(jax.device_put(buf, self._parts.stacked),
                             tt=tt, k=k, mesh=self._parts.mesh)

    def _step_operands(self, blk) -> tuple:
        return (self._carry, blk, self._C, self._node_type, self._mem_unit,
                self._cores_per, self._dyn, self._dyn_ints, self._win,
                self._base_key)

    def _step_statics(self) -> dict:
        statics = dict(cfg=self._scfg, n=self._n,
                       use_kernel=self._use_kernel,
                       kernel_masked=self._masked,
                       cache_faulted=self._faulted)
        if self._parts is not None:
            statics["mesh"] = self._parts.mesh
        return statics

    def _staged(self, rows: ArrivalRows, valid_count: int,
                block: int) -> np.ndarray:
        """The block's upload buffer: staged, or dealt to the parts."""
        parts = self._parts
        if parts is None:
            return self._stage(rows, valid_count)
        with TraceAnnotation("serve.deal", block=block, parts=parts.count):
            return parts.deal(rows, valid_count,
                              self._next_idx // parts.count)

    def lower_step(self, sharding=None):
        """Lower the step program for this service's block shapes, to
        inspect what the compiler emits (``.compile().as_text()``).  With
        a ``sharding`` the operands are abstract shapes placed there — a
        device of a described topology compiles for a chip that is not
        attached.  With mini-clusters ``sharding`` is a one-axis
        :class:`~jax.sharding.Mesh` (say, of a described topology's
        devices) over which the parts are laid instead of the service's
        own."""
        operands = self._step_operands(self._upload(
            self._staged(self._ring.zeros(self._b), self._b, 0)))
        statics = self._step_statics()
        step = _serve_step if self._parts is None else _serve_step_parts
        places = [sharding] * len(operands)
        if self._parts is not None and sharding is not None:
            statics["mesh"] = sharding
            places = [NamedSharding(sharding, s) for s in _STEP_SPECS]
        if sharding is not None:
            operands = [jax.tree.map(
                lambda x, p=p: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                                    sharding=p), op)
                for op, p in zip(operands, places)]
        return step.lower(*operands, **statics)

    def _run_block(self, rows: ArrivalRows, valid_count: int, k: int) -> int:
        """Block ``k``'s round trip, one ``serve.*`` span per phase: one
        upload of the staged block, the step, and one readback of the
        output planes and the published views, whose copies are queued
        behind the step as soon as it is dispatched."""
        b = self._b
        parts = self._parts
        tags = {} if parts is None else {"parts": parts.count}
        t0 = time.perf_counter()
        with TraceAnnotation("serve.upload", block=k, **tags) as span:
            buf = self._staged(rows, valid_count, k)
            span.set_metadata(arrays=1, bytes=buf.nbytes)
            blk = self._upload(buf)
        t_dispatch = time.perf_counter()
        step = _serve_step if parts is None else _serve_step_parts
        with TraceAnnotation("serve.dispatch", block=k):
            self._carry, out = step(*self._step_operands(blk),
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
                             bytes=sum(a.nbytes for a in fetch), **tags):
            got = jax.device_get(fetch)
            if parts is not None:
                with TraceAnnotation("serve.merge", block=k, **tags):
                    got = parts.merge(got)
            for acc, plane in zip(self._outs[:7], got):
                acc.append(plane[:valid_count])
        self._outs[7].append(rows.submit_ms[:valid_count])
        self._part_decisions += (valid_count if parts is None
                                 else parts.valid(valid_count))
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
        msgs = np.asarray(self._carry.msgs).reshape(-1, 4).sum(axis=0)
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
        if self._parts is not None:
            raise NotImplementedError(
                "export_checkpoint with mini_clusters: a checkpoint holds "
                "one part's carry")
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
        if kwargs.get("mini_clusters", 1) != 1:
            raise NotImplementedError(
                "from_checkpoint with mini_clusters: a checkpoint holds "
                "one part's carry")
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
