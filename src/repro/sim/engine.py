"""Discrete-event cluster simulation engine — reproduces the §6 testbed.

Two interchangeable drivers cover the same model:

* ``mode="sequential"`` — the original oracle: one ``lax.scan`` step per task
  arrival, every policy decision made against the live carry.
* ``mode="batched"``   — the paper-shaped driver: an outer ``lax.scan`` over
  *decision blocks* of ``b`` tasks (one cache snapshot per block — exactly
  the §3.2/§4.1 b-batched push boundary).  Within a block, candidate
  sampling and Algorithm-1 scoring are vectorized over all ``b`` tasks at
  once (``dodoor_select_batch`` / the fused ``dodoor_choice`` Pallas kernel
  when ``use_kernel=True``), and the commit — FCFS start times, ring-buffer
  inserts, interference, channel contention — runs as *server-parallel
  rounds*: each server's FCFS chain is independent of every other server's,
  so round ``k`` commits the k-th task of every server simultaneously.

  Every policy rides this driver — the probing baselines included:

  * **PoT (speculative commit).** PoT's probes read other servers' live
    ring buffers mid-block, so its decisions are scored against the current
    carry for *all* pending tasks at once; a task is *safe* if no earlier
    pending commit landed on either of its probed candidates (placements
    inside a safe prefix are provably distinct, so the parallel commit is
    one round).  The longest safe prefix commits in server-parallel rounds,
    and only the conflicting suffix is replayed in the next loop iteration
    — the common low-conflict case runs in O(#conflict-breaks), not O(b).

  * **Prequal (segment scan).** Decisions round-robin over schedulers, so
    any ``S`` consecutive tasks hit ``S`` distinct (and therefore
    independent) probe pools.  The block is processed as a segment scan
    over chunks of ``S`` tasks: pool selection and the pool update
    vectorize across the chunk, the chunk commits in parallel rounds, and
    each task's post-decision probes read ground truth *as of its own
    decision point* by reverting the rb slots written by same-chunk commits
    at or after it ((old, new) slot records telescope, so this is exact
    even when commits collide on a slot).

The batched driver is *exact*: placements, timestamps, and the message
ledger are bit-identical to the sequential oracle for every policy —
see ``tests/test_engine_batched.py``.

Server execution model
----------------------
Each server's CPU cores and memory are modelled as *unit resources* with a
"free-at" timestamp:

* ``core_free[n, CMAX]`` — per-core next-free time (unused core slots padded
  with +inf so heterogeneous core counts never get selected);
* ``mem_free[n, MU]``    — memory discretized into MU equal units per server
  (unit size = capacity/MU; 2 GB on a 128 GB node at MU=64).

FCFS with concurrent execution (§4.2: "multiple tasks can run concurrently
... up to the number of CPU cores") is exact under this model: a task that
is last in the queue starts at

    start = max(enqueue, prev_start[j], c-th earliest core-free,
                u-th earliest mem-unit-free)

(`prev_start` enforces FCFS start ordering; taking the earliest-free units is
work-conserving). The chosen units' free-at times advance to ``start + dur``.

Ground truth for probing policies and data-store pushes comes from a
per-server in-flight ring buffer ``rb_*[n, R]`` holding (release time, cores,
MB, est-duration) of every uncompleted task; a task is *uncompleted* while
``release > now`` (queued tasks have future release, so L/D/RIF include the
queue — §3.1's definition).

Data-store staleness model
--------------------------
The store's view at a push equals truth(now) minus the deltas schedulers have
not yet flushed via ``addNewLoad`` (per-scheduler ``pending`` accumulators,
flushed every ``flush_every`` of that scheduler's own decisions — the paper
only upper-bounds the mini-batch at 2b/num_schedulers; we default to a faster
cadence within that bound, calibrated to the paper's reported 33% message
overhead). Server ``overrideNodeState`` messages are folded in implicitly:
truth(now) already excludes completed tasks, exactly what a completion-time
override reports.  In batched mode the push happens once per full block,
after the block's commit — the same protocol instant as the sequential
per-task trigger ``(i+1) % b == 0``.

Message accounting (Fig. 4/6 "RPC counts processed by all schedulers"):

* every decision: 2 (task recv + placement send);
* PoT: +4 (two synchronous probe round-trips);
* Prequal: +2·r_probe (async probe sends + replies);
* Dodoor: +num_schedulers per batch push, +1 per addNewLoad flush.

Compilation note: scalar model parameters (α, β, interference, the RPC
timing model, the outage window, Prequal's q_rif) are traced operands, not
compile-time constants — sweeping them reuses one compiled program per
(policy, shapes) pair instead of recompiling per configuration.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.policies import dodoor_choice_batch
from ..core.prefilter import feasible_mask, sample_feasible, sample_feasible_batch
from ..kernels.dodoor_choice import dodoor_fused_sparse
from ..kernels.dodoor_choice.kernel import _resolve_interpret
from ..core.rl_score import load_score_batched
from ..core.types import PrequalParams, SchedulerView
from .cluster import CMAX, ClusterSpec
from .decision_trace import finish_trace
from .messages import RpcModel


class RetryPolicy(NamedTuple):
    """Failure-and-recovery knobs (the re-entry layer).

    With a policy set on :class:`EngineConfig`, two failure paths open up:

    * **kill** — a task still running on a server when a freeze window
      (outage/join gate) *opens* is killed at the window start and
      resubmitted;
    * **rejection** — when ``reject_queue_factor > 0``, a server whose
      in-flight count has reached ``factor × cores`` rejects the placement
      outright (hard capacity) instead of queueing it.

    A killed or rejected task re-enters the decision stream as a fresh
    submission at ``fail_time + backoff_ms · backoff_mult^(k-1)`` after its
    k-th failure, until ``max_attempts`` total submissions have been spent —
    then it fails permanently.  Retried decisions pay the full scheduling
    path again (messages, probes, cache reads), which is how the message
    ledger reflects failure cost."""

    max_attempts: int = 3           # total submissions (first try included)
    backoff_ms: float = 250.0       # delay before the first resubmission
    backoff_mult: float = 2.0       # exponential backoff factor
    reject_queue_factor: float = 0.0  # reject when rif ≥ factor·cores;
                                      # ≤ 0 disables hard-capacity rejection


class CacheFaults(NamedTuple):
    """Cache-degradation injection for the data-store push channel
    (attached to :class:`Dynamics` via ``cache_faults``).

    Each batch push is delivered *per scheduler*; a delivery is lost with
    probability ``loss_rate`` (iid per scheduler per push, seeded stream)
    and lost for every scheduler while ``now`` is inside a
    ``loss_windows`` entry.  A scheduler whose delivery is lost keeps its
    previous view — dodoor's load scores go stale beyond the batch
    cadence, while probing policies (PoT/Prequal) keep ground truth.
    ``delay_ms`` lags the snapshot itself: the push carries truth as of
    ``now − delay_ms`` (late ``overrideNodeState`` completion reports).

    Unlike ``store_outages`` (which *suppress* the push — no messages
    sent), a lost delivery was sent and is paid for in the ledger."""

    loss_rate: float = 0.0          # per-scheduler iid delivery-loss prob
    loss_windows: tuple = ()        # ((t0, t1), ...): all pushes lost inside
    delay_ms: float = 0.0           # snapshot lag (truth as of now − delay)
    seed: int = 0                   # loss-draw stream


class LocalityModel(NamedTuple):
    """Data-locality term for Algorithm 1 (DAG runs only; docs/DAGS.md).

    With a model set on :class:`EngineConfig`, the dodoor/(1+β) score of
    a candidate server ``j`` gains

        + gamma · bytes_remote(task, j) / bandwidth_mb_per_ms

    where ``bytes_remote`` sums the task's parent-output MB held on
    servers other than ``j`` — the transfer-time cost of pulling inputs
    across the network.  ``gamma = 0`` is bit-identical to today's score
    (the penalty term is ``+0.0``), which is the pinned contract that
    lets the locality-threaded programs share every parity test with
    the locality-free ones.  The term only exists where parents exist:
    ``simulate`` requires a ``dag`` whenever a model is set."""

    gamma: float = 1.0              # penalty weight (score units per ms)
    bandwidth_mb_per_ms: float = 1.0  # effective network bandwidth

    @property
    def gamma_bw(self) -> float:
        """The fused per-MB coefficient the score actually uses."""
        return float(self.gamma) / float(self.bandwidth_mb_per_ms)


class EngineConfig(NamedTuple):
    """Cluster-level knobs (Require line of Algorithm 1 + §6.1 RPC setup)."""

    policy: str = "dodoor"          # random | pot | dodoor | prequal | one_plus_beta
    num_schedulers: int = 5         # §6.1: 5 scheduler services
    b: int = 50                     # cache batch size (default n/2, §3.2)
    flush_every: int = 2            # addNewLoad cadence (per-scheduler
                                    # decisions); must be ≤ 2b/num_schedulers
    alpha: float = 0.5              # duration weight (§3.2 default)
    beta: float = 0.5               # (1+β) ablation only
    rbuf_slots: int = 256           # in-flight ring buffer per server
    mem_units: int = 64             # memory discretization per server
    interference: float = 0.3       # co-location slowdown: a task starting
                                    # while a fraction f of the node's cores
                                    # are busy runs (1 + interference·f)×
                                    # longer than its profile (cache/memory-
                                    # bandwidth contention — why α=1 packing
                                    # "creates long queues", §6.4)
    outage_ms: tuple = ()           # (start, end): data-store outage window
                                    # (§4.3 graceful degradation) — pushes
                                    # stop, schedulers run on the last-known
                                    # cached view; recovery is automatic at
                                    # the first batch boundary after the end
    rpc: RpcModel = RpcModel()
    prequal: PrequalParams = PrequalParams()
    block_t: int = 256              # fused-kernel tile size (use_kernel only)
    interpret: bool | None = None   # Pallas interpret mode; None = auto
                                    # (compiled on TPU, interpreter elsewhere)
    retry: RetryPolicy | None = None  # failure semantics: None (default)
                                      # keeps today's never-rejected,
                                      # never-killed engine bit-identically;
                                      # a RetryPolicy enables kill-and-retry
                                      # (+ hard-capacity rejection when its
                                      # reject_queue_factor > 0)
    locality: LocalityModel | None = None  # data-locality score term —
                                           # DAG runs only; None keeps
                                           # Algorithm 1 untouched and
                                           # gamma=0 is bit-identical
    trace: bool = False             # opt-in decision telemetry: per-decision
                                    # cache-snapshot age, view error, and
                                    # misplacement planes on SimResult.
                                    # False keeps every program textually
                                    # unchanged (the trace carry leaf is an
                                    # absent pytree node, like retry=None)


class _Dyn(NamedTuple):
    """Traced scalar parameters (see the compilation note in the module
    docstring). One compiled program serves every value of these."""

    alpha: jnp.ndarray
    beta: jnp.ndarray
    interference: jnp.ndarray
    hop_ms: jnp.ndarray
    chan_ms: jnp.ndarray
    push_block_ms: jnp.ndarray
    compute_ms: jnp.ndarray
    outage0: jnp.ndarray      # +inf when no outage is configured
    outage1: jnp.ndarray
    q_rif: jnp.ndarray
    reject_cap: jnp.ndarray   # hard-capacity rejection threshold (rif ≥
                              # cap·cores rejects); +inf when disabled
    gamma_bw: jnp.ndarray     # locality penalty per remote MB
                              # (gamma / bandwidth); 0.0 when no
                              # LocalityModel is configured


class Dynamics(NamedTuple):
    """Declarative server-dynamics timelines (the scenario engine's
    cluster axis) — all times in ms, all fields tuples so the spec is
    hashable (cache/equality key, like :class:`EngineConfig`).

    outages:       ``((server, t0, t1), ...)`` — server unavailable on
                   [t0, t1): masked out of candidate sampling (no new
                   placements land on it) and a committed task whose FCFS
                   start falls inside the window starts at t1 instead
                   (maintenance freeze: queued work resumes at recovery).
    joins:         ``((server, t_join), ...)`` — node churn: the server is
                   part of the fleet arrays from the start but unavailable
                   on [0, t_join).
    leaves:        ``((server, t_leave), ...)`` — unavailable on
                   [t_leave, ∞): a graceful decommission — masked from
                   sampling, but *not* start-gated: already-queued work
                   drains to completion (unlike an outage's freeze).
    slowdowns:     ``((server, t0, t1, mult), ...)`` — transient straggler:
                   a task *starting* inside [t0, t1) runs ``mult``× its
                   interference-stretched duration.
    store_outages: ``((t0, t1), ...)`` — data-store outage windows
                   (generalizes ``EngineConfig.outage_ms`` to a timeline;
                   both are honored).
    cache_faults:  optional :class:`CacheFaults` — per-scheduler push-loss
                   rate/windows and snapshot delay (cache degradation, as
                   opposed to ``store_outages``' full suppression).

    Semantics note: when every feasible server is unavailable the engine
    falls back to uniform placement over the whole fleet (same rule as an
    all-infeasible task) — submission is never rejected by *availability*,
    the task queues until the node recovers.  Hard-capacity rejection is a
    separate, opt-in path (``EngineConfig.retry.reject_queue_factor``).
    """

    outages: tuple = ()
    joins: tuple = ()
    leaves: tuple = ()
    slowdowns: tuple = ()
    store_outages: tuple = ()
    cache_faults: CacheFaults | None = None

    @property
    def has_down_windows(self) -> bool:
        return bool(self.outages or self.joins or self.leaves)

    def merge(self, *others: "Dynamics") -> "Dynamics":
        """Concatenate timelines — composes builder outputs, e.g.
        ``random_churn(...).merge(random_outages(...))``.  ``cache_faults``
        is not a timeline: the first non-None spec wins (merging two
        distinct specs is ambiguous and raises)."""
        ds = (self,) + others
        vals = {}
        for f in self._fields:
            if f == "cache_faults":
                cfs = [d.cache_faults for d in ds
                       if d.cache_faults is not None]
                if len(set(cfs)) > 1:
                    raise ValueError(
                        "merge() saw two distinct cache_faults specs — "
                        "compose loss windows inside one CacheFaults")
                vals[f] = cfs[0] if cfs else None
            else:
                vals[f] = tuple(w for d in ds for w in getattr(d, f))
        return Dynamics(**vals)


class _Win(NamedTuple):
    """Traced window operands a :class:`Dynamics` spec lowers to — shapes
    are program-shaping (pad widths), values are traced, so scenario grids
    stack them on the vmap axis.  Empty slots hold +inf starts (a window
    [+inf, +inf) matches no timestamp) and 1.0 multipliers.

    ``down*`` masks candidate sampling (outages ∪ joins ∪ leaves);
    ``gate*`` additionally freezes FCFS starts to the window end (outages
    ∪ joins only — leaves drain their queues instead)."""

    down0: jnp.ndarray      # [n, Wd] unavailability window starts
    down1: jnp.ndarray      # [n, Wd] window ends
    gate0: jnp.ndarray      # [n, Wg] start-freezing window starts
    gate1: jnp.ndarray      # [n, Wg] ends
    slow0: jnp.ndarray      # [n, Ws] straggler window starts
    slow1: jnp.ndarray      # [n, Ws] ends
    slow_mult: jnp.ndarray  # [n, Ws] duration multipliers
    store0: jnp.ndarray     # [Wo] data-store outage starts
    store1: jnp.ndarray     # [Wo] ends
    closs0: jnp.ndarray     # [Wc] cache-delivery loss window starts
    closs1: jnp.ndarray     # [Wc] ends
    cache_rate: jnp.ndarray   # [] per-scheduler iid push-loss probability
    cache_delay: jnp.ndarray  # [] push snapshot lag (ms)
    cache_seed: jnp.ndarray   # [] int32 loss-draw stream

    @property
    def widths(self) -> tuple:
        return (self.down0.shape[1], self.gate0.shape[1],
                self.slow0.shape[1], self.store0.shape[0],
                self.closs0.shape[0])


def _avail_rows(win: _Win, now):
    """Availability mask from the down windows: ``now`` scalar → [n];
    ``now`` [b] → [b, n].  Used identically by both drivers so the masked
    sampling stays bit-exact between them."""
    if now.ndim == 0:
        return ~jnp.any((win.down0 <= now) & (now < win.down1), axis=-1)
    t = now[:, None, None]
    return ~jnp.any((win.down0[None] <= t) & (t < win.down1[None]), axis=-1)


def _gate_start(win: _Win, j, start):
    """Push a start time landing inside a gate window to the window's end.
    ``j`` scalar + ``start`` scalar (sequential/_commit_one) or per-server
    rows (``j`` is implicit, ``start`` [n] — _commit_rounds).  The unrolled
    loop resolves chains of non-overlapping sorted windows; each iteration
    is the same arithmetic in both drivers."""
    if start.ndim == 0:
        g0, g1 = win.gate0[j], win.gate1[j]          # [Wg]
        for _ in range(g0.shape[0]):
            inwin = (g0 <= start) & (start < g1)
            start = jnp.max(jnp.where(inwin, g1, start))
        return start
    g0, g1 = win.gate0, win.gate1                    # [n, Wg]
    for _ in range(g0.shape[1]):
        inwin = (g0 <= start[:, None]) & (start[:, None] < g1)
        start = jnp.max(jnp.where(inwin, g1, start[:, None]), axis=1)
    return start


def _slow_stretch(win: _Win, j, start):
    """Straggler multiplier for a task starting at ``start`` — product of
    the matching windows' factors, unrolled so the multiply order is
    identical in both drivers (scalar and per-server-row forms)."""
    if start.ndim == 0:
        s0, s1, sm = win.slow0[j], win.slow1[j], win.slow_mult[j]
        stretch = jnp.float32(1.0)
        for w in range(s0.shape[0]):
            inwin = (s0[w] <= start) & (start < s1[w])
            stretch = stretch * jnp.where(inwin, sm[w], 1.0)
        return stretch
    s0, s1, sm = win.slow0, win.slow1, win.slow_mult
    stretch = jnp.ones_like(start)
    for w in range(s0.shape[1]):
        inwin = (s0[:, w] <= start) & (start < s1[:, w])
        stretch = stretch * jnp.where(inwin, sm[:, w], 1.0)
    return stretch


def _store_down(win: _Win, now):
    return jnp.any((win.store0 <= now) & (now < win.store1))


def _suppress_push(win: _Win, dyn: _Dyn, now):
    """True when a data-store batch push firing at ``now`` is suppressed —
    the legacy scalar ``EngineConfig.outage_ms`` window OR any
    ``Dynamics.store_outages`` timeline window covers ``now``.  One
    predicate shared by both drivers (it used to be duplicated verbatim),
    so the §4.3 graceful-degradation semantics cannot drift apart."""
    legacy = (now >= dyn.outage0) & (now < dyn.outage1)
    return legacy | _store_down(win, now)


def _cache_lost(win: _Win, now, push_ord, S: int):
    """Per-scheduler delivery-loss mask [S] for the push with cluster-wide
    ordinal ``push_ord``: iid Bernoulli(cache_rate) draws from the
    CacheFaults seed stream, OR-ed with the loss windows (inside which
    every scheduler loses the delivery).  Keyed on the push ordinal — not
    wall time — so the sequential and batched drivers draw identically."""
    key = jax.random.fold_in(jax.random.PRNGKey(win.cache_seed), push_ord)
    u = jax.random.uniform(key, (S,))
    in_win = jnp.any((win.closs0 <= now) & (now < win.closs1))
    return (u < win.cache_rate) | in_win


class SimResult(NamedTuple):
    """Per-task outcomes (numpy, ms) + aggregate message ledger."""

    server: np.ndarray        # [m] int32 chosen server
    submit_ms: np.ndarray     # [m]
    enqueue_ms: np.ndarray    # [m] submit + scheduling latency
    start_ms: np.ndarray      # [m] execution start on the server
    finish_ms: np.ndarray     # [m] start + actual duration
    sched_ms: np.ndarray      # [m] scheduling latency (enqueue − submit)
    cores: np.ndarray         # [m] cores actually consumed (per node type)
    mem_mb: np.ndarray        # [m]
    msgs_base: int
    msgs_probe: int
    msgs_push: int
    msgs_flush: int
    policy: str
    # Recovery accounting — populated only by runs with cfg.retry set
    # (None otherwise, so retry-disabled results are byte-identical).
    attempts: np.ndarray | None = None   # [m] int32 submissions per task
    failed: np.ndarray | None = None     # [m] bool: permanently failed
    wasted_ms: np.ndarray | None = None  # [m] killed-attempt execution ms
    # Decision-trace telemetry — populated only by runs with cfg.trace set
    # (None otherwise; see docs/OBSERVABILITY.md for definitions).
    view_age_ms: np.ndarray | None = None  # [m] cache-snapshot age at the
                                           # decision (CacheFaults-aware)
    view_err: np.ndarray | None = None     # [m] L1 gap between the cached
                                           # rif column and ground truth,
                                           # averaged over the candidates
    misplaced: np.ndarray | None = None    # [m] bool: ground truth would
                                           # have picked a different server
    cache_push: np.ndarray | None = None   # [m] bool: a store push fired
                                           # at this decision's step
    sched_id: np.ndarray | None = None     # [m] int32 deciding scheduler
    decision_ms: np.ndarray | None = None  # [m] decision wall time (the
                                           # attempt's submit instant)

    @property
    def makespan_ms(self) -> np.ndarray:
        return self.finish_ms - self.submit_ms

    @property
    def wait_ms(self) -> np.ndarray:
        return self.start_ms - self.enqueue_ms

    @property
    def msgs_total(self) -> int:
        return int(self.msgs_base + self.msgs_probe + self.msgs_push
                   + self.msgs_flush)

    @property
    def msgs_per_task(self) -> float:
        return self.msgs_total / max(1, self.server.shape[0])


class _Carry(NamedTuple):
    core_free: jnp.ndarray    # [n, CMAX]
    mem_free: jnp.ndarray     # [n, MU]
    prev_start: jnp.ndarray   # [n]
    rb_release: jnp.ndarray   # [n, R]
    rb_cpu: jnp.ndarray       # [n, R]
    rb_mem: jnp.ndarray       # [n, R]
    rb_dur: jnp.ndarray       # [n, R]
    view_L: jnp.ndarray       # [n, 2] scheduler cached load vectors
    view_D: jnp.ndarray       # [n]
    view_rif: jnp.ndarray     # [n]
    pending: jnp.ndarray      # [S, n, 4] unflushed scheduler deltas
    chan_free: jnp.ndarray    # [n] per-server RPC channel next-free
    push_end: jnp.ndarray     # [] wall time the in-progress push finishes
    pool_server: jnp.ndarray  # [S, s_pool] Prequal probe pools
    pool_rif: jnp.ndarray
    pool_lat: jnp.ndarray
    pool_age: jnp.ndarray
    pool_valid: jnp.ndarray
    msgs: jnp.ndarray         # [4] int32: base, probe, push, flush
    push_at: jnp.ndarray | None = None  # [S] content timestamp of each
                                        # scheduler's view (cfg.trace only;
                                        # None is an absent pytree leaf, so
                                        # trace=False programs are unchanged)


def _init_carry(cfg: EngineConfig, n: int, cores_per,
                faulted: bool) -> _Carry:
    """The t=0 carry, shared by both drivers (it used to be duplicated
    verbatim).  Under cache faults (``faulted``) the view planes grow a
    leading scheduler axis — each scheduler holds its own, possibly
    stale, copy of the store's pushes."""
    S = cfg.num_schedulers
    R = cfg.rbuf_slots
    MU = cfg.mem_units
    vs = (S, n) if faulted else (n,)
    # Pad unavailable cores with +inf (never free).
    core_init = jnp.where(jnp.arange(CMAX)[None, :] < cores_per[:, None],
                          0.0, jnp.inf)
    return _Carry(
        core_free=core_init.astype(jnp.float32),
        mem_free=jnp.zeros((n, MU), jnp.float32),
        prev_start=jnp.zeros((n,), jnp.float32),
        rb_release=jnp.zeros((n, R), jnp.float32),
        rb_cpu=jnp.zeros((n, R), jnp.float32),
        rb_mem=jnp.zeros((n, R), jnp.float32),
        rb_dur=jnp.zeros((n, R), jnp.float32),
        view_L=jnp.zeros(vs + (2,), jnp.float32),
        view_D=jnp.zeros(vs, jnp.float32),
        view_rif=jnp.zeros(vs, jnp.float32),
        pending=jnp.zeros((S, n, 4), jnp.float32),
        chan_free=jnp.zeros((n,), jnp.float32),
        push_end=jnp.zeros((), jnp.float32),
        pool_server=jnp.zeros((S, cfg.prequal.s_pool), jnp.int32),
        pool_rif=jnp.full((S, cfg.prequal.s_pool), jnp.inf, jnp.float32),
        pool_lat=jnp.full((S, cfg.prequal.s_pool), jnp.inf, jnp.float32),
        pool_age=jnp.full((S, cfg.prequal.s_pool), -jnp.inf, jnp.float32),
        pool_valid=jnp.zeros((S, cfg.prequal.s_pool), bool),
        msgs=jnp.zeros((4,), jnp.int32),
        push_at=jnp.zeros((S,), jnp.float32) if cfg.trace else None,
    )


def _truth_rows(carry, rows: jnp.ndarray, now: jnp.ndarray):
    """Ground-truth (L, D, rif) for a set of servers, from the ring buffer."""
    rel = carry.rb_release[rows]                       # [k, R]
    act = (rel > now).astype(jnp.float32)
    L = jnp.stack([jnp.sum(carry.rb_cpu[rows] * act, -1),
                   jnp.sum(carry.rb_mem[rows] * act, -1)], axis=-1)
    D = jnp.sum(carry.rb_dur[rows] * act, -1)
    rif = jnp.sum(act, -1)
    return L, D, rif


def _truth_all(carry, now: jnp.ndarray):
    act = (carry.rb_release > now).astype(jnp.float32)
    L = jnp.stack([jnp.sum(carry.rb_cpu * act, -1),
                   jnp.sum(carry.rb_mem * act, -1)], axis=-1)
    D = jnp.sum(carry.rb_dur * act, -1)
    rif = jnp.sum(act, -1)
    return L, D, rif


def _apply_push(carry: _Carry, now, dyn: _Dyn, win: _Win, S: int,
                faulted: bool, push_ord):
    """Apply one data-store batch push: the store's view is truth(now)
    minus the deltas schedulers have not yet flushed (see the staleness
    model in the module docstring).  Shared by both drivers — it used to
    be duplicated as a closure in each.

    Under cache faults (``faulted``) the snapshot is taken at
    ``now − cache_delay`` (late completion reports) and each scheduler's
    delivery may be lost (:func:`_cache_lost`) — a loser keeps its old
    per-scheduler view.  The unfaulted branch is today's exact path."""
    if not faulted:
        L, D, rif = _truth_all(carry, now)
        unflushed = jnp.sum(carry.pending, axis=0)     # [n, 4]
        kw = {}
        if carry.push_at is not None:
            kw["push_at"] = jnp.full_like(carry.push_at, now)
        return carry._replace(
            view_L=jnp.maximum(0.0, L - unflushed[:, :2]),
            view_D=jnp.maximum(0.0, D - unflushed[:, 2]),
            view_rif=jnp.maximum(0.0, rif - unflushed[:, 3]),
            push_end=now + dyn.push_block_ms, **kw)
    L, D, rif = _truth_all(carry, now - win.cache_delay)
    unflushed = jnp.sum(carry.pending, axis=0)
    store_L = jnp.maximum(0.0, L - unflushed[:, :2])
    store_D = jnp.maximum(0.0, D - unflushed[:, 2])
    store_rif = jnp.maximum(0.0, rif - unflushed[:, 3])
    lost = _cache_lost(win, now, push_ord, S)          # [S]
    kw = {}
    if carry.push_at is not None:
        # A lost delivery keeps the scheduler's old snapshot; a delivered
        # one carries content as of now − cache_delay (late reports age
        # the view even when delivery succeeds).
        kw["push_at"] = jnp.where(lost, carry.push_at,
                                  now - win.cache_delay)
    return carry._replace(
        view_L=jnp.where(lost[:, None, None], carry.view_L, store_L[None]),
        view_D=jnp.where(lost[:, None], carry.view_D, store_D[None]),
        view_rif=jnp.where(lost[:, None], carry.view_rif, store_rif[None]),
        push_end=now + dyn.push_block_ms, **kw)


def _select(policy: str, key, carry: _Carry, r_sub, d_est_srv, now, sched,
            C, cfg: EngineConfig, dyn: _Dyn, win: _Win,
            faulted: bool = False, loc=None):
    """Dispatch the placement policy. Returns (server j, carry, extra_msgs,
    extra latency ms, trace extras).  The trace extras are a
    ``(view_age_ms, v_rif [2], cand [2], use_two)`` capture when
    ``cfg.trace`` is set and the policy schedules off the cached view,
    else ``None`` (probing policies have no snapshot to be stale); view
    error and misplacement are derived post-scan by
    :mod:`repro.sim.decision_trace`.  ``faulted`` switches
    the cached-view policies onto the per-scheduler view planes
    (cache-fault programs).  ``loc``, when given, is the ``(psrv [P],
    pbytes [P])`` locality operand pair of a DAG run: dodoor/(1+β) scores
    gain ``dyn.gamma_bw`` per MB of parent output the candidate would pull
    remotely (same reduction order as the batched path and the fused
    kernel)."""
    avail = _avail_rows(win, now)                       # [n] bool
    mask = feasible_mask(r_sub, C) & avail
    zero = jnp.zeros((), jnp.float32)

    if policy == "random":
        j = sample_feasible(key, mask, 1)[0]
        return j, carry, 0, zero, None

    if policy == "pot":
        cand = sample_feasible(key, mask, 2)
        _, _, rif = _truth_rows(carry, cand, now)       # synchronous probes
        j = jnp.where(rif[1] < rif[0], cand[1], cand[0]).astype(jnp.int32)
        # 2 probe sends + 2 replies; probes fly in parallel → +1 RTT latency.
        return j, carry, 4, 2.0 * dyn.hop_ms, None

    if policy in ("dodoor", "one_plus_beta"):
        k_cand, k_beta = jax.random.split(key)
        cand = sample_feasible(k_cand, mask, 2)
        if faulted:
            # This scheduler's own (possibly loss-degraded) cached view.
            L_ab = carry.view_L[sched, cand]
            D_ab = carry.view_D[sched, cand] + d_est_srv[cand]
        else:
            L_ab = carry.view_L[cand]                   # stale cached view
            D_ab = carry.view_D[cand] + d_est_srv[cand]  # D_j + d_ij
        C_ab = C[cand]
        scores = load_score_batched(r_sub[None], L_ab[None], D_ab[None],
                                    C_ab[None], dyn.alpha)[0]
        if loc is not None:
            psrv, pbytes = loc                          # [P] each
            rem = jnp.sum(
                pbytes[None, :]
                * (psrv[None, :] != cand[:, None]).astype(jnp.float32),
                axis=-1)                                # [2]
            scores = scores + dyn.gamma_bw * rem
        two = jnp.where(scores[0] > scores[1], cand[1], cand[0])
        if policy == "one_plus_beta":
            use_two = jax.random.uniform(k_beta) < dyn.beta
            j = jnp.where(use_two, two, cand[0]).astype(jnp.int32)
        else:
            j = two.astype(jnp.int32)
        tr = None
        if cfg.trace:
            # Capture the cached-rif reads and sampled candidates; ground
            # truth is rebuilt post-scan (repro.sim.decision_trace), so
            # tracing adds no per-step ring scans.  No extra RNG is
            # consumed — placements are unchanged.
            v_rif = (carry.view_rif[sched, cand] if faulted
                     else carry.view_rif[cand])
            use_two_f = (use_two.astype(jnp.float32)
                         if policy == "one_plus_beta"
                         else jnp.ones((), jnp.float32))
            tr = (now - carry.push_at[sched], v_rif, cand, use_two_f)
        # Cache-update blocking: a decision landing inside the push transfer
        # window waits for it to complete (§6.2's "blocking during cache
        # updates"; amortizes to ~push_block/b per decision).
        block = jnp.maximum(0.0, carry.push_end - now)
        return j, carry, 0, block, tr

    if policy == "prequal":
        k_sel, k_rand, k_probe = jax.random.split(key, 3)
        s = sched
        # Entries pointing at currently-down servers are skipped for
        # selection (HCL never routes to a dead node) but stay in the pool
        # — the server may come back before the entry is evicted.
        valid = carry.pool_valid[s] & avail[carry.pool_server[s]]
        rifs = jnp.where(valid, carry.pool_rif[s], jnp.inf)
        lats = jnp.where(valid, carry.pool_lat[s], jnp.inf)
        any_valid = jnp.any(valid)
        n_valid = jnp.maximum(jnp.sum(valid), 1)
        sorted_rif = jnp.sort(rifs)
        q_idx = jnp.clip(
            (dyn.q_rif * n_valid.astype(jnp.float32)).astype(jnp.int32),
            0, rifs.shape[0] - 1)
        threshold = sorted_rif[q_idx]
        cold = valid & (carry.pool_rif[s] <= threshold)
        cold_lat = jnp.where(cold, lats, jnp.inf)
        entry = jnp.where(jnp.any(cold), jnp.argmin(cold_lat), jnp.argmin(rifs))
        rand_j = sample_feasible(k_rand, mask, 1)[0]
        j = jnp.where(any_valid, carry.pool_server[s, entry], rand_j)
        j = j.astype(jnp.int32)
        # b_reuse = 1: consume the used entry.
        new_valid = jnp.where(any_valid,
                              carry.pool_valid[s].at[entry].set(False),
                              carry.pool_valid[s])
        carry = carry._replace(pool_valid=carry.pool_valid.at[s].set(new_valid))

        # Post-scheduling async probes (r_probe servers, true state).
        n = C.shape[0]
        probes = jax.random.randint(k_probe, (cfg.prequal.r_probe,), 0, n)
        pL, pD, prif = _truth_rows(carry, probes, now)
        ps, pr, plat, page, pv = (carry.pool_server[s], carry.pool_rif[s],
                                  carry.pool_lat[s], carry.pool_age[s],
                                  carry.pool_valid[s])
        for i in range(cfg.prequal.r_probe):
            # A probe to a down server gets no reply → no pool entry.
            ok = avail[probes[i]]
            slot_scores = jnp.where(pv, page, -jnp.inf)
            slot = jnp.argmin(slot_scores)       # first invalid, else oldest
            ps = jnp.where(ok, ps.at[slot].set(probes[i]), ps)
            pr = jnp.where(ok, pr.at[slot].set(prif[i]), pr)
            plat = jnp.where(ok, plat.at[slot].set(pD[i]), plat)
            page = jnp.where(ok, page.at[slot].set(now + jnp.float32(i) * 1e-3),
                             page)
            pv = jnp.where(ok, pv.at[slot].set(True), pv)
        # Maintenance (r_remove=1): evict worst-RIF entry when pool is full.
        full = jnp.sum(pv) >= pv.shape[0]
        worst = jnp.argmax(jnp.where(pv, pr, -jnp.inf))
        pv = jnp.where(full, pv.at[worst].set(False), pv)
        carry = carry._replace(
            pool_server=carry.pool_server.at[s].set(ps),
            pool_rif=carry.pool_rif.at[s].set(pr),
            pool_lat=carry.pool_lat.at[s].set(plat),
            pool_age=carry.pool_age.at[s].set(page),
            pool_valid=carry.pool_valid.at[s].set(pv),
        )
        return j, carry, 2 * cfg.prequal.r_probe, zero, None

    raise ValueError(f"unknown policy {policy!r}")


def _commit_one(carry, valid, now, j, cores, mem_mb, dur_raw, d_est_j,
                extra_lat, dyn: _Dyn, win: _Win, cores_per, mem_unit,
                MU: int, retry: bool = False):
    """Commit one placed task to server ``j``: channel contention, FCFS start,
    interference-stretched runtime, unit allocation, ring-buffer insert.
    Shared verbatim by the sequential driver and the batched PoT inner scan
    so the two are arithmetically identical. ``valid=False`` makes every
    state write a no-op (padded block tails).

    ``retry`` (static) adds the failure paths: hard-capacity rejection
    (the enqueue RPC is answered — and paid for — but nothing is queued)
    and kill-at-window-open (a gate window opening strictly inside
    (start, finish) releases the task's units and rb slot at the window
    start).  ``retry=False`` compiles today's arithmetic untouched, which
    is what keeps retry-disabled runs bit-identical.  Returns a 4-tuple of
    outputs, or a 6-tuple ending (killed, rejected) under ``retry``."""
    _, _, rif_j = _truth_rows(carry, j[None], now)
    occupancy = dyn.chan_ms * (1.0 + rif_j[0] / cores_per[j])
    chan_wait = jnp.maximum(0.0, carry.chan_free[j] - now)
    sched_ms = (dyn.compute_ms + extra_lat + chan_wait
                + occupancy + dyn.hop_ms)
    new_chan = jnp.maximum(carry.chan_free[j], now) + occupancy
    carry = carry._replace(chan_free=carry.chan_free.at[j].set(
        jnp.where(valid, new_chan, carry.chan_free[j])))
    enqueue_t = now + sched_ms

    if retry:
        # Hard capacity: the server's in-flight count already fills its
        # queue budget — the RPC reply is a rejection (channel time above
        # was still spent; no units, no rb entry).
        rejected = valid & (rif_j[0] >= dyn.reject_cap
                            * cores_per[j].astype(jnp.float32))
        w = valid & ~rejected
    else:
        w = valid

    c_eff = jnp.clip(cores, 1, cores_per[j]).astype(jnp.int32)
    mu_need = jnp.clip(jnp.ceil(mem_mb / mem_unit[j]), 1, MU).astype(jnp.int32)

    cf = carry.core_free[j]
    mf = carry.mem_free[j]
    cf_sorted = jnp.sort(cf)
    mf_sorted = jnp.sort(mf)
    start = jnp.maximum(
        jnp.maximum(enqueue_t, carry.prev_start[j]),
        jnp.maximum(cf_sorted[c_eff - 1], mf_sorted[mu_need - 1]))
    # Server-dynamics gate: a start landing in a down window resumes at
    # the window's end (maintenance freeze).
    start = _gate_start(win, j, start)
    # Co-location interference: cores still busy when we start stretch the
    # actual runtime (profiles are measured at low occupancy, §6.3).
    pad = CMAX - cores_per[j]
    busy = jnp.sum(cf > start) - pad          # running tasks' cores
    frac = busy.astype(jnp.float32) / cores_per[j].astype(jnp.float32)
    dur = dur_raw * (1.0 + dyn.interference * jnp.clip(frac, 0.0, 1.0))
    dur = dur * _slow_stretch(win, j, start)  # straggler windows
    finish = start + dur

    if retry:
        # Kill: the earliest gate window *opening* strictly inside
        # (start, finish) kills the task at the window start (post-gate,
        # start itself is never inside a window, so strict > is exact).
        g0 = win.gate0[j]
        kt = jnp.full((), jnp.inf, jnp.float32)
        for wi in range(g0.shape[0]):
            opens = (g0[wi] > start) & (g0[wi] < finish)
            kt = jnp.minimum(kt, jnp.where(opens, g0[wi], jnp.inf))
        killed = w & jnp.isfinite(kt)
        rel = jnp.where(killed, kt, finish)   # units/rb free at kill time
    else:
        rel = finish

    c_ranks = jnp.argsort(jnp.argsort(cf))
    m_ranks = jnp.argsort(jnp.argsort(mf))
    cf_new = jnp.where(c_ranks < c_eff, rel, cf)
    mf_new = jnp.where(m_ranks < mu_need, rel, mf)
    carry = carry._replace(
        core_free=carry.core_free.at[j].set(jnp.where(w, cf_new, cf)),
        mem_free=carry.mem_free.at[j].set(jnp.where(w, mf_new, mf)),
        prev_start=carry.prev_start.at[j].set(
            jnp.where(w, start, carry.prev_start[j])),
    )

    # In-flight ring buffer insert (slot with min release time).
    slot = jnp.argmin(carry.rb_release[j])
    carry = carry._replace(
        rb_release=carry.rb_release.at[j, slot].set(
            jnp.where(w, rel, carry.rb_release[j, slot])),
        rb_cpu=carry.rb_cpu.at[j, slot].set(
            jnp.where(w, cores, carry.rb_cpu[j, slot])),
        rb_mem=carry.rb_mem.at[j, slot].set(
            jnp.where(w, mem_mb, carry.rb_mem[j, slot])),
        rb_dur=carry.rb_dur.at[j, slot].set(
            jnp.where(w, d_est_j, carry.rb_dur[j, slot])),
    )
    if not retry:
        return carry, (start, finish, enqueue_t, sched_ms)
    start_o = jnp.where(rejected, enqueue_t, start)
    finish_o = jnp.where(rejected, enqueue_t, rel)
    return carry, (start_o, finish_o, enqueue_t, sched_ms, killed, rejected)


@partial(jax.jit, static_argnames=("cfg", "n", "num_types", "cache_faulted",
                                   "return_carry", "locality"))
def _simulate_jax(xs, C, node_type, mem_unit, cores_per, dyn_vec, dyn_ints,
                  win, cfg: EngineConfig, n: int, num_types: int, seed: int,
                  cache_faulted: bool = False, carry0=None,
                  return_carry: bool = False, locality: bool = False):
    """The sequential scan. xs = (i [m], r_sub [m,2], r_exec [m,T,2],
    d_est [m,T], d_act [m,T], submit [m], task_id [m]) — plus
    (psrv [m,P], pbytes [m,P]) when ``locality`` (DAG waves with a
    LocalityModel; the flag is static because the extra leaves shape the
    scan).

    ``dyn_ints = [b, flush_every]`` are traced: neither shapes the scan
    here, so b/flush sweeps share one compiled program.

    ``cfg.retry`` (static presence) compiles the failure paths into the
    commit; ``cache_faulted`` switches the store views per-scheduler;
    ``carry0``/``return_carry`` let the retry wave loop continue one run's
    cluster state into the next resubmission wave."""
    dyn = _Dyn(*dyn_vec)
    b_dyn, fe_dyn = dyn_ints[0], dyn_ints[1]
    S = cfg.num_schedulers
    retry = cfg.retry is not None
    base_key = jax.random.PRNGKey(seed)

    if carry0 is None:
        carry0 = _init_carry(cfg, n, cores_per, cache_faulted)

    def step(carry: _Carry, inp):
        if locality:
            (i, r_sub, r_exec_t, d_est_t, d_act_t, submit, task_id,
             psrv_t, pbytes_t) = inp
            loc = (psrv_t, pbytes_t)
        else:
            i, r_sub, r_exec_t, d_est_t, d_act_t, submit, task_id = inp
            loc = None
        now = submit
        sched = (i % S).astype(jnp.int32)
        key = jax.random.fold_in(base_key, task_id)    # §5: task-id seeding

        # Per-server demand/duration for this task's node types.
        r_srv = r_exec_t[node_type]                    # [n, 2]
        d_est_srv = d_est_t[node_type]                 # [n]

        j, carry, extra_msgs, extra_lat, tr = _select(
            cfg.policy, key, carry, r_sub, d_est_srv, now, sched, C, cfg,
            dyn, win, faulted=cache_faulted, loc=loc)

        # --- commit: scheduling latency (compute + channel contention +
        # placement hop; the enqueue RPC's service time grows with the
        # target's load — a busy server answers its RPC port slower, which is
        # what makes imbalanced placement pay extra latency, §6.2/§6.3),
        # FCFS start, interference stretch, unit allocation, ring insert.
        cores = r_srv[j, 0]
        mem_mb = r_srv[j, 1]
        dur_raw = d_act_t[node_type[j]]
        if retry:
            carry, (start, finish, enqueue_t, sched_ms, killed, rejected) = \
                _commit_one(carry, jnp.bool_(True), now, j, cores, mem_mb,
                            dur_raw, d_est_srv[j], extra_lat, dyn, win,
                            cores_per, mem_unit, cfg.mem_units, retry=True)
        else:
            carry, (start, finish, enqueue_t, sched_ms) = _commit_one(
                carry, jnp.bool_(True), now, j, cores, mem_mb, dur_raw,
                d_est_srv[j], extra_lat, dyn, win, cores_per, mem_unit,
                cfg.mem_units)

        msgs = carry.msgs.at[0].add(2).at[1].add(extra_msgs)

        # The data store (and its push/flush traffic) only exists for the
        # cached-view policies; probing policies carry no store at all.
        if cfg.policy in ("dodoor", "one_plus_beta"):
            # --- scheduler delta accumulation (addNewLoad payload); a
            #     rejected placement queued nothing, so reports no delta.
            delta = jnp.stack([cores, mem_mb, d_est_srv[j], 1.0])
            if retry:
                delta = delta * jnp.where(rejected, 0.0, 1.0)
            carry = carry._replace(pending=carry.pending.at[sched, j].add(delta))

            # --- addNewLoad flush (per-scheduler cadence)
            do_flush = ((i // S) + 1) % fe_dyn == 0
            carry = carry._replace(pending=jnp.where(
                do_flush, carry.pending.at[sched].set(0.0), carry.pending))
            msgs = jnp.where(do_flush, msgs.at[3].add(1), msgs)

            # --- data-store batch push (every b decisions cluster-wide);
            #     suppressed during a §4.3 store outage (stale views persist,
            #     scheduling continues — graceful degradation by design).
            do_push = ((i + 1) % b_dyn == 0) & ~_suppress_push(win, dyn, now)
            push_ord = (i + 1) // b_dyn if cache_faulted else None
            carry = jax.lax.cond(
                do_push,
                lambda c: _apply_push(c, now, dyn, win, S, cache_faulted,
                                      push_ord),
                lambda c: c, carry)
            msgs = jnp.where(do_push, msgs.at[2].add(S), msgs)
        carry = carry._replace(msgs=msgs)

        out = (j, start, finish, enqueue_t, sched_ms, cores, mem_mb)
        if retry:
            out = out + (killed.astype(jnp.float32),
                         rejected.astype(jnp.float32))
        if cfg.trace:
            if tr is not None:
                age, v_rif, cand, use_two_f = tr
                out = out + (age, v_rif[0], v_rif[1],
                             cand[0].astype(jnp.float32),
                             cand[1].astype(jnp.float32), use_two_f,
                             do_push.astype(jnp.float32))
            else:
                zero = jnp.zeros((), jnp.float32)
                out = out + (zero,) * 7
        return carry, out

    carry, outs = jax.lax.scan(step, carry0, xs)
    if return_carry:
        return carry, outs
    return carry.msgs, outs


def _sorted_fill(arr, k, value):
    """Replace the ``k`` smallest entries of each sorted-ascending row of
    ``arr`` [n, W] with ``value`` [n] (``value`` ≥ the k-th smallest entry),
    keeping the row sorted — an O(W) shift-merge: drop the first ``k``
    entries, then splice the ``k`` copies of ``value`` at their rank."""
    n, W = arr.shape
    iota = jnp.arange(W, dtype=jnp.int32)[None, :]
    kk = k[:, None]
    # Rank of `value` among the surviving entries arr[k:].
    idx = jnp.sum((iota >= kk) & (arr < value[:, None]), axis=1)[:, None]
    src = jnp.where(iota < idx, iota + kk, iota)
    gathered = jnp.take_along_axis(arr, jnp.minimum(src, W - 1), axis=1)
    in_win = (iota >= idx) & (iota < idx + kk)
    return jnp.where(in_win, value[:, None], gathered)


def _commit_rounds(carry: _Carry, valid, now, j, cores, mem_mb, dur_raw,
                   d_est_j, extra_lat, dyn: _Dyn, win: _Win, cores_per,
                   mem_unit, n: int, MU: int, outs0=None,
                   retry: bool = False):
    """Server-parallel commit of the ``valid``-masked tasks of a block —
    used directly by policies whose placements are known up front
    (random/dodoor/(1+β)) and as the inner commit step of the PoT
    speculative loop and the Prequal segment scan.

    Every state row a task's commit reads or writes — ``chan_free[j]``,
    ``core_free[j]``, ``mem_free[j]``, ``prev_start[j]``, ``rb_*[j]`` —
    belongs to its own server, so the per-server FCFS chains are mutually
    independent.  Round ``k`` therefore commits the k-th task of *every*
    server at once (vectorized over the fleet), and a block finishes in
    max-tasks-per-server rounds instead of ``b`` sequential steps.

    The commit reads core/mem unit state only as a *multiset* (c-th earliest
    free time, count busy past ``start``) and replaces the ``c_eff`` earliest
    units with ``finish``; this driver keeps each row sorted ascending and
    performs that update as an O(width) shift-merge — no sorts in the loop —
    which yields bit-identical results to :func:`_commit_one`'s rank-based
    form (the oracle's per-unit identities never reach any output).

    Returns ``(carry, outs)`` with ``outs`` a ``[7, b]`` float32 array —
    rows: start, finish, enqueue, sched_ms, the overwritten rb slot's old
    release, its old est-duration, and the slot index (exact in f32; the
    last three feed Prequal's probe revert).  ``outs0`` seeds the
    accumulator so iterative callers (PoT/Prequal) merge commits from
    successive invocations.

    ``retry`` (static) mirrors :func:`_commit_one`'s failure paths in
    per-server-row form — same arithmetic in the same order, so the two
    drivers stay bit-exact — and widens ``outs`` to ``[9, b]`` with killed
    and rejected rows (f32 0/1).  A rejected task writes no units and no
    rb entry; its outs record still carries (old_rel, old_dur, slot), and
    Prequal's revert of that record is a no-op by construction (the slot
    was never overwritten), keeping the telescoping exact.
    """
    bsz = j.shape[0]
    tt = jnp.arange(bsz, dtype=jnp.int32)
    # Rank of each task within its server's block queue (FCFS order).
    same_before = ((j[None, :] == j[:, None]) & valid[None, :]
                   & (tt[None, :] < tt[:, None]))
    occ = jnp.sum(same_before, axis=1).astype(jnp.int32)        # [b]
    rounds = jnp.max(jnp.where(valid, occ, -1)) + 1

    rows = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        k = state[0]
        return k < rounds

    def body(state):
        k, carry, outs_prev = state
        # This round's task per server (or none).
        tgt = jnp.where(valid & (occ == k), j, n)               # [b]
        sel = jnp.full((n,), -1, jnp.int32).at[tgt].set(tt, mode="drop")
        has = sel >= 0                                          # [n]
        t = jnp.clip(sel, 0, bsz - 1)

        now_s = now[t]
        cores_s = cores[t]
        mem_s = mem_mb[t]
        dur_s = dur_raw[t]
        dest_s = d_est_j[t]
        xlat_s = extra_lat[t]

        act = (carry.rb_release > now_s[:, None]).astype(jnp.float32)
        rif = jnp.sum(act, axis=-1)                             # [n]
        occupancy = dyn.chan_ms * (1.0 + rif / cores_per)
        chan_wait = jnp.maximum(0.0, carry.chan_free - now_s)
        sched_ms = (dyn.compute_ms + xlat_s + chan_wait
                    + occupancy + dyn.hop_ms)
        new_chan = jnp.maximum(carry.chan_free, now_s) + occupancy
        chan_free = jnp.where(has, new_chan, carry.chan_free)
        enqueue_t = now_s + sched_ms

        if retry:
            # Hard capacity (mirrors _commit_one): the channel above was
            # paid, but a full server queues nothing.
            rejected = has & (rif >= dyn.reject_cap
                              * cores_per.astype(jnp.float32))
            has_w = has & ~rejected
        else:
            has_w = has

        c_eff = jnp.clip(cores_s, 1, cores_per).astype(jnp.int32)
        mu_need = jnp.clip(jnp.ceil(mem_s / mem_unit), 1, MU).astype(jnp.int32)

        cf = carry.core_free                                    # [n, CMAX]
        mf = carry.mem_free                                     # [n, MU]
        # Rows are sorted ascending: the c-th earliest free time is a gather.
        core_gate = jnp.take_along_axis(cf, (c_eff - 1)[:, None], axis=1)[:, 0]
        mem_gate = jnp.take_along_axis(mf, (mu_need - 1)[:, None], axis=1)[:, 0]
        start = jnp.maximum(jnp.maximum(enqueue_t, carry.prev_start),
                            jnp.maximum(core_gate, mem_gate))
        start = _gate_start(win, None, start)           # down-window freeze
        pad = CMAX - cores_per
        busy = jnp.sum(cf > start[:, None], axis=-1) - pad
        frac = busy.astype(jnp.float32) / cores_per.astype(jnp.float32)
        dur = dur_s * (1.0 + dyn.interference * jnp.clip(frac, 0.0, 1.0))
        dur = dur * _slow_stretch(win, None, start)     # straggler windows
        finish = start + dur

        if retry:
            # Kill at window open (mirrors _commit_one; kt > start ≥ the
            # unit gates, so the sorted-fill invariant still holds).
            g0 = win.gate0                              # [n, Wg]
            kt = jnp.full((n,), jnp.inf, jnp.float32)
            for wi in range(g0.shape[1]):
                opens = (g0[:, wi] > start) & (g0[:, wi] < finish)
                kt = jnp.minimum(kt, jnp.where(opens, g0[:, wi], jnp.inf))
            killed = has_w & jnp.isfinite(kt)
            rel = jnp.where(killed, kt, finish)
        else:
            rel = finish

        cf_new = _sorted_fill(cf, c_eff, rel)
        mf_new = _sorted_fill(mf, mu_need, rel)
        has_c = has_w[:, None]
        carry = carry._replace(
            core_free=jnp.where(has_c, cf_new, cf),
            mem_free=jnp.where(has_c, mf_new, mf),
            prev_start=jnp.where(has_w, start, carry.prev_start),
            chan_free=chan_free,
        )

        # First index of the row minimum — two monoid reduces (min, then
        # min-of-matching-iota) instead of argmin, whose variadic reduce is
        # an order of magnitude slower on the XLA CPU backend.
        rb_min = jnp.min(carry.rb_release, axis=-1, keepdims=True)
        slot = jnp.min(jnp.where(carry.rb_release == rb_min,
                                 jnp.arange(carry.rb_release.shape[1],
                                            dtype=jnp.int32),
                                 carry.rb_release.shape[1]), axis=-1)
        old_rel = carry.rb_release[rows, slot]                  # pre-write
        old_dur = carry.rb_dur[rows, slot]
        rows_h = jnp.where(has_w, rows, n)                      # drop no-task
        carry = carry._replace(
            rb_release=carry.rb_release.at[rows_h, slot].set(
                rel, mode="drop"),
            rb_cpu=carry.rb_cpu.at[rows_h, slot].set(cores_s, mode="drop"),
            rb_mem=carry.rb_mem.at[rows_h, slot].set(mem_s, mode="drop"),
            rb_dur=carry.rb_dur.at[rows_h, slot].set(dest_s, mode="drop"),
        )

        t_out = jnp.where(has, t, bsz)                          # drop pads
        if retry:
            plane_rows = [jnp.where(rejected, enqueue_t, start),
                          jnp.where(rejected, enqueue_t, rel),
                          enqueue_t, sched_ms, old_rel, old_dur,
                          slot.astype(jnp.float32),
                          killed.astype(jnp.float32),
                          rejected.astype(jnp.float32)]
        else:
            plane_rows = [start, finish, enqueue_t, sched_ms,
                          old_rel, old_dur, slot.astype(jnp.float32)]
        plane = jnp.stack(plane_rows)
        outs = outs_prev.at[:, t_out].set(plane, mode="drop")
        return (k + 1, carry, outs)

    if outs0 is None:
        outs0 = jnp.zeros((9 if retry else 7, bsz), jnp.float32)
    state = (jnp.int32(0), carry, outs0)
    _, carry, outs = jax.lax.while_loop(cond, body, state)
    return carry, outs


def _make_block_step(C, node_type, mem_unit, cores_per, dyn_vec, dyn_ints,
                     win, base_key, cfg: EngineConfig, n: int,
                     use_kernel: bool, kernel_masked: bool = False,
                     cache_faulted: bool = False, locality: bool = False):
    """Build the single-block decision body ``block_step(carry, blk) →
    (carry, out)`` — the unit the batched scan iterates, and the step the
    streaming :class:`repro.serve.DecisionService` drives one compiled
    call at a time (jitted with the carry donated).

    The returned closure is exactly the scan body of
    :func:`_simulate_batched_jax` — same operands, same arithmetic — so
    driving it block-by-block over the same ``[nb, b, …]`` plane is
    bit-exact with the offline scan: the offline engine is the
    correctness oracle for the online one.  ``base_key`` is the
    ``jax.random.PRNGKey(seed)`` each task's decision key folds into.

    The body runs in three ``jax.named_scope``s: ``select`` (keys,
    availability, feasibility and the policy's choice), ``commit`` (the
    commit rounds, PoT's speculative loop, Prequal's segment scan) and
    ``push`` (deltas, flushes, the store push and the message ledger).
    They name the compiled instructions' ``op_name`` metadata, which is
    how a device trace's ops are mapped to stages; no arithmetic changes.
    """
    dyn = _Dyn(*dyn_vec)
    fe_dyn = dyn_ints[1]                 # flush cadence is traced; b shapes
    S = cfg.num_schedulers               # the blocks and stays static
    MU = cfg.mem_units
    policy = cfg.policy
    retry = cfg.retry is not None
    orows = 9 if retry else 7
    trace = cfg.trace

    def block_step(carry: _Carry, blk):
        if locality:
            (idx, r_sub, r_exec_t, d_est_t, d_act_t, submit, task_id, valid,
             psrv, pbytes) = blk
        else:
            idx, r_sub, r_exec_t, d_est_t, d_act_t, submit, task_id, valid \
                = blk
            psrv = pbytes = None
        with jax.named_scope("select"):
            bsz = idx.shape[0]
            tt = jnp.arange(bsz, dtype=jnp.int32)
            now = submit                                        # [b]
            sched = (idx % S).astype(jnp.int32)
            keys = jax.vmap(lambda t: jax.random.fold_in(base_key, t))(task_id)
            # Durations stay factorized as d_est_t [b, num_types] + the
            # server→type map; every consumer gathers per type, so no dense
            # [b, n] duration plane is ever materialized (the operand that
            # collapsed decisions/s above 10⁴ servers).  d_est_t[t, nt[j]] is
            # the same float the old plane held — placements are unchanged.
            avail = _avail_rows(win, now)                           # [b, n]
            mask = feasible_mask(r_sub, C) & avail                  # [b, n]

            # ---- vectorized selection against the block's one cache snapshot
            extra_lat = jnp.zeros((bsz,), jnp.float32)
            probe_msgs = 0
            if policy == "random":
                j = sample_feasible_batch(keys, mask, 1)[:, 0]
            elif policy in ("dodoor", "one_plus_beta"):
                kk = jax.vmap(jax.random.split)(keys)           # [b, 2, key]
                k_cand, k_beta = kk[:, 0], kk[:, 1]
                if use_kernel:
                    # Sparse-gather megakernel: candidate sampling, Algorithm-1
                    # scoring and selection in one Pallas pass over the
                    # factorized duration table (α/block_t/interpret are
                    # static program knobs baked into the grid program).  Under
                    # down-window timelines the availability plane rides into
                    # the in-kernel prefilter, so scenarios are honored with
                    # draws bit-identical to the two-stage masked path.
                    two, cand2, _ = dodoor_fused_sparse(
                        k_cand, r_sub, d_est_t, node_type, carry.view_L,
                        carry.view_D, C, alpha=cfg.alpha,
                        avail=avail if kernel_masked else None,
                        psrv=psrv, pbytes=pbytes,
                        gamma_bw=(cfg.locality.gamma_bw
                                  if locality and cfg.locality is not None
                                  else 0.0),
                        block_t=cfg.block_t, interpret=cfg.interpret)
                elif cache_faulted:
                    # Per-scheduler degraded views: gather each task's own
                    # scheduler's copy, then the same Algorithm-1 arithmetic
                    # as dodoor_choice_batch (bit-exact vs the sequential
                    # faulted read).
                    cand2 = sample_feasible_batch(k_cand, mask, 2)  # [b, 2]
                    d_cand = d_est_t[tt[:, None], node_type[cand2]]
                    L_c = carry.view_L[sched[:, None], cand2]       # [b, 2, 2]
                    D_c = carry.view_D[sched[:, None], cand2] + d_cand
                    scores = load_score_batched(r_sub, L_c, D_c, C[cand2],
                                                dyn.alpha)
                    if locality:
                        rem = jnp.sum(
                            pbytes[:, None, :]
                            * (psrv[:, None, :] != cand2[:, :, None]
                               ).astype(jnp.float32), axis=-1)      # [b, 2]
                        scores = scores + dyn.gamma_bw * rem
                    two = jnp.where(scores[:, 0] > scores[:, 1],
                                    cand2[:, 1], cand2[:, 0])
                elif locality:
                    # Same arithmetic as dodoor_choice_batch, inlined so the
                    # locality penalty lands between scoring and selection —
                    # order-identical to the sequential _select path.
                    cand2 = sample_feasible_batch(k_cand, mask, 2)  # [b, 2]
                    d_cand = d_est_t[tt[:, None], node_type[cand2]]
                    L_c = carry.view_L[cand2]                       # [b, 2, 2]
                    D_c = carry.view_D[cand2] + d_cand
                    scores = load_score_batched(r_sub, L_c, D_c, C[cand2],
                                                dyn.alpha)
                    rem = jnp.sum(
                        pbytes[:, None, :]
                        * (psrv[:, None, :] != cand2[:, :, None]
                           ).astype(jnp.float32), axis=-1)          # [b, 2]
                    scores = scores + dyn.gamma_bw * rem
                    two = jnp.where(scores[:, 0] > scores[:, 1],
                                    cand2[:, 1], cand2[:, 0])
                else:
                    cand2 = sample_feasible_batch(k_cand, mask, 2)  # [b, 2]
                    d_cand = d_est_t[tt[:, None], node_type[cand2]]
                    view = SchedulerView(L=carry.view_L, D=carry.view_D,
                                         rif=carry.view_rif, C=C)
                    two = dodoor_choice_batch(r_sub, cand2, d_cand, view,
                                              dyn.alpha, use_kernel=False)
                if policy == "one_plus_beta":
                    u = jax.vmap(jax.random.uniform)(k_beta)
                    j = jnp.where(u < dyn.beta, two,
                                  cand2[:, 0]).astype(jnp.int32)
                else:
                    j = two.astype(jnp.int32)
                extra_lat = jnp.maximum(0.0, carry.push_end - now)
                if trace:
                    # Capture only what the scan alone knows — the cached-rif
                    # reads and the sampled candidates.  Ground truth is
                    # rebuilt post-scan from the commit history
                    # (repro.sim.decision_trace), so tracing adds no per-step
                    # gather/reduce work.  No extra RNG is consumed —
                    # placements are unchanged.
                    v_rif = (carry.view_rif[sched[:, None], cand2]
                             if cache_faulted else carry.view_rif[cand2])
                    age_t = now - carry.push_at[sched]          # [b]
                    use_two_t = ((u < dyn.beta).astype(jnp.float32)
                                 if policy == "one_plus_beta"
                                 else jnp.ones((bsz,), jnp.float32))
            elif policy == "pot":
                probe_msgs = 4
                cand = sample_feasible_batch(keys, mask, 2)         # [b, 2]
            elif policy == "prequal":
                PP = cfg.prequal
                probe_msgs = 2 * PP.r_probe
                P = PP.s_pool
                kk3 = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
                rand_j = sample_feasible_batch(kk3[:, 1], mask, 1)[:, 0]
                probes = jax.vmap(lambda k: jax.random.randint(
                    k, (PP.r_probe,), 0, n))(kk3[:, 2])             # [b, rp]
            else:
                raise ValueError(f"policy {policy!r} has no batched path")

        # ---- commit
        with jax.named_scope("commit"):
            if policy in ("random", "dodoor", "one_plus_beta"):
                nt_j = node_type[j]                                 # [b]
                cores_t = r_exec_t[tt, nt_j, 0]
                mem_t = r_exec_t[tt, nt_j, 1]
                dur_t = d_act_t[tt, nt_j]
                dest_t = d_est_t[tt, nt_j]
                carry, outs = _commit_rounds(
                    carry, valid, now, j, cores_t, mem_t, dur_t, dest_t,
                    extra_lat, dyn, win, cores_per, mem_unit, n, MU,
                    retry=retry)
            elif policy == "pot":
                # Speculative commit + conflict replay.  Each iteration scores
                # every pending task against the *current* carry, commits the
                # longest conflict-free prefix in parallel rounds, and loops on
                # the suffix.  Safety rule: a pending task conflicts iff an
                # earlier pending task's speculative placement hits one of its
                # two probed candidates — so within a committed prefix every
                # probe read equals the sequential ground truth (and prefix
                # placements are pairwise distinct, making the commit 1 round).
                nt_c = node_type[cand]                              # [b, 2]
                cores_c = r_exec_t[tt[:, None], nt_c, 0]
                mem_c = r_exec_t[tt[:, None], nt_c, 1]
                dur_c = d_act_t[tt[:, None], nt_c]
                dest_c = d_est_t[tt[:, None], nt_c]
                pot_lat = jnp.broadcast_to(2.0 * dyn.hop_ms, (bsz,))

                def spec_cond(state):
                    return state[0] < bsz

                def spec_body(state):
                    p, c, j_acc, outs = state
                    pending = (tt >= p) & valid
                    act = (c.rb_release[cand]
                           > now[:, None, None]).astype(jnp.float32)
                    rif = jnp.sum(act, axis=-1)                     # [b, 2]
                    pick_b = rif[:, 1] < rif[:, 0]
                    j_spec = jnp.where(pick_b, cand[:, 1],
                                       cand[:, 0]).astype(jnp.int32)
                    j_eff = jnp.where(pending, j_spec, n)           # sentinel
                    hit = ((j_eff[None, :] == cand[:, :1])
                           | (j_eff[None, :] == cand[:, 1:]))       # [b, b]
                    unsafe = (jnp.any(hit & (tt[None, :] < tt[:, None]),
                                      axis=1) & pending)
                    q = jnp.min(jnp.where(unsafe, tt, bsz)).astype(jnp.int32)
                    commit = pending & (tt < q)
                    c, outs = _commit_rounds(
                        c, commit, now, j_spec,
                        jnp.where(pick_b, cores_c[:, 1], cores_c[:, 0]),
                        jnp.where(pick_b, mem_c[:, 1], mem_c[:, 0]),
                        jnp.where(pick_b, dur_c[:, 1], dur_c[:, 0]),
                        jnp.where(pick_b, dest_c[:, 1], dest_c[:, 0]),
                        pot_lat, dyn, win, cores_per, mem_unit, n, MU,
                        outs0=outs, retry=retry)
                    j_acc = jnp.where(commit, j_spec, j_acc)
                    return (q, c, j_acc, outs)

                state = (jnp.int32(0), carry, jnp.zeros((bsz,), jnp.int32),
                         jnp.zeros((orows, bsz), jnp.float32))
                _, carry, j, outs = jax.lax.while_loop(spec_cond, spec_body,
                                                       state)
            else:  # prequal — scheduler-parallel segment scan over S-chunks
                nchunks = -(-bsz // S)
                rows_s = jnp.arange(S, dtype=jnp.int32)
                iota_P = jnp.arange(P, dtype=jnp.int32)[None, :]

                def chunk_body(ci, state):
                    c, j_acc, outs = state
                    ic_raw = ci * S + rows_s
                    ok = ic_raw < bsz
                    ic = jnp.minimum(ic_raw, bsz - 1)
                    m_c = ok & valid[ic]
                    s_c = sched[ic]      # S consecutive tasks → S distinct
                    now_c = now[ic]      # schedulers: pools are race-free
                    s_eff = jnp.where(m_c, s_c, S)
                    ic_eff = jnp.where(m_c, ic, bsz)

                    # -- HCL selection from each scheduler's own pool.  Down
                    #    servers' entries are skipped for selection (matching
                    #    the sequential engine) but not deleted.
                    avail_c = _avail_rows(win, now_c)               # [S, n]
                    pv = c.pool_valid[s_c]                          # [S, P]
                    pr = c.pool_rif[s_c]
                    plat = c.pool_lat[s_c]
                    pserv = c.pool_server[s_c]
                    page = c.pool_age[s_c]
                    pv_sel = pv & jnp.take_along_axis(avail_c, pserv, axis=1)
                    rifs = jnp.where(pv_sel, pr, jnp.inf)
                    lats = jnp.where(pv_sel, plat, jnp.inf)
                    any_valid = jnp.any(pv_sel, axis=1)
                    n_val = jnp.maximum(jnp.sum(pv_sel, axis=1), 1)
                    sorted_rif = jnp.sort(rifs, axis=1)
                    q_idx = jnp.clip(
                        (dyn.q_rif * n_val.astype(jnp.float32)
                         ).astype(jnp.int32),
                        0, P - 1)
                    threshold = jnp.take_along_axis(sorted_rif, q_idx[:, None],
                                                    axis=1)[:, 0]
                    cold = pv_sel & (pr <= threshold[:, None])
                    cold_lat = jnp.where(cold, lats, jnp.inf)
                    entry = jnp.where(jnp.any(cold, axis=1),
                                      jnp.argmin(cold_lat, axis=1),
                                      jnp.argmin(rifs, axis=1))
                    j_c = jnp.where(any_valid, pserv[rows_s, entry],
                                    rand_j[ic]).astype(jnp.int32)
                    # b_reuse = 1: consume the used entry.
                    pv = pv & ~(any_valid[:, None]
                                & (iota_P == entry[:, None]))

                    # -- commit the chunk (placements now known; FCFS rank
                    #    within the chunk preserved by _commit_rounds' occ)
                    commit = jnp.zeros((bsz,), bool).at[ic_eff].set(
                        True, mode="drop")
                    j_full = jnp.zeros((bsz,), jnp.int32).at[ic_eff].set(
                        j_c, mode="drop")
                    nt_c = node_type[j_c]

                    def scat(v):
                        return jnp.zeros((bsz,), v.dtype).at[ic_eff].set(
                            v, mode="drop")

                    c, outs = _commit_rounds(
                        c, commit, now, j_full, scat(r_exec_t[ic, nt_c, 0]),
                        scat(r_exec_t[ic, nt_c, 1]), scat(d_act_t[ic, nt_c]),
                        scat(d_est_t[ic, nt_c]),
                        jnp.zeros((bsz,), jnp.float32), dyn, win, cores_per,
                        mem_unit, n, MU, outs0=outs, retry=retry)
                    j_acc = jnp.where(commit, j_full, j_acc)

                    # -- post-scheduling async probes: each task reads ground
                    #    truth as of *its own* decision point.  The chunk
                    #    committed first, so revert the rb slots written by
                    #    same-chunk commits at or after each task — reverse-
                    #    order (old, new) slot records telescope, exact even
                    #    when commits collide on a server or slot.
                    probes_c = probes[ic]                           # [S, rp]
                    rel_rows = c.rb_release[probes_c]           # [S, rp, R]
                    dur_rows = c.rb_dur[probes_c]
                    for kloc in reversed(range(S)):
                        col = ic[kloc]
                        jk = j_full[col]
                        slot_k = outs[6, col].astype(jnp.int32)
                        do = (commit[col] & (rows_s <= kloc)[:, None]
                              & (probes_c == jk))
                        rel_rows = rel_rows.at[:, :, slot_k].set(
                            jnp.where(do, outs[4, col],
                                      rel_rows[:, :, slot_k]))
                        dur_rows = dur_rows.at[:, :, slot_k].set(
                            jnp.where(do, outs[5, col],
                                      dur_rows[:, :, slot_k]))
                    act = (rel_rows > now_c[:, None, None]).astype(jnp.float32)
                    prif = jnp.sum(act, axis=-1)                    # [S, rp]
                    pD = jnp.sum(dur_rows * act, axis=-1)

                    # -- pool insert (sequential r_probe order) + maintenance;
                    #    probes to down servers get no reply → no entry.
                    avail_p = jnp.take_along_axis(avail_c, probes_c, axis=1)
                    for ip in range(PP.r_probe):
                        slot = jnp.argmin(jnp.where(pv, page, -jnp.inf),
                                          axis=1)
                        one = (iota_P == slot[:, None]) & avail_p[:, ip:ip + 1]
                        pserv = jnp.where(one, probes_c[:, ip:ip + 1], pserv)
                        pr = jnp.where(one, prif[:, ip:ip + 1], pr)
                        plat = jnp.where(one, pD[:, ip:ip + 1], plat)
                        page = jnp.where(
                            one, (now_c + jnp.float32(ip) * 1e-3)[:, None],
                            page)
                        pv = jnp.where(one, True, pv)
                    full = jnp.sum(pv, axis=1) >= P
                    worst = jnp.argmax(jnp.where(pv, pr, -jnp.inf), axis=1)
                    pv = pv & ~(full[:, None] & (iota_P == worst[:, None]))
                    c = c._replace(
                        pool_server=c.pool_server.at[s_eff].set(pserv,
                                                                mode="drop"),
                        pool_rif=c.pool_rif.at[s_eff].set(pr, mode="drop"),
                        pool_lat=c.pool_lat.at[s_eff].set(plat, mode="drop"),
                        pool_age=c.pool_age.at[s_eff].set(page, mode="drop"),
                        pool_valid=c.pool_valid.at[s_eff].set(pv, mode="drop"),
                    )
                    return (c, j_acc, outs)

                state = (carry, jnp.zeros((bsz,), jnp.int32),
                         jnp.zeros((orows, bsz), jnp.float32))
                carry, j, outs = jax.lax.fori_loop(0, nchunks, chunk_body,
                                                   state)

        with jax.named_scope("push"):
            o_start, o_finish, o_enq, o_sched = (outs[0], outs[1], outs[2],
                                                 outs[3])
            if policy in ("pot", "prequal"):
                nt_j = node_type[j]
                cores_t = r_exec_t[tt, nt_j, 0]
                mem_t = r_exec_t[tt, nt_j, 1]
                dest_t = d_est_t[tt, nt_j]

            n_valid = jnp.sum(valid).astype(jnp.int32)
            msgs = carry.msgs.at[0].add(2 * n_valid)
            if probe_msgs:
                msgs = msgs.at[1].add(probe_msgs * n_valid)

            # ---- data-store protocol, once per block (cached-view policies)
            if policy in ("dodoor", "one_plus_beta"):
                delta = jnp.stack(
                    [cores_t, mem_t, dest_t, jnp.ones_like(cores_t)], axis=1)
                do_flush = (((idx // S) + 1) % fe_dyn == 0) & valid
                # A delta survives into the carried accumulator iff its
                # scheduler does not flush at or after it within this block
                # (the flush at a task's own step clears the delta it just
                # added).
                flushed_after = jnp.any(
                    (sched[None, :] == sched[:, None])
                    & (tt[None, :] >= tt[:, None]) & do_flush[None, :], axis=1)
                survives = valid & ~flushed_after
                if retry:
                    # A rejected placement queued nothing → reports no delta
                    # (mirrors the sequential engine).
                    survives = survives & ~(outs[8] > 0.5)
                add = jnp.zeros_like(carry.pending).at[
                    sched, jnp.clip(j, 0, n - 1)].add(
                        delta * survives[:, None].astype(delta.dtype))
                sched_flushed = jnp.zeros((S,), bool).at[
                    jnp.where(do_flush, sched, S)].set(True, mode="drop")
                pending = jnp.where(
                    sched_flushed[:, None, None], 0.0, carry.pending) + add
                carry = carry._replace(pending=pending)
                msgs = msgs.at[3].add(jnp.sum(do_flush).astype(jnp.int32))

                # Push fires at the block boundary — only a full block
                # reaches the b-th decision (the padded tail never pushes),
                # matching the sequential trigger (i+1) % b == 0 exactly.
                now_push = now[-1]
                do_push = valid[-1] & ~_suppress_push(win, dyn, now_push)
                push_ord = ((idx[-1] + 1) // dyn_ints[0]) if cache_faulted \
                    else None
                carry = jax.lax.cond(
                    do_push,
                    lambda c: _apply_push(c, now_push, dyn, win, S,
                                          cache_faulted, push_ord),
                    lambda c: c, carry)
                msgs = jnp.where(do_push, msgs.at[2].add(S), msgs)
            carry = carry._replace(msgs=msgs)

        out = (j, o_start, o_finish, o_enq, o_sched, cores_t, mem_t)
        if retry:
            out = out + (outs[7], outs[8])
        if trace:
            if policy in ("dodoor", "one_plus_beta"):
                push_p = jnp.zeros((bsz,), jnp.float32).at[-1].set(
                    do_push.astype(jnp.float32))
                out = out + (age_t, v_rif[:, 0], v_rif[:, 1],
                             cand2[:, 0].astype(jnp.float32),
                             cand2[:, 1].astype(jnp.float32),
                             use_two_t, push_p)
            else:
                z = jnp.zeros((bsz,), jnp.float32)
                out = out + (z,) * 7
        return carry, out

    return block_step


@partial(jax.jit, static_argnames=("cfg", "n", "num_types", "use_kernel",
                                   "kernel_masked", "cache_faulted",
                                   "return_carry", "locality"))
def _simulate_batched_jax(xs, C, node_type, mem_unit, cores_per, dyn_vec,
                          dyn_ints, win, cfg: EngineConfig, n: int,
                          num_types: int, seed: int, use_kernel: bool,
                          kernel_masked: bool = False,
                          cache_faulted: bool = False, carry0=None,
                          return_carry: bool = False, locality: bool = False):
    """The block scan. xs fields are [nb, b, ...]: global index, r_sub,
    r_exec, d_est, d_act, submit, task_id, valid — plus (psrv [nb, b, P],
    pbytes [nb, b, P]) when ``locality`` (DAG waves under a LocalityModel;
    static, the extra leaves shape the scan).

    ``kernel_masked`` selects the megakernel's masked-sampling program
    (the avail plane streamed into the in-kernel prefilter).  It is a
    static knob derived from the Dynamics *spec* — window pad widths are
    always ≥ 1, so the operand shapes cannot reveal whether down windows
    exist — and stays False on dynamics-free runs so they keep the
    cheaper unmasked program.  With an all-true mask both programs draw
    identically, so the flag never changes results.

    ``cfg.retry`` (static presence) compiles the kill/rejection paths and
    widens the per-task outputs with killed/rejected planes;
    ``cache_faulted`` switches the store views per-scheduler;
    ``carry0``/``return_carry`` serve the retry wave loop exactly as in
    :func:`_simulate_jax`.  The scan body comes from
    :func:`_make_block_step` — shared with the streaming service."""
    if carry0 is None:
        carry0 = _init_carry(cfg, n, cores_per, cache_faulted)
    block_step = _make_block_step(
        C, node_type, mem_unit, cores_per, dyn_vec, dyn_ints, win,
        jax.random.PRNGKey(seed), cfg, n, use_kernel, kernel_masked,
        cache_faulted, locality)
    carry, outs = jax.lax.scan(block_step, carry0, xs)
    if return_carry:
        return carry, outs
    return carry.msgs, outs


#: Device-conversion cache: repeated simulate() calls over the same
#: workload/cluster (sweeps, benchmarks, parity tests) skip re-uploading
#: inputs.  Keys use object ids; the keyed objects are pinned in the value
#: so an id is never recycled while its entry lives.  Consequence: workload
#: and cluster objects are treated as IMMUTABLE after their first simulate()
#: call — mutating their numpy arrays in place afterwards would be silently
#: ignored (both are frozen dataclasses, so this matches their contract;
#: build a new object via dataclasses.replace instead).
_CONV_CACHE: dict = {}
_CONV_CACHE_MAX = 64


def _conv_cached(key, pins, builder):
    hit = _CONV_CACHE.get(key)
    if hit is not None:
        return hit[1]
    if len(_CONV_CACHE) >= _CONV_CACHE_MAX:
        _CONV_CACHE.clear()
    val = builder()
    _CONV_CACHE[key] = (pins, val)
    return val


def _make_dyn(cfg: EngineConfig) -> jnp.ndarray:
    """The traced-scalar parameters, packed as one [12] device array (a
    single transfer; unpacked into :class:`_Dyn` inside the jit)."""
    def build():
        o0, o1 = cfg.outage_ms if cfg.outage_ms else (np.inf, np.inf)
        cap = np.inf
        if cfg.retry is not None and cfg.retry.reject_queue_factor > 0:
            cap = cfg.retry.reject_queue_factor
        gbw = cfg.locality.gamma_bw if cfg.locality is not None else 0.0
        return jnp.asarray(np.array(
            [cfg.alpha, cfg.beta, cfg.interference, cfg.rpc.hop_ms,
             cfg.rpc.chan_ms, cfg.rpc.push_block_ms, cfg.rpc.compute_ms,
             o0, o1, cfg.prequal.q_rif, cap, gbw], np.float32))

    return _conv_cached(("dyn", cfg), (), build)


def _cluster_arrays(cluster: ClusterSpec, mem_units: int):
    def build():
        return (jnp.asarray(cluster.C),
                jnp.asarray(cluster.node_type),
                jnp.asarray(cluster.C[:, 0], jnp.int32),
                jnp.asarray(cluster.C[:, 1] / mem_units, jnp.float32))

    return _conv_cached(("cluster", id(cluster), mem_units), cluster, build)


def _make_dyn_ints(cfg: EngineConfig) -> jnp.ndarray:
    """[b, flush_every] as traced int32 operands."""
    return _conv_cached(
        ("dyn_ints", cfg.b, cfg.flush_every), (),
        lambda: jnp.asarray(np.array([cfg.b, cfg.flush_every], np.int32)))


def _pack_windows(rows: dict, n: int, width: int, fill):
    """[n, width] start/end (+ optional payload) planes from per-server
    window lists, sorted by start so `_gate_start`'s chained resolution is
    exact for non-overlapping windows."""
    k = len(fill)
    out = [np.full((n, width), f, np.float32) for f in fill]
    for srv, wins in rows.items():
        for wi, entry in enumerate(sorted(wins)):
            for a, v in zip(out, entry):
                a[srv, wi] = v
    return out


def _lower_dynamics(dynamics, n: int,
                    widths: tuple | None = None) -> _Win:
    """Lower a :class:`Dynamics` spec to :class:`_Win` operand planes.

    ``widths=(Wd, Wg, Ws, Wo, Wc)`` overrides the minimal pad widths — the
    scenario grid aligns every scenario to shared shapes (one compiled
    program); padding never changes results (empty windows are inert), so
    per-run and grid lowerings agree bit-exactly.  Cached per
    (dynamics, n, widths): the spec is a hashable NamedTuple.
    """
    dynamics = dynamics if dynamics is not None else Dynamics()
    if not isinstance(dynamics, Dynamics):
        raise TypeError(f"dynamics must be a Dynamics spec, "
                        f"got {type(dynamics).__name__}")

    def build():
        servers = [int(e[0]) for field in ("outages", "joins", "leaves",
                                           "slowdowns")
                   for e in getattr(dynamics, field)]
        for srv in servers:
            if not 0 <= srv < n:
                raise ValueError(f"dynamics server {srv} outside fleet "
                                 f"of {n}")
        down: dict = {}
        gate: dict = {}
        for srv, t0, t1 in dynamics.outages:
            down.setdefault(int(srv), []).append((float(t0), float(t1)))
            gate.setdefault(int(srv), []).append((float(t0), float(t1)))
        for srv, t in dynamics.joins:
            if float(t) <= 0.0:
                continue                  # present from the start: inert
            down.setdefault(int(srv), []).append((0.0, float(t)))
            gate.setdefault(int(srv), []).append((0.0, float(t)))
        for srv, t in dynamics.leaves:
            # sampling mask only: a leaver drains, so no start gate
            down.setdefault(int(srv), []).append((float(t), np.inf))
        slow: dict = {}
        for srv, t0, t1, mult in dynamics.slowdowns:
            slow.setdefault(int(srv), []).append(
                (float(t0), float(t1), float(mult)))
        for wins in down.values():
            if any(t1 <= t0 for t0, t1 in wins):
                raise ValueError("dynamics window needs t1 > t0")
        for wins in slow.values():
            if any(t1 <= t0 or mult <= 0 for t0, t1, mult in wins):
                raise ValueError("slowdown needs t1 > t0 and mult > 0")
        if any(t1 <= t0 for t0, t1 in dynamics.store_outages):
            raise ValueError("store outage needs t1 > t0")
        cfault = dynamics.cache_faults
        if cfault is not None:
            if not isinstance(cfault, CacheFaults):
                raise TypeError("cache_faults must be a CacheFaults spec")
            if not 0.0 <= cfault.loss_rate <= 1.0:
                raise ValueError("cache_faults.loss_rate must be in [0, 1]")
            if cfault.delay_ms < 0.0:
                raise ValueError("cache_faults.delay_ms must be ≥ 0")
            if any(t1 <= t0 for t0, t1 in cfault.loss_windows):
                raise ValueError("cache loss window needs t1 > t0")

        wd = max(1, max((len(v) for v in down.values()), default=0))
        wg = max(1, max((len(v) for v in gate.values()), default=0))
        ws = max(1, max((len(v) for v in slow.values()), default=0))
        wo = max(1, len(dynamics.store_outages))
        wc = max(1, len(cfault.loss_windows) if cfault is not None else 0)
        if widths is not None:
            need = (wd, wg, ws, wo, wc)
            if any(w < r for w, r in zip(widths, need)):
                raise ValueError(f"widths {widths} < required {need}")
            wd, wg, ws, wo, wc = widths

        d0, d1 = _pack_windows(down, n, wd, (np.inf, np.inf))
        g0, g1 = _pack_windows(gate, n, wg, (np.inf, np.inf))
        s0, s1, sm = _pack_windows(slow, n, ws, (np.inf, np.inf, 1.0))
        o0 = np.full((wo,), np.inf, np.float32)
        o1 = np.full((wo,), np.inf, np.float32)
        for wi, (t0, t1) in enumerate(sorted(dynamics.store_outages)):
            o0[wi], o1[wi] = t0, t1
        c0 = np.full((wc,), np.inf, np.float32)
        c1 = np.full((wc,), np.inf, np.float32)
        rate, delay, cseed = 0.0, 0.0, 0
        if cfault is not None:
            for wi, (t0, t1) in enumerate(sorted(cfault.loss_windows)):
                c0[wi], c1[wi] = t0, t1
            rate, delay, cseed = (cfault.loss_rate, cfault.delay_ms,
                                  int(cfault.seed))
        return _Win(*(jnp.asarray(a)
                      for a in (d0, d1, g0, g1, s0, s1, sm, o0, o1,
                                c0, c1)),
                    cache_rate=jnp.float32(rate),
                    cache_delay=jnp.float32(delay),
                    cache_seed=jnp.int32(cseed))

    return _conv_cached(("win", dynamics, n, widths), (), build)


def _static_cfg(cfg: EngineConfig, for_kernel: bool = False,
                keep_b: bool = False) -> EngineConfig:
    """Collapse traced-scalar fields to canonical values so one compiled
    program serves every (α, β, interference, RPC, outage, q_rif, b,
    flush_every) setting.  ``keep_b`` retains ``b`` — the batched driver's
    block shape depends on it.  ``for_kernel`` retains α/block_t/interpret,
    which the fused Pallas kernel bakes into its grid program."""
    return cfg._replace(
        alpha=cfg.alpha if for_kernel else 0.5,
        beta=0.5,
        interference=0.3,
        b=cfg.b if keep_b else 50,
        flush_every=2,
        outage_ms=(),
        rpc=RpcModel(),
        prequal=cfg.prequal._replace(q_rif=0.84),
        block_t=cfg.block_t if for_kernel else 256,
        interpret=cfg.interpret if for_kernel else None,
        # Only the *presence* of a RetryPolicy shapes the program (kill/
        # reject arithmetic + widened outputs); its knobs are host-side
        # (wave loop) or traced (reject_cap), so all retry settings share
        # one compiled program per driver.
        retry=None if cfg.retry is None else RetryPolicy(),
        # LocalityModel: presence gates the two-stage penalty (whose
        # gamma_bw rides traced in _Dyn), but the fused kernel bakes
        # gamma_bw into its program like alpha — retain it for_kernel.
        locality=(None if cfg.locality is None
                  else (cfg.locality if for_kernel else LocalityModel())),
    )


def _validate_config(cfg: EngineConfig) -> None:
    """Shared sanity checks for ``simulate`` and ``sweep.simulate_many``."""
    if cfg.b < 1 or cfg.flush_every < 1:
        raise ValueError(
            f"b={cfg.b} and flush_every={cfg.flush_every} must be ≥ 1")
    if cfg.policy == "dodoor":
        bound = max(1, 2 * cfg.b // max(1, cfg.num_schedulers))
        if cfg.flush_every > bound:
            raise ValueError(
                f"flush_every={cfg.flush_every} violates the §4.1 mini-batch "
                f"bound 2b/num_schedulers = {bound}")
    if cfg.retry is not None:
        rp = cfg.retry
        if not isinstance(rp, RetryPolicy):
            raise TypeError("EngineConfig.retry must be a RetryPolicy")
        if rp.max_attempts < 1:
            raise ValueError("retry.max_attempts must be ≥ 1")
        if rp.backoff_ms < 0.0 or rp.backoff_mult <= 0.0:
            raise ValueError(
                "retry needs backoff_ms ≥ 0 and backoff_mult > 0")
    if cfg.locality is not None:
        lm = cfg.locality
        if not isinstance(lm, LocalityModel):
            raise TypeError("EngineConfig.locality must be a LocalityModel")
        if lm.gamma < 0.0:
            raise ValueError("locality.gamma must be ≥ 0")
        if lm.bandwidth_mb_per_ms <= 0.0:
            raise ValueError("locality.bandwidth_mb_per_ms must be > 0")


def _blocked_inputs(workload, b: int):
    """The batched driver's xs: the workload reshaped to [nb, b, ...] decision
    blocks (edge-padded ragged tail + validity mask), cached on device per
    (workload, b) so sweeps and repeated runs share one upload."""
    m = workload.r_submit.shape[0]
    nb = -(-m // b)

    def build_blocks():
        pad = nb * b - m

        def prep(a):
            a = np.ascontiguousarray(a)
            if pad:
                a = np.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                           mode="edge")
            return jnp.asarray(a.reshape((nb, b) + a.shape[1:]))

        ids = np.arange(nb * b, dtype=np.int32)
        ids_dev = jnp.asarray(ids.reshape(nb, b))
        return (
            ids_dev,
            prep(workload.r_submit),
            prep(workload.r_exec),
            prep(workload.d_est),
            prep(workload.d_act),
            prep(workload.submit_ms),
            ids_dev,                                   # task ids
            jnp.asarray((ids < m).reshape(nb, b)),
        )

    return _conv_cached(("blocks", id(workload), b), workload, build_blocks)


def resolve_use_kernel(use_kernel, interpret: bool | None = None) -> bool:
    """Resolve the ``use_kernel`` knob (``"auto"`` | True | False) to the
    boolean the batched driver compiles under.

    ``"auto"`` picks the fused Pallas megakernel only where its lowering
    actually *compiles* — a real TPU backend, or an explicit
    ``interpret=False`` override (the same rule as
    ``kernel._resolve_interpret``).  Off-accelerator the kernel runs the
    Pallas interpreter and measures ~0.6× the two-stage jnp path (the
    ``BENCH_study.json`` ``masked_kernel`` row), so auto keeps the
    two-stage path there.  ``True`` forces the kernel everywhere
    (interpret mode included — the CI parity path), ``False`` forces the
    two-stage path everywhere.  Pinned by ``tests/test_engine_batched.py``.
    """
    if isinstance(use_kernel, str):
        if use_kernel != "auto":
            raise ValueError(
                f"use_kernel must be True, False or 'auto', got "
                f"{use_kernel!r}")
        return not _resolve_interpret(interpret)
    return bool(use_kernel)


def _simulate_with_retries(workload, cluster: ClusterSpec, cfg: EngineConfig,
                           seed: int, mode: str, use_kernel: bool,
                           dynamics, masked: bool,
                           faulted: bool) -> SimResult:
    """The re-entry queue: run the decision stream in *waves*.

    Wave 1 is the full workload.  Tasks killed by a freeze window or
    rejected at hard capacity re-enter as wave k+1, resubmitted at
    ``fail_time + backoff_ms·mult^(k-1)`` (sorted by retry time, original
    index as tie-break), with fresh decision randomness (task key
    ``orig_index + (attempt-1)·m``).  The cluster carry — ring buffers,
    unit clocks, channels, cached views, pools, message ledger — threads
    from wave to wave, so retries contend with the load their first
    attempts created.  Wave-local cadences (scheduler round-robin, flush,
    push) restart per wave: a resubmission is a fresh decision to the
    scheduling layer.  Tasks still failing after ``max_attempts``
    submissions fail permanently (``SimResult.failed``); their recorded
    finish is the last kill/reject time.

    Both drivers run the same wave plan — the sequential oracle at exact
    wave length, the batched driver padded to whole ``b``-blocks — so the
    seq-vs-batched parity guarantee extends to every failure path."""
    rp = cfg.retry
    n = cluster.num_servers
    C, node_type, cores_per, mem_unit = _cluster_arrays(cluster,
                                                        cfg.mem_units)
    dyn = _make_dyn(cfg)
    dyn_i = _make_dyn_ints(cfg)
    win = _lower_dynamics(dynamics, n)
    m = workload.r_submit.shape[0]
    batched = mode == "batched"
    scfg = (_static_cfg(cfg, for_kernel=use_kernel, keep_b=True) if batched
            else _static_cfg(cfg))
    b = cfg.b

    host = {f: np.ascontiguousarray(getattr(workload, f))
            for f in ("r_submit", "r_exec", "d_est", "d_act", "submit_ms")}

    server = np.zeros(m, np.int32)
    fin = {k: np.zeros(m, np.float32)
           for k in ("start", "finish", "enq", "sched", "cores", "mem")}
    attempts = np.zeros(m, np.int32)
    wasted = np.zeros(m, np.float64)
    trace = cfg.trace
    if trace:
        # A retried task's record is its *final* attempt's decision.
        tr_pl = {k: np.zeros(m, np.float32)
                 for k in ("age", "verr", "misp", "push")}
        sched_id = np.zeros(m, np.int32)
        decision_ms = np.zeros(m, np.float32)

    idx = np.arange(m)                       # original ids, this wave
    submit_w = host["submit_ms"].astype(np.float32)
    carry = None
    for a in range(1, rp.max_attempts + 1):
        mw = idx.shape[0]
        task_id = (idx + (a - 1) * m).astype(np.int32)
        # Wave-entry ring state: the trace post-pass folds the live load
        # the earlier waves left behind into this wave's ground truth.
        ring0 = None
        if trace and carry is not None:
            ring0 = tuple(np.asarray(p) for p in
                          (carry.rb_release, carry.rb_cpu,
                           carry.rb_mem, carry.rb_dur))
        if batched:
            nb = -(-mw // b)
            pad = nb * b - mw

            def blk(arr):
                arr = np.ascontiguousarray(arr)
                if pad:
                    arr = np.pad(arr, ((0, pad),) + ((0, 0),)
                                 * (arr.ndim - 1), mode="edge")
                return jnp.asarray(arr.reshape((nb, b) + arr.shape[1:]))

            ids = np.arange(nb * b, dtype=np.int32)
            xs = (jnp.asarray(ids.reshape(nb, b)),
                  blk(host["r_submit"][idx]), blk(host["r_exec"][idx]),
                  blk(host["d_est"][idx]), blk(host["d_act"][idx]),
                  blk(submit_w), blk(task_id),
                  jnp.asarray((ids < mw).reshape(nb, b)))
            carry, outs = _simulate_batched_jax(
                xs, C, node_type, mem_unit, cores_per, dyn, dyn_i, win,
                scfg, n, cluster.num_types, seed, use_kernel, masked,
                cache_faulted=faulted, carry0=carry, return_carry=True)
            outs = [np.asarray(o).reshape(nb * b)[:mw] for o in outs]
        else:
            xs = (jnp.arange(mw, dtype=jnp.int32),
                  jnp.asarray(host["r_submit"][idx]),
                  jnp.asarray(host["r_exec"][idx]),
                  jnp.asarray(host["d_est"][idx]),
                  jnp.asarray(host["d_act"][idx]),
                  jnp.asarray(submit_w), jnp.asarray(task_id))
            carry, outs = _simulate_jax(
                xs, C, node_type, mem_unit, cores_per, dyn, dyn_i, win,
                scfg, n, cluster.num_types, seed,
                cache_faulted=faulted, carry0=carry, return_carry=True)
            outs = [np.asarray(o) for o in outs]

        j_w, start_w, fin_w, enq_w, sch_w, cor_w, mem_w = outs[:7]
        k_w, r_w = outs[7], outs[8]
        killed = k_w > 0.5
        server[idx] = j_w
        for k, v in (("start", start_w), ("finish", fin_w), ("enq", enq_w),
                     ("sched", sch_w), ("cores", cor_w), ("mem", mem_w)):
            fin[k][idx] = v
        attempts[idx] = a
        wasted[idx[killed]] += (fin_w - start_w)[killed].astype(np.float64)
        if trace:
            age_w, vr0_w, vr1_w, c0_w, c1_w, u2_w, push_w = outs[9:16]
            verr_w, misp_w = finish_trace(
                j=j_w, finish=fin_w, cores=cor_w, mem=mem_w,
                now=submit_w, v_rif=(vr0_w, vr1_w), cand=(c0_w, c1_w),
                use_two=u2_w, r_sub=host["r_submit"][idx],
                d_est=host["d_est"][idx], node_type=np.asarray(node_type),
                C=np.asarray(C), alpha=cfg.alpha, policy=cfg.policy,
                R=cfg.rbuf_slots, rejected=(r_w > 0.5), init_ring=ring0)
            tr_pl["age"][idx] = age_w
            tr_pl["verr"][idx] = verr_w
            tr_pl["misp"][idx] = misp_w
            tr_pl["push"][idx] = push_w
            # Wave-local round-robin: the wave restarts cadences, so the
            # deciding scheduler is the wave-local index mod S.
            sched_id[idx] = np.arange(mw) % cfg.num_schedulers
            decision_ms[idx] = submit_w

        fail_w = killed | (r_w > 0.5)
        if not fail_w.any():
            idx = idx[:0]
            break
        # Re-entry queue for the next wave: killed → resubmit from the
        # kill time, rejected → from the reject reply, plus exponential
        # backoff.  Sorted by retry time (original id breaks ties).
        t_retry = fin_w[fail_w].astype(np.float64) \
            + rp.backoff_ms * (rp.backoff_mult ** (a - 1))
        idx = idx[fail_w]
        order = np.lexsort((idx, t_retry))
        idx = idx[order]
        submit_w = t_retry[order].astype(np.float32)

    failed = np.zeros(m, bool)
    failed[idx] = True
    msgs = np.asarray(carry.msgs)
    return SimResult(
        server=server, submit_ms=host["submit_ms"],
        enqueue_ms=fin["enq"], start_ms=fin["start"],
        finish_ms=fin["finish"], sched_ms=fin["sched"],
        cores=fin["cores"], mem_mb=fin["mem"],
        msgs_base=int(msgs[0]), msgs_probe=int(msgs[1]),
        msgs_push=int(msgs[2]), msgs_flush=int(msgs[3]),
        policy=cfg.policy, attempts=attempts, failed=failed,
        wasted_ms=wasted.astype(np.float32),
        **({"view_age_ms": tr_pl["age"], "view_err": tr_pl["verr"],
            "misplaced": tr_pl["misp"] > 0.5,
            "cache_push": tr_pl["push"] > 0.5,
            "sched_id": sched_id, "decision_ms": decision_ms}
           if trace else {}),
    )


def _simulate_dag(workload, cluster: ClusterSpec, cfg: EngineConfig,
                  seed: int, mode: str, use_kernel: bool, dynamics,
                  masked: bool, faulted: bool, plan) -> SimResult:
    """The frontier loop: run a task graph level by level.

    Waves are the plan's longest-path topological levels, so every task's
    parents have finished — and their placements are known to the
    locality gather — before it is submitted.  A task's *effective*
    submit time is ``max(trace submit, max_p(finish[p] + edge_delay))``
    (the ready-set rule); within a wave, decisions run in ready-time
    order (original index breaks ties).  The cluster carry threads from
    wave to wave exactly as in :func:`_simulate_with_retries`, and
    wave-local cadences (scheduler round-robin, flush, push) restart per
    wave — a newly-ready frontier is a fresh decision stream to the
    scheduling layer.

    With ``cfg.locality`` set, each wave streams its tasks' parent
    placements/payloads (``psrv``/``pbytes``, −1/0 padded) into the
    decision: Algorithm 1's score gains ``gamma_bw · Σ_p bytes_p ·
    [server_p ≠ candidate]`` on both candidates.  ``gamma = 0`` adds
    ``+0.0`` and is bit-identical to running without a LocalityModel.

    Both drivers consume the identical wave plan — the sequential oracle
    at exact wave length, the batched driver edge-padded to whole
    ``b``-blocks — so finish planes (hence every later wave's ready
    times) inherit the engine's seq-vs-batched bit-exactness inductively.

    Returns a :class:`SimResult` whose ``submit_ms`` holds the
    *effective* submit times (``summarize`` latency is then queueing +
    service past readiness, not past the trace timestamp)."""
    n = cluster.num_servers
    C, node_type, cores_per, mem_unit = _cluster_arrays(cluster,
                                                        cfg.mem_units)
    dyn = _make_dyn(cfg)
    dyn_i = _make_dyn_ints(cfg)
    win = _lower_dynamics(dynamics, n)
    m = workload.r_submit.shape[0]
    batched = mode == "batched"
    scfg = (_static_cfg(cfg, for_kernel=use_kernel, keep_b=True) if batched
            else _static_cfg(cfg))
    b = cfg.b
    loc_on = cfg.locality is not None and plan.max_parents > 0

    host = {f: np.ascontiguousarray(getattr(workload, f))
            for f in ("r_submit", "r_exec", "d_est", "d_act", "submit_ms")}

    server = np.zeros(m, np.int32)
    fin = {k: np.zeros(m, np.float32)
           for k in ("start", "finish", "enq", "sched", "cores", "mem")}
    eff_submit = np.zeros(m, np.float32)
    submit0 = host["submit_ms"].astype(np.float64)
    trace = cfg.trace
    if trace:
        tr_pl = {k: np.zeros(m, np.float32)
                 for k in ("age", "verr", "misp", "push")}
        sched_id = np.zeros(m, np.int32)

    carry = None
    psrv_w = pbytes_w = None
    for lv in range(plan.num_levels):
        sel = np.flatnonzero(plan.level == lv)
        par = plan.parents_pad[sel]                          # [w, P]
        fin_par = np.where(
            par >= 0, fin["finish"][np.maximum(par, 0)].astype(np.float64),
            -np.inf)
        ready = np.maximum(
            submit0[sel],
            np.max(fin_par + plan.pdelay_pad[sel], axis=1, initial=-np.inf))
        order = np.lexsort((sel, ready))
        idx = sel[order]
        submit_w = ready[order].astype(np.float32)
        mw = idx.shape[0]
        task_id = idx.astype(np.int32)
        # Wave-entry ring state: earlier levels' still-running tasks are
        # part of this wave's ground truth (see _simulate_with_retries).
        ring0 = None
        if trace and carry is not None:
            ring0 = tuple(np.asarray(p) for p in
                          (carry.rb_release, carry.rb_cpu,
                           carry.rb_mem, carry.rb_dur))
        if loc_on:
            pidx = plan.parents_pad[idx]
            psrv_w = np.where(pidx >= 0, server[np.maximum(pidx, 0)],
                              -1).astype(np.int32)
            pbytes_w = np.ascontiguousarray(plan.pbytes_pad[idx])
        if batched:
            nb = -(-mw // b)
            pad = nb * b - mw

            def blk(arr):
                arr = np.ascontiguousarray(arr)
                if pad:
                    arr = np.pad(arr, ((0, pad),) + ((0, 0),)
                                 * (arr.ndim - 1), mode="edge")
                return jnp.asarray(arr.reshape((nb, b) + arr.shape[1:]))

            ids = np.arange(nb * b, dtype=np.int32)
            xs = (jnp.asarray(ids.reshape(nb, b)),
                  blk(host["r_submit"][idx]), blk(host["r_exec"][idx]),
                  blk(host["d_est"][idx]), blk(host["d_act"][idx]),
                  blk(submit_w), blk(task_id),
                  jnp.asarray((ids < mw).reshape(nb, b)))
            if loc_on:
                xs = xs + (blk(psrv_w), blk(pbytes_w))
            carry, outs = _simulate_batched_jax(
                xs, C, node_type, mem_unit, cores_per, dyn, dyn_i, win,
                scfg, n, cluster.num_types, seed, use_kernel, masked,
                cache_faulted=faulted, carry0=carry, return_carry=True,
                locality=loc_on)
            outs = [np.asarray(o).reshape(nb * b)[:mw] for o in outs]
        else:
            xs = (jnp.arange(mw, dtype=jnp.int32),
                  jnp.asarray(host["r_submit"][idx]),
                  jnp.asarray(host["r_exec"][idx]),
                  jnp.asarray(host["d_est"][idx]),
                  jnp.asarray(host["d_act"][idx]),
                  jnp.asarray(submit_w), jnp.asarray(task_id))
            if loc_on:
                xs = xs + (jnp.asarray(psrv_w), jnp.asarray(pbytes_w))
            carry, outs = _simulate_jax(
                xs, C, node_type, mem_unit, cores_per, dyn, dyn_i, win,
                scfg, n, cluster.num_types, seed,
                cache_faulted=faulted, carry0=carry, return_carry=True,
                locality=loc_on)
            outs = [np.asarray(o) for o in outs]

        j_w, start_w, fin_w, enq_w, sch_w, cor_w, mem_w = outs[:7]
        server[idx] = j_w
        for k, v in (("start", start_w), ("finish", fin_w), ("enq", enq_w),
                     ("sched", sch_w), ("cores", cor_w), ("mem", mem_w)):
            fin[k][idx] = v
        eff_submit[idx] = submit_w
        if trace:
            age_w, vr0_w, vr1_w, c0_w, c1_w, u2_w, push_w = outs[7:14]
            verr_w, misp_w = finish_trace(
                j=j_w, finish=fin_w, cores=cor_w, mem=mem_w,
                now=submit_w, v_rif=(vr0_w, vr1_w), cand=(c0_w, c1_w),
                use_two=u2_w, r_sub=host["r_submit"][idx],
                d_est=host["d_est"][idx], node_type=np.asarray(node_type),
                C=np.asarray(C), alpha=cfg.alpha, policy=cfg.policy,
                R=cfg.rbuf_slots,
                gamma_bw=(cfg.locality.gamma_bw if loc_on else 0.0),
                psrv=psrv_w if loc_on else None,
                pbytes=pbytes_w if loc_on else None, init_ring=ring0)
            tr_pl["age"][idx] = age_w
            tr_pl["verr"][idx] = verr_w
            tr_pl["misp"][idx] = misp_w
            tr_pl["push"][idx] = push_w
            sched_id[idx] = np.arange(mw) % cfg.num_schedulers

    msgs = np.asarray(carry.msgs)
    return SimResult(
        server=server, submit_ms=eff_submit,
        enqueue_ms=fin["enq"], start_ms=fin["start"],
        finish_ms=fin["finish"], sched_ms=fin["sched"],
        cores=fin["cores"], mem_mb=fin["mem"],
        msgs_base=int(msgs[0]), msgs_probe=int(msgs[1]),
        msgs_push=int(msgs[2]), msgs_flush=int(msgs[3]),
        policy=cfg.policy,
        **({"view_age_ms": tr_pl["age"], "view_err": tr_pl["verr"],
            "misplaced": tr_pl["misp"] > 0.5,
            "cache_push": tr_pl["push"] > 0.5,
            "sched_id": sched_id, "decision_ms": eff_submit}
           if trace else {}),
    )


def simulate(workload, cluster: ClusterSpec, cfg: EngineConfig,
             seed: int = 0, *, mode: str = "sequential",
             use_kernel: bool | str = "auto", dynamics=None,
             dag=None) -> SimResult:
    """Run a full experiment: one workload trace through one policy.

    mode:
        ``"sequential"`` — one scan step per task (the oracle).
        ``"batched"``    — decision-block driver (see module docstring);
        exact-parity with the oracle for every policy, much faster (PoT
        runs the speculative commit, Prequal the scheduler-parallel
        segment scan).
    use_kernel:
        batched mode only — route the dodoor/(1+β) decision through the
        fused sample→score→select sparse-gather Pallas megakernel
        (``repro.kernels.dodoor_choice.dodoor_fused_sparse``) instead of
        the two-stage jnp path; ``cfg.block_t``/``cfg.interpret`` control
        the tile size and interpret-vs-compiled lowering (``None`` =
        auto-detect: compiled on TPU only).  The default ``"auto"``
        selects the kernel exactly where its lowering compiles (see
        :func:`resolve_use_kernel`) — two-stage off-accelerator, kernel on
        TPU; pass True/False to force a path.
    dynamics:
        optional :class:`Dynamics` spec — per-server outage/churn
        timelines, straggler windows, data-store outage windows (see the
        scenario engine, ``repro.sim.scenarios``).  Exact in both modes
        and on the kernel path: ``use_kernel=True`` routes the down-window
        availability plane into the megakernel's masked-sampling prefilter
        (draw-for-draw identical to the two-stage masked path).  A
        ``cache_faults`` spec switches the cached-view policies onto
        per-scheduler (possibly loss-degraded) views — this forces the
        two-stage path (the megakernel reads only the shared view).

    Failure semantics: with ``cfg.retry`` set, killed/rejected tasks ride
    the re-entry wave loop (:func:`_simulate_with_retries`) and the result
    carries ``attempts``/``failed``/``wasted_ms``; with ``retry=None``
    results are bit-identical to the pre-failure-layer engine.

    dag:
        optional task graph — a spec from ``repro.workloads.dags`` (or a
        prebuilt :class:`~repro.workloads.dags.DagPlan`).  Tasks then run
        through the frontier loop (:func:`_simulate_dag`): a task becomes
        submittable at ``max(trace submit, max_p(finish[p] +
        edge_delay))``, and the result's ``submit_ms`` holds those
        *effective* submit times.  An edgeless DAG falls through to the
        independent-task path and is bit-identical to ``dag=None``.
        ``cfg.locality`` (a :class:`LocalityModel`) requires a dag — it
        charges Algorithm 1 for each candidate's remote parent bytes —
        and ``gamma = 0`` is bit-identical to no LocalityModel at all.
        DAGs do not yet compose with ``cfg.retry`` (both own the
        host-side wave loop) — that combination raises.

    ``workload`` and ``cluster`` are cached on device by object identity
    (they are frozen dataclasses): do not mutate their arrays in place
    between calls — derive a new object with ``dataclasses.replace``.
    """
    if mode not in ("sequential", "batched"):
        raise ValueError(f"unknown mode {mode!r}")
    use_kernel = resolve_use_kernel(use_kernel, cfg.interpret)
    _validate_config(cfg)
    if dynamics is not None and not isinstance(dynamics, Dynamics):
        raise TypeError(f"dynamics must be a Dynamics spec, got "
                        f"{type(dynamics).__name__}")
    plan = None
    if dag is not None:
        from ..workloads.dags import dag_plan
        plan = dag_plan(dag, workload.r_submit.shape[0])
        if cfg.retry is not None:
            raise NotImplementedError(
                "dag together with a RetryPolicy: both own the host-side "
                "wave loop — run task-graph workloads without retries, or "
                "retries without a dag.")
    elif cfg.locality is not None:
        raise ValueError(
            "EngineConfig.locality needs a dag: the penalty reads parent "
            "placements, which only task-graph workloads carry.")
    if cfg.outage_ms:
        warnings.warn(
            "EngineConfig.outage_ms is deprecated — use "
            "Dynamics(store_outages=((t0, t1),)); simulate() routes the "
            "scalar window through the store-outage timeline "
            "(bit-identical suppression arithmetic).",
            DeprecationWarning, stacklevel=2)
        legacy = Dynamics(store_outages=(
            (float(cfg.outage_ms[0]), float(cfg.outage_ms[1])),))
        dynamics = legacy if dynamics is None else dynamics.merge(legacy)
        cfg = cfg._replace(outage_ms=())
    faulted = dynamics is not None and dynamics.cache_faults is not None
    if faulted:
        # Per-scheduler degraded views need the two-stage gather path;
        # the fused megakernel only reads the shared store view.
        use_kernel = False
    masked = (use_kernel and dynamics is not None
              and dynamics.has_down_windows)
    if plan is not None and plan.num_edges:
        return _simulate_dag(workload, cluster, cfg, seed, mode, use_kernel,
                             dynamics, masked, faulted, plan)
    if cfg.retry is not None:
        return _simulate_with_retries(workload, cluster, cfg, seed, mode,
                                      use_kernel, dynamics, masked, faulted)
    n = cluster.num_servers
    C, node_type, cores_per, mem_unit = _cluster_arrays(cluster,
                                                        cfg.mem_units)
    dyn = _make_dyn(cfg)
    win = _lower_dynamics(dynamics, n)

    m = workload.r_submit.shape[0]
    batched = mode == "batched"
    if batched:
        b = cfg.b
        nb = -(-m // b)
        xs = _blocked_inputs(workload, b)
        msgs, outs = _simulate_batched_jax(
            xs, C, node_type, mem_unit, cores_per, dyn, _make_dyn_ints(cfg),
            win, _static_cfg(cfg, for_kernel=use_kernel, keep_b=True), n,
            cluster.num_types, seed, use_kernel, masked,
            cache_faulted=faulted)
        outs = tuple(np.asarray(o).reshape(nb * b, *o.shape[2:])[:m]
                     for o in outs)
    else:
        def build_seq():
            ids = jnp.arange(m, dtype=jnp.int32)
            return (
                ids,
                jnp.asarray(workload.r_submit),
                jnp.asarray(workload.r_exec),
                jnp.asarray(workload.d_est),
                jnp.asarray(workload.d_act),
                jnp.asarray(workload.submit_ms),
                ids,                                       # task ids
            )

        xs = _conv_cached(("seq", id(workload)), workload, build_seq)
        msgs, outs = _simulate_jax(xs, C, node_type, mem_unit, cores_per,
                                   dyn, _make_dyn_ints(cfg), win,
                                   _static_cfg(cfg), n,
                                   cluster.num_types, seed,
                                   cache_faulted=faulted)
        outs = tuple(np.asarray(o) for o in outs)
    msgs = np.asarray(msgs)
    j, start, finish, enq, sched_ms, cores, mem_mb = outs[:7]
    trace_kw = {}
    if cfg.trace:
        age, vr0, vr1, c0, c1, u2, pushf = outs[7:14]
        submit = np.asarray(workload.submit_ms, np.float32)
        verr, misp = finish_trace(
            j=j, finish=finish, cores=cores, mem=mem_mb, now=submit,
            v_rif=(vr0, vr1), cand=(c0, c1), use_two=u2,
            r_sub=np.asarray(workload.r_submit),
            d_est=np.asarray(workload.d_est),
            node_type=np.asarray(node_type), C=np.asarray(C),
            alpha=cfg.alpha, policy=cfg.policy, R=cfg.rbuf_slots)
        trace_kw = {
            "view_age_ms": age, "view_err": verr, "misplaced": misp,
            "cache_push": pushf > 0.5,
            "sched_id": (np.arange(m) % cfg.num_schedulers).astype(np.int32),
            "decision_ms": submit,
        }
    return SimResult(
        server=j.astype(np.int32),
        submit_ms=np.asarray(workload.submit_ms),
        enqueue_ms=enq, start_ms=start, finish_ms=finish, sched_ms=sched_ms,
        cores=cores, mem_mb=mem_mb,
        msgs_base=int(msgs[0]), msgs_probe=int(msgs[1]),
        msgs_push=int(msgs[2]), msgs_flush=int(msgs[3]),
        policy=cfg.policy, **trace_kw,
    )
