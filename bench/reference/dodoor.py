"""Plain reference of the ``dodoor`` policy on a FunctionBench fleet.

Written from the paper's Algorithm 1 and the semantics the configuration
files state, in plain ``jax.numpy``; it imports nothing of the program.
One decision at a time, in arrival order:

1. Prefilter: a server is a candidate when its capacity covers the task's
   declared demand in both dimensions (all servers when none does).
2. Two candidates, each the ``rank``-th feasible server with ``rank =
   min(floor(u * k), k - 1) + 1`` for the two uniforms
   ``jax.random.uniform(split(fold_in(PRNGKey(seed), i))[0], (2,))``.
3. Load score from the scheduler's cached view (Eq. 1 and LOADSCORE):
   ``RL = r.L / sum(C^2)``, then ``(1-a) RL/(RL_A+RL_B) + a (D+d)/(sum)``;
   the lower score wins, the first candidate on a tie.
4. Commit, first come first served on the chosen server: the RPC channel
   charges ``chan_ms (1 + rif / cores)`` and the placement hop; the task
   starts when its enqueue time, the server's previous start, its c-th
   earliest free core and its u-th earliest free memory unit allow; it runs
   its actual duration stretched by ``1 + interference * busy share``; the
   server's in-flight list keeps (release, cores, MB, profiled duration) in
   the slot that frees first.
5. Ledger: two messages per decision; each of the S schedulers (decision
   ``i`` belongs to ``i mod S``) flushes its unreported load every
   ``flush_every`` of its own decisions (one message); every ``b``-th
   decision the store pushes truth minus the unflushed load to all S
   schedulers (S messages), and decisions that arrive before the push has
   been applied wait for it.

Since the cached view changes only at a push, the candidates and scores of
the ``b`` decisions between two pushes are computed together; the commits
and the ledger run one decision after another.  ``dtype`` is the
precision of step 3: ``float32`` is what the configuration states, and
``bfloat16`` is the control that must fail the comparison.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-9
TASKS_PER_CALL = 4096   # decisions one compiled call of the reference makes


class State(NamedTuple):
    core_free: jnp.ndarray   # [n, W] free-at time per core (+inf past cores)
    mem_free: jnp.ndarray    # [n, MU] free-at time per memory unit
    prev_start: jnp.ndarray  # [n]
    rb_rel: jnp.ndarray      # [n, R] in-flight list: release time
    rb_cpu: jnp.ndarray      # [n, R] cores
    rb_mem: jnp.ndarray      # [n, R] MB
    rb_dur: jnp.ndarray      # [n, R] profiled duration
    chan_free: jnp.ndarray   # [n]
    view_L: jnp.ndarray      # [n, 2] the schedulers' cached view
    view_D: jnp.ndarray      # [n]
    pending: jnp.ndarray     # [S, n, 4] unflushed (cores, MB, dur, count)
    push_end: jnp.ndarray    # [] time the last push finishes applying
    msgs: jnp.ndarray        # [4] base, probe, push, flush


def _init(C, S: int, R: int, MU: int) -> State:
    n = C.shape[0]
    W = int(np.max(np.asarray(C[:, 0])))
    cores = C[:, 0]
    z = jnp.zeros((n,), jnp.float32)
    return State(
        core_free=jnp.where(jnp.arange(W)[None, :] < cores[:, None], 0.0,
                            jnp.inf).astype(jnp.float32),
        mem_free=jnp.zeros((n, MU), jnp.float32), prev_start=z,
        rb_rel=jnp.zeros((n, R), jnp.float32),
        rb_cpu=jnp.zeros((n, R), jnp.float32),
        rb_mem=jnp.zeros((n, R), jnp.float32),
        rb_dur=jnp.zeros((n, R), jnp.float32), chan_free=z,
        view_L=jnp.zeros((n, 2), jnp.float32), view_D=z,
        pending=jnp.zeros((S, n, 4), jnp.float32),
        push_end=jnp.zeros((), jnp.float32),
        msgs=jnp.zeros((4,), jnp.int32))


def _candidates(base_key, ids, r_sub, C):
    """Step 1 and 2 for a set of decisions: [b, 2] server indices."""
    n = C.shape[0]
    keys = jax.vmap(lambda i: jax.random.split(
        jax.random.fold_in(base_key, i))[0])(ids)
    u = jax.vmap(lambda k: jax.random.uniform(k, (2,)))(keys)     # [b, 2]
    feasible = jnp.all(r_sub[:, None, :] <= C[None, :, :], axis=-1)
    count = jnp.cumsum(feasible.astype(jnp.int32), axis=1)        # [b, n]
    k = count[:, -1]
    none = k == 0
    count = jnp.where(none[:, None], jnp.arange(1, n + 1)[None, :], count)
    k = jnp.where(none, n, k)
    rank = jnp.minimum((u * k[:, None].astype(jnp.float32)).astype(jnp.int32),
                       k[:, None] - 1) + 1                        # [b, 2]
    # The rank-th feasible server: the first whose running count reaches it.
    return jnp.argmax(count[:, None, :] >= rank[:, :, None],
                      axis=-1).astype(jnp.int32)


def _choose(r_sub, cand, d_cand, view_L, view_D, C, alpha, dtype):
    """Step 3 in ``dtype``: the winning candidate of each decision."""
    r = r_sub.astype(dtype)[:, None, :]                           # [b, 1, 2]
    L = view_L[cand].astype(dtype)                                # [b, 2, 2]
    Cc = C[cand].astype(dtype)
    D = (view_D[cand] + d_cand).astype(dtype)                     # [b, 2]
    eps = jnp.asarray(EPS, dtype)
    a = jnp.asarray(alpha, dtype)
    rl = jnp.sum(r * L, axis=-1) / jnp.sum(Cc * Cc, axis=-1)      # [b, 2]
    rl_sum = rl[:, 0] + rl[:, 1]
    d_sum = D[:, 0] + D[:, 1]
    half = jnp.asarray(0.5, dtype)
    rl_frac = jnp.where(rl_sum[:, None] > eps, rl / (rl_sum[:, None] + eps),
                        half)
    d_frac = jnp.where(d_sum[:, None] > eps, D / (d_sum[:, None] + eps), half)
    score = rl_frac * (1 - a) + d_frac * a
    return jnp.where(score[:, 0] > score[:, 1], cand[:, 1], cand[:, 0])


def _commit(p, C, node_type, st: State, task):
    """Step 4 and the per-decision part of step 5 for one decision."""
    (i, j, valid, now, extra, r_exec, d_est, d_act) = task
    S = st.pending.shape[0]
    MU = st.mem_free.shape[1]
    nt = node_type[j]
    cores_j = C[j, 0]
    cores, mem = r_exec[nt, 0], r_exec[nt, 1]
    dur_est, dur_act = d_est[nt], d_act[nt]

    rel = st.rb_rel[j]
    rif = jnp.sum((rel > now).astype(jnp.float32))
    occupancy = p["chan_ms"] * (1.0 + rif / cores_j)
    chan_wait = jnp.maximum(0.0, st.chan_free[j] - now)
    sched = p["compute_ms"] + extra + chan_wait + occupancy + p["hop_ms"]
    enqueue = now + sched

    c_need = jnp.clip(cores, 1, cores_j).astype(jnp.int32)
    u_need = jnp.clip(jnp.ceil(mem / (C[j, 1] / MU)), 1, MU).astype(jnp.int32)
    cf, mf = st.core_free[j], st.mem_free[j]
    start = jnp.maximum(jnp.maximum(enqueue, st.prev_start[j]),
                        jnp.maximum(jnp.sort(cf)[c_need - 1],
                                    jnp.sort(mf)[u_need - 1]))
    real = jnp.arange(cf.shape[0]) < cores_j
    busy = jnp.sum((cf > start) & real).astype(jnp.float32)
    stretch = 1.0 + p["interference"] * jnp.clip(busy / cores_j, 0.0, 1.0)
    # The stretched duration is a quantity of its own, rounded to f32
    # before it is added (the select keeps a compiler from fusing the
    # multiply into the add, whose single rounding the model does not have).
    finish = start + jnp.where(valid, dur_act * stretch, 0.0)
    # The c (u) earliest-free units are taken until the task finishes.
    cf_new = jnp.where(jnp.argsort(jnp.argsort(cf)) < c_need, finish, cf)
    mf_new = jnp.where(jnp.argsort(jnp.argsort(mf)) < u_need, finish, mf)
    slot = jnp.argmin(rel)

    def put(a, row):
        return a.at[j].set(jnp.where(valid, row, a[j]))

    sched_id = i % S
    pend = st.pending.at[sched_id, j].add(
        jnp.where(valid, jnp.stack([cores, mem, dur_est, 1.0]), 0.0))
    flush = valid & (((i // S) + 1) % p["flush_every"] == 0)
    pend = jnp.where(flush, pend.at[sched_id].set(0.0), pend)
    msgs = st.msgs + jnp.where(valid, 1, 0) * jnp.array([2, 0, 0, 0]) \
        + jnp.where(flush, 1, 0) * jnp.array([0, 0, 0, 1])
    st = st._replace(
        core_free=put(st.core_free, cf_new), mem_free=put(st.mem_free, mf_new),
        prev_start=put(st.prev_start, start),
        rb_rel=put(st.rb_rel, rel.at[slot].set(finish)),
        rb_cpu=put(st.rb_cpu, st.rb_cpu[j].at[slot].set(cores)),
        rb_mem=put(st.rb_mem, st.rb_mem[j].at[slot].set(mem)),
        rb_dur=put(st.rb_dur, st.rb_dur[j].at[slot].set(dur_est)),
        chan_free=put(st.chan_free,
                      jnp.maximum(st.chan_free[j], now) + occupancy),
        pending=pend, msgs=msgs.astype(jnp.int32))
    return st, (start, finish)


def _push(p, st: State, now) -> State:
    """The store's push: truth at ``now`` minus what is not yet flushed."""
    live = (st.rb_rel > now).astype(jnp.float32)
    truth = jnp.stack([jnp.sum(st.rb_cpu * live, -1),
                       jnp.sum(st.rb_mem * live, -1),
                       jnp.sum(st.rb_dur * live, -1)], axis=-1)   # [n, 3]
    unflushed = jnp.sum(st.pending, axis=0)[:, :3]
    view = jnp.maximum(0.0, truth - unflushed)
    S = st.pending.shape[0]
    return st._replace(view_L=view[:, :2], view_D=view[:, 2],
                       push_end=now + p["push_block_ms"],
                       msgs=st.msgs.at[2].add(S))


@partial(jax.jit, static_argnames=("b", "dtype"))
def _blocks(p, C, node_type, base_key, st, xs, *, b: int, dtype):
    def block(st, blk):
        ids, r_sub, r_exec, d_est, d_act, submit, valid = blk
        cand = _candidates(base_key, ids, r_sub, C)
        tt = jnp.arange(b)
        d_cand = d_est[tt[:, None], node_type[cand]]
        j = _choose(r_sub, cand, d_cand, st.view_L, st.view_D, C,
                    p["alpha"], dtype)
        extra = jnp.maximum(0.0, st.push_end - submit)
        st, (start, finish) = jax.lax.scan(
            partial(_commit, p, C, node_type), st,
            (ids, j, valid, submit, extra, r_exec, d_est, d_act))
        last = jnp.max(jnp.where(valid, ids, -1))
        st = jax.lax.cond(valid[-1] & ((last + 1) % b == 0),
                          lambda s: _push(p, s, submit[-1]), lambda s: s, st)
        return st, (j, start, finish)

    return jax.lax.scan(block, st, xs)


def simulate(fleet, policy: dict, tasks, seed: int, *,
             dtype=jnp.float32) -> dict:
    """Place every task of ``tasks`` (a FunctionBench ``Tasks`` stream, in
    arrival order) on ``fleet`` under ``policy`` (the configuration's
    ``policy`` block).  Returns numpy ``server``, ``start_ms``,
    ``finish_ms`` per decision and the four message counters ``msgs``."""
    b = int(policy["b"])
    S = int(policy["num_schedulers"])
    m = len(tasks)
    G = max(1, TASKS_PER_CALL // b)
    nb = -(-m // b)
    calls = -(-nb // G)
    pad = calls * G * b - m
    p = {k: jnp.float32(policy[k]) for k in
         ("alpha", "interference", "hop_ms", "chan_ms", "push_block_ms",
          "compute_ms")}
    p["flush_every"] = jnp.int32(policy["flush_every"])
    C = jnp.asarray(fleet.C)
    node_type = jnp.asarray(fleet.node_type)
    st = _init(C, S, int(policy["rbuf_slots"]), int(policy["mem_units"]))
    base_key = jax.random.PRNGKey(seed)

    def shaped(a):
        a = np.pad(np.asarray(a), ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                   mode="edge")
        return a.reshape((calls, G, b) + a.shape[1:])

    ids = np.arange(m + pad, dtype=np.int32).reshape(calls, G, b)
    valid = (np.arange(m + pad) < m).reshape(calls, G, b)
    cols = [shaped(a) for a in (tasks.r_submit, tasks.r_exec, tasks.d_est,
                                tasks.d_act, tasks.submit_ms)]
    outs = []
    for c in range(calls):
        xs = (ids[c], *(a[c] for a in cols), valid[c])
        st, out = _blocks(p, C, node_type, base_key, st, xs, b=b, dtype=dtype)
        outs.append(out)
    j, start, finish = (np.concatenate([np.asarray(o[k]).reshape(-1)
                                        for o in outs])[:m]
                        for k in range(3))
    return {"server": j.astype(np.int32), "start_ms": start,
            "finish_ms": finish, "msgs": np.asarray(st.msgs)}
