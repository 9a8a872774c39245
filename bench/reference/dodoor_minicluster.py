"""Plain reference of the ``dodoor`` policy over the §4.2 mini-clusters.

It imports nothing of the program.  The fleet and the task stream are split
as the configuration's guarantees say: server ``j`` belongs to mini-cluster
``j mod k`` and task ``i`` (in arrival order) to mini-cluster ``i mod k``.
Each mini-cluster is then a fleet of its own: ``bench/reference/dodoor.py``
(its file unedited, its scoring step as below) places its tasks in their
order, with the block ``b / k``, the seed ``seed + c`` and its own
schedulers, store and ledger.  The answers are merged back into arrival
order with fleet-wide server ids, and the ``k`` ledgers are summed.

The mini-clusters share nothing, so they run side by side: mini-cluster
``c`` on device ``c mod d`` of the ``d`` devices, each from a thread of its
own.  ``dtype`` goes to every mini-cluster: ``float32`` is what the
configuration states, ``bfloat16`` the control that must fail.

One step differs from ``dodoor.py`` as XLA compiles it: the load score.
LOADSCORE takes ``RL_A / (RL_A + RL_B)`` of each candidate's RL, itself
rounded to ``dtype``; compiled as written, XLA folds the two divisions
into ``r·L_A / (ΣC_A² (RL_A + RL_B))``, one rounding fewer.  Azure VMs
load both dimensions in proportion (7,000 MB a core), so two candidates'
scores tie exactly often enough that the fold flips a few choices of a
window.  :func:`_choose` keeps each RL as a value of its own; the instance
of ``dodoor.py`` loaded here scores with it, and the file is unedited.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from harness import named

DODOOR = named.module(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "dodoor.py"))


def _choose(r_sub, cand, d_cand, view_L, view_D, C, alpha, dtype):
    """``dodoor.py``'s step 3, each candidate's RL rounded to ``dtype``
    before its fraction is taken (the barrier keeps XLA from folding
    ``(r·L / ΣC²) / (RL_A + RL_B)`` into one division)."""
    r = r_sub.astype(dtype)[:, None, :]                           # [b, 1, 2]
    L = view_L[cand].astype(dtype)                                # [b, 2, 2]
    Cc = C[cand].astype(dtype)
    D = (view_D[cand] + d_cand).astype(dtype)                     # [b, 2]
    eps = jnp.asarray(DODOOR.EPS, dtype)
    a = jnp.asarray(alpha, dtype)
    rl = jax.lax.optimization_barrier(
        jnp.sum(r * L, axis=-1) / jnp.sum(Cc * Cc, axis=-1))      # [b, 2]
    rl_sum = rl[:, 0] + rl[:, 1]
    d_sum = D[:, 0] + D[:, 1]
    half = jnp.asarray(0.5, dtype)
    rl_frac = jnp.where(rl_sum[:, None] > eps, rl / (rl_sum[:, None] + eps),
                        half)
    d_frac = jnp.where(d_sum[:, None] > eps, D / (d_sum[:, None] + eps), half)
    score = rl_frac * (1 - a) + d_frac * a
    return jnp.where(score[:, 0] > score[:, 1], cand[:, 1], cand[:, 0])


DODOOR._choose = _choose     # this module's own instance of dodoor.py only


def simulate(fleet, policy: dict, tasks, seed: int, *, mini_clusters: int,
             dtype=jnp.float32) -> dict:
    """Place every task of ``tasks`` on ``fleet`` split into
    ``mini_clusters`` parts under ``policy`` (``b`` fleet-wide).  Returns
    numpy ``server``, ``start_ms``, ``finish_ms`` per decision and the
    four message counters ``msgs``, summed over the parts."""
    k = int(mini_clusters)
    n, m = fleet.n, len(tasks)
    part_policy = dict(policy, b=int(policy["b"]) // k)
    devices = jax.devices()

    def part(c: int):
        servers = np.arange(c, n, k)
        mine = np.arange(c, m, k)
        if not len(mine):
            return None
        own_fleet = fleet._replace(C=fleet.C[servers],
                                   node_type=fleet.node_type[servers])
        own_tasks = type(tasks)(*(a[mine] for a in tasks))
        with jax.default_device(devices[c % len(devices)]):
            return servers, mine, DODOOR.simulate(
                own_fleet, part_policy, own_tasks, seed + c, dtype=dtype)

    with ThreadPoolExecutor(max_workers=k) as pool:
        parts = [f.result() for f in [pool.submit(part, c)
                                      for c in range(k)]]
    out = {"server": np.zeros(m, np.int32),
           "start_ms": np.zeros(m, np.float32),
           "finish_ms": np.zeros(m, np.float32),
           "msgs": np.zeros(4, np.int64)}
    for got in filter(None, parts):
        servers, mine, res = got
        out["server"][mine] = servers[res["server"]]
        out["start_ms"][mine] = res["start_ms"]
        out["finish_ms"][mine] = res["finish_ms"]
        out["msgs"] += res["msgs"]
    return out
