"""The system under test: the program's served path, and nothing else of it.

The benchmark drives ``repro.serve.DecisionService`` through its public
calls (``submit``, ``step``, ``flush``, ``result``) and reads one counter of
it, ``compiles``.  This module is the only one that imports the program.

How the service is built is the configuration's: its ``policy`` block sets
every knob of ``EngineConfig``, and its optional ``service`` block gives
keywords that go to ``DecisionService`` as they stand, wherever the
harness builds one (the same keywords go to the configuration's plain
reference).  A configuration that needs another keyword names it in its
own file; no file here changes.  An unknown keyword fails at set-up with
the program's own ``TypeError``.
"""
from __future__ import annotations

import numpy as np

from repro.serve import DecisionService
from repro.sim import ClusterSpec, EngineConfig, RpcModel


def engine_config(policy: dict) -> EngineConfig:
    """The program's configuration, every knob set from the cell's
    configuration file (none left to the program's defaults)."""
    return EngineConfig(
        policy=policy["name"], num_schedulers=int(policy["num_schedulers"]),
        b=int(policy["b"]), flush_every=int(policy["flush_every"]),
        alpha=float(policy["alpha"]), rbuf_slots=int(policy["rbuf_slots"]),
        mem_units=int(policy["mem_units"]),
        interference=float(policy["interference"]),
        rpc=RpcModel(hop_ms=float(policy["hop_ms"]),
                     chan_ms=float(policy["chan_ms"]),
                     push_block_ms=float(policy["push_block_ms"]),
                     compute_ms=float(policy["compute_ms"])))


def cluster(fleet) -> ClusterSpec:
    return ClusterSpec(C=fleet.C, node_type=fleet.node_type,
                       type_names=fleet.type_names)


def service(fleet, policy: dict, seed: int, capacity: int,
            keywords: dict | None = None) -> DecisionService:
    """A fresh service with the configuration's ``service`` keywords;
    without them, on the served path's defaults (kernel choice and snapshot
    publishing as the program decides them)."""
    return DecisionService(cluster(fleet), engine_config(policy), seed=seed,
                           capacity=max(int(capacity), int(policy["b"])),
                           **(keywords or {}))


def submit(svc: DecisionService, tasks) -> int:
    return svc.submit(tasks.r_submit, tasks.r_exec, tasks.d_est,
                      tasks.d_act, tasks.submit_ms)


def warm_up(fleet, policy: dict, tasks, keywords: dict | None = None) -> int:
    """Compile and run every program the window will use on a throwaway
    service built as the window's is: one full block through ``step`` and
    a ragged tail through ``flush``.  Returns the service's
    compiled-program count."""
    b = int(policy["b"])
    svc = service(fleet, policy, seed=0, capacity=2 * b, keywords=keywords)
    submit(svc, tasks.rows(0, b + b // 2))
    svc.step()
    svc.flush()
    svc.result()
    return svc.compiles


def placements(svc: DecisionService) -> dict:
    """What the timed path produced: each decision's server and start time,
    and the four message counters."""
    res = svc.result()
    return {"server": np.asarray(res.server), "start_ms": np.asarray(
        res.start_ms), "msgs": np.array([res.msgs_base, res.msgs_probe,
                                         res.msgs_push, res.msgs_flush])}
