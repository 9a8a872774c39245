"""The FunctionBench task mix of the paper's §6.3 (Tables 3 and 4).

A copy of the program's generator arithmetic, kept with the benchmark so
that the yardstick does not move when the program does.  Eight serverless
functions, drawn uniformly; each has per-node-type cores, memory (MB) and a
profiled duration (ms).  The scheduler sees the profile; the task runs for
the profile times a lognormal(0, sigma) factor, sigma being the
configuration's ``tasks.duration_noise_sigma``.
"""
from __future__ import annotations

import numpy as np

from harness.tasks import Tasks

# Table 4: {task: {node_type: (cores, mem_mb, time_ms)}}
TABLE4 = {
    "float_op": {"c6525-25g": (1, 8, 219), "c6620": (2, 8, 275),
                 "m510": (2, 8, 349), "xl170": (2, 8, 239)},
    "pyaes": {"c6525-25g": (1, 9, 222), "c6620": (2, 11, 288),
              "m510": (2, 11, 362), "xl170": (1, 11, 251)},
    "linpack": {"c6525-25g": (8, 29, 372), "c6620": (14, 34, 504),
                "m510": (4, 35, 595), "xl170": (5, 31, 431)},
    "matmul": {"c6525-25g": (8, 41, 456), "c6620": (14, 38, 547),
               "m510": (4, 39, 699), "xl170": (5, 37, 473)},
    "chameleon": {"c6525-25g": (2, 38, 585), "c6620": (2, 37, 569),
                  "m510": (2, 38, 966), "xl170": (2, 38, 612)},
    "rnn_name_gen": {"c6525-25g": (8, 468, 2084), "c6620": (14, 470, 1738),
                     "m510": (4, 468, 3132), "xl170": (5, 467, 2068)},
    "lr_predict": {"c6525-25g": (8, 210, 2937), "c6620": (14, 209, 2462),
                   "m510": (4, 210, 4341), "xl170": (5, 210, 3144)},
    "lr_train": {"c6525-25g": (8, 212, 4744), "c6620": (14, 213, 3532),
                 "m510": (4, 212, 16201), "xl170": (5, 212, 7852)},
}
TASKS = tuple(TABLE4)


def profiles(type_names) -> tuple[np.ndarray, np.ndarray]:
    """(res [tasks, T, 2], dur [tasks, T]) with node types in the fleet's
    order."""
    res = np.zeros((len(TASKS), len(type_names), 2), np.float32)
    dur = np.zeros((len(TASKS), len(type_names)), np.float32)
    for i, task in enumerate(TASKS):
        for j, nt in enumerate(type_names):
            cores, mem, ms = TABLE4[task][nt]
            res[i, j] = (cores, mem)
            dur[i, j] = ms
    return res, dur


def core_seconds_per_task(fleet, params: dict) -> float:
    """Mean core-seconds a task of the uniform mix holds, over the fleet:
    each node type's mean of cores x seconds, weighted by that type's share
    of the fleet's cores."""
    res, dur = profiles(fleet.type_names)
    per_type = (res[:, :, 0] * dur / 1e3).mean(axis=0)         # [T]
    share = np.array([fleet.C[fleet.node_type == t, 0].sum()
                      for t in range(len(fleet.type_names))]) / fleet.cores
    return float(per_type @ share)


def draw(rng: np.random.Generator, fleet, m: int, params: dict,
         submit_ms: np.ndarray) -> Tasks:
    """``m`` tasks of the uniform mix with the given virtual arrivals."""
    res, dur = profiles(fleet.type_names)
    sigma = float(params["duration_noise_sigma"])
    kind = rng.integers(0, len(TASKS), size=m)
    noise = np.exp(rng.normal(0.0, sigma, size=(m, 1))).astype(np.float32)
    d_est = dur[kind]
    r_exec = res[kind]
    return Tasks(r_submit=r_exec.mean(axis=1, dtype=np.float32),
                 r_exec=r_exec, d_est=d_est, d_act=d_est * noise,
                 submit_ms=np.asarray(submit_ms, np.float32))
