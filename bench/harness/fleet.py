"""Fleet synthesis from a configuration's ``fleet`` block.

A copy of the program's fleet arithmetic (the paper's Table-2 testbed and
its scaled generalisation), kept here so that the yardstick does not move
when the program does.  A fleet is a list of node types, each with cores,
memory and a count; servers are laid out type by type and then shuffled by
a fixed permutation so that uniform candidate draws are not correlated with
type blocks.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Fleet(NamedTuple):
    C: np.ndarray          # [n, 2] float32 capacities (cores, MB)
    node_type: np.ndarray  # [n] int32 index into type_names
    type_names: tuple

    @property
    def n(self) -> int:
        return int(self.C.shape[0])

    @property
    def cores(self) -> float:
        return float(self.C[:, 0].sum())


def dhondt(weights, n: int) -> np.ndarray:
    """Highest-averages seat allocation of ``n`` servers over ``weights``."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    counts = np.zeros(len(w), np.int64)
    for _ in range(n):
        counts[np.argmax(w / (counts + 1))] += 1
    return counts


def build(spec: dict) -> Fleet:
    """``spec``: ``{"types": [{"name", "cores", "mem_mb", "count"}...],
    "servers": n (optional), "shuffle_seed": s}``.  With ``servers`` the
    type counts are the D'Hondt allocation of ``n`` over the listed counts
    (the testbed's mix scaled); without it the counts are used as given."""
    types = spec["types"]
    if "servers" in spec:
        counts = dhondt([t["count"] for t in types], int(spec["servers"]))
    else:
        counts = np.array([t["count"] for t in types], np.int64)
    caps = np.array([[t["cores"], t["mem_mb"]] for t in types], np.float32)
    node_type = np.repeat(np.arange(len(types), dtype=np.int32), counts)
    C = caps[node_type]
    perm = np.random.RandomState(int(spec["shuffle_seed"])).permutation(
        node_type.shape[0])
    return Fleet(C=np.ascontiguousarray(C[perm]),
                 node_type=np.ascontiguousarray(node_type[perm]),
                 type_names=tuple(t["name"] for t in types))
