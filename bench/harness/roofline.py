"""Device peaks and the sparse kernel's compulsory bytes.

The peaks live in ``bench/peaks.json``, keyed by JAX's ``device_kind``,
each with its source; a device that is not there is an error, never a
default.

``sparse_kernel_bytes`` counts what one call of the program's sparse
two-choice kernel must move between HBM and the core, whatever its tiling:
each task row's inputs (a two-word PRNG key, the K demands, the per-type
duration row) and outputs (the choice, two candidates, two scores), and
one read of the transposed server table ``[8, NP]`` (L, D, 1/sum C^2, C,
node type, padded to 8 rows and NP = n rounded up to 128 lanes).  The
table's block index is constant over the grid, so it is read once per call
and not once per tile of rows.  The kernel's work is element work on the
vector unit over ``[tile, NP]`` feasibility planes; no published peak
covers that, so its roofline is bound by bytes alone.
"""
from __future__ import annotations

import json
import os

LANES = 128
TABLE_ROWS = 8


class UnknownDevice(LookupError):
    pass


def peaks(path: str, device_kind: str) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in {os.path.basename(path)}"
            f" (known: {sorted(table)})")
    return table[device_kind]


def sparse_kernel_bytes(tasks: int, n: int, k: int = 2,
                        node_types: int = 4) -> int:
    """Compulsory HBM bytes of one call over ``tasks`` decisions and ``n``
    servers (4-byte words throughout)."""
    n_pad = -(-n // LANES) * LANES
    row_in = 2 + k + node_types        # key words, demands, duration row
    row_out = 1 + 2 + 2                # choice, candidates, scores
    return 4 * (tasks * (row_in + row_out) + TABLE_ROWS * n_pad)


def roofline_pct(bytes_moved: int, seconds: float, hbm_bytes_per_s: float
                 ) -> float:
    """Least time the bytes need at peak bandwidth, over the time taken."""
    return 100.0 * bytes_moved / hbm_bytes_per_s / seconds
