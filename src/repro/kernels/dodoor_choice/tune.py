"""`block_t` tile autotuning for the fused Dodoor kernels.

The Pallas megakernels grid a decision batch into tiles of ``block_t``
rows.  The right tile is shape- and backend-dependent: big tiles
amortize the server-table broadcast, small tiles avoid padding waste on
partial blocks and keep interpret-mode trip counts short.  Rather than
hard-code one number, :func:`autotune_block_t` sweeps candidate tiles at
a given batch shape and returns the measured curve plus the winner — the
benchmark harness runs it at the CI gate point and persists the result
into ``BENCH_engine.json`` so tile regressions are visible across PRs.

Timing is min-of-reps wall clock after a warmup call (same discipline as
``benchmarks/bench_kernels._best_of``): the minimum is robust to
scheduler noise on shared CI boxes, and the warmup keeps compile time
out of the measurement.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import LANES
from .ops import _clamp_block, _round_up, dodoor_fused_sparse

DEFAULT_CANDIDATES = (64, 128, 256, 512)


def _sweep_inputs(T: int, N: int, TT: int, seed: int):
    """Random but fixed-seed operands at the sweep shape, mirroring the
    engine's factorized duration model (d_types [T, TT] + node_type [N])."""
    rng = np.random.RandomState(seed)
    r = jnp.asarray(rng.rand(T, 2).astype(np.float32) * 8)
    d_types = jnp.asarray(rng.rand(T, TT).astype(np.float32) * 1000)
    node_type = jnp.asarray(rng.randint(0, TT, N).astype(np.int32))
    L = jnp.asarray(rng.rand(N, 2).astype(np.float32) * 50)
    D = jnp.asarray(rng.rand(N).astype(np.float32) * 5000)
    C = jnp.asarray(8.0 + rng.rand(N, 2).astype(np.float32) * 100)
    base = jax.random.PRNGKey(seed)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(T))
    return keys, r, d_types, node_type, L, D, C


def autotune_block_t(T: int, N: int, *, TT: int = 4,
                     candidates=DEFAULT_CANDIDATES, reps: int = 3,
                     seed: int = 0, interpret: bool | None = None) -> dict:
    """Time :func:`dodoor_fused_sparse` at batch shape ``[T, N]`` across
    ``block_t`` candidates and pick the fastest.

    Candidates that clamp to the same effective tile (small ``T`` caps
    the tile at the padded batch size) are timed once and reported once,
    so a smoke-sized sweep doesn't re-run identical programs.

    Returns ``{"T", "N", "TT", "best_block_t", "best_ms", "curve"}``
    where ``curve`` is a list of ``{"block_t", "effective_block_t",
    "ms"}`` rows sorted by candidate tile — the shape persisted under
    ``block_t_autotune`` in ``BENCH_engine.json``.
    """
    keys, r, d_types, node_type, L, D, C = _sweep_inputs(T, N, TT, seed)

    curve = []
    timed: dict[int, float] = {}          # effective tile -> ms
    for bt in candidates:
        eff = _clamp_block(T, bt, _round_up(N, LANES))
        if eff not in timed:
            def run(bt=bt):
                choice, _, _ = dodoor_fused_sparse(
                    keys, r, d_types, node_type, L, D, C,
                    block_t=bt, interpret=interpret)
                return choice.block_until_ready()
            run()                         # warmup / compile
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                run()
                best = min(best, time.perf_counter() - t0)
            timed[eff] = best * 1e3
        curve.append({"block_t": int(bt), "effective_block_t": int(eff),
                      "ms": round(timed[eff], 4)})

    best_row = min(curve, key=lambda row: row["ms"])
    return {"T": int(T), "N": int(N), "TT": int(TT),
            "best_block_t": int(best_row["block_t"]),
            "best_ms": best_row["ms"], "curve": curve}
