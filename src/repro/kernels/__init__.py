"""repro.kernels — Pallas TPU kernels for the framework's compute hot-spots.

Each subpackage ships three layers:

* ``kernel.py`` — the ``pl.pallas_call`` body with explicit BlockSpec VMEM
  tiling.  On a TPU it compiles through Mosaic; elsewhere (the CPU test
  path, ``JAX_PLATFORMS=cpu``) it runs in Pallas interpret mode.  Of the
  scheduler's kernels only the sparse ``dodoor_fused_sparse`` megakernel is
  on the engine's path and compiled for the chip (``tests/
  test_chip_compile.py``); the dense variants and ``rl_score`` have only
  run interpreted;
* ``ops.py``    — the jit'd public wrapper (padding, grid math, dtypes);
* ``ref.py``    — the pure-jnp oracle every kernel is tested against.

Kernels:

* ``rl_score``       — batched Eq.-1 RL scores (tasks × servers) as an MXU
                       matmul with fused per-server capacity scaling. The
                       paper's hot path, re-thought for the systolic array.
* ``dodoor_choice``  — fused Algorithm-1 two-choice: one-hot candidate
                       gathers (MXU-friendly, no scatter/gather unit),
                       loadScore, and argmin select, one pass over VMEM.
* ``flash_attention``— blockwise-softmax attention (causal / local-window /
                       GQA) for the serving stack's long-context cells.
* ``ssd_chunk``      — Mamba-2 SSD intra-chunk quadratic block (the chunked
                       state-space-duality algorithm's MXU-heavy part).
"""
from . import dodoor_choice, flash_attention, rl_score, ssd_chunk

__all__ = ["rl_score", "dodoor_choice", "flash_attention", "ssd_chunk"]
