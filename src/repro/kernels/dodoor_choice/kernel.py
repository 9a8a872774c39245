"""Pallas kernels: fused Algorithm-1 two-choice selection.

TPU adaptation. The GPU/CPU-natural implementation gathers L[cand], D[cand],
C[cand] with a scatter/gather unit; the TPU has none worth feeding from
VMEM, so the gathers are recast as **one-hot matmuls** on the MXU:

    onehot[t, j] = (cand[t] == j)              (VPU compare against an iota)
    L_cand       = onehot @ L                  (MXU, [block_t,N]×[N,K])
    D_cand       = onehot @ D                  (same pass)

Two entry points share that trick:

* ``dodoor_choice_pallas`` — the two-stage form: candidates are sampled
  outside (``sample_feasible_batch``) and only score+select fuse.
* ``dodoor_fused_pallas``  — the megakernel: candidate *sampling* moves
  inside too, so the whole sample → score → select chain is one pass with
  one HBM read of the server table per tile and no [T, 2] candidate /
  duration intermediates round-tripping through HBM.
* ``dodoor_fused_masked_pallas`` — the megakernel's masked-sampling form:
  a per-task ``avail [T, N]`` 0/1 plane (the scenario engine's down-window
  mask) is streamed per tile and ANDed into the in-kernel prefilter, so
  ``use_kernel=True`` stays legal under outage/churn timelines.  Sampling
  arithmetic is otherwise identical, so draws remain bit-exact against
  ``sample_feasible_batch`` on the intersected mask.
* ``dodoor_fused_sparse_pallas`` — the sparse-candidate-gather
  megakernel, with optional availability and locality planes.  It is the
  only kernel the engine runs, and the only one made for the chip's
  compiler (the ones above have only run interpreted).  The dense form
  streams a ``d [T, N]`` per-server duration plane per tile; the sparse
  form streams the engine's ``d_types [T, TT]`` table instead (TT = node
  types, ~4) and carries each server's node type as one more table
  field, so the candidate's duration is a TT-wide pick.  The gathered
  duration is the *same float* the dense kernel gathers (``d[t, j] ==
  d_types[t, node_type[j]]`` by construction).

Sparse megakernel layout (what Mosaic compiles)
-----------------------------------------------
Servers run along the 128-wide lane axis.  The server table is stored
transposed, one row per field, padded to the 8-row sublane tile and to
``NP`` = n rounded up to 128 lanes:

    tbl[8, NP] = [ L (K rows) | D | 1/ΣC² | C (K rows) | node_type | 0 ]

At K = 2 and n = 10⁴ that is 8 × 10,112 × 4 B ≈ 316 KiB (a row-major
``[n, 7]`` table would pad its 7 columns to 128 lanes, ≈ 4.9 MiB).  Its
block is pinned to grid index 0, so it is read from HBM once.  Per-task
values are ``[block_t, 1]`` columns and per-server values ``[1, NP]``
rows, so every intermediate plane is a lane-dense ``[block_t, NP]`` tile:
the feasibility mask, the lane iota, each binary-search probe and each
gather's hit mask.  ``ops._clamp_block`` caps ``block_t`` so one 32-bit
plane stays within ``PLANE_BYTES`` (1 MiB): 24 rows at n = 10⁴, about
0.93 MiB a plane.  A few such planes live at once, plus the
double-buffered ``avail`` plane of the masked form; ``VMEM_LIMIT_BYTES``
(32 MiB) raises the scoped-VMEM limit above the 16 MiB default to give
them room.  All outputs are 2-D (``choice [T, 1]``), so no rank-1 block
has to be a multiple of 128.

Two steps are built from operations Mosaic lowers:

* The inverse-CDF pick of ``sample_feasible`` takes the first server whose
  inclusive feasible count reaches the rank.  The count is nondecreasing,
  so a binary search over the server index finds it; each probe is a
  masked lane sum, and ``ceil(log2 n)`` probes replace the prefix sum
  (``cumsum`` has no Mosaic lowering).  The result is the same index.
* Each candidate field is a masked lane sum with one nonzero term, so the
  gathered L, D, 1/ΣC² and node type are the stored f32 values exactly.
  No matmul is involved; an f32 matmul at default precision on the TPU's
  MXU rounds its operands to bf16.

Megakernel PRNG scheme
----------------------
Candidate draws must be *draw-for-draw identical* to the two-stage path's
``jax.random.uniform(k_cand, (2,))``, so the kernel re-implements JAX's
threefry2x32 generator inline (20 rounds, rotation schedule
(13,15,26,6)/(17,29,16,24), key-schedule constant 0x1BD11BDA).  The
installed JAX (0.9) draws in the partitionable layout (its
``jax_threefry_partitionable`` default is on), where element i of the
draw hashes counter ``(0, i)`` and folds the two output words:

    hi_i, lo_i = threefry2x32(key, counts=(0, i))       i = 0, 1
    u_i        = bitcast((hi_i ^ lo_i) >> 9 | 0x3F800000, f32) - 1.0

exactly the mantissa fill JAX uses for float32 uniforms.  The program
follows the installation's default and sets no flag.  The two uniforms
then drive the same inverse-CDF pick as ``sample_feasible``: rank
``min(int(u·k), k-1)+1``, index = #servers whose inclusive feasible count
is below the rank (with the uniform-over-all fallback when no server is
feasible).  ``tests/test_kernels.py`` / ``tests/test_engine_batched.py``
pin this bit-for-bit against ``jax.random.uniform`` and
``sample_feasible_batch``.

Grid: 1-D over decision-batch tiles of ``block_t``. The server table is
broadcast to every grid step (index_map pins it to block 0).

``interpret=None`` auto-detects the backend: compiled on TPU, interpreter
mode elsewhere (the CPU test/CI path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-9

#: Servers ride the 128-wide lane axis of the sparse kernel's planes.
LANES = 128
#: Bytes one ``[block_t, NP]`` 32-bit plane of the sparse kernel may take;
#: ``ops._clamp_block`` sizes ``block_t`` from it.
PLANE_BYTES = 1 << 20
#: Scoped-VMEM limit of the sparse kernel: its tile holds a handful of
#: such planes (mask, iota, probe temporaries, the double-buffered avail
#: plane) plus the pinned table.
VMEM_LIMIT_BYTES = 32 << 20

# threefry2x32 rotation schedule (Salmon et al.; matches jax._src.prng).
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA


def _resolve_interpret(interpret):
    """``None`` → interpreter mode unless running on a real TPU backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _threefry2x32(k0, k1, x0, x1):
    """20-round threefry2x32, vectorized over uint32 arrays — bit-identical
    to JAX's generator (verified against ``jax.random.uniform``/``split``)."""
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    x = [x0 + ks[0], x1 + ks[1]]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x[0] = x[0] + x[1]
            x[1] = (x[1] << r) | (x[1] >> (32 - r))
            x[1] = x[0] ^ x[1]
        x[0] = x[0] + ks[(i + 1) % 3]
        x[1] = x[1] + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x[0], x[1]


def _unit_float(bits):
    """uint32 bits → float32 in [0, 1) via JAX's mantissa fill."""
    fb = (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(fb, jnp.float32) - 1.0


def _uniform_pair(k0, k1):
    """``jax.random.uniform(key, (2,))`` for key words ``(k0, k1)``, in the
    partitionable threefry layout: element i is ``hi ^ lo`` of
    ``threefry2x32(key, (0, i))``."""
    zero = jnp.zeros_like(k0)
    a0, b0 = _threefry2x32(k0, k1, zero, zero)
    a1, b1 = _threefry2x32(k0, k1, zero, zero + jnp.uint32(1))
    return _unit_float(a0 ^ b0), _unit_float(a1 ^ b1)


def _row_terms(k, r, row, d_c):
    """Eq. 1's RL and the duration term for gathered candidate rows
    ``row[:, :k]`` = L, ``[:, k]`` = D, ``[:, k+1]`` = 1/ΣC²."""
    rl = jnp.sum(r * row[:, :k], axis=-1) * row[:, k + 1]
    return rl, row[:, k] + d_c


def _pair_scores(alpha, rl_a, D_a, rl_b, D_b):
    """LOADSCORE from both candidates' RL and duration terms (shared by
    every kernel)."""
    rl_sum = rl_a + rl_b
    d_sum = D_a + D_b
    rl_fa = jnp.where(rl_sum > _EPS, rl_a / (rl_sum + _EPS), 0.5)
    rl_fb = jnp.where(rl_sum > _EPS, rl_b / (rl_sum + _EPS), 0.5)
    d_fa = jnp.where(d_sum > _EPS, D_a / (d_sum + _EPS), 0.5)
    d_fb = jnp.where(d_sum > _EPS, D_b / (d_sum + _EPS), 0.5)
    score_a = rl_fa * (1.0 - alpha) + d_fa * alpha
    score_b = rl_fb * (1.0 - alpha) + d_fb * alpha
    return score_a, score_b


def _kernel(alpha, r_ref, cand_ref, d_ref, tbl_ref, out_choice_ref,
            out_scores_ref):
    # r_ref:    [block_t, K]   task demands
    # cand_ref: [block_t, 2]   candidate ids (int32)
    # d_ref:    [block_t, 2]   per-candidate task durations
    # tbl_ref:  [N, K+2]       server table: [L (K) | D | 1/ΣC²]
    # outputs:  [block_t] int32, [block_t, 2] f32
    tbl = tbl_ref[...]
    n = tbl.shape[0]
    k = r_ref.shape[1]
    cand = cand_ref[...]                                   # [bt, 2]
    ids = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)   # [1, N]

    def gather(which):
        onehot = (cand[:, which][:, None] == ids).astype(jnp.float32)
        return jnp.dot(onehot, tbl, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    row_a = gather(0)                                      # [bt, K+2]
    row_b = gather(1)
    r = r_ref[...]
    score_a, score_b = _pair_scores(
        alpha, *_row_terms(k, r, row_a, d_ref[:, 0]),
        *_row_terms(k, r, row_b, d_ref[:, 1]))

    out_scores_ref[:, 0] = score_a
    out_scores_ref[:, 1] = score_b
    out_choice_ref[...] = jnp.where(score_a > score_b, cand[:, 1],
                                    cand[:, 0]).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "block_t", "interpret"))
def dodoor_choice_pallas(r, cand, d_cand, tbl, *, alpha: float,
                         block_t: int = 256, interpret: bool | None = None):
    """r [T,K], cand [T,2] int32, d_cand [T,2], tbl [N, K+2] → (choice [T],
    scores [T,2]). T must be a multiple of block_t (ops.py pads)."""
    T, K = r.shape
    N = tbl.shape[0]
    grid = (T // block_t,)
    kern = functools.partial(_kernel, alpha)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, K), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 2), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 2), lambda i: (i, 0)),
            pl.BlockSpec((N, K + 2), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t,), lambda i: (i,)),
            pl.BlockSpec((block_t, 2), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T,), jnp.int32),
            jax.ShapeDtypeStruct((T, 2), jnp.float32),
        ],
        interpret=_resolve_interpret(interpret),
    )(r, cand, d_cand, tbl)


def _fused_kernel(alpha, k, masked, *refs):
    # key_ref:  [block_t, 2]   per-task uint32 PRNG key (k_cand)
    # r_ref:    [block_t, K]   task demands
    # d_ref:    [block_t, N]   per-server estimated durations
    # avail_ref:[block_t, N]   (masked form only) 0/1 availability plane —
    #                          per-task down-window mask from the scenario
    #                          engine's Dynamics timelines
    # tbl_ref:  [N, 2K+2]      server table: [L | D | 1/ΣC² | C]
    # outputs:  choice [bt] i32, cand [bt, 2] i32, scores [bt, 2] f32
    if masked:
        (key_ref, r_ref, d_ref, avail_ref, tbl_ref, out_choice_ref,
         out_cand_ref, out_scores_ref) = refs
    else:
        (key_ref, r_ref, d_ref, tbl_ref, out_choice_ref, out_cand_ref,
         out_scores_ref) = refs
        avail_ref = None
    tbl = tbl_ref[...]
    n = tbl.shape[0]
    r = r_ref[...]
    bt = r.shape[0]

    # --- prefilter (Algorithm 1 line 2) from the table's capacity columns,
    #     intersected with the per-task availability plane in the masked
    #     form (down windows: outages ∪ joins ∪ leaves)
    caps = tbl[:, k + 2:]                                  # [N, K]
    mask = jnp.all(r[:, None, :] <= caps[None, :, :], axis=-1)   # [bt, N]
    if avail_ref is not None:
        mask = mask & (avail_ref[...] > 0.0)
    cnt = jnp.cumsum(mask.astype(jnp.int32), axis=1)       # inclusive
    total = cnt[:, -1]                                     # [bt]
    any_ok = total > 0
    pos = jax.lax.broadcasted_iota(jnp.int32, (bt, n), 1)
    # No-feasible fallback: uniform over all servers (submission is never
    # rejected) — identical to sample_feasible's eff_cnt/kk substitution.
    eff_cnt = jnp.where(any_ok[:, None], cnt, pos + 1)
    kk = jnp.where(any_ok, total, n)                       # [bt]

    # --- per-task PRNG: uniform(k_cand, (2,)) via inline threefry
    u0, u1 = _uniform_pair(key_ref[:, 0], key_ref[:, 1])

    # --- inverse-CDF prefix-sum pick (two independent RandomInt draws)
    kk_f = kk.astype(jnp.float32)
    km1 = kk - 1
    tgt0 = jnp.minimum((u0 * kk_f).astype(jnp.int32), km1) + 1
    tgt1 = jnp.minimum((u1 * kk_f).astype(jnp.int32), km1) + 1
    cand0 = jnp.sum((eff_cnt < tgt0[:, None]).astype(jnp.int32), axis=1)
    cand1 = jnp.sum((eff_cnt < tgt1[:, None]).astype(jnp.int32), axis=1)

    # --- gather candidate rows + per-candidate durations, score, select
    ids = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    d = d_ref[...]

    def gather(c):
        onehot = (c[:, None] == ids).astype(jnp.float32)
        row = jnp.dot(onehot, tbl, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
        d_c = jnp.sum(onehot * d, axis=-1)
        return row, d_c

    row_a, d_a = gather(cand0)
    row_b, d_b = gather(cand1)
    score_a, score_b = _pair_scores(alpha, *_row_terms(k, r, row_a, d_a),
                                    *_row_terms(k, r, row_b, d_b))

    out_cand_ref[:, 0] = cand0.astype(jnp.int32)
    out_cand_ref[:, 1] = cand1.astype(jnp.int32)
    out_scores_ref[:, 0] = score_a
    out_scores_ref[:, 1] = score_b
    out_choice_ref[...] = jnp.where(score_a > score_b, cand1,
                                    cand0).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "block_t", "interpret"))
def dodoor_fused_pallas(keys, r, d, tbl, *, alpha: float,
                        block_t: int = 256, interpret: bool | None = None):
    """keys [T,2] uint32, r [T,K], d [T,N], tbl [N, 2K+2] → (choice [T],
    cand [T,2], scores [T,2]). T must be a multiple of block_t (ops pads)."""
    T, K = r.shape
    N = tbl.shape[0]
    grid = (T // block_t,)
    kern = functools.partial(_fused_kernel, alpha, K, False)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, 2), lambda i: (i, 0)),
            pl.BlockSpec((block_t, K), lambda i: (i, 0)),
            pl.BlockSpec((block_t, N), lambda i: (i, 0)),
            pl.BlockSpec((N, 2 * K + 2), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t,), lambda i: (i,)),
            pl.BlockSpec((block_t, 2), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 2), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T,), jnp.int32),
            jax.ShapeDtypeStruct((T, 2), jnp.int32),
            jax.ShapeDtypeStruct((T, 2), jnp.float32),
        ],
        interpret=_resolve_interpret(interpret),
    )(keys, r, d, tbl)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "block_t", "interpret"))
def dodoor_fused_masked_pallas(keys, r, d, avail, tbl, *, alpha: float,
                               block_t: int = 256,
                               interpret: bool | None = None):
    """The masked-sampling megakernel: like :func:`dodoor_fused_pallas`
    with an extra ``avail [T, N]`` 0/1 float32 plane ANDed into the
    in-kernel prefilter, so the scenario engine's per-server down windows
    (outages, churn) ride the fused path.  The threefry draws and the
    inverse-CDF pick are untouched — draws stay bit-identical to
    ``sample_feasible_batch(keys, capacity_mask & avail, 2)``."""
    T, K = r.shape
    N = tbl.shape[0]
    grid = (T // block_t,)
    kern = functools.partial(_fused_kernel, alpha, K, True)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, 2), lambda i: (i, 0)),
            pl.BlockSpec((block_t, K), lambda i: (i, 0)),
            pl.BlockSpec((block_t, N), lambda i: (i, 0)),
            pl.BlockSpec((block_t, N), lambda i: (i, 0)),
            pl.BlockSpec((N, 2 * K + 2), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t,), lambda i: (i,)),
            pl.BlockSpec((block_t, 2), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 2), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T,), jnp.int32),
            jax.ShapeDtypeStruct((T, 2), jnp.int32),
            jax.ShapeDtypeStruct((T, 2), jnp.float32),
        ],
        interpret=_resolve_interpret(interpret),
    )(keys, r, d, avail, tbl)


def _fused_sparse_kernel(alpha, k, n, masked, gamma_bw, locality, *refs):
    # Every per-task value is a [bt, 1] column and every per-server value a
    # [1, NP] row (NP = servers padded to 128 lanes), so each plane is a
    # lane-dense [bt, NP] tile that Mosaic lowers without relayouts.
    # key_ref:  [bt, 2]   per-task uint32 PRNG key (k_cand)
    # r_ref:    [bt, K]   task demands
    # dt_ref:   [bt, TT]  per-*type* estimated durations (TT = node types)
    # avail_ref:[bt, NP]  (masked form only) 0/1 availability plane
    # psrv_ref: [bt, P]   (locality form only) parent servers (i32, -1
    #                     where absent)
    # pbytes_ref:[bt, P]  (locality form only) parent output MB (0 where
    #                     absent — an absent parent is inert)
    # tbl_ref:  [R, NP]   transposed server table, one row per field:
    #                     [L (K) | D | 1/ΣC² | C (K) | node_type | 0 pad]
    # outputs:  choice [bt, 1] i32, cand [bt, 2] i32, scores [bt, 2] f32
    refs = list(refs)
    key_ref, r_ref, dt_ref = refs[:3]
    at = 3
    avail_ref = psrv_ref = pbytes_ref = None
    if masked:
        avail_ref = refs[at]
        at += 1
    if locality:
        psrv_ref, pbytes_ref = refs[at], refs[at + 1]
        at += 2
    tbl_ref, out_choice_ref, out_cand_ref, out_scores_ref = refs[at:]
    bt = r_ref.shape[0]
    n_pad = tbl_ref.shape[1]
    r = r_ref[...]
    pos = jax.lax.broadcasted_iota(jnp.int32, (bt, n_pad), 1)

    # --- prefilter (Algorithm 1 line 2): r ≤ C in every dimension, on the
    #     real servers only, intersected with the availability plane.
    mask = pos < n
    for c in range(k):
        mask = mask & (r[:, c:c + 1] <= tbl_ref[k + 2 + c:k + 3 + c, :])
    if avail_ref is not None:
        mask = mask & (avail_ref[...] > 0.0)
    ones = mask.astype(jnp.int32)
    total = jnp.sum(ones, axis=1, keepdims=True)           # [bt, 1]
    any_ok = total > 0
    kk = jnp.where(any_ok, total, n)

    # --- the two RandomInt draws, exactly sample_feasible's arithmetic:
    #     rank = min(int(u·kk), kk-1) + 1, index = #servers whose inclusive
    #     feasible count is below the rank.  The count is nondecreasing, so
    #     that index is the first j with count[j] ≥ rank: a binary search
    #     whose probes are masked lane sums (no prefix-sum primitive, which
    #     Mosaic does not lower).  With nothing feasible, sample_feasible
    #     counts every server, and the index is rank - 1.
    keys = key_ref[...]
    u0, u1 = _uniform_pair(keys[:, 0:1], keys[:, 1:2])
    kk_f = kk.astype(jnp.float32)
    tgt0 = jnp.minimum((u0 * kk_f).astype(jnp.int32), kk - 1) + 1
    tgt1 = jnp.minimum((u1 * kk_f).astype(jnp.int32), kk - 1) + 1
    lo0 = lo1 = jnp.zeros((bt, 1), jnp.int32)
    hi0 = hi1 = jnp.full((bt, 1), n - 1, jnp.int32)
    for _ in range(max(n - 1, 0).bit_length()):
        mid0 = (lo0 + hi0) >> 1
        mid1 = (lo1 + hi1) >> 1
        ok0 = jnp.sum(jnp.where(pos <= mid0, ones, 0), axis=1,
                      keepdims=True) >= tgt0
        ok1 = jnp.sum(jnp.where(pos <= mid1, ones, 0), axis=1,
                      keepdims=True) >= tgt1
        lo0, hi0 = jnp.where(ok0, lo0, mid0 + 1), jnp.where(ok0, mid0, hi0)
        lo1, hi1 = jnp.where(ok1, lo1, mid1 + 1), jnp.where(ok1, mid1, hi1)
    cand0 = jnp.where(any_ok, lo0, tgt0 - 1)
    cand1 = jnp.where(any_ok, lo1, tgt1 - 1)

    # --- sparse gather: each table field of the candidate is a masked lane
    #     sum with one nonzero term, so it is the stored f32 exactly (no
    #     matmul, whose f32 precision on the MXU is not exact).  The
    #     candidate's node type rides out as a table field, and a TT-wide
    #     pick resolves its duration; no [bt, N] duration operand exists.
    dt = dt_ref[...]                                       # [bt, TT]
    tio = jax.lax.broadcasted_iota(jnp.int32, (1, dt.shape[1]),
                                   1).astype(jnp.float32)

    def gather(cand):
        hit = pos == cand

        def field(i):
            return jnp.sum(jnp.where(hit, tbl_ref[i:i + 1, :], 0.0), axis=1,
                           keepdims=True)

        # L as a [bt, K] tile, so that RL reduces over K exactly as the
        # jnp paths do (a reduction, not a chain of adds).
        lane = jax.lax.broadcasted_iota(jnp.int32, (bt, k), 1)
        l_c = field(0)
        for c in range(1, k):
            l_c = jnp.where(lane == c, field(c), l_c)
        rl = jnp.sum(r * l_c, axis=1, keepdims=True) * field(k + 1)
        d_c = jnp.sum(jnp.where(field(2 * k + 2) == tio, dt, 0.0), axis=1,
                      keepdims=True)
        return rl, field(k) + d_c

    score_a, score_b = _pair_scores(alpha, *gather(cand0), *gather(cand1))

    if locality:
        # Data-locality penalty (Algorithm 1 + LocalityModel): each
        # candidate is charged gamma/bandwidth per MB of parent output it
        # would have to pull remotely.  Same reduction order as the
        # two-stage path; gamma_bw = 0 adds +0.0 and reproduces the
        # locality-free scores bit-exactly.
        psrv = psrv_ref[...]                               # [bt, P] i32
        pb = pbytes_ref[...]                               # [bt, P] f32
        rem_a = jnp.sum(jnp.where(psrv != cand0, pb, 0.0), axis=1,
                        keepdims=True)
        rem_b = jnp.sum(jnp.where(psrv != cand1, pb, 0.0), axis=1,
                        keepdims=True)
        score_a = score_a + gamma_bw * rem_a
        score_b = score_b + gamma_bw * rem_b

    first = jax.lax.broadcasted_iota(jnp.int32, (bt, 2), 1) == 0
    out_cand_ref[...] = jnp.where(first, cand0, cand1)
    out_scores_ref[...] = jnp.where(first, score_a, score_b)
    out_choice_ref[...] = jnp.where(score_a > score_b, cand1, cand0)


@functools.partial(jax.jit,
                   static_argnames=("n", "alpha", "gamma_bw", "block_t",
                                    "interpret"))
def dodoor_fused_sparse_pallas(keys, r, d_types, tbl, avail=None, psrv=None,
                               pbytes=None, *, n: int, alpha: float,
                               gamma_bw: float = 0.0, block_t: int = 256,
                               interpret: bool | None = None):
    """keys [T,2] uint32, r [T,K], d_types [T,TT], tbl [R, NP] (the
    transposed server table, NP a multiple of 128 ≥ n) → (choice [T,1],
    cand [T,2], scores [T,2]).  T must be a multiple of block_t (ops.py
    pads).

    ``avail [T, NP]`` (optional) is the 0/1 availability plane ANDed into
    the prefilter.  ``psrv [T, P]`` (int32 parent servers, −1 padded) and
    ``pbytes [T, P]`` (parent output MB, 0 padded) stream the locality
    gather: each candidate's score is charged ``gamma_bw`` per MB of parent
    output held on a different server.  ``gamma_bw = 0`` with planes
    present is bit-identical to running without them."""
    T, K = r.shape
    R, NP = tbl.shape
    TT = d_types.shape[1]
    masked = avail is not None
    locality = psrv is not None
    kern = functools.partial(_fused_sparse_kernel, alpha, K, n, masked,
                             gamma_bw, locality)

    def rows(width):
        return pl.BlockSpec((block_t, width), lambda i: (i, 0))

    in_specs = [rows(2), rows(K), rows(TT)]
    operands = [keys, r, d_types]
    if masked:
        in_specs.append(rows(NP))
        operands.append(avail)
    if locality:
        in_specs += [rows(psrv.shape[1]), rows(psrv.shape[1])]
        operands += [psrv, pbytes]
    in_specs.append(pl.BlockSpec((R, NP), lambda i: (0, 0)))
    operands.append(tbl)
    return pl.pallas_call(
        kern,
        grid=(T // block_t,),
        in_specs=in_specs,
        out_specs=[rows(1), rows(2), rows(2)],
        name="dodoor_fused_sparse",
        out_shape=[
            jax.ShapeDtypeStruct((T, 1), jnp.int32),
            jax.ShapeDtypeStruct((T, 2), jnp.int32),
            jax.ShapeDtypeStruct((T, 2), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=_resolve_interpret(interpret),
    )(*operands)
