"""Public wrappers for the fused Dodoor two-choice kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import (LANES, PLANE_BYTES, dodoor_choice_pallas,
                     dodoor_fused_masked_pallas, dodoor_fused_pallas,
                     dodoor_fused_sparse_pallas)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _clamp_block(T: int, block_t: int, n_pad: int | None = None) -> int:
    """The tile of task rows: a multiple of 8 (the sublane tile) covering
    the batch, capped at ``block_t`` so small decision blocks (the engine's
    partial tail, or b ≪ 256) do not pay for a full tile of padding.  With
    ``n_pad`` (the sparse kernel's lane-padded server count) it is also
    capped so one ``[tile, n_pad]`` 32-bit plane fits ``PLANE_BYTES`` —
    at n = 10⁴ that is 24 rows."""
    cap = block_t
    if n_pad is not None:
        cap = min(cap, PLANE_BYTES // (4 * n_pad) // 8 * 8)
    return max(8, min(cap, _round_up(T, 8)))


def _key_data(keys: jnp.ndarray) -> jnp.ndarray:
    """Raw uint32 [T, 2] key words from either legacy or typed PRNG keys."""
    if jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
        keys = jax.random.key_data(keys)
    return keys.astype(jnp.uint32)


def dodoor_choice(r: jnp.ndarray, cand: jnp.ndarray, d_cand: jnp.ndarray,
                  L: jnp.ndarray, D: jnp.ndarray, C: jnp.ndarray,
                  alpha: float = 0.5, *, block_t: int = 256,
                  interpret: bool | None = None):
    """Fused Algorithm-1 selection for a pre-sampled decision batch (see
    ref.py for the oracle semantics). Builds the packed server table
    [L | D | 1/ΣC²] once per cache refresh and pads the batch to the tile
    size. ``interpret=None`` auto-detects the backend (compiled on TPU)."""
    T, K = r.shape
    block_t = _clamp_block(T, block_t)
    inv = 1.0 / jnp.sum(C.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    tbl = jnp.concatenate([L.astype(jnp.float32),
                           D.astype(jnp.float32)[:, None], inv], axis=-1)
    pad = (-T) % block_t
    if pad:
        r = jnp.pad(r, ((0, pad), (0, 0)))
        cand = jnp.pad(cand, ((0, pad), (0, 0)))
        d_cand = jnp.pad(d_cand, ((0, pad), (0, 0)))
    choice, scores = dodoor_choice_pallas(
        r.astype(jnp.float32), cand.astype(jnp.int32),
        d_cand.astype(jnp.float32), tbl, alpha=alpha, block_t=block_t,
        interpret=interpret)
    return choice[:T], scores[:T]


def dodoor_fused(keys: jnp.ndarray, r: jnp.ndarray, d: jnp.ndarray,
                 L: jnp.ndarray, D: jnp.ndarray, C: jnp.ndarray,
                 alpha: float = 0.5, *, avail: jnp.ndarray | None = None,
                 block_t: int = 256, interpret: bool | None = None):
    """Megakernel: sample → score → select in one Pallas pass.

    keys [T, 2]: per-task candidate-draw PRNG keys (the engine passes the
    first key of ``jax.random.split(fold_in(base, task_id))``); r [T, K]
    task demands; d [T, N] per-server estimated durations.  Candidate
    sampling happens *inside* the kernel (inline threefry + prefix-sum
    inverse CDF over the table's capacity columns) and is draw-for-draw
    identical to ``sample_feasible_batch(keys, feasible_mask(r, C), 2)``.

    avail [T, N] (optional): per-task server availability — the scenario
    engine's down-window mask.  When given, the masked-sampling kernel
    ANDs it into the in-kernel prefilter, keeping draws bit-identical to
    ``sample_feasible_batch(keys, feasible_mask(r, C) & avail, 2)``; when
    ``None`` the original unmasked program runs (no extra operand).

    Returns (choice [T] int32, cand [T, 2] int32, scores [T, 2] f32).
    """
    T, K = r.shape
    block_t = _clamp_block(T, block_t)
    Cf = C.astype(jnp.float32)
    inv = 1.0 / jnp.sum(Cf ** 2, axis=-1, keepdims=True)
    tbl = jnp.concatenate([L.astype(jnp.float32),
                           D.astype(jnp.float32)[:, None], inv, Cf], axis=-1)
    keys = _key_data(keys)
    pad = (-T) % block_t
    if pad:
        # Padded rows run through the full pipeline on zero demand/keys and
        # are sliced away — zero demand is always feasible (and padded
        # avail rows are all-ones), so the fallback branch never corrupts
        # the shared prefix-sum lanes.
        keys = jnp.pad(keys, ((0, pad), (0, 0)))
        r = jnp.pad(r, ((0, pad), (0, 0)))
        d = jnp.pad(d, ((0, pad), (0, 0)))
    if avail is None:
        choice, cand, scores = dodoor_fused_pallas(
            keys, r.astype(jnp.float32), d.astype(jnp.float32), tbl,
            alpha=alpha, block_t=block_t, interpret=interpret)
    else:
        avail = avail.astype(jnp.float32)
        if pad:
            avail = jnp.pad(avail, ((0, pad), (0, 0)),
                            constant_values=1.0)
        choice, cand, scores = dodoor_fused_masked_pallas(
            keys, r.astype(jnp.float32), d.astype(jnp.float32), avail, tbl,
            alpha=alpha, block_t=block_t, interpret=interpret)
    return choice[:T], cand[:T], scores[:T]


def dodoor_fused_sparse(keys: jnp.ndarray, r: jnp.ndarray,
                        d_types: jnp.ndarray, node_type: jnp.ndarray,
                        L: jnp.ndarray, D: jnp.ndarray, C: jnp.ndarray,
                        alpha: float = 0.5, *,
                        avail: jnp.ndarray | None = None,
                        psrv: jnp.ndarray | None = None,
                        pbytes: jnp.ndarray | None = None,
                        gamma_bw: float = 0.0,
                        block_t: int = 256,
                        interpret: bool | None = None):
    """Sparse-candidate-gather megakernel: like :func:`dodoor_fused` but
    without the dense ``d [T, N]`` per-server duration plane.

    d_types [T, TT] is each task's estimated duration *per node type*
    (TT = number of node types, ~4) and node_type [N] maps servers to
    types — the factorization the engine's duration model already has
    (``d[t, j] == d_types[t, node_type[j]]``).  The kernel carries
    node_type as one extra server-table field and resolves each sampled
    candidate's duration with a tiny pick over the TT columns, so the
    per-task bytes touched drop from O(N) to O(TT).

    Candidate draws are bit-exact vs ``sample_feasible_batch`` (same
    threefry uniforms and inverse-CDF rank as :func:`dodoor_fused`), and
    choices/scores are exactly the dense megakernel's on the factorized
    ``d`` — the gathered duration is the same float.

    avail [T, N] (optional): per-task server availability, ANDed into the
    prefilter (the masked-sampling form).

    psrv [T, P] / pbytes [T, P] (optional, together): the locality
    gather — each task's parent servers (int32, −1 padded) and their
    output sizes in MB (0 padded).  With ``gamma_bw > 0`` every
    candidate's score is charged ``gamma_bw · Σ_p pbytes[p] ·
    [psrv[p] ≠ candidate]`` (the LocalityModel penalty); ``gamma_bw = 0``
    is bit-identical to running without the planes.

    Returns (choice [T] int32, cand [T, 2] int32, scores [T, 2] f32).
    """
    T, K = r.shape
    n = C.shape[0]
    n_pad = _round_up(n, LANES)
    block_t = _clamp_block(T, block_t, n_pad)
    if (psrv is None) != (pbytes is None):
        raise ValueError("psrv and pbytes must be given together")
    # The server table, transposed so that servers run along lanes: one
    # row per field, padded to the 8-row sublane tile and to n_pad lanes.
    # The kernel masks the padded servers out of every draw.
    Cf = C.astype(jnp.float32)
    inv = 1.0 / jnp.sum(Cf ** 2, axis=-1)
    fields = ([L[:, c].astype(jnp.float32) for c in range(K)]
              + [D.astype(jnp.float32), inv]
              + [Cf[:, c] for c in range(K)]
              + [node_type.astype(jnp.float32)])
    tbl = jnp.stack(fields)
    tbl = jnp.pad(tbl, ((0, (-tbl.shape[0]) % 8), (0, n_pad - n)))

    def rows(x, value=0):
        # Padded task rows run through the full pipeline and are sliced
        # away: zero keys and demand, every server available, no parents.
        pad = (-T) % block_t
        return jnp.pad(x, ((0, pad), (0, 0)), constant_values=value)

    operands = dict(avail=None, psrv=None, pbytes=None)
    if avail is not None:
        avail = jnp.pad(avail.astype(jnp.float32), ((0, 0), (0, n_pad - n)))
        operands["avail"] = rows(avail, 1.0)
    if psrv is not None:
        operands["psrv"] = rows(psrv.astype(jnp.int32), -1)
        operands["pbytes"] = rows(pbytes.astype(jnp.float32))
    choice, cand, scores = dodoor_fused_sparse_pallas(
        rows(_key_data(keys)), rows(r.astype(jnp.float32)),
        rows(d_types.astype(jnp.float32)), tbl, **operands, n=n,
        alpha=alpha, gamma_bw=float(gamma_bw), block_t=block_t,
        interpret=interpret)
    return choice[:T, 0], cand[:T], scores[:T]
