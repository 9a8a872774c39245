"""The paper's anti-affinity Resource-Load (RL) score and loadScore (§3.2).

Equation 1:
    RL(r_i, L_j, C_j) = (r_iᵀ · L_j) / Σ_k C_jk²

Final pairwise load score for candidates j, p (Algorithm 1, LOADSCORE):
    loadScore_ij = (1-α)·RL_j/(RL_j+RL_p) + α·(D_j+d_ij)/(D_j+d_ij+D_p+d_ip)

Lower is better — the score measures *anti-affinity* between the task and the
server, in contrast to Tetris' alignment (affinity) score.

All functions are pure jnp and vmap/scan friendly. ``rl_score_matrix`` is the
batched form (tasks × servers) that the Pallas kernel
(`repro.kernels.rl_score`) implements for the MXU; `ref.py` of that kernel
delegates here so the kernel is tested against this exact definition.

The per-decision scores reduce ``r · L`` as an elementwise product and a sum,
never as a dot: on a TPU an f32 dot at default precision rounds its operands
to bf16, and the sequential, batched, served and kernel paths must compute
the same f32 score on every backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-9  # guards 0/0 when both candidates are fully idle


def rl(r: jnp.ndarray, L: jnp.ndarray, C: jnp.ndarray) -> jnp.ndarray:
    """Eq. 1 for a single (task, server) pair.

    r: [K] task demand; L: [K] server load; C: [K] server capacity.
    """
    return jnp.sum(r * L) / jnp.sum(C * C)


def rl_score_matrix(r: jnp.ndarray, L: jnp.ndarray, C: jnp.ndarray) -> jnp.ndarray:
    """Batched Eq. 1: tasks [T, K] × servers [N, K] → scores [T, N].

    score[t, j] = (r_t · L_j) / ||C_j||²  — a matmul with per-column scaling.
    """
    inv_cap = 1.0 / jnp.sum(C * C, axis=-1)          # [N]
    rl_tn = jnp.matmul(r, L.T, precision=jax.lax.Precision.HIGHEST)
    return rl_tn * inv_cap[None, :]                  # [T, N]


def load_score_pair(
    r: jnp.ndarray,
    L_a: jnp.ndarray,
    L_b: jnp.ndarray,
    D_a: jnp.ndarray,
    D_b: jnp.ndarray,
    C_a: jnp.ndarray,
    C_b: jnp.ndarray,
    alpha: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Algorithm 1's LOADSCORE — normalized pairwise scores for candidates A, B.

    ``D_a``/``D_b`` must already include the task's own estimated duration on
    that candidate (the call site passes ``D_A + d_iA`` per line 10).
    Returns (score_A, score_B); the lower one wins.
    """
    rl_a = rl(r, L_a, C_a)
    rl_b = rl(r, L_b, C_b)
    rl_sum = rl_a + rl_b
    d_sum = D_a + D_b
    # Degenerate sums (both candidates idle) mean indifference: 0.5 / 0.5.
    rl_frac_a = jnp.where(rl_sum > _EPS, rl_a / (rl_sum + _EPS), 0.5)
    rl_frac_b = jnp.where(rl_sum > _EPS, rl_b / (rl_sum + _EPS), 0.5)
    d_frac_a = jnp.where(d_sum > _EPS, D_a / (d_sum + _EPS), 0.5)
    d_frac_b = jnp.where(d_sum > _EPS, D_b / (d_sum + _EPS), 0.5)
    score_a = rl_frac_a * (1.0 - alpha) + d_frac_a * alpha
    score_b = rl_frac_b * (1.0 - alpha) + d_frac_b * alpha
    return score_a, score_b


def load_score_batched(
    r: jnp.ndarray,       # [T, K]
    L_ab: jnp.ndarray,    # [T, 2, K] candidate loads
    D_ab: jnp.ndarray,    # [T, 2]    candidate durations incl. task's own d
    C_ab: jnp.ndarray,    # [T, 2, K] candidate capacities
    alpha: float,
) -> jnp.ndarray:
    """Vectorized LOADSCORE over a batch of tasks with 2 candidates each.

    Returns scores [T, 2].
    """
    rl_ab = (jnp.sum(r[:, None, :] * L_ab, axis=-1)
             / jnp.sum(C_ab * C_ab, axis=-1))
    rl_sum = jnp.sum(rl_ab, axis=-1, keepdims=True)
    d_sum = jnp.sum(D_ab, axis=-1, keepdims=True)
    rl_frac = jnp.where(rl_sum > _EPS, rl_ab / (rl_sum + _EPS), 0.5)
    d_frac = jnp.where(d_sum > _EPS, D_ab / (d_sum + _EPS), 0.5)
    return rl_frac * (1.0 - alpha) + d_frac * alpha
