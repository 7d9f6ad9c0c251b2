"""Alternating projections / randomised block-coordinate solver (§5.1.1 baseline;
Shalev-Shwartz & Zhang 2013 SDCA; Tu et al. 2016; Wu et al. 2024).

Each step picks a random coordinate block I (|I| = p), solves the p×p block system
exactly, and updates the *maintained residual* incrementally:

    Δ = (K_II + σ² I_p)⁻¹ r_I ;   α_I += Δ ;   r −= (K_:I + σ² E_I) Δ

O(n·p + p³) per step, one kernel row-block gather (``rows_t_mv`` for the
residual update plus the exact ``block_at`` sub-solve — like SDD there is no
forward/transpose pair over one panel, so the SGD-style ``rows_pair_mv`` fusion
does not apply) — the third solver family the Ch. 5 improvements (warm start,
pathwise estimator) are demonstrated on.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .base import (
    FLAG_NONFINITE,
    LinearOperator,
    SolveResult,
    as_matrix_rhs,
    finalize,
)


@partial(jax.jit, static_argnames=("num_steps", "block_size"))
def solve_ap(
    op: LinearOperator,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    key: jax.Array,
    num_steps: int = 2000,
    block_size: int = 512,
    tol: float = 1e-2,
) -> SolveResult:
    b2, squeeze = as_matrix_rhs(b)
    n, s = b2.shape
    sigma2 = op.noise
    if x0 is None:
        a0 = jnp.zeros_like(b2)
        r0 = b2  # α₀ == 0 ⇒ the initial residual is free (no A·0 matvec)
        init_mv = 0
    else:
        a0 = x0[:, None] if x0.ndim == 1 else x0
        r0 = b2 - op.mv(a0)
        init_mv = 1

    def step(carry, t):
        alpha, r, fl = carry
        idx = jax.random.randint(jax.random.fold_in(key, t), (block_size,), 0, n)
        # only the p×p principal block is materialised; the (p, n) panel the seed
        # gathered per step is replaced by one fused transposed row-block matvec
        kii = op.block_at(idx) + sigma2 * jnp.eye(block_size, dtype=b2.dtype)
        # duplicate indices in idx would double-count; deduplicate by weighting is
        # avoided simply by solving the (possibly singular-duplicated) system with a
        # small extra jitter — exactness per-step is not required for convergence.
        delta = jnp.linalg.solve(
            kii + 1e-6 * jnp.eye(block_size, dtype=b2.dtype), r[idx]
        )  # (p, s)
        # in-loop health check on the (p, s) block update: a NaN/Inf column (a
        # poisoned RHS, or a block solve gone bad) flags and freezes — its Δ is
        # zeroed, so the column-independent updates below leave it untouched
        ok = jnp.all(jnp.isfinite(delta), axis=0)
        healthy = (fl & FLAG_NONFINITE) == 0
        fl = fl | jnp.where(healthy & ~ok, FLAG_NONFINITE, 0).astype(jnp.int32)
        delta = jnp.where((healthy & ok)[None, :], delta, 0.0)
        alpha = alpha.at[idx].add(delta)
        # r −= (K[:, idx] + σ²E_idx) Δ: the σ² scatter goes first, on the
        # carried residual, because a mesh-sharded operator may return the
        # fused K[:, idx] @ Δ row-sharded, and a scatter into a sharded array
        # needs an output sharding the solver cannot know
        r = r.at[idx].add(-sigma2 * delta) - op.rows_t_mv(idx, delta)
        return (alpha, r, fl), None

    fl0 = jnp.where(
        jnp.all(jnp.isfinite(r0), axis=0), 0, FLAG_NONFINITE
    ).astype(jnp.int32)
    (alpha, r, fl), _ = jax.lax.scan(step, (a0, r0, fl0), jnp.arange(num_steps))
    # the maintained residual IS b − A α — finalize adds no extra matvec
    return finalize(
        op, alpha, b2, num_steps, squeeze, tol=tol, residual=r, matvecs=init_mv,
        flags=fl,
    )
