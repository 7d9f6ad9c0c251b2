"""Pathwise conditioning (§2.1.2, Eq. 2.12) driven by iterative solvers (Ch. 3–4).

A posterior function sample is a *function*

    f_|y(·) = f(·) + K_(·)X (v* − α*_i),
        v*   = (K+σ²I)⁻¹ y                  (posterior-mean representer weights)
        α*_i = (K+σ²I)⁻¹ (f_X^i + ε_i)      (per-sample uncertainty-reduction weights)

with f a prior sample approximated by random Fourier features. All s+1 linear systems
share the coefficient matrix, so they are solved as ONE batched multi-RHS call to any
solver in core/solvers (this batch is also where Ch. 5's probe vectors ride along —
see core/mll.py). Evaluating the result at new X* costs one kernel matvec: one solve
per *sample*, not per location — the property that makes Thompson sampling and BO
tractable (§3.3.2).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.ops import gram_mv
from .kernels_fn import KernelParams
from .rff import PriorSamples, sample_prior
from .solvers.base import Gram, SolveResult
from .solvers.spec import SpecLike, as_spec, solve


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PosteriorFunctions:
    """s posterior function samples + the posterior mean, evaluable anywhere.

    Evaluation is one fused prior-feature matvec Φ(·) @ W plus one
    cross-covariance matvec K(·, X) @ [weights], both through the same backend
    that drove the solve — neither the (n*, 2m) feature matrix nor the (n*, n)
    cross-Gram block is ever materialised, and both paths carry custom VJPs, so
    Thompson sampling's Adam ascent differentiates straight through the fused
    kernels by default on TPU.
    """

    params: KernelParams
    x: jax.Array  # (n, d) training inputs
    prior: PriorSamples  # s prior functions
    v_mean: jax.Array  # (n,) representer weights of the mean
    alpha: jax.Array  # (n, s) per-sample uncertainty-reduction weights
    solve_info: Optional[SolveResult] = None
    backend: str = dataclasses.field(default="auto", metadata=dict(static=True))

    @property
    def num_samples(self) -> int:
        return self.alpha.shape[1]

    def mean(self, xs: jax.Array) -> jax.Array:
        return gram_mv(self.params, xs, self.v_mean, z=self.x, backend=self.backend)

    def __call__(self, xs: jax.Array) -> jax.Array:
        """Evaluate all samples at xs → (n*, s)."""
        w = self.v_mean[:, None] - self.alpha  # (n, s)
        return self.prior(xs) + gram_mv(
            self.params, xs, w, z=self.x, backend=self.backend
        )

    def sample_mean_and_var(
        self, xs: jax.Array, f_prior: Optional[jax.Array] = None
    ) -> tuple[jax.Array, jax.Array]:
        """Mean and sample variance at xs. One cross-covariance matvec serves
        both, over the columns ``[v_mean | v_mean − alpha]``; ``f_prior`` is
        ``self.prior(xs)`` when the caller has evaluated it already."""
        f_prior = self.prior(xs) if f_prior is None else f_prior
        cols = jnp.concatenate(
            [self.v_mean[:, None], self.v_mean[:, None] - self.alpha], axis=1
        )
        k = gram_mv(self.params, xs, cols, z=self.x, backend=self.backend)
        return k[:, 0], jnp.var(f_prior + k[:, 1:], axis=1)

    def blocked_mean_and_var(
        self, xs_blocks: jax.Array, f_prior: Optional[jax.Array] = None
    ) -> tuple[jax.Array, jax.Array]:
        """Batched query path: many query blocks through ONE fused pass.

        ``xs_blocks`` is ``(B, L, d)`` — B queries padded to a common block
        length L (the serving engine's fixed bucket shapes). The blocks are
        flattened into a single ``(B·L, d)`` evaluation, so one fused
        cross-covariance matvec and one prior feature matvec serve all B
        queries, and the results are reshaped back to ``(B, L)`` mean and
        variance. Padding rows cost flops but not correctness — callers slice
        their valid prefix. ``f_prior``: the prior paths at the flattened rows,
        if the caller evaluated them.
        """
        b, l, d = xs_blocks.shape
        mean, var = self.sample_mean_and_var(xs_blocks.reshape(b * l, d), f_prior)
        return mean.reshape(b, l), var.reshape(b, l)

    def sample_paths(
        self, xs: jax.Array, w_prior: jax.Array, alpha: jax.Array
    ) -> jax.Array:
        """Evaluate *fresh* posterior sample paths at ``xs`` → (n*, s).

        A fresh pathwise sample is defined by new prior weight columns
        ``w_prior`` (num_features, s) on this posterior's feature map and the
        solved uncertainty-reduction weights ``alpha`` (n, s) for the targets
        ``Φ(X) w_prior + ε`` (the serving engine batches those solves across
        requests):

            f_|y(·) = Φ(·) w_prior + K(·, X) (v_mean − alpha)

        Zero columns are exact mean paths (zero prior weights, zero alpha), so
        bucket-padded weight columns evaluate to the posterior mean and slice
        off cleanly.
        """
        w = self.v_mean[:, None] - alpha  # (n, s)
        return self.prior.phi_mv(xs, w_prior) + gram_mv(
            self.params, xs, w, z=self.x, backend=self.backend
        )


def pathwise_target_rows(
    noise,
    y_rows: jax.Array,
    f_rows: jax.Array,
    key: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pathwise target rows for ONE row block, in ``solve()``'s (b, δ) convention.

    Returns (data (m, 1+s), delta (m, 1+s), eps (m, s)) with data =
    [y | f_X^1 .. f_X^s] and δ = [0 | ε_1/σ² .. ε_s/σ²]; ε is drawn fresh from
    ``key``. Because the targets are row-local (each row only needs its own
    prior-path value and noise draw), appending k observations appends k target
    rows: ``fit_state`` builds the whole system from one call over all n rows,
    while ``extend_state``/``update_state_lowrank`` call it on just the k new
    rows and keep the old rows' stored draws — which is exactly what makes the
    old solution a valid warm start / low-rank-correctable solution.
    """
    eps = jnp.sqrt(noise) * jax.random.normal(key, f_rows.shape, dtype=f_rows.dtype)
    data = jnp.concatenate([y_rows[:, None], f_rows], axis=1)
    delta = jnp.concatenate([jnp.zeros_like(y_rows)[:, None], eps / noise], axis=1)
    return data, delta, eps


def pathwise_targets(
    op: Gram,
    y: jax.Array,
    prior: PriorSamples,
    key: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Batched targets for the pathwise solve in ``solve()``'s (b, δ) convention.

    Returns (data (n, 1+s), delta (n, 1+s)) with data = [y | f_X^1 .. f_X^s] and
    δ = [0 | ε_1/σ² .. ε_s/σ²]: the system solved is (K+σ²I)V = data + σ²δ =
    [y | f_X+ε]. Keeping ε in the δ channel lets SGD apply the Eq. 3.6
    variance-reduction shift; every other solver folds it into the RHS.
    """
    # prior defaults to backend="auto": fused RFF matvec on TPU, features on CPU
    f_x = prior(op.x)  # (n, s)
    data, delta, _ = pathwise_target_rows(op.noise, y, f_x, key)
    return data, delta


def posterior_functions(
    params: KernelParams,
    x: jax.Array,
    y: jax.Array,
    key: jax.Array,
    *,
    num_samples: int = 16,
    num_features: int = 2048,
    spec: Optional[SpecLike] = None,
    x0: Optional[jax.Array] = None,
    **spec_overrides,
) -> PosteriorFunctions:
    """End-to-end pathwise posterior: RFF prior + one batched iterative solve.

    ``spec`` is any registered :class:`~repro.core.solvers.spec.SolverSpec`
    (instance, class, or name like ``"sdd"``); defaults to CG. Extra keyword
    arguments are spec-field overrides (``spec="cg", max_iters=50``).
    """
    s = as_spec("cg" if spec is None else spec, **spec_overrides)
    backend = getattr(s, "backend", None) or "auto"
    kp, ke, ks = jax.random.split(key, 3)
    op = Gram(x=x, params=params, backend=backend)
    prior = sample_prior(params, kp, num_samples, num_features, x.shape[1])
    data, delta = pathwise_targets(op, y, prior, ke)
    res = solve(op, data, s, key=ks, x0=x0, delta=delta)
    sol = res.solution
    return PosteriorFunctions(
        params=params,
        x=x,
        prior=prior,
        v_mean=sol[:, 0],
        alpha=sol[:, 1:],
        solve_info=res,
        backend=backend,
    )
