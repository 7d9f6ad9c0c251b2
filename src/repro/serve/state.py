"""Fitted posterior state as pytrees + the warm-start cache.

The engine's whole working set is a handful of pytrees — exactly the objects
the core library already produces: representer weights ``v_mean``, per-sample
uncertainty weights ``alpha``, :class:`~repro.core.rff.PriorSamples` pathwise
paths, and a :class:`~repro.core.solvers.spec.SolverSpec`. This module owns

* :class:`PosteriorState` — one fitted posterior, plus the pieces pathwise
  conditioning needs to update it *incrementally*: the prior paths are
  functions evaluable anywhere, so when new observations arrive the RHS of the
  refit solve extends the old one row-wise (old rows keep their stored noise
  draws ``eps`` and cached prior values ``f_x``). Two update paths:
  :func:`extend_state` re-solves the extended system with the old solution,
  zero-padded to the new n, as a strong warm start (Ch. 5 §5.3 — measurably
  fewer iterations than a cold refit); :func:`update_state_lowrank` skips the
  (n+k)-re-solve entirely with a rank-k bordered-system correction whose
  iterative cost is k solve columns at the OLD n;
* :class:`WarmStartCache` — previous solve solutions keyed by
  ``(hyperparameter fingerprint, request kind)`` and, within that, by the
  request seed; a repeat query reuses its previous representer weights as
  ``x0`` and converges in a handful of iterations.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
from collections import OrderedDict
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.kernels_fn import TANIMOTO, KernelParams, gram
from ..core.operators import Gram
from ..core.pathwise import PosteriorFunctions, pathwise_target_rows
from ..core.solvers.base import (
    FLAG_BREAKDOWN,
    FLAG_NONFINITE,
    FLAG_STAGNATION,
    SolveResult,
)
from ..core.rff import PriorSamples, sample_prior
from ..core.solvers.spec import SolverSpec, as_spec, solve, solve_bordered
from ..kernels.tanimoto import check_counts


def hypers_fingerprint(params: KernelParams, n: int) -> str:
    """A hashable identity for 'the linear system being solved'.

    Covers the kernel hyperparameters (values + kind) *and* the training-set
    size n: after ``add_observations`` the operator changes shape, so cached
    solutions keyed under the old fingerprint become unreachable instead of
    surfacing as shape errors inside the solver (see ``_validate_x0``).
    """
    h = hashlib.sha256()
    h.update(params.kind.encode())
    h.update(np.int64(n).tobytes())
    for leaf in jax.tree_util.tree_leaves(
        (params.log_lengthscale, params.log_signal, params.log_noise)
    ):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()[:16]


@dataclasses.dataclass
class PosteriorState:
    """One fitted posterior, held long-lived by the engine.

    ``eps`` (the fit solve's noise draws) and ``f_x`` (the prior paths
    evaluated on the training rows) are retained so incremental refits can
    extend the *same* pathwise linear systems row-wise instead of drawing (or
    re-evaluating) anything over the old rows — that is what makes the old
    solution a useful warm start for :func:`extend_state` and an exactly
    correctable one for :func:`update_state_lowrank`.
    """

    params: KernelParams
    x: jax.Array  # (n, d)
    y: jax.Array  # (n,)
    spec: SolverSpec
    post: PosteriorFunctions  # v_mean, alpha, prior paths — all pytrees
    eps: jax.Array  # (n, s) fit-solve noise draws (pathwise targets)
    f_x: jax.Array  # (n, s) prior paths at x, cached at fit, extended row-wise
    fit_result: SolveResult
    hypers_key: str

    @property
    def n(self) -> int:
        return int(self.x.shape[0])

    @property
    def prior(self) -> PriorSamples:
        return self.post.prior

    def operator(self) -> Gram:
        """The (K + σ²I) operator every serve-time solve runs against."""
        return Gram(x=self.x, params=self.params)


def fit_state(
    params: KernelParams,
    x: jax.Array,
    y: jax.Array,
    key: jax.Array,
    *,
    spec,
    num_samples: int = 16,
    num_features: int = 2048,
    x0: Optional[jax.Array] = None,
    span: Optional[Callable] = None,
) -> PosteriorState:
    """Fit the engine's posterior state: one batched pathwise solve.

    Same math as :func:`~repro.core.pathwise.posterior_functions`, but keeps
    the noise draws ``eps`` so :func:`extend_state` can refit incrementally.
    A ``tanimoto`` posterior refuses inputs that are not integer counts
    0..``MAX_COUNT`` (kernels/tanimoto.py). ``span(phase, **meta)``, if given, is opened as
    ``features`` around the prior's evaluation on x (the engine's spans).
    """
    s = as_spec(spec)
    if params.kind == TANIMOTO:
        check_counts(x)
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    kp, ke, ks = jax.random.split(key, 3)
    op = Gram(x=x, params=params)
    prior = sample_prior(params, kp, num_samples, num_features, x.shape[1])
    span = span or (lambda phase, **meta: contextlib.nullcontext())
    with span("features", kind=params.kind, rows=int(x.shape[0])):
        f_x = prior(x)  # (n, s)
    data, delta, eps = pathwise_target_rows(op.noise, y, f_x, ke)
    res = solve(op, data, s, key=ks, x0=x0, delta=delta)
    sol = res.solution
    post = PosteriorFunctions(
        params=params,
        x=x,
        prior=prior,
        v_mean=sol[:, 0],
        alpha=sol[:, 1:],
        solve_info=res,
    )
    return PosteriorState(
        params=params,
        x=x,
        y=y,
        spec=s,
        post=post,
        eps=eps,
        f_x=f_x,
        fit_result=res,
        hypers_key=hypers_fingerprint(params, x.shape[0]),
    )


def extend_state(
    state: PosteriorState,
    x_new: jax.Array,
    y_new: jax.Array,
    key: jax.Array,
    *,
    warm: bool = True,
) -> PosteriorState:
    """Incremental posterior update: new observations, warm-started refit.

    Pathwise conditioning makes this cheap: the prior paths are functions, so
    ``f_X`` on the extended inputs is the *same* columns with new rows
    appended, old rows keep their stored noise draws, and only the new rows
    draw fresh ones. The refit therefore solves a system whose RHS agrees with
    the old one on the first n rows — the old solution, zero-padded to the new
    n, is the warm start that cuts iterations (measured by the engine's
    ``refit_iterations_saved`` counter and gated in the serve benchmark).
    """
    x_new = jnp.atleast_2d(jnp.asarray(x_new))
    y_new = jnp.atleast_1d(jnp.asarray(y_new))
    x2 = jnp.concatenate([state.x, x_new], axis=0)
    y2 = jnp.concatenate([state.y, y_new], axis=0)
    op = Gram(x=x2, params=state.params)
    prior = state.prior
    ke, ks = jax.random.split(key)
    f_new = prior(x_new)  # same paths, evaluated on the k NEW rows only —
    # old rows reuse the cached state.f_x instead of re-running the fused
    # feature pass over all n of them on every refit
    _, _, eps_new = pathwise_target_rows(op.noise, y_new, f_new, ke)
    eps2 = jnp.concatenate([state.eps, eps_new], axis=0)
    f_x2 = jnp.concatenate([state.f_x, f_new], axis=0)
    data = jnp.concatenate([y2[:, None], f_x2], axis=1)
    delta = jnp.concatenate([jnp.zeros_like(y2)[:, None], eps2 / op.noise], axis=1)
    x0 = None
    if warm:
        old = jnp.concatenate(
            [state.post.v_mean[:, None], state.post.alpha], axis=1
        )
        x0 = jnp.concatenate(
            [old, jnp.zeros((x_new.shape[0], old.shape[1]), dtype=old.dtype)],
            axis=0,
        )
    res = solve(op, data, state.spec, key=ks, x0=x0, delta=delta)
    sol = res.solution
    post = PosteriorFunctions(
        params=state.params,
        x=x2,
        prior=prior,
        v_mean=sol[:, 0],
        alpha=sol[:, 1:],
        solve_info=res,
    )
    return PosteriorState(
        params=state.params,
        x=x2,
        y=y2,
        spec=state.spec,
        post=post,
        eps=eps2,
        f_x=f_x2,
        fit_result=res,
        hypers_key=hypers_fingerprint(state.params, x2.shape[0]),
    )


@jax.jit
def _true_rel_residual(op, sol, rhs):
    """Certification pass: ``rhs - op.mv(sol)`` norms, jitted so the one
    extended-operator matvec costs one solver iteration, not an eager
    dispatch of the whole blocked kernel pipeline."""
    residual = rhs - op.mv(sol)
    rn = jnp.linalg.norm(residual, axis=0)
    bn = jnp.maximum(jnp.linalg.norm(rhs, axis=0), 1e-30)
    return rn, rn / bn


def _or_flags(flags) -> jax.Array:
    """OR-reduce a per-column flag vector to one combined bitmask."""
    f = jnp.atleast_1d(jnp.asarray(flags, dtype=jnp.int32))
    return (
        jnp.max(f & FLAG_NONFINITE)
        | jnp.max(f & FLAG_BREAKDOWN)
        | jnp.max(f & FLAG_STAGNATION)
    )


def update_state_lowrank(
    state: PosteriorState,
    x_new: jax.Array,
    y_new: jax.Array,
    key: jax.Array,
    *,
    z_tol_factor: float = 1e-1,
) -> PosteriorState:
    """Rank-k incremental posterior update via the bordered-system identity.

    Pathwise conditioning makes appending k observations a rank-k correction to
    the representer weights and per-sample uncertainty weights, NOT a fresh
    (n+k)-row solve: all 1+s systems share (K+σ²I), so one k-column solve
    Z = (K_old+σ²I)⁻¹ K(X_old, X_new) against the OLD operator, a dense k×k
    Schur factorization, and closed-form back-substitution extend every column
    of [v_mean | alpha] at once (:func:`~repro.core.solvers.spec.solve_bordered`
    has the algebra). Cost scales with k solve columns at the old n —
    independent of the sample count s — versus :func:`extend_state`'s
    (1+s)-column re-solve at n+k.

    Draw convention matches :func:`extend_state` (``ke, ks = split(key)``; new
    rows' noise draws from ``ke``), so at matching seeds both paths extend the
    *same* linear system and agree to solver tolerance.

    The returned ``fit_result`` is certified against the EXTENDED operator:
    one (n+k)-matvec computes the true residual of the corrected solution
    (accounted in ``matvecs`` on top of the Z solve's), so accumulated drift
    across successive low-rank updates is observable — the engine's ``auto``
    policy compacts (falls back to a full warm refit) when it exceeds the spec
    tolerance budget. The solver, not the cache, certifies freshness.

    ``z_tol_factor``: the back-substitution amplifies Z-solve error by ‖w‖
    (the Schur system's σ²-scaled conditioning), so the k correction columns
    are solved ``z_tol_factor`` TIGHTER than the state's spec tolerance. The
    premium is cheap: the Z columns are smooth kernel columns, which CG
    contracts roughly twice as fast as the fit system's noise-bearing RHS, so
    even the tightened solve stays strictly below a warm full refit's
    iterations. The default 1e-1 keeps the certified residual at ~1.5× the
    spec tol per update in the serving regime (measured in ``bench_serve``'s
    write-heavy section); successive updates stack drift until the engine's
    ``auto`` budget (``compaction_tol_factor`` × tol) forces a compaction.
    """
    x_new = jnp.atleast_2d(jnp.asarray(x_new))
    y_new = jnp.atleast_1d(jnp.asarray(y_new))
    x2 = jnp.concatenate([state.x, x_new], axis=0)
    y2 = jnp.concatenate([state.y, y_new], axis=0)
    op_old = state.operator()
    prior = state.prior
    ke, ks = jax.random.split(key)
    f_new = prior(x_new)  # same paths, new rows only (f_x is cached)
    data_new, delta_new, eps_new = pathwise_target_rows(
        op_old.noise, y_new, f_new, ke
    )
    rhs_new = data_new + op_old.noise * delta_new  # [y_new | f_new + eps_new]
    sol_old = jnp.concatenate(
        [state.post.v_mean[:, None], state.post.alpha], axis=1
    )
    b_cols = gram(state.params, state.x, x_new)  # (n, k) cross-covariance
    c_new = gram(state.params, x_new)  # (k, k), noise added inside the helper
    tol = float(getattr(state.spec, "tol", 1e-2))
    z_spec = (
        dataclasses.replace(state.spec, tol=tol * z_tol_factor)
        if dataclasses.is_dataclass(state.spec)
        else state.spec
    )
    sol_ext, z_result = solve_bordered(
        op_old, b_cols, c_new, rhs_new, sol_old, z_spec, key=ks
    )

    # certify the corrected solution against the EXTENDED operator: one
    # (n+k)-matvec gives the TRUE residual, so the result's convergence story
    # is as honest as a full refit's — drift from the inherited r_old and the
    # inexact Z shows up here instead of silently accumulating
    op2 = Gram(x=x2, params=state.params)
    eps2 = jnp.concatenate([state.eps, eps_new], axis=0)
    f_x2 = jnp.concatenate([state.f_x, f_new], axis=0)
    rhs_ext = jnp.concatenate([y2[:, None], f_x2 + eps2], axis=1)
    rn, rel = _true_rel_residual(op2, sol_ext, rhs_ext)
    # any frozen Z column poisons every output column through Z·w — carry the
    # correction solve's flags onto all of them, plus the final payload check
    carried = _or_flags(z_result.flags)
    col_ok = jnp.all(jnp.isfinite(sol_ext), axis=0) & jnp.isfinite(rn)
    flags = jnp.broadcast_to(carried, rel.shape).astype(jnp.int32)
    flags = flags | jnp.where(col_ok, 0, FLAG_NONFINITE).astype(jnp.int32)
    flags = jnp.where((rel <= tol) & col_ok, flags & ~FLAG_STAGNATION, flags)
    res = SolveResult(
        solution=sol_ext,
        residual_norm=rn,
        rel_residual=rel,
        iterations=z_result.iterations,  # k correction columns at the old n
        converged=jnp.all((rel <= tol) & (flags == 0)),
        matvecs=jnp.asarray(z_result.matvecs) + 1,  # + the certification matvec
        flags=flags,
    )
    post = PosteriorFunctions(
        params=state.params,
        x=x2,
        prior=prior,
        v_mean=sol_ext[:, 0],
        alpha=sol_ext[:, 1:],
        solve_info=res,
    )
    return PosteriorState(
        params=state.params,
        x=x2,
        y=y2,
        spec=state.spec,
        post=post,
        eps=eps2,
        f_x=f_x2,
        fit_result=res,
        hypers_key=hypers_fingerprint(state.params, x2.shape[0]),
    )


class WarmStartCache:
    """Previous solve solutions, keyed by ``(hypers fingerprint, request kind)``
    and — within a key — by the request seed that generated the RHS columns.

    A repeat query (same seed, same hyperparameters, same kind) regenerates the
    exact same RHS columns, so its cached solution is a near-exact warm start:
    CG re-verifies it in a couple of iterations instead of re-solving — the
    serving analogue of Ch. 5's warm-started MLL inner solves, but still every
    bit as fresh (the solver, not the cache, certifies the residual).

    Plain LRU over ``(key, seed)`` entries; values are host-side numpy copies
    so cached solutions never pin device buffers.
    """

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._entries: OrderedDict[Tuple[str, str, int], np.ndarray] = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def probe(self, hypers_key: str, kind: str, seed: int) -> bool:
        """Non-mutating hit test (used at submit time to tag requests warm)."""
        return (hypers_key, kind, seed) in self._entries

    def lookup(
        self, hypers_key: str, kind: str, seed: int
    ) -> Optional[np.ndarray]:
        entry = self._entries.get((hypers_key, kind, seed))
        if entry is not None:
            self._entries.move_to_end((hypers_key, kind, seed))
        return entry

    def store(
        self, hypers_key: str, kind: str, seed: int, solution: jax.Array
    ) -> None:
        self._entries[(hypers_key, kind, seed)] = np.asarray(solution)
        self._entries.move_to_end((hypers_key, kind, seed))
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)

    def purge(self, hypers_key: str) -> int:
        """Drop every entry NOT keyed under ``hypers_key``; returns the count.

        After a refit re-keys the engine, entries under a superseded
        fingerprint are permanently unreachable (probes and lookups always use
        the live key) yet still occupy LRU slots until natural eviction —
        crowding out warm starts that could actually hit. The engine calls
        this on every re-key and surfaces the count as ``cache_purged``.
        """
        stale = [k for k in self._entries if k[0] != hypers_key]
        for k in stale:
            del self._entries[k]
        return len(stale)
