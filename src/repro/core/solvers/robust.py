"""Escalation ladder on top of ``solve()`` — structured recovery from solver
breakdown (docs/robustness.md).

The solver loops (cg/sgd/sdd/ap) detect per-column trouble *inside* their
while_loop/scan bodies and report it as ``SolveResult.flags`` — non-finite
residuals, CG breakdown (pᵀAp ≤ 0), stagnation — with flagged columns frozen so
they cannot contaminate the shared multi-RHS matvec. ``solve_robust`` is the
layer that *reacts*: it runs the base solve, reads the flags once (the only
happy-path cost — no extra matvec, no payload re-validation), and walks flagged
columns down a configurable rung sequence:

1. **jitter** — re-solve with a noise bump ε·mean(diag A) added to the system,
   the classic GP Cholesky-retry move (Lin et al.; GPML folklore). Recovery is
   judged against the rung's *own* regularised system (K + σ²I + εI): for a
   near-singular K the residual of the ε-regularised solution measured against
   the original operator is Θ(ε/(σ²+ε)) by construction, so re-measuring there
   would declare every jitter rung a failure — the whole point of the rung is
   to accept the nearby well-posed system, exactly as a jittered Cholesky does.
2. **precondition** — attach/upgrade a Nyström preconditioner (operators with
   the ``precond_factor`` capability) and re-run CG.
3. **switch family** — a stochastic spec (SGD/SDD/AP) that diverged re-runs
   flagged columns under preconditioned CG (step-size-free).
4. **dense fallback** — for n ≤ ``dense_fallback_max_n``, materialise the
   operator and Cholesky-solve, escalating jitter until the factorisation
   succeeds. The unconditional last resort.

Only the flagged columns ride the ladder — healthy columns of a batch keep
their base-solve payload untouched — and every rung taken is recorded in the
returned :class:`SolveReport`. This is the serving engine's poison-request
rescue path (serve/engine.py) and usable directly by library callers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..operators import LinearOperator, supports
from .base import (
    FLAG_STAGNATION,
    FROZEN_FLAGS,
    SolveResult,
    as_matrix_rhs,
    flag_names,
)
from .spec import CG, Jacobi, Nystrom, SpecLike, as_spec, solve


# ---------------------------------------------------------------------------
# Policy and report types
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EscalationPolicy:
    """Configuration of the rung sequence ``solve_robust`` walks.

    All fields are plain static data; the default policy is the full ladder.
    An empty ladder (``jitter=()``, ``switch_to_cg=False``,
    ``dense_fallback_max_n=0``) degrades ``solve_robust`` to "base solve +
    structured report", whose happy-path matvecs ``bench_robust`` counts
    against plain ``solve``.
    """

    #: noise bumps, as multiples of mean(diag A); one rung per entry
    jitter: Tuple[float, ...] = (1e-6, 1e-3)
    #: Nyström rank for the precondition rung (needs ``precond_factor``)
    precond_rank: int = 64
    #: re-run flagged columns of a stochastic solve under CG
    switch_to_cg: bool = True
    #: iteration budget for ladder CG rungs
    cg_max_iters: int = 1000
    #: tolerance for ladder CG rungs; None inherits the spec's own ``tol``
    cg_tol: Optional[float] = None
    #: largest n for which the dense Cholesky fallback is permitted (0 = never)
    dense_fallback_max_n: int = 4096
    #: treat FLAG_STAGNATION columns as escalation candidates (advisory flag)
    escalate_on_stagnation: bool = True
    #: also escalate healthy-but-unconverged columns (off by default: slow
    #: convergence is normal for iteration-budgeted serving solves)
    escalate_on_unconverged: bool = False


@dataclasses.dataclass(frozen=True)
class RungRecord:
    """One rung taken: which columns it attempted and which it recovered."""

    rung: str  # "jitter:1e-06" | "precond:nystrom" | "switch:cg" | "dense:cholesky"
    columns: Tuple[int, ...]  # column indices this rung attempted
    recovered: Tuple[int, ...]  # subset that came back healthy
    flags_before: Tuple[int, ...]  # per attempted column, pre-rung bitmask
    iterations: int
    matvecs: int

    @property
    def flag_names_before(self) -> Tuple[Tuple[str, ...], ...]:
        return tuple(flag_names(m) for m in self.flags_before)


@dataclasses.dataclass(frozen=True)
class SolveReport:
    """What ``solve_robust`` did: the merged result plus the audit trail."""

    result: SolveResult  # merged payload (healthy base columns + rung rescues)
    rungs: Tuple[RungRecord, ...]  # every rung taken, in order (empty = happy path)
    escalated: bool  # any column left the happy path
    recovered: bool  # True iff no column is still flagged after the ladder
    failed_columns: Tuple[int, ...]  # columns still bad after the final rung

    @property
    def ladder(self) -> Tuple[str, ...]:
        return tuple(r.rung for r in self.rungs)


# ---------------------------------------------------------------------------
# The jittered operator wrapper (rung 1)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _JitteredOp(LinearOperator):
    """``inner + eps·I``: the noise-bump wrapper the jitter rungs solve against.

    Only the σ²I split changes — ``noise``/``mv``/``diag_part`` gain ε, while
    the kernel-side capabilities (``rows_mv``/``rows_t_mv``/``block_at``/
    ``precond_factor``/``x``/``params``…) forward untouched via ``__getattr__``:
    the stochastic solvers add ``op.noise`` themselves, so forwarding the raw
    kernel rows is exactly right. ``hasattr`` capability detection follows the
    forwarding, so the wrapper advertises precisely the inner's capability set.
    """

    inner: Any  # the wrapped LinearOperator (a pytree)
    eps: jax.Array  # () the absolute ridge added

    @property
    def shape(self) -> tuple:
        return self.inner.shape

    @property
    def noise(self) -> jax.Array:
        return self.inner.noise + self.eps

    def mv(self, v: jax.Array) -> jax.Array:
        return self.inner.mv(v) + self.eps * v

    def diag_part(self) -> jax.Array:
        return self.inner.diag_part() + self.eps

    def dense(self) -> jax.Array:
        n = self.inner.shape[0]
        return self.inner.dense() + self.eps * jnp.eye(n)

    def prepare_for_solve(self) -> "_JitteredOp":
        # explicit (not via __getattr__): forwarding would return the prepared
        # *inner* and silently drop the jitter
        prep = getattr(self.inner, "prepare_for_solve", None)
        if callable(prep):
            return dataclasses.replace(self, inner=prep())
        return self

    def __getattr__(self, name: str):
        if name.startswith("__") or name in ("inner", "eps"):
            raise AttributeError(name)
        return getattr(object.__getattribute__(self, "inner"), name)


# ---------------------------------------------------------------------------
# solve_robust
# ---------------------------------------------------------------------------


def _bad_mask(res: SolveResult, tol: float, policy: EscalationPolicy) -> np.ndarray:
    """Host-side boolean mask of escalation-candidate columns. One small
    device→host transfer of the (s,) flags vector — the entire happy-path
    cost of ``solve_robust``."""
    fl = np.atleast_1d(jax.device_get(res.flags)).astype(np.int64)
    mask = FROZEN_FLAGS | (FLAG_STAGNATION if policy.escalate_on_stagnation else 0)
    bad = (fl & mask) != 0
    if policy.escalate_on_unconverged:
        rel = np.atleast_1d(np.asarray(jax.device_get(res.rel_residual)))
        bad = bad | ~(rel <= tol)  # NaN-safe: NaN fails the comparison → bad
    return bad


def _pin_backend(op, spec):
    """Replicate solve()'s backend pinning on the *inner* operator, so ladder
    rungs can run with ``backend=None`` specs — ``dataclasses.replace`` on a
    forwarding wrapper would otherwise reject the foreign ``backend`` field."""
    backend = getattr(spec, "backend", None)
    if (
        backend is not None
        and dataclasses.is_dataclass(op)
        and getattr(op, "backend", backend) != backend
    ):
        op = dataclasses.replace(op, backend=backend)
    return op


def _diag_scale(op) -> float:
    return float(jnp.mean(op.diag_part()))


def _ladder(op, spec, policy: EscalationPolicy, key):
    """Yield (rung_name, rung_op, rung_spec) in escalation order. The base
    operator arrives backend-pre-pinned; every rung spec carries
    ``backend=None`` so solve() never tries to replace() a wrapper."""
    scale = None
    cg_tol = policy.cg_tol if policy.cg_tol is not None else float(
        getattr(spec, "tol", 1e-2)
    )
    is_cg = isinstance(spec, CG)
    base_spec = dataclasses.replace(spec, backend=None) if getattr(
        spec, "backend", None
    ) is not None else spec

    for j in policy.jitter:
        if scale is None:
            scale = _diag_scale(op)
        eps = jnp.asarray(j * scale)
        yield f"jitter:{j:g}", _JitteredOp(inner=op, eps=eps), base_spec

    pc_cls = Nystrom if supports(op, "precond_factor") else Jacobi
    pc = pc_cls(rank=policy.precond_rank) if pc_cls is Nystrom else pc_cls()
    if is_cg and getattr(spec, "precond", None) is None:
        yield "precond:" + pc.name, op, dataclasses.replace(
            base_spec, precond=pc, max_iters=max(
                policy.cg_max_iters, base_spec.max_iters
            )
        )
    elif not is_cg and policy.switch_to_cg:
        yield "switch:cg", op, CG(
            max_iters=policy.cg_max_iters, tol=cg_tol, precond=pc
        )


def _dense_rescue(op, b_bad, tol: float, policy: EscalationPolicy):
    """Final rung: materialise + Cholesky, escalating jitter until the
    factorisation holds. Returns (solution, rel, flags, rung_name) or None."""
    n = op.shape[0]
    if n > policy.dense_fallback_max_n or not supports(op, "dense"):
        return None
    a = op.dense()
    if not bool(jnp.all(jnp.isfinite(a))):
        return None  # a poisoned operator has no dense escape
    scale = float(jnp.mean(jnp.diag(a)))
    for j in (0.0,) + tuple(policy.jitter) + (1e-2,):
        aj = a + (j * scale) * jnp.eye(n, dtype=a.dtype)
        l, low = jax.scipy.linalg.cho_factor(aj, lower=True)
        if not bool(jnp.all(jnp.isfinite(l))):
            continue
        x = jax.scipy.linalg.cho_solve((l, low), b_bad)
        # judged against the rung's own (jittered) system, like rung 1
        rn = jnp.linalg.norm(aj @ x - b_bad, axis=0)
        bn = jnp.maximum(jnp.linalg.norm(b_bad, axis=0), 1e-30)
        rel = rn / bn
        ok = jnp.all(jnp.isfinite(x), axis=0) & (rel <= max(tol, 1e-4))
        if bool(jnp.any(ok)):
            flags = jnp.where(ok, 0, FROZEN_FLAGS).astype(jnp.int32)
            return x, rel, flags, f"dense:cholesky(jitter={j:g})"
    return None


def solve_robust(
    op,
    b: jax.Array,
    spec: SpecLike = "cg",
    *,
    key: Optional[jax.Array] = None,
    x0: Optional[jax.Array] = None,
    delta: Optional[jax.Array] = None,
    policy: EscalationPolicy = EscalationPolicy(),
    **overrides: Any,
) -> SolveReport:
    """``solve()`` with breakdown recovery: run the base solve, then walk any
    flagged columns down the escalation ladder.

    Happy path (no flags): exactly one base ``solve()`` plus a single host
    readback of the (s,) flags vector — zero extra matvecs, zero extra O(n·s)
    work (``bench_robust`` gates the matvec equality with plain ``solve``).

    On escalation only the flagged columns are re-solved (cold, per rung);
    healthy columns keep their base payload bit-for-bit. The merged
    ``SolveResult`` in the returned report carries the rescued columns'
    residuals *as judged by the rescuing rung's system* (see module docstring
    for why), cleared flags for recovered columns, and the summed matvec bill.
    Columns no rung could save stay flagged (``report.failed_columns``) so
    callers fail them structurally instead of consuming NaNs.
    """
    s = as_spec(spec, **overrides)
    res = solve(op, b, s, key=key, x0=x0, delta=delta)
    tol = float(getattr(s, "tol", 1e-2))
    bad = _bad_mask(res, tol, policy)
    if not bad.any():
        return SolveReport(
            result=res, rungs=(), escalated=False, recovered=True,
            failed_columns=(),
        )

    b2, squeeze = as_matrix_rhs(jnp.asarray(b))
    d2 = None
    if delta is not None:
        d2 = as_matrix_rhs(jnp.asarray(delta))[0]

    # merged payload, host-mutated column-wise then reassembled
    sol = jnp.atleast_2d(res.solution.T).T if squeeze else res.solution
    sol = jnp.array(sol)
    rn = jnp.atleast_1d(res.residual_norm)
    rel = jnp.atleast_1d(res.rel_residual)
    fl = jnp.atleast_1d(jnp.asarray(res.flags, dtype=jnp.int32))
    total_matvecs = int(jax.device_get(res.matvecs))

    pinned = _pin_backend(op, s)
    rungs = []
    rung_key = key if key is not None else jax.random.PRNGKey(0)

    def _attempt(name, rsol, rrel, rflags, riters, rmv):
        """Merge one rung's output for the currently-bad columns."""
        nonlocal sol, rn, rel, fl, bad, total_matvecs
        cols = np.nonzero(bad)[0]
        rres = SolveResult(
            solution=rsol, residual_norm=rrel * 0.0, rel_residual=rrel,
            iterations=jnp.asarray(riters), converged=jnp.asarray(False),
            matvecs=jnp.asarray(rmv), flags=rflags,
        )
        ok = ~_bad_mask(rres, tol, policy)
        recovered_cols = tuple(int(c) for c, o in zip(cols, ok) if o)
        rungs.append(
            RungRecord(
                rung=name,
                columns=tuple(int(c) for c in cols),
                recovered=recovered_cols,
                flags_before=tuple(
                    int(v) for v in np.asarray(jax.device_get(fl))[cols]
                ),
                iterations=int(riters),
                matvecs=int(rmv),
            )
        )
        total_matvecs += int(rmv)
        if recovered_cols:
            idx = jnp.asarray(recovered_cols)
            src = jnp.asarray([int(np.nonzero(cols == c)[0][0]) for c in recovered_cols])
            sol = sol.at[:, idx].set(rsol[:, src])
            rel = rel.at[idx].set(rrel[src])
            rn = rn.at[idx].set(
                rrel[src] * jnp.maximum(jnp.linalg.norm(b2[:, idx], axis=0), 1e-30)
            )
            fl = fl.at[idx].set(rflags[src])
            bad[np.asarray(recovered_cols)] = False

    for name, rung_op, rung_spec in _ladder(pinned, s, policy, rung_key):
        if not bad.any():
            break
        cols = np.nonzero(bad)[0]
        kb = None
        if rung_key is not None:
            rung_key, kb = jax.random.split(rung_key)
        rres = solve(
            rung_op, b2[:, cols], rung_spec, key=kb,
            delta=None if d2 is None else d2[:, cols],
        )
        _attempt(
            name,
            jnp.atleast_2d(rres.solution.T).T,
            jnp.atleast_1d(rres.rel_residual),
            jnp.atleast_1d(jnp.asarray(rres.flags, dtype=jnp.int32)),
            int(jax.device_get(rres.iterations)),
            int(jax.device_get(rres.matvecs)),
        )

    if bad.any():
        cols = np.nonzero(bad)[0]
        rescue = _dense_rescue(pinned, b2[:, cols], tol, policy)
        if rescue is not None:
            x, rrel, rflags, name = rescue
            _attempt(name, x, rrel, rflags, 0, 0)

    failed = tuple(int(c) for c in np.nonzero(bad)[0])
    merged = SolveResult(
        solution=sol[:, 0] if squeeze else sol,
        residual_norm=rn,
        rel_residual=rel,
        iterations=res.iterations,
        converged=jnp.all((rel <= tol) & (fl == 0)),
        matvecs=jnp.asarray(total_matvecs),
        flags=fl,
    )
    return SolveReport(
        result=merged,
        rungs=tuple(rungs),
        escalated=True,
        recovered=not failed,
        failed_columns=failed,
    )
