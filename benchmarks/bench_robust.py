"""Guardrail-overhead benchmark: ``solve_robust`` must be free on the happy
path and effective on the broken one (``docs/robustness.md``).

Three row families in ``BENCH_bench_robust.json``:

* ``robust_overhead`` — plain ``solve`` vs ``solve_robust`` on a
  well-conditioned system: identical matvec counts (the in-loop health checks
  reuse reductions the solvers already compute; the ladder adds one host
  readback of the (s,) flags vector). Matvec equality is the structural gate
  ``check_matvecs.py --robust-baseline`` enforces.
* ``robust_recovery`` — the near-singular stagnation problem: the ladder
  recovers every flagged column and the row records which rungs it took and
  what the rescue cost in matvecs.
* ``robust_failure`` — a poisoned (NaN) RHS: every rung declines, the report
  is a structured failure, and the healthy columns' payloads survive intact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import EscalationPolicy, Gram, make_params, solve, solve_robust
from repro.testing import nan_columns, near_singular_problem

from .common import Report

#: gated workload shape — keep in lockstep with the committed baseline.
#: s=16 is a serving-realistic RHS width (the engine buckets columns to
#: powers of two).
N, D_IN, S = 512, 3, 16
SPEC_KW = dict(max_iters=120, tol=1e-4)


def _happy_problem():
    key = jax.random.PRNGKey(0)
    kx, kb = jax.random.split(key)
    x = jax.random.uniform(kx, (N, D_IN))
    params = make_params("matern32", lengthscale=0.5, signal=1.0, noise=0.1,
                         d=D_IN)
    return Gram(x=x, params=params), jax.random.normal(kb, (N, S))


def run(report: Report, full: bool = False):
    op, b = _happy_problem()

    # ---- happy path: the guardrails must cost nothing ----------------------
    plain = solve(op, b, "cg", **SPEC_KW)
    robust = solve_robust(op, b, "cg", **SPEC_KW)
    assert not robust.escalated, "happy-path problem escalated — bench invalid"
    plain_mv, robust_mv = int(plain.matvecs), int(robust.result.matvecs)
    report.add(
        "robust_overhead", "plain", f"n={N} s={S}", matvecs=plain_mv,
    )
    report.add(
        "robust_overhead", "robust", f"n={N} s={S}",
        matvecs=robust_mv, matvecs_equal=int(plain_mv == robust_mv),
    )

    # ---- recovery: near-singular stagnation rides the ladder home ----------
    op_ns, b_ns, _, _ = near_singular_problem(96, 3)
    rep = solve_robust(
        op_ns, b_ns, "cg", max_iters=200, tol=1e-6, stall_window=30,
        policy=EscalationPolicy(),
    )
    report.add(
        "robust_recovery", "ladder", "near_singular n=96",
        recovered=int(rep.recovered),
        rungs=len(rep.rungs),
        failed_columns=len(rep.failed_columns),
        matvecs=int(rep.result.matvecs),
        ladder=" > ".join(rep.ladder),
    )

    # ---- structured failure: a poisoned RHS fails loudly, not silently -----
    rep_bad = solve_robust(op, nan_columns(b, (1,)), "cg", **SPEC_KW)
    healthy_ok = bool(
        jnp.array_equal(rep_bad.result.solution[:, 0], plain.solution[:, 0])
    )
    report.add(
        "robust_failure", "nan_rhs", f"n={N} s={S}",
        escalated=int(rep_bad.escalated),
        failed_columns=len(rep_bad.failed_columns),
        healthy_columns_intact=int(healthy_ok),
    )
