"""Matvec/iteration-count regression gate (CI step, CPU only): run bench_solvers and
bench_mll in smoke mode and fail if any counted total exceeds its committed
baseline (``results/BENCH_bench_solvers.json``, ``results/BENCH_bench_mll.json``).

Matvec counts are the structural perf guarantee of the solver layer (CG spends
exactly one matvec per iteration, SGD/SDD exactly one, AP zero — see
``docs/solvers.md``); a refactor that silently reintroduces an A·0 warm-start
residual or a recomputed finalize residual shows up here as counts drifting
above the baseline, long before wall-clock noise would reveal it. The bench_mll
gate adds the Ch. 5 claim: warm-started MLL optimisation totals *fewer* inner
CG iterations — a change that breaks warm starting (or the pathwise probe
batching) inflates ``solver_iters`` far past the slack. Smoke modes keep the
committed problem sizes, PRNG keys and CG specs (so iteration counts are
comparable) and only cut work the gate does not compare.

The serve gate extends the same idea to the serving engine (``bench_serve``):
its batched-solve matvec count must stay within the committed baseline (the
whole point of coalescing D requests is ONE solve's worth of matvecs), and the
warm resubmission row must use strictly fewer solver iterations than the cold
row — a broken warm-start cache (stale keying, dropped x0) shows up here as
warm == cold. With ``--refit`` it also gates the write-heavy rows: the rank-k
incremental update (``update_state_lowrank``) must spend strictly fewer
column-matvecs than the warm full refit at k ≪ n, with certified drift and
posterior mean/variance parity vs the full refit both under the 1e-4 serving
bound — a regression that re-solves the world on ``add_observations`` (or
breaks the bordered algebra) fails here.

The robust gate (``bench_robust``) closes the loop on the guardrail work
(``docs/robustness.md``): ``solve_robust`` on a healthy system must spend
*exactly* the same matvecs as plain ``solve`` (the in-loop health checks reuse
reductions the solvers already compute; the ladder's only happy-path cost is
one host readback of the flags vector), the near-singular recovery row must
still recover, and the measured wall overhead must stay under a loose
anti-regression bound (the committed <2% number comes from ``bench_robust``
itself; the CI bound is wider because container timing is noisy).

The distributed gate (``bench_distributed``) guards the comm-strategy work
(``docs/distributed.md``): solver matvec counts per comm strategy are gated
*exactly* (they are budget-determined — CG pinned below convergence, SGD's one
finalize residual, AP's zero), the per-matvec collective schedule counted in
the jaxpr must not exceed the committed baseline, the ring matvec must stage
zero ``all_gather``, and distributed SGD must trace zero materialised-feature
dispatches (the (n, 2q) matrix never exists). The measurements run in a forced
4-device subprocess so the mesh doesn't leak into this process's jax.

Two further gates ride on the same smoke run:

* **wall-clock per iteration** — bench_solvers times a 200-step stochastic
  solve probe and reports ``us_per_iter`` for SGD/SDD per dataset; the gate
  fails if a fresh probe exceeds the committed number by more than
  ``--walltime-slack`` (default 1.0 → 2× headroom: container timing is noisy,
  the gate only catches step-cost blowups like a de-fused pair step or a
  re-scalarised covariance map, not percent-level drift). ``--skip-walltime``
  for machines whose timing is incomparable to the committed baseline's.
* **autotune-table freshness** — the committed ``results/AUTOTUNE_gram.json``
  (the ``block="auto"`` lookup table, kernels/autotune.py) must cover exactly
  the shape grid the resolver expects; growing the grid without re-running
  ``bench_gram_kernel`` (which emits the artifact) fails here instead of
  silently falling back to the heuristic for the new keys.

Usage:
    PYTHONPATH=src python -m benchmarks.check_matvecs \
        [--baseline results/BENCH_bench_solvers.json] \
        [--mll-baseline results/BENCH_bench_mll.json | --skip-mll] \
        [--serve-baseline results/BENCH_bench_serve.json | --skip-serve] \
        [--refit] \
        [--robust-baseline results/BENCH_bench_robust.json | --skip-robust] \
        [--distributed-baseline results/BENCH_bench_distributed.json | --skip-distributed] \
        [--autotune-table results/AUTOTUNE_gram.json | --skip-autotune] \
        [--slack 0.15] [--walltime-slack 1.0 | --skip-walltime]

``--slack`` tolerates small cross-platform jitter (fp32 reduction order):
measured > ceil(baseline · (1 + slack)) fails.

Run it with ``JAX_PLATFORMS=cpu`` and not on the chip machine: its baselines
are XLA-CPU counts and timings, and its distributed gate starts a child
process on forced host-platform devices, which cannot share a chip with a
parent that already holds it. The chip run is ``python chip_smoke.py``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys

from repro.kernels import autotune

from . import bench_distributed, bench_mll, bench_robust, bench_serve, bench_solvers
from .common import Report


def _metric_rows(rows, metric: str) -> dict:
    """{(table, method, dataset): value} for rows that report ``metric``."""
    out = {}
    for r in rows:
        metrics = r["metrics"] if isinstance(r, dict) else r.metrics
        if metric in metrics:
            key = tuple(
                (r[k] if isinstance(r, dict) else getattr(r, k))
                for k in ("table", "method", "dataset")
            )
            out[key] = int(metrics[metric])
    return out


def _gate(name: str, baseline: dict, measured: dict, slack: float) -> tuple:
    """Compare measured counts against the baseline; returns (compared, failures)."""
    compared = 0
    failures = []
    print(f"\n{name} gate (slack {slack:.0%}):")
    for key, base in sorted(baseline.items()):
        if key not in measured:
            continue
        compared += 1
        allowed = math.ceil(base * (1.0 + slack))
        got = measured[key]
        status = "ok" if got <= allowed else "REGRESSION"
        print(f"  {'/'.join(key):45s} baseline={base:4d} allowed={allowed:4d} "
              f"measured={got:4d}  {status}")
        if got > allowed:
            failures.append((key, base, got))
    return compared, failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--baseline", default="results/BENCH_bench_solvers.json",
        help="committed bench_solvers JSON to gate matvec counts against",
    )
    ap.add_argument(
        "--mll-baseline", default="results/BENCH_bench_mll.json",
        help="committed bench_mll JSON to gate warm-start iteration totals against",
    )
    ap.add_argument(
        "--skip-mll", action="store_true",
        help="gate bench_solvers matvec counts only",
    )
    ap.add_argument(
        "--serve-baseline", default="results/BENCH_bench_serve.json",
        help="committed bench_serve JSON to gate batched-solve matvecs against",
    )
    ap.add_argument(
        "--skip-serve", action="store_true",
        help="skip the serving-engine gate",
    )
    ap.add_argument(
        "--refit", action="store_true",
        help="also gate the write-heavy serve_refit rows: the rank-k "
        "incremental update's column-matvec spend vs the committed baseline, "
        "strictly below the full warm refit on the fresh run, with certified "
        "drift and lowrank-vs-full posterior parity under the 1e-4 serving "
        "bound (requires the serve gate)",
    )
    ap.add_argument(
        "--robust-baseline", default="results/BENCH_bench_robust.json",
        help="committed bench_robust JSON to gate guardrail matvecs against",
    )
    ap.add_argument(
        "--skip-robust", action="store_true",
        help="skip the solver-guardrail gate",
    )
    ap.add_argument(
        "--robust-overhead-pct", type=float, default=10.0,
        help="max measured happy-path wall overhead of solve_robust (loose "
        "CI bound; the committed <2%% number lives in bench_robust itself)",
    )
    ap.add_argument(
        "--distributed-baseline",
        default="results/BENCH_bench_distributed.json",
        help="committed bench_distributed JSON: solver matvec counts per comm "
        "strategy are gated EXACTLY (zero slack — they are budget-determined), "
        "collectives-per-matvec must not exceed the baseline, and the fresh "
        "run must show zero all_gather on the ring path and zero "
        "materialised-feature traces in distributed SGD",
    )
    ap.add_argument(
        "--skip-distributed", action="store_true",
        help="skip the distributed comm-strategy gate (spawns a forced "
        "4-device subprocess)",
    )
    ap.add_argument(
        "--slack", type=float, default=0.15,
        help="fractional headroom over the baseline before failing",
    )
    ap.add_argument(
        "--walltime-slack", type=float, default=1.0,
        help="fractional headroom on the per-iteration wall-clock gate "
        "(default 1.0 → measured may be up to 2× the committed us_per_iter; "
        "generous on purpose — the gate catches step-cost blowups, not noise)",
    )
    ap.add_argument(
        "--skip-walltime", action="store_true",
        help="skip the wall-clock-per-iteration gate (incomparable hardware)",
    )
    ap.add_argument(
        "--autotune-table", default=autotune.DEFAULT_TABLE_PATH,
        help="committed block-autotune table whose keys must match the "
        "resolver's expected shape grid",
    )
    ap.add_argument(
        "--skip-autotune", action="store_true",
        help="skip the autotune-table freshness gate",
    )
    args = ap.parse_args(argv)
    if args.refit and args.skip_serve:
        print("ERROR: --refit gates bench_serve rows and cannot be combined "
              "with --skip-serve", file=sys.stderr)
        return 2

    with open(args.baseline) as f:
        base_matvecs = _metric_rows(json.load(f)["rows"], "matvecs")
    if not base_matvecs:
        print(f"ERROR: no matvec counts in {args.baseline}", file=sys.stderr)
        return 2

    report = Report()
    bench_solvers.run(report, full=False, smoke=True)
    compared, failures = _gate(
        f"matvecs vs {args.baseline}",
        base_matvecs, _metric_rows(report.rows, "matvecs"), args.slack,
    )
    if compared == 0:
        # each gate must compare > 0 rows, or a label drift between the bench
        # and its committed baseline would silently void the gate
        print("ERROR: no comparable matvec rows between baseline and smoke run",
              file=sys.stderr)
        return 2

    if not args.skip_walltime:
        with open(args.baseline) as f:
            base_walltime = _metric_rows(json.load(f)["rows"], "us_per_iter")
        if not base_walltime:
            print(f"ERROR: no us_per_iter rows in {args.baseline} — regenerate "
                  "it with benchmarks.run --only bench_solvers (or pass "
                  "--skip-walltime)", file=sys.stderr)
            return 2
        c_wt, f_wt = _gate(
            f"us_per_iter vs {args.baseline}",
            base_walltime, _metric_rows(report.rows, "us_per_iter"),
            args.walltime_slack,
        )
        if c_wt == 0:
            print("ERROR: no comparable us_per_iter rows between baseline and "
                  "smoke run", file=sys.stderr)
            return 2
        compared += c_wt
        failures += f_wt

    if not args.skip_autotune:
        committed = set(autotune.load_table(args.autotune_table))
        expected = autotune.expected_keys()
        missing = sorted(expected - committed)
        extra = sorted(committed - expected)
        print(f"\nautotune freshness gate ({args.autotune_table}):")
        print(f"  expected {len(expected)} keys, committed {len(committed)}  "
              f"{'ok' if not (missing or extra) else 'STALE'}")
        compared += 1
        if missing or extra:
            for k in missing[:8]:
                print(f"  missing: {k}", file=sys.stderr)
            for k in extra[:8]:
                print(f"  extra:   {k}", file=sys.stderr)
            print("  the committed table's shape grid drifted from "
                  "kernels/autotune.py — re-run benchmarks.run --only "
                  "bench_gram_kernel to regenerate it", file=sys.stderr)
            failures.append((("autotune", "table", "keys"),
                             len(expected), len(committed)))

    if not args.skip_mll:
        with open(args.mll_baseline) as f:
            base_iters = _metric_rows(json.load(f)["rows"], "solver_iters")
        if not base_iters:
            print(f"ERROR: no solver_iters in {args.mll_baseline}", file=sys.stderr)
            return 2
        mll_report = Report()
        bench_mll.run(mll_report, full=False, smoke=True)
        c2, f2 = _gate(
            f"mll solver_iters vs {args.mll_baseline}",
            base_iters, _metric_rows(mll_report.rows, "solver_iters"), args.slack,
        )
        if c2 == 0:
            print("ERROR: no comparable solver_iters rows between mll baseline "
                  "and smoke run", file=sys.stderr)
            return 2
        compared += c2
        failures += f2

    if not args.skip_serve:
        with open(args.serve_baseline) as f:
            serve_rows = json.load(f)["rows"]
        base_serve = {
            k: v for k, v in _metric_rows(serve_rows, "matvecs").items()
            if k[0] == "serve_solve"
        }
        if not base_serve:
            print(f"ERROR: no serve_solve matvecs in {args.serve_baseline}",
                  file=sys.stderr)
            return 2
        serve_report = Report()
        bench_serve.run(serve_report, full=False, smoke=True)
        c3, f3 = _gate(
            f"serve matvecs vs {args.serve_baseline}",
            base_serve, _metric_rows(serve_report.rows, "matvecs"), args.slack,
        )
        if c3 == 0:
            print("ERROR: no comparable serve_solve rows between serve "
                  "baseline and smoke run", file=sys.stderr)
            return 2
        compared += c3
        failures += f3
        # warm resubmissions must beat cold solves on iterations, in the fresh
        # run itself — this is a structural property, not a baseline diff
        warm_iters = _metric_rows(serve_report.rows, "iterations")
        cold = {k: v for k, v in warm_iters.items()
                if k[0] == "serve_warmstart" and k[1] == "cold"}
        warm = {(t, "warm", d): warm_iters.get((t, "warm", d))
                for (t, _, d) in cold}
        print("\nserve warm-start gate:")
        for (t, _, d), base in sorted(cold.items()):
            got = warm[(t, "warm", d)]
            status = "ok" if got is not None and got < base else "REGRESSION"
            print(f"  {t}/{d:24s} cold={base:4d} warm={got!s:>4s}  {status}")
            compared += 1
            if status != "ok":
                failures.append(((t, "warm", d), base, got))

        if args.refit:
            # committed-baseline gate on the write-heavy rows: the rank-k
            # update's column-matvec spend (k solve columns at the old n + one
            # certification pass) must not drift above the committed numbers
            base_refit = {
                k: v
                for k, v in _metric_rows(serve_rows, "matvec_columns").items()
                if k[0] == "serve_refit"
            }
            if not base_refit:
                print(f"ERROR: no serve_refit matvec_columns rows in "
                      f"{args.serve_baseline} — regenerate it with "
                      "benchmarks.run --only bench_serve", file=sys.stderr)
                return 2
            c_r, f_r = _gate(
                f"serve refit matvec_columns vs {args.serve_baseline}",
                base_refit,
                _metric_rows(serve_report.rows, "matvec_columns"), args.slack,
            )
            if c_r == 0:
                print("ERROR: no comparable serve_refit rows between baseline "
                      "and smoke run", file=sys.stderr)
                return 2
            compared += c_r
            failures += f_r
            # structural gates on the fresh run itself: for k ≪ n the rank-k
            # path must spend strictly fewer column-matvecs than the full warm
            # refit (its spend is s-independent; the refit pays 1+s columns
            # every iteration), its certified drift against the extended
            # operator must stay under the 1e-4 serving bound, and its
            # posterior must match the full refit to the same bound
            fresh = {r.method: r.metrics for r in serve_report.rows
                     if r.table == "serve_refit"}
            lo_m, fu_m = fresh.get("lowrank"), fresh.get("full-warm")
            if lo_m is None or fu_m is None:
                print("ERROR: fresh run missing serve_refit lowrank/full-warm "
                      "rows", file=sys.stderr)
                return 2
            print("\nserve refit structural gate:")
            for name, got, ok in (
                ("lowrank_below_full_matvec_columns",
                 int(lo_m["matvec_columns"]),
                 int(lo_m["matvec_columns"]) < int(fu_m["matvec_columns"])),
                ("certified_drift", float(lo_m["rel_residual"]),
                 float(lo_m["rel_residual"]) <= 1e-4),
                ("posterior_mean_parity", float(lo_m["mean_err"]),
                 float(lo_m["mean_err"]) <= 1e-4),
                ("posterior_var_parity", float(lo_m["var_err"]),
                 float(lo_m["var_err"]) <= 1e-4),
            ):
                print(f"  {name}={got:g}  {'ok' if ok else 'REGRESSION'}")
                compared += 1
                if not ok:
                    failures.append((("serve_refit", "lowrank", name), 0,
                                     int(got) if got >= 1 else 1))

    if not args.skip_robust:
        with open(args.robust_baseline) as f:
            base_robust = _metric_rows(json.load(f)["rows"], "matvecs")
        if not base_robust:
            print(f"ERROR: no matvec counts in {args.robust_baseline}",
                  file=sys.stderr)
            return 2
        robust_report = Report()
        bench_robust.run(robust_report, full=False, smoke=True)
        c4, f4 = _gate(
            f"robust matvecs vs {args.robust_baseline}",
            base_robust, _metric_rows(robust_report.rows, "matvecs"),
            args.slack,
        )
        if c4 == 0:
            print("ERROR: no comparable robust rows between baseline and "
                  "smoke run", file=sys.stderr)
            return 2
        compared += c4
        failures += f4
        # structural gates on the fresh run itself (baseline-independent):
        # guardrails must be matvec-free on the happy path, the ladder must
        # still recover the near-singular problem, and the wall overhead must
        # stay under the loose CI bound
        print("\nrobust guardrail gate:")
        for r in robust_report.rows:
            m = r.metrics
            if r.table == "robust_overhead" and r.method == "robust":
                eq = bool(m.get("matvecs_equal"))
                oh = float(m.get("overhead_pct", 0.0))
                oh_ok = oh <= args.robust_overhead_pct
                print(f"  matvecs_equal={int(eq)}  overhead_pct={oh:.2f} "
                      f"(bound {args.robust_overhead_pct:.0f}%)  "
                      f"{'ok' if eq and oh_ok else 'REGRESSION'}")
                compared += 2
                if not eq:
                    failures.append((("robust_overhead", "robust",
                                      "matvecs_equal"), 1, 0))
                if not oh_ok:
                    failures.append((("robust_overhead", "robust",
                                      "overhead_pct"),
                                     int(args.robust_overhead_pct), int(oh)))
            if r.table == "robust_recovery":
                rec = bool(m.get("recovered"))
                print(f"  recovery recovered={int(rec)}  "
                      f"{'ok' if rec else 'REGRESSION'}")
                compared += 1
                if not rec:
                    failures.append((("robust_recovery", r.method,
                                      "recovered"), 1, 0))

    if not args.skip_distributed:
        with open(args.distributed_baseline) as f:
            dist_rows = json.load(f)["rows"]
        base_dist_mv = {
            k: v for k, v in _metric_rows(dist_rows, "matvecs").items()
            if k[0] == "dist_solve"
        }
        base_dist_coll = {
            k: v for k, v in _metric_rows(dist_rows, "collectives").items()
            if k[0] == "dist_collectives"
        }
        if not base_dist_mv or not base_dist_coll:
            print(f"ERROR: no dist_solve/dist_collectives rows in "
                  f"{args.distributed_baseline}", file=sys.stderr)
            return 2
        dist_report = Report()
        bench_distributed.run(dist_report, full=False, smoke=True)
        # matvec counts per comm strategy are budget-determined (CG pinned
        # below convergence, SGD's single finalize residual, AP's zero) —
        # exact, zero slack
        c5, f5 = _gate(
            f"distributed matvecs vs {args.distributed_baseline}",
            base_dist_mv, _metric_rows(dist_report.rows, "matvecs"), 0.0,
        )
        # the collective schedule may only shrink: a refactor that sneaks an
        # extra gather/psum into the matvec shows up here
        c6, f6 = _gate(
            f"distributed collectives/matvec vs {args.distributed_baseline}",
            base_dist_coll, _metric_rows(dist_report.rows, "collectives"), 0.0,
        )
        if c5 == 0 or c6 == 0:
            print("ERROR: no comparable distributed rows between baseline and "
                  "fresh run", file=sys.stderr)
            return 2
        compared += c5 + c6
        failures += f5 + f6
        # structural gates on the fresh run itself (baseline-independent): the
        # ring matvec stages ZERO all_gather, and distributed SGD never
        # materialises the (n, 2q) feature matrix on either comm path
        print("\ndistributed structural gate:")
        for r in dist_report.rows:
            m = r.metrics
            if r.table == "dist_collectives" and r.method == "mv_ring":
                ag = int(m.get("all_gather", -1))
                print(f"  ring all_gather/mv={ag}  "
                      f"{'ok' if ag == 0 else 'REGRESSION'}")
                compared += 1
                if ag != 0:
                    failures.append(((r.table, r.method, "all_gather"), 0, ag))
            if r.table == "dist_solve" and r.method.startswith("sgd_"):
                mat = int(m.get("feature_traces_materialised", -1))
                fused = int(m.get("feature_traces_fused", 0))
                ok_feat = mat == 0 and fused > 0
                print(f"  {r.method} materialised_feature_traces={mat} "
                      f"fused={fused}  {'ok' if ok_feat else 'REGRESSION'}")
                compared += 1
                if not ok_feat:
                    failures.append(((r.table, r.method,
                                      "feature_traces_materialised"), 0, mat))

    if failures:
        print(f"\n{len(failures)} count regression(s):", file=sys.stderr)
        for key, base, got in failures:
            print(f"  {'/'.join(key)}: {base} -> {got}", file=sys.stderr)
        return 1
    print(f"\nall {compared} counts within baseline")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
