"""Benchmark harness entry point: `PYTHONPATH=src python -m benchmarks.run [--full]
[--only bench_solvers,...]`. One module per paper table/figure (DESIGN.md §7).

Each bench additionally emits a machine-readable ``BENCH_<name>.json`` into
``--outdir`` (default ``results/``): wall time, per-row metrics (RMSE/NLL,
solver iterations, full-Gram-matvec counts where the bench reports them), so the
performance trajectory is tracked across PRs instead of living in scrollback.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import inspect
import json
import os
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

from .common import Report

BENCHES = [
    "bench_solvers",  # Table 3.1 / 4.1
    "bench_dual",  # Figures 4.1–4.3
    "bench_mll",  # Figure 5.1 + §5.4
    "bench_kronecker",  # Chapter 6
    "bench_thompson",  # Figures 3.7 / 4.4
    "bench_serve",  # serving engine: continuous batching + warm starts
    "bench_robust",  # guardrail overhead + escalation-ladder recovery
    "bench_distributed",  # ring vs gather comm strategies (4-device subprocess)
    "bench_molecules",  # Table 4.2
    "bench_gram_kernel",  # Pallas tile sweep
    "bench_roofline",  # §Roofline (reads dry-run JSONL)
]


def _dump_bench_json(outdir: str, name: str, payload: dict) -> str:
    os.makedirs(outdir, exist_ok=True)
    path = os.path.join(outdir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-sized datasets")
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI-sized run for benches that support it (bench_solvers: same "
        "problem sizes, slashed stochastic step budgets — matvec counts stay "
        "baseline-comparable, RMSE rows do not)",
    )
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--out", default=None, help="dump all rows as JSONL")
    ap.add_argument(
        "--outdir", default="results",
        help="directory for the per-bench BENCH_<name>.json files",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    names = args.only.split(",") if args.only else BENCHES
    report = Report()
    failures = 0
    for name in names:
        print(f"\n=== {name} ===", flush=True)
        t0 = time.time()
        mark = len(report.rows)
        ok = True
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            kwargs = {"full": args.full}
            if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
                kwargs["smoke"] = True
            mod.run(report, **kwargs)
            print(f"=== {name} done in {time.time()-t0:.0f}s ===")
        except Exception:
            traceback.print_exc()
            failures += 1
            ok = False
        path = _dump_bench_json(
            args.outdir,
            name,
            {
                "bench": name,
                "ok": ok,
                "full": bool(args.full),
                "wall_seconds": round(time.time() - t0, 3),
                "rows": [dataclasses.asdict(r) for r in report.rows[mark:]],
            },
        )
        print(f"    wrote {path}")
    report.dump(args.out)
    print(f"\n{len(report.rows)} rows; {failures} bench failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
