"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 benchmarks/chip/calibrate.py --workload pol.mixed --seconds 8 \
        --seeds 11,12,13 --control-seeds 21,22,23 --control bf16 \
        --fault wrong_draws --fault-seeds 31,32,33

Runs the cell once per seed in one process (the program as the configuration
states it), then once per control seed with the solver's tile precision
lowered to ``--control`` (the control that the comparison must fail), then
once per fault seed with each ``--fault`` of ``faults.py`` planted, and
prints every run's compared numbers as one JSON line. The benchmark's own
runs never run it; ``PERF.md`` records its readings and the limits set from
them.
"""
import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.faults import FAULTS  # noqa: E402


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="bf16")
    ap.add_argument("--fault", action="append", default=[], choices=sorted(FAULTS))
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    log = lambda m: print(f"[calibrate] {m}", file=sys.stderr, flush=True)  # noqa: E731
    bench = harness.Bench()
    runs = [(s, None, None) for s in _seeds(args.seeds)]
    runs += [(s, args.control, None) for s in _seeds(args.control_seeds)]
    runs += [(s, None, f) for f in args.fault for s in _seeds(args.fault_seeds)]
    for seed, control, fault in runs:
        t0 = time.perf_counter()
        try:
            with FAULTS[fault]() if fault else contextlib.nullcontext():
                res = harness.run_cell(bench, args.workload, seed, args.seconds, False,
                                       t_start=t0, control=control, log=log)
            line = dict(seed=seed, control=control, fault=fault, correct=res["correct"],
                        checks={k: v["value"] for k, v in res["checks"].items()},
                        metrics={k: v["value"] for k, v in res["metrics"].items()})
        except Exception as e:  # noqa: BLE001 — a control that crashes has failed
            line = dict(seed=seed, control=control, fault=fault, correct=False,
                        error=repr(e))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
