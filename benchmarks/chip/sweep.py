"""Find the knee of an open-loop cell: the highest offered rate it sustains.

    python3 benchmarks/chip/sweep.py --workload pol.mixed --seed 5 --seconds 15 \
        --rates 4,8,12,16

One process sets the cell up once and runs one window per rate (the
warm-start cache emptied before each), printing per rate the latency
percentiles, the completions inside the window, how long the backlog took
to drain after it, and the mean latency of the requests due in the first and
in the last third of the window. A rate is sustained when no request failed,
the 95th percentile of latency is within ``--p95-limit-ms``, the last third
waits no longer than 1.5 times the first third plus half a second (the
backlog does not grow), and the last request completed within five seconds
of the window's end; the knee is the highest rate up to which every rate of
the sweep is sustained. Run it on the chip when a benchmark PR
sets a cell's rate; the benchmark's own runs never run it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.monitor import CompileMonitor, Spans  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--p95-limit-ms", type=float, default=1500.0)
    args = ap.parse_args(argv)
    log = lambda m: print(f"[sweep] {m}", file=sys.stderr, flush=True)  # noqa: E731

    from repro.serve.state import WarmStartCache

    bench = harness.Bench()
    cell = bench.cell(args.workload)
    traffic = bench.traffic(cell["traffic"])
    devices = harness.require_devices(int(cell["chips"]))
    harness.enable_cache(bench.root)
    monitor = CompileMonitor()
    runner = bench.kind(traffic["kind"]).Runner(
        config=bench.config(cell["config"]), traffic=traffic, seed=args.seed,
        devices=devices, spans=Spans(), monitor=monitor, log=log)
    runner.setup()
    log(f"set-up {time.perf_counter() - T_START:.3f} s")
    knee, held = None, True
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        runner.traffic = dict(traffic, rate_per_s=rate)
        runner.seed = args.seed + 1 + i
        runner.engine.cache = WarmStartCache(max_entries=runner.engine.cache.max_entries)
        before = monitor.snapshot()
        t0 = time.perf_counter()
        out = runner.window(args.seconds)
        due = np.array([it["due"] for it in runner.items])
        third = args.seconds / 3
        early = 1e3 * float(np.mean(runner.latencies[due < third]))
        late = 1e3 * float(np.mean(runner.latencies[due >= 2 * third]))
        done = runner.tail["last_done_s"]
        sustained = (out["failed"] == 0 and late <= 1.5 * early + 500.0
                     and out["metrics"]["latency_p95_ms"] <= args.p95_limit_ms
                     and done is not None and done <= args.seconds + 5.0)
        held = held and sustained
        if held:
            knee = rate
        print(json.dumps(dict(rate_per_s=rate, wall_s=time.perf_counter() - t0,
                              inside=monitor.delta(before, monitor.snapshot()),
                              **out["metrics"], failed=out["failed"],
                              attempted=out["attempted"], early_ms=early, late_ms=late,
                              sustained=sustained, **runner.tail,
                              counters=runner.counters)), flush=True)
    print(json.dumps(dict(knee_per_s=knee)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
