"""Run one benchmark cell once on the chip(s) of this host.

    python3 benchmarks/chip/run.py --workload pol.mixed --seed 7 --seconds 20 --trace 0

Run from the root of a checkout. Exits 1 with no result line when JAX finds
no TPU or fewer chips than the cell asks for. See ``harness.py``.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
