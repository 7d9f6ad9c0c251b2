"""Faults planted in the Tanimoto path, and the runs that read them.

    python3 benchmarks/chip/faults_tanimoto.py --workload dock.screen \
        --seconds 20 --fault binary_form --fault r_exponential \
        --fault half_columns --fault-seeds 31,32

Each fault is a context manager that patches the program in this process and
restores it on exit (compiled programs are dropped on both sides, so no
program traced with or without it is reused). The command runs the cell
once per fault and seed with it planted and prints every run's compared
numbers as one JSON line: the upper readings that ``dock.screen``'s limits
are set against, beside ``calibrate.py --control bf16``. The benchmark's own
runs never plant one.
"""
import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.faults import _patched  # noqa: E402


@contextlib.contextmanager
def _fresh(*patches):
    import jax

    jax.clear_caches()
    try:
        with contextlib.ExitStack() as stack:
            for obj, name, value in patches:
                stack.enter_context(_patched(obj, name, value))
            yield
    finally:
        jax.clear_caches()


@contextlib.contextmanager
def binary_form():
    """The fused kernel computes the binary-data form x·z / (|x|² + |z|² −
    x·z), a different kernel on counts."""
    import jax
    import jax.numpy as jnp

    import repro.kernels.tanimoto as tk

    def tile(x, z):
        inner = jax.lax.dot_general(x, z, (((1,), (1,)), ((), ())),
                                    precision=jax.lax.Precision.HIGHEST,
                                    preferred_element_type=jnp.float32)
        xn = jnp.sum(x * x, axis=-1, keepdims=True)
        zn = jnp.sum(z * z, axis=-1)[None, :]
        den = xn + zn - inner
        return inner / (den + (den == 0.0).astype(jnp.float32))

    with _fresh((tk, "_tanimoto_tile", tile)):
        yield


@contextlib.contextmanager
def r_exponential():
    """The features' r drawn from Exp(1) in place of Gamma(2, 1)."""
    import jax

    import repro.core.rff as rff
    from repro.kernels.tanimoto import rf_table

    draw = rff.make_tanimoto_features

    def exp_r(params, key, num_features, d):
        ff = draw(params, key, num_features, d)
        r = jax.random.exponential(jax.random.fold_in(key, 99), ff.r.shape)
        return dataclasses.replace(
            ff, r=r, table=rf_table(r, ff.c, ff.beta, ff.salt))

    with _fresh((rff, "make_tanimoto_features", exp_r)):
        yield


@contextlib.contextmanager
def half_columns():
    """The fit hands back every second pathwise column as zeros."""
    import repro.serve.state as state

    solve = state.solve

    def dropped(op, data, spec, **kw):
        res = solve(op, data, spec, **kw)
        return dataclasses.replace(res, solution=res.solution.at[:, 1::2].set(0.0))

    with _fresh((state, "solve", dropped)):
        yield


FAULTS = {"binary_form": binary_form, "r_exponential": r_exponential,
          "half_columns": half_columns}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", action="append", required=True, choices=sorted(FAULTS))
    ap.add_argument("--fault-seeds", required=True)
    args = ap.parse_args(argv)
    log = lambda m: print(f"[faults] {m}", file=sys.stderr, flush=True)  # noqa: E731
    bench = harness.Bench()
    for fault in args.fault:
        for seed in (int(s) for s in args.fault_seeds.split(",") if s):
            t0 = time.perf_counter()
            try:
                with FAULTS[fault]():
                    res = harness.run_cell(bench, args.workload, seed, args.seconds,
                                           False, t_start=t0, log=log)
                line = dict(seed=seed, fault=fault, correct=res["correct"],
                            checks={k: v["value"] for k, v in res["checks"].items()})
            except Exception as e:  # noqa: BLE001 — a fault that crashes has failed
                line = dict(seed=seed, fault=fault, correct=False, error=repr(e))
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
