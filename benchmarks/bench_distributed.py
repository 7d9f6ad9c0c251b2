"""Distributed solve path: comm-strategy comparison on a forced 4-device mesh.

Measures the ring-overlapped sharded matvec (``ShardedGram(comm="ring")``,
docs/distributed.md) against the gather baseline — per-matvec collective
schedule (counted in the jaxpr: ``all_gather`` / ``ppermute`` / ``psum``),
solver matvec accounting per comm strategy, ring-vs-gather parity, and the
trace-counter proof that distributed SGD's regulariser never materialises the
(n, 2q) feature matrix.

The measurements run in a *subprocess* with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` set before jax imports:
the parent (benchmarks.run or check_matvecs) has already initialised a
single-device jax, and the forced host platform must not leak into it. The
worker prints one JSON document; the parent turns it into Report rows.

Gate (check_matvecs --distributed-baseline): matvec counts exact (zero slack),
collectives-per-matvec ≤ the committed baseline, ring ``all_gather`` == 0 and
SGD materialised-feature traces == 0 structurally on the fresh run.

CPU-only structure gate: the worker forces ``JAX_PLATFORMS=cpu`` and four
host-platform devices, so on a TPU machine it would still measure the CPU
(and a child that wanted the chip would find it held by the parent). Do not
run it on the chip machine; the four-chip path on real chips is
``python chip_smoke.py --chips 4``. The ring's *wall-clock* win needs real
interconnect — on a host-platform mesh the ppermute is a memcpy, so
``us_per_mv`` here is informational (schedule structure, not speed, is what
CI gates).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import Report

DEVICES = 4

_WORKER = r"""
import json, re, time
import jax, jax.numpy as jnp, numpy as np
from repro.core import ShardedGram, make_params, solve, CG, SGD, AP
from repro.core.distributed import distributed_solve, shard_training_rows
from repro.kernels.ops import FEATURE_TRACE_COUNTS, reset_feature_trace_counts

n, d, s = map(int, (NSIZE, 3, 4))
devices = DEVCOUNT
mesh = jax.make_mesh((devices,), ("data",))
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (n, d))
y = jnp.sin(x.sum(-1))
v = jax.random.normal(jax.random.fold_in(key, 1), (n, s))
p = make_params("se", lengthscale=1.0, noise=0.2, d=d)
xs = shard_training_rows(mesh, x)

out = {"n": n, "devices": devices, "comm": {}}
ops = {
    "gather": ShardedGram(x=xs, params=p, mesh=mesh, comm="gather"),
    "ring": ShardedGram(x=xs, params=p, mesh=mesh, comm="ring"),
}
for comm, op in ops.items():
    rec = {}
    # collective schedule of one matvec, straight from the jaxpr
    txt = str(jax.make_jaxpr(lambda w: op.mv(w))(v))
    for coll in ("all_gather", "ppermute", "psum"):
        rec[coll] = len(re.findall(rf"\b{coll}\b", txt))
    rec["collectives"] = rec["all_gather"] + rec["ppermute"] + rec["psum"]
    # wall per matvec (informational on a host-platform mesh)
    mv = jax.jit(lambda w: op.mv(w))
    mv(v).block_until_ready()
    t0 = time.time()
    reps = 20
    for _ in range(reps):
        r = mv(v)
    r.block_until_ready()
    rec["us_per_mv"] = (time.time() - t0) / reps * 1e6
    # solver accounting, comm-invariant: CG below its convergence region spends
    # exactly its budget, SGD exactly the finalize residual, AP zero
    res_cg = distributed_solve(p, xs, y, mesh, CG(max_iters=15, tol=1e-12),
                               comm=comm)
    rec["cg_matvecs"] = int(res_cg.matvecs)
    rec["cg_iterations"] = int(res_cg.iterations)
    reset_feature_trace_counts()
    res_sgd = distributed_solve(
        p, xs, y, mesh,
        SGD(num_steps=200, batch_size=64, num_features=32),
        comm=comm, backend="pallas", key=key,
    )
    rec["sgd_matvecs"] = int(res_sgd.matvecs)
    rec["sgd_feature_traces_materialised"] = int(FEATURE_TRACE_COUNTS["features"])
    rec["sgd_feature_traces_fused"] = int(FEATURE_TRACE_COUNTS["pallas"])
    res_ap = distributed_solve(p, xs, y, mesh,
                               AP(num_steps=30, block_size=32),
                               comm=comm, key=key)
    rec["ap_matvecs"] = int(res_ap.matvecs)
    out["comm"][comm] = rec

out["mv_parity"] = float(jnp.max(jnp.abs(
    jnp.asarray(ops["ring"].mv(v)) - jnp.asarray(ops["gather"].mv(v)))))
print("BENCH_JSON:" + json.dumps(out))
"""


def _run_worker(n: int) -> dict:
    code = (
        "import os\n"
        f'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={DEVICES}"\n'
        'os.environ["JAX_PLATFORMS"] = "cpu"\n'
        + _WORKER.replace("NSIZE", str(n)).replace("DEVCOUNT", str(DEVICES))
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    if r.returncode != 0:
        raise RuntimeError(f"distributed worker failed:\n{r.stderr[-4000:]}")
    for line in r.stdout.splitlines():
        if line.startswith("BENCH_JSON:"):
            return json.loads(line[len("BENCH_JSON:"):])
    raise RuntimeError(f"no BENCH_JSON line in worker output:\n{r.stdout[-2000:]}")


def run(report: Report, full: bool = False, smoke: bool = False) -> None:
    n = 1024 if full else 256
    data = _run_worker(n)
    ds = f"synthetic-{data['n']}x{data['devices']}dev"
    for comm, rec in data["comm"].items():
        report.add(
            "dist_collectives", f"mv_{comm}", ds,
            all_gather=rec["all_gather"], ppermute=rec["ppermute"],
            psum=rec["psum"], collectives=rec["collectives"],
            us_per_mv=rec["us_per_mv"],
        )
        report.add("dist_solve", f"cg_{comm}", ds,
                   matvecs=rec["cg_matvecs"], iterations=rec["cg_iterations"])
        report.add("dist_solve", f"sgd_{comm}", ds,
                   matvecs=rec["sgd_matvecs"],
                   feature_traces_materialised=rec[
                       "sgd_feature_traces_materialised"],
                   feature_traces_fused=rec["sgd_feature_traces_fused"])
        report.add("dist_solve", f"ap_{comm}", ds, matvecs=rec["ap_matvecs"])
    report.add("dist_mv", "ring_vs_gather", ds, parity=data["mv_parity"])
