"""Chip smoke: the GP serving path end to end on one TPU chip.

    python chip_smoke.py              # one chip: fit, serve, write, parity
    python chip_smoke.py --chips 4    # the row-sharded solve on a 4-chip host

One chip (no arguments). The paper's ``pol`` shape at full n (15,000 x 26,
generated from ``--seed``) goes through the entry points a user calls:

* fit    — ``GPEngine`` (matern32, ``spec="cg"``, backend ``auto``, 16
  pathwise samples): one batched 17-column CG solve through the fused Pallas
  Gram matvec;
* serve  — a few dozen mixed predict / sample / thompson_step requests through
  ``submit``/``step`` (``serve_gp.drive``, a closed loop), with repeat seeds
  so the warm-start path runs; Thompson ascent differentiates through the
  fused kernels, so their backward passes run too;
* write  — one ``add_observations`` of 4 rows on the default ``auto`` policy
  (rank-k bordered update plus its certification matvec);
* check  — the fused Gram matvec on a few hundred rows against a plain
  float32 dense reference at ``precision=HIGHEST``, and the fit's relative
  residual recomputed through that reference.

Four chips (``--chips 4``, nothing else runs). The ``3droad`` shape
(434,874 x 3, trimmed to a multiple of 4 rows) is row-sharded over
``jax.make_mesh((4,), ("data",))``; CG at a fixed iteration budget runs through
``distributed_solve`` with ``comm="gather"`` and ``comm="ring"``, and both are
compared with a one-chip ``solve(Gram(...))`` of the same system and budget.
Shard placement, per-device peak bytes and the collectives in the compiled
HLO are printed.

Every run fails (non-zero exit, no ``"ok": true``) when JAX finds no TPU, when
a phase fails, when a Gram matvec ran on the chunked or dense backend or a
feature matrix was materialised, when a request failed or was retried, or
when a result is off its bound. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
One process drives the chip; nothing else is started.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: Max relative error (per RHS column, 2-norm) of the fused Pallas Gram
#: matvec against the float32 HIGHEST-precision dense reference. The vectors
#: are the fit's solution columns, whose terms cancel heavily in K @ v, so
#: fp32 rounding of K's entries shows up amplified: fp32 contractions give
#: 1.4e-5 on a TPU v5e, one bf16 pass per contraction gives 0.19. The bound
#: leaves a 7x margin over the first and fails the second.
PARITY_BOUND = 1e-4
#: Max relative difference between a 4-chip CG solution (gather or ring) and
#: the one-chip solve of the same system at the same iteration budget.
SHARDED_BOUND = 1e-3
#: Rows of the full-n problem the parity check evaluates.
PARITY_ROWS = 256
#: pol hyperparameters (inputs are standardised; targets normalised).
POL_LENGTHSCALE, POL_NOISE = 4.0, 0.05
#: 3droad hyperparameters and the CG iteration budget of the sharded phase.
#: Chosen so that 20 float32 CG iterations are a stable function of the
#: system, not fitted: at lengthscale 0.5 / noise 0.1 the residual grows and
#: merely reordering the rows moves the 20th iterate by ~2e-2 (measured on a
#: density-matched 20,000-row analogue), at 0.1 / 1.0 by ~4e-6.
ROAD_LENGTHSCALE, ROAD_NOISE, ROAD_ITERS = 0.1, 1.0, 20


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(f"[chip_smoke] {message}", flush=True)


# ---------------------------------------------------------------------------
# Plain float32 reference, independent of the library's kernels
# ---------------------------------------------------------------------------


def reference_matern32_mv(x_rows, x, v, lengthscale, signal, *, chunk=64):
    """K(x_rows, x) @ v for the Matérn-3/2 kernel: distances from explicit
    differences (no distance-as-matmul identity), the contraction at
    ``precision=HIGHEST``, rows in chunks so no (rows, n) block is held."""
    import jax
    import jax.numpy as jnp

    xs = x / lengthscale
    rows = x_rows / lengthscale
    pad = (-rows.shape[0]) % chunk
    rows = jnp.pad(rows, ((0, pad), (0, 0))).reshape(-1, chunk, x.shape[1])

    def block(r):
        d2 = jnp.sum((r[:, None, :] - xs[None, :, :]) ** 2, axis=-1)
        s = jnp.sqrt(3.0) * jnp.sqrt(d2)
        k = signal * (1.0 + s) * jnp.exp(-s)
        return jnp.matmul(k, v, precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(block, rows).reshape(-1, v.shape[1])
    return out[: x_rows.shape[0]]


def reference_rel_residual(x, rhs, sol, params):
    """Per-column ||rhs − (K + σ²I) sol|| / ||rhs|| through the reference."""
    import jax
    import jax.numpy as jnp

    kv = jax.jit(reference_matern32_mv)(
        x, x, sol, params.lengthscale, params.signal
    )
    res = rhs - (kv + params.noise * sol)
    return jnp.linalg.norm(res, axis=0) / jnp.linalg.norm(rhs, axis=0)


def column_rel_err(got, ref):
    import jax.numpy as jnp

    return jnp.linalg.norm(got - ref, axis=0) / jnp.linalg.norm(ref, axis=0)


# ---------------------------------------------------------------------------
# One chip: fit → serve → write → parity
# ---------------------------------------------------------------------------


def backend_counters() -> dict:
    from repro.kernels.ops import (
        FEATURE_TRACE_COUNTS, GRAM_TILING_TRACE_COUNTS, MATVEC_TRACE_COUNTS,
    )

    return dict(gram=dict(MATVEC_TRACE_COUNTS),
                tiling=dict(GRAM_TILING_TRACE_COUNTS),
                features=dict(FEATURE_TRACE_COUNTS))


def serving_phase(x, y, x_new, y_new, params, *, seed: int,
                  num_samples: int = 16, requests: int = 30,
                  parity_rows: int = PARITY_ROWS) -> dict:
    """Fit a ``GPEngine``, serve mixed traffic, append rows, check the results
    against the plain reference; raises :class:`SmokeFailure` on any miss."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import (
        gram_mv, reset_feature_trace_counts, reset_matvec_trace_counts,
    )
    from repro.launch.serve_gp import drive, request_stream
    from repro.serve import GPEngine, PREDICT, SAMPLE, SOLVE_KINDS, THOMPSON
    from repro.serve.metrics import span

    reset_matvec_trace_counts()
    reset_feature_trace_counts()
    d = x.shape[1]

    with span("fit"):
        engine = GPEngine(params, x, y, spec="cg", num_samples=num_samples,
                          seed=seed)
        fit = engine.state
        jax.block_until_ready(fit.post.alpha)
    tol = float(engine.spec.tol)
    say(f"fit: n={fit.n} d={d} columns={1 + num_samples} "
        f"iterations={int(fit.fit_result.iterations)} "
        f"solver_rel_residual_max={float(jnp.max(fit.fit_result.rel_residual))} "
        f"tol={tol}")

    mix = {PREDICT: 2, SAMPLE: 2, THOMPSON: 1}
    stream = list(request_stream(requests, mix, d, jax.random.PRNGKey(seed + 1),
                                 num_rows=16, num_samples=4))
    repeats = [r for r in stream if r[0] in SOLVE_KINDS][:6]  # warm starts
    stream += repeats
    with span("serve"):
        handles, _ = drive(engine, stream, depth=8)
        jax.block_until_ready([h.result().value for h in handles if h.done])
    comps = [h.result() for h in handles]
    errors = [c.error for c in comps if not c.ok]
    check(not errors, f"{len(errors)} requests failed; first: {errors[:1]}")
    for c in comps:
        for name, val in c.value.items():
            check(bool(np.isfinite(np.asarray(val)).all()),
                  f"request {c.request_id} ({c.kind}) {name} is not finite")
    snap = engine.stats()
    say(f"serve: requests={len(comps)} served={snap['requests_served']} "
        f"steps={snap['steps']} solves={snap['solves']} "
        f"solver_iterations={snap['solver_iterations']} "
        f"warm_hits={snap['warm_hits']} "
        f"iterations_saved_warm={snap['iterations_saved_warm']}")
    for key in ("failed", "retries", "escalations"):
        check(snap[key] == 0, f"engine stats {key}={snap[key]}")
    check(snap["warm_hits"] > 0, "no warm-start hit: repeat seeds missed")

    with span("write"):
        engine.add_observations(x_new, y_new)
        jax.block_until_ready(engine.state.post.alpha)
    snap = engine.stats()
    say(f"write: k={x_new.shape[0]} n={snap['n']} "
        f"lowrank_updates={snap['lowrank_updates']} "
        f"compactions={snap['compactions']} "
        f"certified_rel_residual={snap['last_refit_rel_residual']}")

    counters = backend_counters()
    say(f"backend counters: {counters}")
    check(counters["gram"]["pallas"] > 0, "no fused Pallas Gram matvec ran")
    check(counters["gram"]["chunked"] + counters["gram"]["dense"] == 0,
          f"Gram matvecs fell back off Pallas: {counters['gram']}")
    check(counters["features"]["features"] == 0,
          f"feature matrix materialised: {counters['features']}")
    check(counters["tiling"]["symmetric"] > 0,
          f"no solve matvec took the symmetric grid: {counters['tiling']}")

    with span("reference"):
        idx = jax.random.choice(jax.random.PRNGKey(seed + 2), fit.n,
                                (parity_rows,), replace=False)
        v = jnp.concatenate([fit.post.v_mean[:, None], fit.post.alpha], axis=1)
        fused = gram_mv(params, x[idx], v, z=x)
        ref = jax.jit(reference_matern32_mv)(
            x[idx], x, v, params.lengthscale, params.signal
        )
        parity = float(jnp.max(column_rel_err(fused, ref)))
        rhs = jnp.concatenate([fit.y[:, None], fit.f_x + fit.eps], axis=1)
        fit_res = float(jnp.max(reference_rel_residual(x, rhs, v, params)))
        st = engine.state
        sol = jnp.concatenate([st.post.v_mean[:, None], st.post.alpha], axis=1)
        rhs_w = jnp.concatenate([st.y[:, None], st.f_x + st.eps], axis=1)
        write_res = float(jnp.max(reference_rel_residual(st.x, rhs_w, sol,
                                                         params)))
    budget = engine.compaction_tol_factor * tol
    say(f"parity: rows={parity_rows} max_column_rel_err={parity} "
        f"bound={PARITY_BOUND}")
    say(f"reference residual: fit={fit_res} (tol {tol}) "
        f"after_write={write_res} (budget {budget})")
    check(parity <= PARITY_BOUND,
          f"fused Gram matvec off the reference: {parity} > {PARITY_BOUND}")
    check(fit_res <= tol, f"fit residual {fit_res} > tol {tol}")
    check(write_res <= budget, f"post-write residual {write_res} > {budget}")
    return dict(parity=parity, fit_residual=fit_res, write_residual=write_res,
                stats=snap, counters=counters)


def run_one_chip(args) -> None:
    from repro.core.kernels_fn import make_params
    from repro.data.pipeline import regression_dataset
    from repro.serve.metrics import span

    with span("data"):
        data = regression_dataset("pol", seed=args.seed)
    x, y = data["x"], data["y"]
    params = make_params("matern32", lengthscale=POL_LENGTHSCALE, signal=1.0,
                         noise=POL_NOISE, d=x.shape[1])
    serving_phase(x, y, data["x_test"][:4], data["y_test"][:4], params,
                  seed=args.seed)


# ---------------------------------------------------------------------------
# Four chips: the row-sharded solve against the one-chip solve
# ---------------------------------------------------------------------------


def _collectives(hlo: str) -> dict:
    import re

    return {op: len(re.findall(rf"\b{op}(?:-start)?\(", hlo))
            for op in ("all-gather", "all-reduce", "collective-permute",
                       "reduce-scatter", "all-to-all")}


def sharded_phase(x, y, params, mesh, *, iters: int = ROAD_ITERS,
                  bound: float = SHARDED_BOUND) -> dict:
    """CG at a fixed budget through ``distributed_solve`` (gather and ring)
    on row-sharded ``x``, compared with the one-chip solve of the same
    system; raises :class:`SmokeFailure` on any miss."""
    import jax
    import jax.numpy as jnp

    from repro.core.distributed import distributed_solve, shard_training_rows
    from repro.core.operators import Gram, ShardedGram
    from repro.core.solvers.spec import CG, solve
    from repro.kernels.ops import (
        MATVEC_TRACE_COUNTS, reset_matvec_trace_counts,
    )
    from repro.serve.metrics import span

    reset_matvec_trace_counts()
    spec = CG(max_iters=iters, tol=1e-30)  # the budget binds
    devices = list(mesh.devices.flat)
    with span("shard"):
        xs = shard_training_rows(mesh, x)
        jax.block_until_ready(xs)
    held = {}
    for sh in xs.addressable_shards:
        rows = sh.data.shape[0]
        held[sh.device.id] = held.get(sh.device.id, 0) + rows
        say(f"shard: device={sh.device.id} rows={sh.index[0].start}:"
            f"{sh.index[0].stop} ({rows})")
    check(sorted(held) == sorted(d.id for d in devices)
          and all(r > 0 for r in held.values()),
          f"not every device holds rows: {held}")

    probe = jax.random.normal(jax.random.PRNGKey(0), (x.shape[0], 1), x.dtype)
    sols, mvs = {}, {}
    for comm in ("gather", "ring"):
        with span(f"solve_{comm}"):
            res = distributed_solve(params, xs, y, mesh, spec, comm=comm)
            sols[comm] = jax.device_get(res.solution).reshape(-1, 1)
        check(int(res.iterations) == iters,
              f"{comm}: {int(res.iterations)} iterations, budget {iters}")
        op = ShardedGram(x=xs, params=params, mesh=mesh, comm=comm)
        mv = jax.jit(op.mv).lower(probe).compile()
        hlo = mv.as_text()
        mvs[comm] = jax.device_get(mv(probe))
        say(f"{comm}: iterations={int(res.iterations)} "
            f"hlo_collectives={_collectives(hlo)} "
            f"fused_kernel={'tpu_custom_call' in hlo}")
    peaks = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices}
    say(f"peak_bytes_in_use per device: {peaks}")

    with span("solve_one_chip"):
        op1 = Gram(x=jax.device_put(x, devices[0]), params=params)
        ref = solve(op1, jax.device_put(y, devices[0]), spec)
        ref_sol = jax.device_get(ref.solution).reshape(-1, 1)
        ref_mv = jax.device_get(
            jax.jit(op1.mv)(jax.device_put(probe, devices[0]))
        )
    mv_errs = {c: float(column_rel_err(m, ref_mv)[0]) for c, m in mvs.items()}
    diffs = {c: float(column_rel_err(s, ref_sol)[0]) for c, s in sols.items()}
    say(f"one-chip reference: n={x.shape[0]} iterations={int(ref.iterations)}; "
        f"matvec rel_err gather={mv_errs['gather']} ring={mv_errs['ring']} "
        f"bound={PARITY_BOUND}; solution rel_diff gather={diffs['gather']} "
        f"ring={diffs['ring']} bound={bound}")
    say(f"gram backend counters: {dict(MATVEC_TRACE_COUNTS)}")
    check(MATVEC_TRACE_COUNTS["pallas"] > 0, "no fused Pallas Gram matvec ran")
    check(MATVEC_TRACE_COUNTS["chunked"] + MATVEC_TRACE_COUNTS["dense"] == 0,
          f"Gram matvecs fell back off Pallas: {dict(MATVEC_TRACE_COUNTS)}")
    for comm in ("gather", "ring"):
        check(mv_errs[comm] <= PARITY_BOUND,
              f"{comm} matvec off the one-chip matvec: {mv_errs[comm]}")
        check(diffs[comm] <= bound,
              f"{comm} solve off the one-chip solve: {diffs[comm]}")
    return dict(diffs=diffs, mv_errs=mv_errs, held=held, peaks=peaks)


def run_four_chips(args) -> None:
    import jax

    from repro.core.kernels_fn import make_params
    from repro.data.pipeline import regression_dataset
    from repro.serve.metrics import span

    check(len(jax.devices()) == 4,
          f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    with span("data"):
        data = regression_dataset("3droad", seed=args.seed, n_test=1)
    n = data["n"] - data["n"] % 4  # equal shards: 434,874 -> 434,872 rows
    x, y = data["x"][:n], data["y"][:n]
    say(f"3droad: n={n} (of {data['n']}) d={x.shape[1]}")
    params = make_params("matern32", lengthscale=ROAD_LENGTHSCALE, signal=1.0,
                         noise=ROAD_NOISE, d=x.shape[1])
    mesh = jax.make_mesh((4,), ("data",))
    sharded_phase(x, y, params, mesh)


# ---------------------------------------------------------------------------


def report_jit() -> dict:
    """Print the process's tracing, lowering and compile work per phase
    (``repro.serve.metrics``: the smoke's own spans and the engine's)."""
    from repro.serve.metrics import jit_totals

    totals = jit_totals()
    for phase, c in sorted(totals.items()):
        say(f"jit {phase}: trace_s={c['trace_s']:.3f} lower_s={c['lower_s']:.3f} "
            f"compile_s={c['compile_s']:.3f} compiles={c['compiles']} "
            f"cache_hits={c['cache_hits']} cache_misses={c['cache_misses']}")
    return totals


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the row-sharded solve phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind}); no fallback", file=sys.stderr)
        return 1
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")
    say(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args)
        else:
            run_one_chip(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"total: wall_s={time.perf_counter() - t0:.3f}")
    report_jit()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
