"""Serving engine + multi-RHS batching (src/repro/serve, solve_batched).

Two layers of guarantees:

* solver layer — ``solve_batched`` coalesces per-consumer RHS blocks into one
  multi-RHS solve whose per-block solutions match independent single-block
  solves (CG freezes converged columns; the stochastic solvers' column updates
  are independent given the shared key), while spending ONE solve's worth of
  matvecs for the whole batch;
* engine layer — FIFO fairness, bucket-padding correctness, warm-vs-cold
  iteration reduction, determinism under interleaved arrival orders, and the
  ``stats()`` counter contract the benchmark relies on.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernels_fn import make_params
from repro.core.operators import Gram
from repro.core.rff import PriorSamples
from repro.core.solvers.spec import AP, CG, SDD, SGD, solve, solve_batched
from repro.serve import (
    FIFOScheduler,
    GPEngine,
    Request,
    bucket,
    extend_state,
    fit_state,
    percentile,
    update_state_lowrank,
)


@pytest.fixture(scope="module")
def small_problem():
    key = jax.random.PRNGKey(0)
    n, d = 96, 2
    x = jax.random.uniform(key, (n, d))
    y = jnp.sin(4.0 * x[:, 0]) + 0.5 * jnp.cos(3.0 * x[:, 1])
    params = make_params("matern32", lengthscale=0.5, signal=1.0, noise=0.1, d=d)
    return dict(x=x, y=y, params=params, n=n, d=d)


@pytest.fixture(scope="module")
def op(small_problem):
    return Gram(x=small_problem["x"], params=small_problem["params"])


def _rhs_blocks(small_problem):
    key = jax.random.PRNGKey(3)
    n = small_problem["n"]
    b1 = small_problem["y"]  # (n,) 1-D block
    b2 = jax.random.normal(jax.random.fold_in(key, 1), (n, 3))
    b3 = jax.random.normal(jax.random.fold_in(key, 2), (n, 2))
    return [b1, b2, b3]


# ---------------------------------------------------------------------------
# solve_batched: stacked RHS columns match independent solves, for every
# solver family, with shared matvec accounting
# ---------------------------------------------------------------------------


# CG compares at its convergence floor: a column that converges before the
# batch's slowest column freezes, so its trajectory can differ from the solo
# run's by (solver tolerance)-level float32 drift. The fixed-step stochastic
# solvers share their index sequences through the key and match near-exactly.
@pytest.mark.parametrize(
    "spec",
    [
        CG(max_iters=300, tol=1e-4),
        SGD(num_steps=60, batch_size=32, num_features=32),
        SDD(num_steps=60, batch_size=32, step_size_times_n=5.0),
        AP(num_steps=80, block_size=32),
    ],
    ids=["cg", "sgd", "sdd", "ap"],
)
def test_solve_batched_matches_single_solves(op, small_problem, spec):
    blocks = _rhs_blocks(small_problem)
    key = jax.random.PRNGKey(11)
    batched = solve_batched(op, blocks, spec, key=key)
    assert len(batched) == len(blocks)
    total_single_matvecs = 0
    for blk, res in zip(blocks, batched):
        solo = solve(op, blk, spec, key=key)
        np.testing.assert_allclose(
            np.asarray(res.solution), np.asarray(solo.solution),
            rtol=1e-2, atol=1e-3,
        )
        assert res.solution.shape == solo.solution.shape  # squeeze preserved
        total_single_matvecs += int(solo.matvecs)
    # the whole batch spends ONE solve's worth of full-operator matvecs —
    # every block's result reports the same shared totals
    shared = {(int(r.iterations), int(r.matvecs)) for r in batched}
    assert len(shared) == 1
    assert int(batched[0].matvecs) <= total_single_matvecs


def test_solve_batched_column_padding_is_inert(op, small_problem):
    blocks = _rhs_blocks(small_problem)
    spec = CG(max_iters=300, tol=1e-4)
    plain = solve_batched(op, blocks, spec)
    padded = solve_batched(op, blocks, spec, pad_columns_to=16)
    for a, b in zip(plain, padded):
        # padding changes the compiled matvec width, so agreement is at the
        # solver-tolerance level, not bitwise
        np.testing.assert_allclose(
            np.asarray(a.solution), np.asarray(b.solution), rtol=2e-2, atol=2e-2
        )
        assert bool(b.converged)


def test_solve_batched_mixed_warm_cold_blocks(op, small_problem):
    blocks = _rhs_blocks(small_problem)
    spec = CG(max_iters=300, tol=1e-4)
    cold = solve_batched(op, blocks, spec)
    warm = solve_batched(
        op, blocks, spec,
        x0_blocks=[cold[0].solution, None, cold[2].solution],
    )
    for a, b in zip(cold, warm):
        np.testing.assert_allclose(
            np.asarray(a.solution), np.asarray(b.solution), rtol=2e-2, atol=2e-2
        )
    # warm columns are already converged: the batch's budget is the cold block's
    assert int(warm[0].iterations) <= int(cold[0].iterations)


# ---------------------------------------------------------------------------
# x0 validation at the solve() boundary
# ---------------------------------------------------------------------------


def test_x0_shape_mismatch_is_a_clear_error(op, small_problem):
    y = small_problem["y"]
    with pytest.raises(ValueError, match="warm start x0"):
        solve(op, jnp.stack([y, y], axis=1), "cg", x0=y)  # 1-D x0, 2-column b
    with pytest.raises(ValueError, match="stale warm-start"):
        solve(op, y, "cg", x0=y[:-1])  # old-n cache entry


def test_x0_dtype_mismatch_is_a_clear_error(op, small_problem):
    y = small_problem["y"]
    with pytest.raises(TypeError, match="dtype"):
        solve(op, y, "cg", x0=y.astype(jnp.float16))


def test_x0_matching_shape_still_accepted(op, small_problem):
    y = small_problem["y"]
    sol = solve(op, y, CG(max_iters=200, tol=1e-4)).solution
    res = solve(op, y, CG(max_iters=200, tol=1e-4), x0=sol)
    assert int(res.iterations) <= 2  # re-verifying a solution is nearly free


# ---------------------------------------------------------------------------
# scheduler: grouping, caps, FIFO with position-preserving skips
# ---------------------------------------------------------------------------


def _req(i, kind, rows=4, cols=4, warm=False):
    xs = None if kind == "thompson_step" else jnp.zeros((rows, 2))
    return Request(
        id=i, kind=kind, xs=xs, num_samples=cols, seed=i, arrival=float(i),
        warm=warm,
    )


def test_scheduler_coalesces_compatible_and_preserves_positions():
    sched = FIFOScheduler(max_batch_requests=8, max_rhs_columns=64)
    sched.add(_req(0, "sample"))
    sched.add(_req(1, "predict"))
    sched.add(_req(2, "thompson_step"))  # solve group: joins request 0
    sched.add(_req(3, "sample", warm=True))  # warm never mixes with cold
    plan = sched.next_batch()
    assert [r.id for r in plan.requests] == [0, 2]
    assert plan.group == "solve_cold"
    # skipped requests keep arrival order: predict is now head-of-line
    assert sched.next_batch().group == "predict"
    assert sched.next_batch().group == "solve_warm"
    assert sched.next_batch() is None


def test_scheduler_respects_column_cap():
    sched = FIFOScheduler(max_batch_requests=8, max_rhs_columns=8)
    for i in range(3):
        sched.add(_req(i, "sample", cols=4))
    plan = sched.next_batch()
    assert [r.id for r in plan.requests] == [0, 1]  # 8 columns — third waits
    assert [r.id for r in sched.next_batch().requests] == [2]
    with pytest.raises(ValueError, match="RHS columns"):
        sched.add(_req(9, "sample", cols=9))


def test_bucket_ladder():
    assert bucket(1, 16) == 16
    assert bucket(17, 16) == 32
    assert bucket(5, 1) == 8  # next pow2
    assert bucket(8, 8) == 8


# ---------------------------------------------------------------------------
# engine: lifecycle, padding correctness, warm starts, determinism, stats
# ---------------------------------------------------------------------------


def _engine(small_problem, **kw):
    kw.setdefault("spec", CG(max_iters=300, tol=1e-4))
    kw.setdefault("num_samples", 4)
    kw.setdefault("num_features", 128)
    return GPEngine(
        small_problem["params"], small_problem["x"], small_problem["y"], **kw
    )


def test_predict_padding_matches_direct_evaluation(small_problem):
    eng = _engine(small_problem)
    xs = small_problem["x"][:5] + 0.01  # odd row count → real bucket padding
    h = eng.predict(xs)
    eng.step()
    mean, var = h.result().value["mean"], h.result().value["var"]
    mean_ref, var_ref = eng.state.post.sample_mean_and_var(xs)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mean_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(var_ref), atol=1e-5)


def test_predict_split_rows_gives_large_queries_their_own_pass(small_problem):
    """A query at or above ``predict_split_rows`` is computed in a pass of its
    own bucket: the batch's small queries are not padded to its size, and
    every answer still equals direct evaluation, in submit order."""
    eng = _engine(small_problem, predict_split_rows=32)
    x = small_problem["x"]
    queries = [x[:3] + 0.01, x[:40] + 0.02, x[:5] + 0.03]
    handles = [eng.predict(q) for q in queries]
    before = eng.stats()
    assert len(eng.step()) == 3
    after = eng.stats()
    # passes: the two small queries in 2 blocks of 16 rows, the large one in 64
    assert after["feature_rows"] - before["feature_rows"] == 2 * 16 + 64
    assert after["predict_padded_rows"] - before["predict_padded_rows"] == 96 - 48
    for q, h in zip(queries, handles):
        mean_ref, var_ref = eng.state.post.sample_mean_and_var(q)
        np.testing.assert_allclose(np.asarray(h.result().value["mean"]),
                                   np.asarray(mean_ref), atol=1e-5)
        np.testing.assert_allclose(np.asarray(h.result().value["var"]),
                                   np.asarray(var_ref), atol=1e-5)
    with pytest.raises(ValueError, match="predict_split_rows"):
        _engine(small_problem, predict_split_rows=0)


def test_fifo_completion_order_and_batching(small_problem):
    eng = _engine(small_problem)
    xs = small_problem["x"][:3]
    ids = [
        eng.sample(xs, num_samples=2, seed=1).request.id,
        eng.predict(xs).request.id,
        eng.sample(xs, num_samples=2, seed=2).request.id,
    ]
    first = eng.step()  # head is a cold sample → both samples coalesce
    assert [c.request_id for c in first] == [ids[0], ids[2]]
    assert first[0].metrics["batch_columns"] == 4
    assert first[0].metrics["iterations"] == first[1].metrics["iterations"]
    second = eng.step()
    assert [c.request_id for c in second] == [ids[1]]


def test_warm_repeat_uses_fewer_iterations(small_problem):
    eng = _engine(small_problem)
    xs = small_problem["x"][:4]
    cold = eng.sample(xs, num_samples=4, seed=77)
    eng.run_until_idle()
    warm = eng.sample(xs, num_samples=4, seed=77)
    assert warm.request.warm
    eng.run_until_idle()
    cold_iters = cold.result().metrics["iterations"]
    warm_iters = warm.result().metrics["iterations"]
    assert warm_iters < cold_iters
    np.testing.assert_allclose(
        np.asarray(cold.result().value["samples"]),
        np.asarray(warm.result().value["samples"]),
        rtol=1e-4, atol=1e-5,
    )
    snap = eng.stats()
    assert snap["warm_hits"] == 1
    assert snap["iterations_saved_warm"] > 0


def test_deterministic_under_interleaved_arrival_orders(small_problem):
    xs_a = small_problem["x"][:5]
    xs_b = small_problem["x"][5:8]

    eng1 = _engine(small_problem)  # both samples coalesce into one solve
    h1a = eng1.sample(xs_a, num_samples=3, seed=101)
    h1b = eng1.sample(xs_b, num_samples=2, seed=202)
    eng1.run_until_idle()

    eng2 = _engine(small_problem)  # a predict interleaves; solves split
    h2b = eng2.sample(xs_b, num_samples=2, seed=202)
    eng2.step()
    eng2.predict(xs_a)
    h2a = eng2.sample(xs_a, num_samples=3, seed=101)
    eng2.run_until_idle()

    for ha, hb in ((h1a, h2a), (h1b, h2b)):
        np.testing.assert_allclose(
            np.asarray(ha.result().value["samples"]),
            np.asarray(hb.result().value["samples"]),
            rtol=1e-4, atol=1e-5,
        )


def test_row_and_column_buckets_do_not_change_payloads(small_problem):
    eng1 = _engine(small_problem, row_bucket_min=16, col_bucket_min=8)
    eng2 = _engine(small_problem, row_bucket_min=4, col_bucket_min=2)
    xs = small_problem["x"][:5]
    h1 = eng1.sample(xs, num_samples=3, seed=5)
    h2 = eng2.sample(xs, num_samples=3, seed=5)
    eng1.run_until_idle()
    eng2.run_until_idle()
    np.testing.assert_allclose(
        np.asarray(h1.result().value["samples"]),
        np.asarray(h2.result().value["samples"]),
        rtol=1e-4, atol=1e-5,
    )


def test_thompson_step_returns_in_bounds_points(small_problem):
    eng = _engine(small_problem)
    h = eng.thompson_step(num_samples=3, seed=4, ascent_steps=5, num_candidates=64)
    eng.run_until_idle()
    pts = np.asarray(h.result().value["points"])
    assert pts.shape == (3, small_problem["d"])
    assert (pts >= 0.0).all() and (pts <= 1.0).all()
    assert h.result().value["values"].shape == (3,)


def test_engine_stats_counters_and_handles(small_problem):
    eng = _engine(small_problem)
    with pytest.raises(ValueError, match="unknown request kind"):
        eng.submit("decode", small_problem["x"][:2])
    with pytest.raises(ValueError, match="xs must be None"):
        eng.submit("thompson_step", small_problem["x"][:2])
    h = eng.sample(small_problem["x"][:2], num_samples=2, seed=1)
    with pytest.raises(RuntimeError, match="still queued"):
        h.result()
    eng.predict(small_problem["x"][:3])
    eng.run_until_idle()
    snap = eng.stats()
    assert snap["requests_submitted"] == 2
    assert snap["requests_served"] == {"sample": 1, "predict": 1}
    assert snap["rhs_columns"] == 2
    assert snap["padded_columns"] == 6  # bucketed up to col_bucket_min=8
    assert snap["solves"] == 1
    assert snap["queue_depth"] == 0
    assert snap["solver"] == "cg"
    assert snap["predict_rows"] == 3


def test_add_observations_warm_refit_saves_iterations(small_problem):
    key = jax.random.PRNGKey(9)
    st = fit_state(
        small_problem["params"], small_problem["x"], small_problem["y"],
        key, spec=CG(max_iters=300, tol=1e-4), num_samples=4, num_features=128,
    )
    x_new = small_problem["x"][:6] + 0.02
    y_new = small_problem["y"][:6]
    k2 = jax.random.PRNGKey(10)
    warm = extend_state(st, x_new, y_new, k2, warm=True)
    cold = extend_state(st, x_new, y_new, k2, warm=False)
    assert int(warm.fit_result.iterations) < int(cold.fit_result.iterations)
    np.testing.assert_allclose(
        np.asarray(warm.post.v_mean), np.asarray(cold.post.v_mean),
        rtol=2e-2, atol=2e-2,
    )
    # engine-level: the refit counters move and the cache re-keys (old entries
    # are unreachable under the new fingerprint, so no stale-x0 shape errors)
    eng = _engine(small_problem)
    eng.sample(small_problem["x"][:2], num_samples=2, seed=1)
    eng.run_until_idle()
    old_key = eng.state.hypers_key
    eng.add_observations(x_new, y_new)
    assert eng.state.hypers_key != old_key
    assert eng.state.n == small_problem["n"] + 6
    assert eng.stats()["refits"] == 1
    repeat = eng.sample(small_problem["x"][:2], num_samples=2, seed=1)
    assert not repeat.request.warm  # cache is keyed by (hypers, n): re-keyed
    eng.run_until_idle()

# ---------------------------------------------------------------------------
# percentile: nearest-rank definition (regression for round-half-even bias)
# ---------------------------------------------------------------------------


def test_percentile_nearest_rank():
    assert percentile([], 50) == 0.0
    # N=1: every quantile is the single value
    for q in (0, 50, 99, 100):
        assert percentile([5.0], q) == 5.0
    # N=2: p50 is the 1st order statistic (⌈1.0⌉), anything above picks the 2nd
    assert percentile([2.0, 1.0], 0) == 1.0
    assert percentile([2.0, 1.0], 50) == 1.0
    assert percentile([2.0, 1.0], 51) == 2.0
    assert percentile([2.0, 1.0], 99) == 2.0
    # N=4: p50 is the 2nd smallest — int(round(...)) used to pick the 3rd
    assert percentile([4.0, 1.0, 3.0, 2.0], 50) == 2.0
    assert percentile([4.0, 1.0, 3.0, 2.0], 100) == 4.0
    # N=100: p50 is the 50th order statistic — the old rounding picked the 51st
    xs = [float(i) for i in range(1, 101)]
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 50) == 50.0
    assert percentile(xs, 99) == 99.0
    assert percentile(xs, 100) == 100.0


# ---------------------------------------------------------------------------
# rank-k incremental updates: parity, cost accounting, prior-row economy,
# engine policies, compaction, interleaved writes
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def update_problem():
    key = jax.random.PRNGKey(21)
    n, k, d = 64, 5, 2
    x = jax.random.uniform(key, (n + k, d))
    y = jnp.sin(4.0 * x[:, 0]) + 0.5 * jnp.cos(3.0 * x[:, 1])
    params = make_params("matern32", lengthscale=0.5, signal=1.0, noise=0.5, d=d)
    xt = jax.random.uniform(jax.random.PRNGKey(22), (20, d))
    return dict(x=x, y=y, params=params, n=n, k=k, xt=xt)


@pytest.mark.parametrize(
    "spec,parity_tol",
    [
        (CG(max_iters=400, tol=1e-6), 1e-4),
        (SGD(num_steps=2000, batch_size=32, num_features=64), 5e-2),
    ],
    ids=["cg", "sgd"],
)
def test_lowrank_update_matches_full_refit(update_problem, spec, parity_tol):
    """The bordered correction and the full row-extension refit extend the SAME
    linear system at matching seeds (shared draw convention), so their
    posteriors agree to solver accuracy. CG converges, so parity meets the
    1e-4 incremental-update budget outright. SGD sits at its stochastic
    optimisation floor (~0.1 relative residual — constant-step gradient noise,
    cf. test_sgd_variance_reduced_objective's documented atol): there the
    guarantees are that the bordered algebra does not AMPLIFY the solver's own
    error, and that the certification matvec reports the drift honestly
    (converged=False), which is exactly what the engine's auto policy uses to
    compact instead of silently serving a drifted posterior."""
    t = update_problem
    n = t["n"]
    st = fit_state(
        t["params"], t["x"][:n], t["y"][:n], jax.random.PRNGKey(2),
        spec=spec, num_samples=4, num_features=128,
    )
    ukey = jax.random.PRNGKey(3)
    lo = update_state_lowrank(st, t["x"][n:], t["y"][n:], ukey)
    fu = extend_state(st, t["x"][n:], t["y"][n:], ukey, warm=True)
    ml, vl = lo.post.sample_mean_and_var(t["xt"])
    mf, vf = fu.post.sample_mean_and_var(t["xt"])
    np.testing.assert_allclose(np.asarray(ml), np.asarray(mf), atol=parity_tol)
    np.testing.assert_allclose(np.asarray(vl), np.asarray(vf), atol=parity_tol)
    np.testing.assert_allclose(
        np.asarray(lo.post.mean(t["xt"])), np.asarray(fu.post.mean(t["xt"])),
        atol=parity_tol,
    )
    drift = float(jnp.max(lo.fit_result.rel_residual))
    assert bool(lo.fit_result.healthy)
    if isinstance(spec, CG):
        assert drift <= 1e-4  # certified against the extended operator
    else:
        assert drift <= 2.0 * float(jnp.max(fu.fit_result.rel_residual))
        assert not bool(lo.fit_result.converged)  # auto policy sees the floor


def test_lowrank_update_solves_only_k_columns(small_problem):
    """Cost accounting: the rank-k path spends its iterations on k correction
    columns against the OLD n-operator plus exactly ONE certification matvec
    of the extended operator — strictly below the warm full refit's spend on
    the same update, which re-solves all 1+s columns at n+k."""
    st = fit_state(
        small_problem["params"], small_problem["x"], small_problem["y"],
        jax.random.PRNGKey(9), spec=CG(max_iters=300, tol=1e-4),
        num_samples=4, num_features=128,
    )
    x_new = small_problem["x"][:6] + 0.02
    y_new = small_problem["y"][:6]
    ukey = jax.random.PRNGKey(10)
    lo = update_state_lowrank(st, x_new, y_new, ukey)
    fu = extend_state(st, x_new, y_new, ukey, warm=True)
    # CG: matvecs == iterations, + the one certification matvec
    assert int(lo.fit_result.matvecs) == int(lo.fit_result.iterations) + 1
    assert int(lo.fit_result.iterations) < int(fu.fit_result.iterations)
    assert int(lo.fit_result.matvecs) < int(fu.fit_result.matvecs)
    assert lo.n == st.n + 6
    # certified drift lands inside the engine's default auto budget (4× tol)
    assert float(jnp.max(lo.fit_result.rel_residual)) <= 4.0 * 1e-4


def test_incremental_updates_evaluate_prior_on_new_rows_only(
    small_problem, monkeypatch
):
    """Both incremental paths reuse the cached ``f_x`` rows: the prior paths
    are evaluated on the k NEW rows only, never re-run over all n old rows
    (the fused feature pass is the other O(n) cost a rank-k update avoids)."""
    st = fit_state(
        small_problem["params"], small_problem["x"], small_problem["y"],
        jax.random.PRNGKey(9), spec=CG(max_iters=300, tol=1e-4),
        num_samples=4, num_features=128,
    )
    x_new = small_problem["x"][:6] + 0.02
    y_new = small_problem["y"][:6]
    rows_seen = []
    orig_call = PriorSamples.__call__

    def spy(self, xs):
        rows_seen.append(int(jnp.asarray(xs).shape[0]))
        return orig_call(self, xs)

    monkeypatch.setattr(PriorSamples, "__call__", spy)
    lo = update_state_lowrank(st, x_new, y_new, jax.random.PRNGKey(10))
    fu = extend_state(st, x_new, y_new, jax.random.PRNGKey(10), warm=True)
    assert rows_seen and max(rows_seen) == 6, rows_seen
    # the cached rows carried over bit-exactly; only the tail is fresh
    np.testing.assert_array_equal(np.asarray(lo.f_x[:96]), np.asarray(st.f_x))
    np.testing.assert_array_equal(np.asarray(fu.f_x[:96]), np.asarray(st.f_x))


def test_engine_update_policies_and_cache_purge(small_problem):
    x_new = small_problem["x"][:6] + 0.02
    y_new = small_problem["y"][:6]
    with pytest.raises(ValueError, match="update_policy"):
        _engine(small_problem, update_policy="bogus")

    eng = _engine(small_problem)  # default auto
    with pytest.raises(ValueError, match="update must be"):
        eng.add_observations(x_new, y_new, update="bogus")
    eng.sample(small_problem["x"][:2], num_samples=2, seed=1)
    eng.run_until_idle()
    assert eng.stats()["warm_cache_entries"] == 1
    eng.add_observations(x_new, y_new)  # auto: drift within budget → lowrank
    snap = eng.stats()
    assert snap["refits"] == 1
    assert snap["lowrank_updates"] == 1
    assert snap["lowrank_rows"] == 6
    assert snap["compactions"] == 0
    assert snap["refit_iterations"] == 0  # no full solve ran
    assert snap["lowrank_matvecs"] == snap["lowrank_iterations"] + 1
    assert 0.0 < snap["last_refit_rel_residual"] <= 4.0 * 1e-4
    assert snap["n"] == small_problem["n"] + 6
    # the re-key made the old cache entry unreachable — it was purged, and the
    # post-update engine still serves correctly-shaped, finite payloads
    assert snap["cache_purged"] == 1
    assert snap["warm_cache_entries"] == 0
    h = eng.predict(small_problem["x"][:3])
    eng.run_until_idle()
    assert np.isfinite(np.asarray(h.result().value["mean"])).all()

    # update="full" on an auto engine forces the refit path for one call
    eng2 = _engine(small_problem)
    eng2.add_observations(x_new, y_new, update="full")
    snap2 = eng2.stats()
    assert snap2["refits"] == 1
    assert snap2["lowrank_updates"] == 0
    assert snap2["refit_iterations"] > 0


def test_engine_compaction_trigger(small_problem):
    """A zero drift budget forces the auto fallback whatever the lowrank
    path's certified residual (any float32 residual is > 0): the engine
    re-solves in full (compaction), and the resulting state is certified at
    the spec tolerance."""
    x_new = small_problem["x"][:6] + 0.02
    y_new = small_problem["y"][:6]
    eng = _engine(small_problem, compaction_tol_factor=0.0)
    eng.add_observations(x_new, y_new)
    snap = eng.stats()
    assert snap["compactions"] == 1
    assert snap["lowrank_updates"] == 0
    assert snap["refits"] == 1
    assert snap["refit_iterations"] > 0  # the fallback full refit ran
    assert snap["last_refit_rel_residual"] <= 1e-4
    assert bool(eng.state.fit_result.converged)
    assert eng.state.n == small_problem["n"] + 6


def test_interleaved_writes_fifo_and_bystanders(small_problem):
    """Write-heavy interleaving: ``add_observations`` drains the queue first,
    so a request submitted before the write is served against the state it was
    submitted under — bit-exact with a write-free engine — and post-write
    requests preserve FIFO semantics against the updated state."""
    xs = small_problem["x"][:3]
    x_new = small_problem["x"][:4] + 0.03
    y_new = small_problem["y"][:4]

    writer = _engine(small_problem)
    bystander = _engine(small_problem)
    hw = writer.sample(xs, num_samples=2, seed=11)
    hb = bystander.sample(xs, num_samples=2, seed=11)
    writer.add_observations(x_new, y_new)  # drains hw against pre-write state
    bystander.run_until_idle()
    np.testing.assert_array_equal(
        np.asarray(hw.result().value["samples"]),
        np.asarray(hb.result().value["samples"]),
    )

    # post-write: FIFO coalescing still holds on the updated state
    ids = [
        writer.sample(xs, num_samples=2, seed=21).request.id,
        writer.predict(xs).request.id,
        writer.sample(xs, num_samples=2, seed=22).request.id,
    ]
    first = writer.step()
    assert [c.request_id for c in first] == [ids[0], ids[2]]
    second = writer.step()
    assert [c.request_id for c in second] == [ids[1]]
    for comp in (*first, *second):
        assert comp.ok
        assert all(
            np.isfinite(np.asarray(v)).all() for v in comp.value.values()
        )
    # a second write interleaves just as well (alternating write/read traffic)
    writer.add_observations(x_new + 0.05, y_new)
    h2 = writer.sample(xs, num_samples=2, seed=21)
    writer.run_until_idle()
    assert not h2.request.warm  # both writes re-keyed the cache
    assert writer.state.n == small_problem["n"] + 8
    assert np.isfinite(np.asarray(h2.result().value["samples"])).all()


def test_refit_savings_rebaseline(small_problem):
    """``refit_iterations_saved`` credits warm refits against the most recent
    COLD fit-system solve; a ``warm=False`` refit re-baselines (n and
    iterations), so savings are never measured against a stale smaller-n
    reference."""
    eng = _engine(small_problem)
    snap0 = eng.stats()
    assert snap0["refit_baseline_n"] == small_problem["n"]
    assert snap0["refit_baseline_iters"] == int(eng.state.fit_result.iterations)

    x1 = small_problem["x"][:4] + 0.02
    y1 = small_problem["y"][:4]
    eng.add_observations(x1, y1, update="full", warm=False)
    snap1 = eng.stats()
    cold_iters = int(eng.state.fit_result.iterations)
    assert snap1["refit_baseline_n"] == small_problem["n"] + 4
    assert snap1["refit_baseline_iters"] == cold_iters
    assert snap1["refit_iterations_saved"] == 0  # cold refits never credit

    eng.add_observations(x1 + 0.05, y1, update="full", warm=True)
    snap2 = eng.stats()
    warm_iters = snap2["refit_iterations"] - cold_iters
    assert snap2["refit_baseline_n"] == small_problem["n"] + 4  # unchanged
    assert snap2["refit_iterations_saved"] == max(0, cold_iters - warm_iters)


@pytest.mark.parametrize("raise_in", [None, "_execute_predict", "_execute_solve"])
def test_serve_gp_exit_status_on_exec_error(monkeypatch, capsys, raise_in):
    """``serve_gp`` exits 1 when a batch raised on every retry (its requests
    completed with ``exec_error``) and 0 on a clean run. The injected fault
    is an exception, not a numeric condition."""
    from repro.launch import serve_gp

    monkeypatch.setattr(serve_gp, "enable_compile_cache", lambda: None)
    if raise_in is not None:
        def boom(self, plan):
            raise RuntimeError("injected batch execution fault")

        monkeypatch.setattr(GPEngine, raise_in, boom)
    rc = serve_gp.main([
        "--n", "64", "--d", "2", "--requests", "4", "--depth", "2",
        "--num-features", "64", "--num-samples", "2",
        "--mix", "predict=1,sample=1", "--repeat", "0",
    ])
    err = capsys.readouterr().err
    if raise_in is None:
        assert rc == 0
        assert "exec" not in err
    else:
        assert rc == 1
        assert "injected batch execution fault" in err
