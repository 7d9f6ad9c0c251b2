"""Open-loop serving traffic through ``GPEngine.submit``/``step``.

The mix file gives the rate and the shape of the traffic:

* ``rate_per_s``: offered load. ``rate_per_s * seconds`` requests arrive with
  exponential gaps (Poisson arrivals), whatever the system does;
* ``mix``: weights of ``predict`` / ``sample`` / ``thompson_step``;
* ``predict_rows``: query rows of a predict request, drawn uniformly from
  the list (log-spaced sizes);
* ``sample_rows``: query rows of a sample request, a number or a list;
* ``samples``: pathwise samples per sample/thompson request, a number or a
  list;
* ``zipf_a`` / ``identities``: each solve request's (kind, seed) identity is
  drawn Zipf(a) over that many seeds, so the warm-start cache sees a hot set
  and a cold tail;
* ``thompson``: the ascent options of thompson requests (candidates, top
  candidates, Adam steps and rate);
* ``warmup_requests`` (per warm-up round), ``check_requests`` (how many
  completed requests the reference re-checks), ``drain_s`` (how long after
  the window the run waits for the last requests).

The engine compiles its per-request host-side array operations (slices,
concatenations, padding) once per distinct shape, about a third of a second
each on a TPU v5e. A predict batch's shapes follow from its bucket and each
query's row count, so any set of predict sizes can be enumerated. A solve
batch concatenates its requests' weight, noise and query columns, which
compiles once per ordered tuple of their sizes: with one sample count and one
sample row count that is one shape per batch size, and the warm-up runs them
all; with several, the tuples cannot be enumerated and some compile inside
the window (the run prints how many).

Every seed gets the same work: the number of requests, the multiset of kinds,
row counts, sample counts, identity ranks and gaps are stratified quantiles of
their distributions; the seed shuffles their order, relabels the identities
and draws the query blocks. Query blocks are host NumPy arrays, as a client
sends them. Warm-up traffic uses identities disjoint from the window's.

Each request is timed from when it was due to the host-side completion of
its result (``step()`` returns after the batch's results are ready); a
request that fails or never completes counts at the time the run gave up
on it.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.chip import reference as ref
from benchmarks.chip.data import key_seed, regression_data
from benchmarks.chip.harness import nearest_rank

KINDS = ("predict", "sample", "thompson_step")
SOLVE = ("sample", "thompson_step")
WINDOW_IDS = 10_000
WARMUP_IDS = 5_000_000


def _stratified_counts(weights: dict, total: int) -> dict:
    tot = sum(weights.values())
    quota = {k: total * w / tot for k, w in weights.items()}
    counts = {k: int(q) for k, q in quota.items()}
    rest = total - sum(counts.values())
    for k in sorted(quota, key=lambda k: counts[k] - quota[k])[:rest]:
        counts[k] += 1
    return counts


def _quantiles(k: int) -> np.ndarray:
    return (np.arange(k) + 0.5) / k


def schedule(traffic: dict, seed: int, seconds: float, d: int,
             id_base: int = WINDOW_IDS) -> list:
    """The requests of one window: dicts with ``due`` (seconds from the
    window's start), ``kind``, ``xs``, ``num_samples``, ``seed``,
    ``options``; sorted by ``due``."""
    rng = np.random.default_rng([seed, 1])
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    counts = _stratified_counts(traffic["mix"], n)
    kinds = np.array([k for k in KINDS for _ in range(counts.get(k, 0))])
    rng.shuffle(kinds)

    gaps = -np.log1p(-_quantiles(n)) / rate
    rng.shuffle(gaps)
    due = np.cumsum(gaps) * (seconds * n / (n + 1)) / gaps.sum()

    def sizes(key, count):
        out = np.resize(np.atleast_1d(np.asarray(traffic[key], dtype=int)), count)
        rng.shuffle(out)
        return out

    with_rows = np.flatnonzero(kinds != "thompson_step")
    pred = kinds[with_rows] == "predict"
    rows = np.zeros(len(with_rows), dtype=int)
    rows[pred] = sizes("predict_rows", int(pred.sum()))
    rows[~pred] = sizes("sample_rows", int((~pred).sum()))

    solve_idx = np.flatnonzero(kinds != "predict")
    samples = sizes("samples", len(solve_idx))

    pool = int(traffic["identities"])
    pmf = np.arange(1, pool + 1, dtype=float) ** -float(traffic["zipf_a"])
    cdf = np.cumsum(pmf) / pmf.sum()
    idents = np.zeros(n, dtype=int)
    for kind in SOLVE:
        idx = np.flatnonzero(kinds == kind)
        ranks = np.minimum(np.searchsorted(cdf, _quantiles(len(idx))), pool - 1)
        rng.shuffle(ranks)
        idents[idx] = id_base + rng.permutation(pool)[ranks]

    nrows = np.zeros(n, dtype=int)
    nrows[with_rows] = rows
    nsamp = np.zeros(n, dtype=int)
    nsamp[solve_idx] = samples
    out = []
    for i in range(n):
        kind = str(kinds[i])
        item = dict(due=float(due[i]), kind=kind, xs=None, num_samples=None,
                    seed=None, options={})
        if kind != "thompson_step":
            item["xs"] = rng.standard_normal((int(nrows[i]), d), dtype=np.float32)
        if kind in SOLVE:
            item["num_samples"] = int(nsamp[i])
            item["seed"] = int(idents[i])
        if kind == "thompson_step":
            item["options"] = dict(traffic["thompson"])
        out.append(item)
    return out


class Runner:
    """One open-loop run against one ``GPEngine``."""

    def __init__(self, *, config, traffic, seed, devices, spans, monitor, log,
                 control=None):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.device = devices[0]
        self.spans, self.monitor, self.log = spans, monitor, log
        # the control: the program's own lower-precision solver path, and the
        # reference one precision lower in place of the served payloads
        self.control = control
        self.precision = control or config["precision"]

    # ------------------------------------------------------------------ set-up

    def setup(self):
        import jax

        from repro.core.kernels_fn import make_params
        from repro.core.solvers.spec import CG
        from repro.kernels.ops import (reset_feature_trace_counts,
                                       reset_matvec_trace_counts)
        from repro.serve import GPEngine

        cfg = self.config
        reset_matvec_trace_counts()
        reset_feature_trace_counts()
        x, y = regression_data(cfg["n"], cfg["d"], self.seed, cfg["data_noise"])
        self.x = jax.device_put(x, self.device)
        self.y = jax.device_put(y, self.device)
        self.params = make_params(cfg["kernel"], lengthscale=cfg["lengthscale"],
                                  signal=cfg["signal_std"], noise=cfg["noise_std"],
                                  d=cfg["d"])
        spec = CG(tol=cfg["tol"], max_iters=cfg["max_iters"],
                  precision=self.precision)
        with self.spans.span("fit"):
            self.engine = GPEngine(self.params, self.x, self.y, spec=spec,
                                   num_samples=cfg["num_samples"],
                                   num_features=cfg["num_features"],
                                   seed=key_seed(self.seed))
            jax.block_until_ready(self.engine.state.post.alpha)
        self.log(f"fit: iterations={int(self.engine.state.fit_result.iterations)}")
        with self.spans.span("warmup"):
            self._warmup()

    def _drain(self):
        while len(self.engine.scheduler):
            self.engine.step()

    def _batch(self, reqs, s=None):
        """Submit ``reqs`` (kind, rows, seed) with ``s`` samples each together
        and serve them."""
        d = self.config["d"]
        opts = dict(self.traffic["thompson"])
        for kind, rows, seed in reqs:
            xs = None if rows is None else self._rng.standard_normal((rows, d), dtype=np.float32)
            if kind == "predict":
                self.engine.submit(kind, xs)
            elif kind == "sample":
                self.engine.submit(kind, xs, num_samples=s, seed=seed)
            else:
                self.engine.submit(kind, num_samples=s, seed=seed, **opts)
        self._drain()

    def _warmup(self):
        """Run every batch shape the mix can produce, then two rounds of the
        mix from disjoint seeds.

        Predict batches are shaped by (requests bucketed to a power of two,
        the largest query's row bucket) and each query's row count; solve
        batches of one sample count and one sample row count by their number
        of requests k, the number j of sample requests among them, and cold
        or warm. Solve batches that mix sizes are left to the two rounds."""
        from repro.serve.scheduler import bucket

        eng, tr = self.engine, self.traffic
        self._rng = np.random.default_rng([self.seed, 2])
        ids = iter(range(WARMUP_IDS, WARMUP_IDS + 100_000))
        max_req = eng.scheduler.max_batch_requests
        sizes = sorted(set(int(r) for r in tr["predict_rows"]))
        row_min = eng.row_bucket_min
        for nb in sorted({bucket(k, 1) for k in range(1, max_req + 1)}):
            for rb in sorted({bucket(r, row_min) for r in sizes}):
                own = [r for r in sizes if bucket(r, row_min) == rb]
                if nb == 1:  # a lone query sets the bucket itself
                    for r in own:
                        self._batch([("predict", r, None)])
                    continue
                top, rest = max(own), [r for r in sizes if r <= rb]
                for g in range(0, len(rest), nb - 1):
                    group = ([top] + rest[g:g + nb - 1] + [top] * nb)[:nb]
                    self._batch([("predict", r, None) for r in group])
        col_min = eng.col_bucket_min
        for s in sorted({int(v) for v in np.atleast_1d(tr["samples"])}):
            for r_s in sorted({int(v) for v in np.atleast_1d(tr["sample_rows"])}):
                k_max = min(max_req, eng.scheduler.max_rhs_columns // s)
                samp = [next(ids) for _ in range(k_max)]
                thom = [next(ids) for _ in range(k_max)]
                # cold: one batch per column bucket, then every identity once
                for k in sorted({min(k for k in range(1, k_max + 1)
                                     if bucket(k * s, col_min) == cb)
                                 for cb in {bucket(k * s, col_min)
                                            for k in range(1, k_max + 1)}}):
                    self._batch([("sample", r_s, next(ids)) for _ in range(k)], s)
                self._batch([("sample", r_s, i) for i in samp], s)
                self._batch([("thompson_step", None, i) for i in thom], s)
                # warm: every k, and every (column bucket, row bucket) pair
                # that a batch of k requests with j sample requests among
                # them can make
                seen = set()
                for k in range(1, k_max + 1):
                    cb = bucket(k * s, col_min)
                    for j in range(k, -1, -1):
                        key = (cb, bucket(j * r_s, row_min) if j else 0)
                        if j < k and (key in seen or (j == 0 and k > 1)):
                            continue
                        seen.add(key)
                        self._batch([("sample", r_s, i) for i in samp[:j]]
                                    + [("thompson_step", None, i) for i in thom[:k - j]], s)
        # two rounds of the mix itself: one burst (full batches), one trickle
        for r, group in enumerate((max_req, 2)):
            before = self.monitor.snapshot()
            items = schedule(tr, self.seed + 1000 + r, tr["warmup_requests"] / tr["rate_per_s"],
                             self.config["d"], id_base=WARMUP_IDS + 100_000 * (r + 1))
            for i, it in enumerate(items):
                self._submit(it)
                if (i + 1) % group == 0:
                    eng.step()
            self._drain()
            self.log(f"warm-up round {r}: "
                     f"{self.monitor.delta(before, self.monitor.snapshot())}")

    def _submit(self, it):
        return self.engine.submit(it["kind"], it["xs"], num_samples=it["num_samples"],
                                  seed=it["seed"], **it["options"])

    # ------------------------------------------------------------------ window

    def window(self, seconds: float) -> dict:
        eng, spans = self.engine, self.spans
        items = schedule(self.traffic, self.seed, seconds, self.config["d"])
        self.items = items
        n = len(items)
        drain_s = float(self.traffic["drain_s"])
        before = eng.stats()
        handles = [None] * n
        late = np.zeros(n)
        done_at = np.full(n, np.nan)
        by_id = {}
        i = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while i < n and items[i]["due"] <= now:
                with spans.span("submit"):
                    h = self._submit(items[i])
                handles[i] = h
                by_id[h.request.id] = i
                late[i] = now - items[i]["due"]
                i += 1
            if len(eng.scheduler):
                with spans.span("engine.step"):
                    comps = eng.step()
                t = time.perf_counter() - t0
                for c in comps:
                    if c.request_id in by_id:
                        done_at[by_id[c.request_id]] = t
            elif i < n:
                with spans.span("wait_arrival"):
                    time.sleep(max(0.0, items[i]["due"] - now))
            else:
                break
            if now > seconds + drain_s:
                break
        t_end = time.perf_counter() - t0
        self._drain()  # what the run gave up on leaves the engine idle
        after = eng.stats()
        self.handles = handles
        ok = np.array([h is not None and h.done and h.result().ok for h in handles])
        due = np.array([it["due"] for it in items])
        lat = np.where(ok, done_at - due, t_end - due)
        self.latencies = lat
        served = int(np.sum(ok & (done_at <= seconds)))
        self.failed = int(n - ok.sum())
        self.counters = {k: after[k] - before[k] for k in (
            "rhs_columns", "padded_columns", "solver_iterations", "solves",
            "warm_hits", "warm_misses", "predict_rows", "predict_padded_rows",
            "steps", "escalations", "retries", "failed")}
        self.counters["solve_requests"] = sum(1 for it in items if it["kind"] in SOLVE)
        self.counters["batches"] = {
            g: after["batches"].get(g, 0) - before["batches"].get(g, 0)
            for g in after["batches"]}
        self.tail = dict(last_done_s=float(np.nanmax(done_at)) if ok.any() else None,
                         generator_late_p95_ms=1e3 * nearest_rank(late, 95))
        self.log(f"window: requests={n} ok={int(ok.sum())} served_in_window={served} "
                 f"{self.tail} counters={self.counters}")
        return dict(
            attempted=n, failed=self.failed,
            metrics=dict(
                latency_p95_ms=1e3 * nearest_rank(lat, 95),
                latency_p90_ms=1e3 * nearest_rank(lat, 90),
                latency_p50_ms=1e3 * nearest_rank(lat, 50),
                served_per_s=served / seconds,
            ))

    # ------------------------------------------------------------------- check

    def release(self):
        """Copy what the check needs to the host, then free the engine."""
        import jax

        from repro.kernels.ops import FEATURE_TRACE_COUNTS, MATVEC_TRACE_COUNTS

        eng = self.engine
        st = eng.state
        rng = np.random.default_rng([self.seed, 3])
        k = int(self.traffic["check_requests"])
        comps = {}
        for i, h in enumerate(self.handles):
            if h is not None and h.done and h.result().ok:
                comps[i] = h.result()
        # the last completion of each solve identity is the one whose solution
        # the warm-start cache holds
        last = {}
        for i in sorted(comps, key=lambda i: comps[i].request_id):
            it = self.items[i]
            if it["kind"] in SOLVE:
                last[(it["kind"], it["seed"])] = i
        pick = {}
        for kind in KINDS:
            if kind == "predict":
                pool = [i for i in comps if self.items[i]["kind"] == kind]
            else:
                pool = [i for (kd, _), i in last.items() if kd == kind
                        and eng.cache.probe(st.hypers_key, kd, self.items[i]["seed"])]
            pool = sorted(pool)
            chosen = list(rng.permutation(pool)[: max(1, k // 3)]) if pool else []
            if kind != "thompson_step" and pool:
                chosen.append(max(pool, key=lambda i: self.items[i]["xs"].shape[0]))
            pick[kind] = sorted(set(int(i) for i in chosen))
        checked = []
        for kind, idx in pick.items():
            for i in idx:
                it, c = self.items[i], comps[i]
                entry = dict(kind=kind, xs=it["xs"], seed=it["seed"],
                             options=it["options"],
                             value={k2: np.asarray(v) for k2, v in c.value.items()})
                if kind in SOLVE:
                    w, eps, _ = eng._request_draws(self.handles[i].request)
                    entry.update(w=np.asarray(w), eps=np.asarray(eps),
                                 alpha=np.asarray(eng.cache.lookup(
                                     st.hypers_key, kind, it["seed"])))
                checked.append(entry)
        self.checked = checked
        self.fit = dict(
            v=np.asarray(st.post.v_mean), alpha=np.asarray(st.post.alpha),
            w=np.asarray(st.prior.w), eps=np.asarray(st.eps),
            omega=np.asarray(st.prior.ff.omega))
        self.traces = dict(gram=dict(MATVEC_TRACE_COUNTS),
                           features=dict(FEATURE_TRACE_COUNTS))
        self.counters["fit_iterations"] = int(st.fit_result.iterations)
        self.engine = None
        self.handles = None
        del eng, st

    def check(self) -> dict:
        import jax
        import jax.numpy as jnp

        cfg = self.config
        kind, ls = cfg["kernel"], cfg["lengthscale"]
        sig, noise = cfg["signal_std"] ** 2, cfg["noise_std"] ** 2
        put = lambda a: jax.device_put(jnp.asarray(a), self.device)  # noqa: E731
        x, y = self.x, self.y

        # the program's random draws against their distributions: the fit's
        # and the checked requests' apart, so neither hides the other
        solve_draws = [e for e in self.checked if e["kind"] in SOLVE]

        def pooled(key):
            return np.concatenate([e[key].ravel() for e in solve_draws]) \
                if solve_draws else None

        omega_ks = ref.spectral_ks(kind, self.fit["omega"], ls)
        w_ks, eps_ks = ref.normal_ks(self.fit["w"]), ref.normal_ks(
            self.fit["eps"], cfg["noise_std"])
        if solve_draws:
            w_ks = max(w_ks, ref.normal_ks(pooled("w")))
            eps_ks = max(eps_ks, ref.normal_ks(pooled("eps"), cfg["noise_std"]))

        fit = {k: put(v) for k, v in self.fit.items()}
        omega = fit["omega"]

        def path_values(xs, w, alpha, low=False):
            return ref.path_values(kind, xs, x, omega, w, fit["v"][:, None] - alpha,
                                   ls, sig, low=low)

        # the fit: [y | f_X + eps] against [v_mean | alpha]
        rhs = jnp.concatenate([y[:, None], ref.rff_mv(x, omega, fit["w"], sig)
                               + fit["eps"]], axis=1)
        sol = jnp.concatenate([fit["v"][:, None], fit["alpha"]], axis=1)
        fit_res = float(jnp.max(ref.rel_residual(kind, x, rhs, sol, ls, sig, noise)))

        low = self.control is not None
        solve_rhs, solve_sol = [], []
        errs = {"predict": 0.0, "sample": 0.0, "thompson_step": 0.0}
        ascent_gap = 0.0
        for e in self.checked:
            v = e["value"]
            if e["kind"] == "predict":
                xs = put(e["xs"])

                def moments(lo):
                    mean = ref.kernel_mv(kind, xs, x, fit["v"][:, None], ls, sig, low=lo)
                    var = jnp.var(path_values(xs, fit["w"], fit["alpha"], lo), axis=1)
                    return mean, var[:, None]

                mean_ref, var_ref = moments(False)
                got = moments(True) if low else (put(v["mean"])[:, None],
                                                  put(v["var"])[:, None])
                err = max(float(ref.column_rel_err(got[0], mean_ref)[0]),
                          float(ref.column_rel_err(got[1], var_ref)[0]))
            else:
                w, alpha = put(e["w"]), put(e["alpha"])
                solve_rhs.append(ref.rff_mv(x, omega, w, sig) + put(e["eps"]))
                solve_sol.append(alpha)
                if e["kind"] == "sample":
                    xs = put(e["xs"])
                    want = path_values(xs, w, alpha)
                    got = path_values(xs, w, alpha, True) if low else put(v["samples"])
                    err = float(jnp.max(ref.column_rel_err(got, want)))
                else:
                    pts = put(v["points"])
                    want = jnp.diagonal(path_values(pts, w, alpha))
                    got = (jnp.diagonal(path_values(pts, w, alpha, True)) if low
                           else put(v["values"]))
                    err = float(ref.column_rel_err(got[:, None], want[:, None])[0])
                    # the acquisition itself: what the reference's ascent from
                    # the request's candidates reaches, against what was served
                    opts = e["options"]

                    def reach(lo):
                        key = jax.random.split(jax.random.PRNGKey(e["seed"]), 3)[2]
                        return ref.thompson_values(
                            kind, key, x, y, omega, w, fit["v"][:, None] - alpha, ls, sig,
                            num_candidates=int(opts["num_candidates"]),
                            num_top=int(opts["num_top"]),
                            ascent_steps=int(opts["ascent_steps"]), lr=float(opts["lr"]),
                            low=lo)

                    best = reach(False)
                    served = reach(True) if low else put(v["values"])
                    ascent_gap = max(ascent_gap, float(
                        jnp.linalg.norm(jnp.maximum(best - served, 0.0))
                        / jnp.linalg.norm(best)))
            errs[e["kind"]] = max(errs[e["kind"]], err)
        solve_res = 0.0
        if solve_rhs:
            solve_res = float(jnp.max(ref.rel_residual(
                kind, x, jnp.concatenate(solve_rhs, 1), jnp.concatenate(solve_sol, 1),
                ls, sig, noise)))
        traces = self.traces
        off_pallas = (traces["gram"]["chunked"] + traces["gram"]["dense"]
                      + traces["features"]["features"])
        limits = cfg["check_limits"]
        checked = {k: sum(1 for e in self.checked if e["kind"] == k) for k in KINDS}
        self.log(f"checked requests: {checked}; backend traces: {traces}")
        return {
            "fit_residual": dict(value=fit_res, limit=limits["fit_residual"]),
            "solve_residual": dict(value=solve_res, limit=limits["solve_residual"]),
            "predict_err": dict(value=errs["predict"], limit=limits["predict_err"]),
            "sample_err": dict(value=errs["sample"], limit=limits["sample_err"]),
            "thompson_err": dict(value=errs["thompson_step"],
                                 limit=limits["thompson_err"]),
            "ascent_gap": dict(value=ascent_gap, limit=limits["ascent_gap"]),
            "omega_ks": dict(value=omega_ks, limit=limits["omega_ks"]),
            "w_ks": dict(value=w_ks, limit=limits["w_ks"]),
            "eps_ks": dict(value=eps_ks, limit=limits["eps_ks"]),
            "failed_requests": dict(value=float(self.failed), limit=0.0),
            "off_pallas_traces": dict(value=float(off_pallas), limit=0.0),
        }
