"""Open-loop molecule screening through ``GPEngine.submit``/``step``.

One Tanimoto posterior (configuration ``kernel: tanimoto``) scores count
fingerprints by posterior mean and variance; predict requests only. The mix
file gives:

* ``rate_per_s``: offered load; ``rate_per_s * seconds`` requests arrive with
  exponential gaps (Poisson arrivals), whatever the system does;
* ``mix``: weights of ``hit_list`` (a chemist's small designed set) and
  ``library`` (a campaign's chunk of a screening library) requests;
* ``hit_rows`` / ``library_rows``: molecules per request of each kind, drawn
  uniformly from the list;
* ``warmup_requests`` (one burst of the mix after the shape warm-up),
  ``check_requests`` (how many completed requests the reference re-checks,
  the largest among them), ``drain_s`` (how long after the window the run
  waits for the last requests).

Every query molecule is a fresh fingerprint from the configuration's law
(``fingerprints.py``), made on the host before the window from the seed.
Every seed gets the same work: the number of requests, the multiset of kinds
and row counts and the gaps are stratified quantiles of their distributions;
the seed shuffles their order and draws the molecules. Short and long
requests share one queue; the engine computes a library chunk in a pass of
its own row bucket (the configuration's ``predict_split_rows``), so it never
pads the hit lists batched beside it to its size. Warm-up runs every predict
pass shape the mix can make (the number of requests bucketed to a power of
two, the largest query's row bucket, each query's row count), then one burst
of the mix, on molecules of other seeds.

Each request is timed from when it was due to the host-side completion of
its result; a request that fails or never completes counts at the time the
run gave up on it. ``check()`` holds the served answers against the plain
reference (``reference_tanimoto.py``) and the program's random draws against
their laws.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.chip import reference_tanimoto as rt
from benchmarks.chip.data import key_seed
from benchmarks.chip.fingerprints import fingerprints, screening_data
from benchmarks.chip.harness import nearest_rank
from benchmarks.chip.reference import normal_ks
from benchmarks.chip.traffic.kinds.open_loop import _quantiles, _stratified_counts

KINDS = ("hit_list", "library")
ROWS = {"hit_list": "hit_rows", "library": "library_rows"}


def schedule(traffic: dict, seed: int, seconds: float, cfg: dict) -> list:
    """The requests of one window: dicts with ``due`` (seconds from the
    window's start), ``kind`` and ``xs`` (host float32 counts); sorted by
    ``due``."""
    rng = np.random.default_rng([seed, 1])
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    counts = _stratified_counts(traffic["mix"], n)
    kinds = np.array([k for k in KINDS for _ in range(counts.get(k, 0))])
    rng.shuffle(kinds)
    gaps = -np.log1p(-_quantiles(n)) / rate
    rng.shuffle(gaps)
    due = np.cumsum(gaps) * (seconds * n / (n + 1)) / gaps.sum()
    rows = np.zeros(n, dtype=int)
    for kind in KINDS:
        idx = np.flatnonzero(kinds == kind)
        sizes = np.resize(np.asarray(traffic[ROWS[kind]], dtype=int), len(idx))
        rng.shuffle(sizes)
        rows[idx] = sizes
    return [dict(due=float(due[i]), kind=str(kinds[i]),
                 xs=fingerprints(rng, int(rows[i]), cfg)) for i in range(n)]


class Runner:
    """One open-loop screening run against one ``GPEngine``."""

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
        from repro.kernels.tanimoto import MAX_COUNT
        from repro.serve import GPEngine

        cfg = self.config
        if cfg["max_count"] != MAX_COUNT:
            raise ValueError(f"the configuration's max_count {cfg['max_count']} is "
                             f"not the fused kernels' count-plane depth {MAX_COUNT}")
        reset_matvec_trace_counts()
        reset_feature_trace_counts()
        self.x_host, y = screening_data(cfg, self.seed)
        self.x = jax.device_put(self.x_host, self.device)
        self.y = jax.device_put(y, self.device)
        params = make_params(cfg["kernel"], signal=cfg["signal_std"],
                             noise=cfg["noise_std"])
        spec = CG(tol=cfg["tol"], max_iters=cfg["max_iters"],
                  precision=self.precision)
        with self.spans.span("fit"):
            self.engine = GPEngine(params, self.x, self.y, spec=spec,
                                   num_samples=cfg["num_samples"],
                                   num_features=cfg["num_features"],
                                   predict_split_rows=cfg["predict_split_rows"],
                                   seed=key_seed(self.seed))
            jax.block_until_ready(self.engine.state.post.alpha)
        self.log(f"fit: iterations={int(self.engine.state.fit_result.iterations)}")
        with self.spans.span("warmup"):
            self._warmup()

    def _drain(self):
        while len(self.engine.scheduler):
            self.engine.step()

    def _warmup(self):
        """Every predict pass shape the mix can make, then one burst of the
        mix. A pass's shapes follow from (its requests bucketed to a power of
        two, its largest query's row bucket) and each query's row count; a
        query rides in a pass of ``rb``-row blocks when the engine's
        ``predict_split_rows`` puts its bucket there."""
        from repro.serve.scheduler import bucket

        eng, tr, cfg = self.engine, self.traffic, self.config
        rng = np.random.default_rng([self.seed, 2])
        max_req = eng.scheduler.max_batch_requests
        sizes = sorted({int(r) for k in KINDS for r in tr[ROWS[k]]})
        row_min, split = eng.row_bucket_min, eng.predict_split_rows

        def alone(b):  # a bucket computed in passes of its own
            return bool(split) and b >= split

        def rides(r, rb):
            b = bucket(r, row_min)
            return b == rb if alone(rb) else b <= rb and not alone(b)

        def batch(rows):
            for r in rows:
                eng.submit("predict", fingerprints(rng, r, cfg))
            self._drain()

        for nb in sorted({bucket(k, 1) for k in range(1, max_req + 1)}):
            for rb in sorted({bucket(r, row_min) for r in sizes}):
                own = [r for r in sizes if bucket(r, row_min) == rb]
                if nb == 1:  # a lone query sets the bucket itself
                    for r in own:
                        batch([r])
                    continue
                top, rest = max(own), [r for r in sizes if rides(r, rb)]
                for g in range(0, len(rest), nb - 1):
                    batch(([top] + rest[g:g + nb - 1] + [top] * nb)[:nb])
        before = self.monitor.snapshot()
        items = schedule(tr, self.seed + 1000, tr["warmup_requests"] / tr["rate_per_s"], cfg)
        for it in items:
            eng.submit("predict", it["xs"])
        self._drain()
        self.log(f"warm-up burst: {self.monitor.delta(before, self.monitor.snapshot())}")

    # ------------------------------------------------------------------ window

    def window(self, seconds: float) -> dict:
        eng, spans = self.engine, self.spans
        items = schedule(self.traffic, self.seed, seconds, self.config)
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
                    h = eng.submit("predict", items[i]["xs"])
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
            "predict_rows", "predict_padded_rows", "feature_rows", "steps",
            "retries", "failed")}
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
                latency_p50_ms=1e3 * nearest_rank(lat, 50),
                served_per_s=served / seconds,
            ))

    # ------------------------------------------------------------------- check

    def release(self):
        """Copy what the check needs to the host, then free the engine."""
        from repro.kernels.ops import FEATURE_TRACE_COUNTS, MATVEC_TRACE_COUNTS

        st = self.engine.state
        rng = np.random.default_rng([self.seed, 3])
        done = [i for i, h in enumerate(self.handles)
                if h is not None and h.done and h.result().ok]
        k = int(self.traffic["check_requests"])
        pick = set(int(i) for i in rng.permutation(done)[: max(1, k - 1)]) if done else set()
        if done:
            pick.add(max(done, key=lambda i: self.items[i]["xs"].shape[0]))
        self.checked = [
            dict(xs=self.items[i]["xs"],
                 value={k2: np.asarray(v) for k2, v in self.handles[i].result().value.items()})
            for i in sorted(pick)]
        ff = st.prior.ff
        self.fit = dict(
            v=np.asarray(st.post.v_mean), alpha=np.asarray(st.post.alpha),
            w=np.asarray(st.prior.w), eps=np.asarray(st.eps),
            r=np.asarray(ff.r), c=np.asarray(ff.c), beta=np.asarray(ff.beta),
            salt=np.asarray(ff.salt))
        self.traces = dict(gram=dict(MATVEC_TRACE_COUNTS),
                           features=dict(FEATURE_TRACE_COUNTS))
        self.counters["fit_iterations"] = int(st.fit_result.iterations)
        self.engine = None
        self.handles = None

    def check(self) -> dict:
        import jax
        import jax.numpy as jnp

        cfg = self.config
        sig, noise = cfg["signal_std"] ** 2, cfg["noise_std"] ** 2
        put = lambda a: jax.device_put(jnp.asarray(a), self.device)  # noqa: E731
        x, y, fit = self.x, self.y, self.fit
        tables_host = rt.ioffe_table(fit, cfg["max_count"])
        tables, salt = tuple(put(a) for a in tables_host), put(fit["salt"])
        w, v, alpha = put(fit["w"]), put(fit["v"]), put(fit["alpha"])

        # the fit: [y | Phi w + eps] against [v_mean | alpha] on seeded rows
        rows = rt.fit_rows(x.shape[0], cfg["fit_check_rows"], self.seed)
        xr = jnp.take(x, put(rows), axis=0)
        rhs = jnp.concatenate([y[rows][:, None], rt.rf_mv(xr, tables, salt, w, sig)
                               + put(fit["eps"][rows])], 1)
        sol = jnp.concatenate([v[:, None], alpha], axis=1)
        fit_res = float(jnp.max(rt.rel_residual_rows(xr, x, rows, rhs, sol, sig, noise)))

        # each checked request's served mean and variance against the
        # reference's, as the root mean square of the difference over the
        # request's rows in units of the prior's standard deviation (and
        # variance): a scale no request's answer can shrink
        low = self.control is not None
        predict_err = var_err = 0.0
        for e in self.checked:
            xs = put(e["xs"])
            mean_ref, var_ref = rt.moments(xs, x, tables, salt, w, v, alpha, sig)
            if low:
                mean_got, var_got = rt.moments(xs, x, tables, salt, w, v, alpha, sig,
                                               low=True)
            else:
                mean_got, var_got = put(e["value"]["mean"]), put(e["value"]["var"])
            rms = lambda d: float(jnp.sqrt(jnp.mean(d * d)))  # noqa: E731
            predict_err = max(predict_err, rms(mean_got - mean_ref) / np.sqrt(sig))
            var_err = max(var_err, rms(var_got - var_ref) / sig)

        a, b = rt.edited_pairs(self.x_host, fit["r"].shape[0], self.seed, cfg["max_count"])
        rf_collision = rt.collision_z(a, b, tables)
        traces = self.traces
        off_pallas = (traces["gram"]["chunked"] + traces["gram"]["dense"]
                      + traces["features"]["features"])
        sizes = [e["xs"].shape[0] for e in self.checked]
        self.log(f"checked predict requests: {len(sizes)} (rows {sizes}); fit rows "
                 f"checked: {len(rows)}; backend traces: {traces}")
        limits = cfg["check_limits"]
        values = dict(
            fit_residual=fit_res, predict_err=predict_err, var_err=var_err,
            r_ks=rt.gamma2_ks(fit["r"]), c_ks=rt.gamma2_ks(fit["c"]),
            beta_ks=rt.uniform_ks(fit["beta"]), w_ks=normal_ks(fit["w"]),
            eps_ks=normal_ks(fit["eps"], cfg["noise_std"]), rf_collision=rf_collision)
        out = {k: dict(value=val, limit=limits[k]) for k, val in values.items()}
        out["failed_requests"] = dict(value=float(self.failed), limit=0.0)
        out["off_pallas_traces"] = dict(value=float(off_pallas), limit=0.0)
        return out
