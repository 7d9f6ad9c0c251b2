"""Table 3.1 / 4.1: regression baselines — CG vs SGD vs SDD vs SVGP.

Reproduces the paper's table *structure and claims* on synthetic UCI-shaped data:
RMSE / NLL / time per method, plus the low-noise (ill-conditioned) RMSE row where
CG degrades and SGD/SDD stay stable (§3.3.1 "robustness to ill-conditioning")."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels_fn import make_params
from repro.core.pathwise import posterior_functions
from repro.core.solvers.spec import CG, SDD, SGD
from repro.core.svgp import sgpr
from repro.data.pipeline import regression_dataset

from .common import Report, nll_gaussian, rmse, timed


def _mv_equiv(spec, n: int) -> float:
    """Equivalent-full-matvec spend of a stochastic solve, from row-block
    accounting: a row-block contraction touches p·n kernel entries (p/n of a
    full n² matvec) and a feature contraction touches n·2q entries (2q/n).
    SGD spends two row contractions (the K[idx,:] panel pair) and two feature
    contractions (Φᵀ· then Φ·) per step; SDD one row contraction. The +1 is the
    exact finalize residual — the only *full* matvec either solver executes,
    which is why their ``matvecs`` column reads 1."""
    steps, p = spec.num_steps, spec.batch_size
    if isinstance(spec, SGD):
        per_step = (2.0 * p + 4.0 * spec.num_features) / n
    else:
        per_step = p / n
    return round(1.0 + steps * per_step, 1)


def run(report: Report, full: bool = False, smoke: bool = False):
    """``smoke=True`` (the CI matvec-regression gate — see check_matvecs.py)
    keeps the exact problem sizes and CG specs of the default run, so CG's
    counted matvecs stay comparable to the committed baseline, but slashes the
    stochastic solvers' step budgets: their matvec count is structural (the one
    exact finalize residual), independent of num_steps, while their wall time is
    not. Smoke RMSE/NLL rows are therefore meaningless — only matvecs matter."""
    datasets = ["pol", "elevators", "bike"] if not full else list(
        __import__("repro.data.pipeline", fromlist=["UCI_SHAPES"]).UCI_SHAPES)
    scale = 1.0 if full else 0.25  # scaled-down n for the CPU container
    stoch_steps = 100 if smoke else 8000
    for name in datasets:
        data = regression_dataset(name, seed=0)
        n = int(data["n"] * scale)
        x, y = data["x"][:n], data["y"][:n]
        xt, yt = data["x_test"], data["y_test"]
        d = x.shape[1]
        p = make_params("matern32", lengthscale=float(np.sqrt(d)) * 0.5,
                        signal=1.0, noise=0.1, d=d)

        budget = dict(num_samples=16, num_features=2048)
        for method, spec in [
            ("CG", CG(max_iters=150, tol=1e-3)),
            ("SGD", SGD(num_steps=stoch_steps, batch_size=256, step_size_times_n=0.5)),
            ("SDD", SDD(num_steps=stoch_steps, batch_size=256, step_size_times_n=2.0)),
        ]:
            pf, dt = timed(posterior_functions, p, x, y, jax.random.PRNGKey(0),
                           spec=spec, **budget)
            mu, var = pf.sample_mean_and_var(xt)
            info = pf.solve_info
            # matvecs = full (K+σ²I) matvecs the solve actually spent (CG: one
            # per iteration — the seed paid two extra per solve; SGD/SDD: the
            # single exact-residual check, their loops touch only row blocks).
            # mv_equiv makes the cost columns comparable across families: for
            # the stochastic solvers it converts the per-step row-block and
            # feature work into full-matvec equivalents (see _mv_equiv) —
            # "matvecs: 1" alone badly understates what SGD/SDD spend.
            extra = {}
            if isinstance(spec, CG):
                extra["mv_equiv"] = float(int(info.matvecs))
            else:
                extra["mv_equiv"] = _mv_equiv(spec, n)
            report.add("solvers(T3.1/4.1)", method, name,
                       rmse=rmse(mu, yt), nll=nll_gaussian(yt, mu, var),
                       seconds=round(dt, 2), iters=int(info.iterations),
                       matvecs=int(info.matvecs), **extra)
        # SVGP baseline (collapsed SGPR with m inducing points)
        z = x[:: max(1, n // 512)][:512]
        post, dt = timed(sgpr, p, x, y, z)
        mu = post.mean(xt)
        var = post.var(xt)
        report.add("solvers(T3.1/4.1)", "SVGP(SGPR)", name,
                   rmse=rmse(mu, yt), nll=nll_gaussian(yt, mu, var),
                   seconds=round(dt, 2))

        # low-noise, ill-conditioned row (RMSE† in Table 3.1)
        p_low = dataclasses.replace(p, log_noise=jnp.log(jnp.asarray(0.001)))
        for method, spec in [
            ("CG", CG(max_iters=150, tol=1e-3)),
            ("SDD", SDD(num_steps=stoch_steps, batch_size=256, step_size_times_n=2.0)),
        ]:
            pf, dt = timed(posterior_functions, p_low, x, y, jax.random.PRNGKey(0),
                           spec=spec, num_samples=4, num_features=2048)
            mu = pf.mean(xt)
            report.add("solvers-lownoise", method, name, rmse=rmse(mu, yt),
                       seconds=round(dt, 2),
                       matvecs=int(pf.solve_info.matvecs))
