"""Table 4.2: molecule–protein binding affinity — Tanimoto-kernel GP via SDD
(synthetic fingerprints/scores; structure matches the DOCKSTRING benchmark)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels_fn import TANIMOTO, make_params
from repro.core.solvers.base import Gram
from repro.core.solvers.spec import CG, SDD, solve
from repro.data.pipeline import molecule_fingerprints
from repro.kernels.ops import gram_mv

from .common import Report


def _r2(y, pred):
    y, pred = np.asarray(y), np.asarray(pred)
    ss_res = ((y - pred) ** 2).sum()
    ss_tot = ((y - y.mean()) ** 2).sum()
    return float(1.0 - ss_res / ss_tot)


def run(report: Report, full: bool = False):
    n = 8192 if full else 2048
    for protein_seed, name in enumerate(["ESR2", "F2", "KIT"]):
        data = molecule_fingerprints(n=n, dim=1024, seed=protein_seed)
        p = make_params(TANIMOTO, signal=1.0, noise=0.3)
        op = Gram(x=data["x"], params=p)
        for method, spec in [
            ("SDD", SDD(num_steps=6000, batch_size=256, step_size_times_n=2.0)),
            ("CG", CG(max_iters=200, tol=1e-4)),
        ]:
            res = solve(op, data["y"], spec, key=jax.random.PRNGKey(0))
            # the cross-covariance matvec K(X*, X) @ v, never the (n*, n) block
            pred = gram_mv(p, data["x_test"], res.solution, z=data["x"])
            report.add("molecules(T4.2)", method, name, r2=round(_r2(data["y_test"], pred), 3))
        # mean predictor control
        report.add("molecules(T4.2)", "mean-baseline", name,
                   r2=round(_r2(data["y_test"], np.zeros(len(data["y_test"]))), 3))
