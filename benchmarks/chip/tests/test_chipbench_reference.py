"""The plain reference's Thompson ascent and its statistics of the random
draws, checked against autodiff, scipy and the program's own samplers."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import reference as ref  # noqa: E402


def tiny_paths(seed=0, n=40, d=3, m=16, s=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, d))
    omega = jax.random.normal(ks[1], (m, d))
    w = jax.random.normal(ks[2], (2 * m, s))
    c = 0.1 * jax.random.normal(ks[3], (n, s))
    y = jax.random.normal(ks[4], (n,))
    return x, y, omega, w, c


def test_path_grad_matches_autodiff():
    x, _, omega, w, c = tiny_paths()
    pts = jax.random.uniform(jax.random.PRNGKey(9), (6, 3))
    cols = jnp.array([0, 1, 2, 3, 0, 1])

    def total(p):
        vals = ref.path_values("matern32", p, x, omega, w, c, 0.7, 1.3)
        return jnp.sum(vals[jnp.arange(6), cols])

    want = jax.grad(total)(pts)
    got = ref.path_grad("matern32", pts, cols, x, omega, w, c, 0.7, 1.3)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_ascent_raises_the_paths():
    x, y, omega, w, c = tiny_paths()
    kw = dict(num_candidates=64, num_top=2, lr=0.05)
    key = jax.random.PRNGKey(3)
    start = ref.thompson_values("matern32", key, x, y, omega, w, c, 0.7, 1.3,
                                ascent_steps=0, **kw)
    end = ref.thompson_values("matern32", key, x, y, omega, w, c, 0.7, 1.3,
                              ascent_steps=10, **kw)
    # a start pinned to a corner of the box by the clip may not move
    assert bool(jnp.all(end >= start)) and int(jnp.sum(end > start)) >= 3


def test_ks_scaled_matches_scipy():
    from scipy import stats

    v = np.random.default_rng(1).normal(size=500)
    want = stats.kstest(v, "norm").statistic * np.sqrt(500)
    assert ref.normal_ks(v) == pytest.approx(want, rel=1e-9)
    assert ref.normal_ks(2 * v, scale=2.0) == pytest.approx(want, rel=1e-9)


def test_spectral_ks_separates_the_densities_at_the_cell_size():
    from repro.core.kernels_fn import make_params, spectral_sample

    params = make_params("matern32", lengthscale=4.0, signal=1.0, noise=0.05, d=26)
    reads = [ref.spectral_ks("matern32", spectral_sample(params, jax.random.PRNGKey(k),
                                                         512, 26), 4.0)
             for k in range(3)]
    assert max(reads) < 2.5
    gaussian = jax.random.normal(jax.random.PRNGKey(5), (512, 26)) / 4.0
    assert ref.spectral_ks("matern32", gaussian, 4.0) > 2.5
    assert ref.spectral_ks("matern32", 2 * spectral_sample(
        params, jax.random.PRNGKey(6), 512, 26), 4.0) > 2.5
