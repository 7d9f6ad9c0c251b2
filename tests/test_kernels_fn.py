"""Covariance-function unit + property tests (§2.1.3, §2.2.2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core.kernels_fn import (
    SE, MATERN12, MATERN32, MATERN52, TANIMOTO,
    gram, make_params, matvec, spectral_sample,
)
from repro.core.rff import make_fourier_features, sample_prior

KINDS = [SE, MATERN12, MATERN32, MATERN52]


@pytest.mark.parametrize("kind", KINDS)
def test_gram_symmetric_psd(kind):
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 3))
    p = make_params(kind, lengthscale=0.9, signal=1.3, d=3)
    k = gram(p, x)
    np.testing.assert_allclose(k, k.T, rtol=1e-5)
    evals = np.linalg.eigvalsh(np.asarray(k, np.float64))
    assert evals.min() > -1e-4
    # diag ≈ signal variance (distance-as-matmul gives d²≈1e-6 wobble on the diag,
    # which the non-smooth matern12 amplifies to ~1e-3 relative)
    np.testing.assert_allclose(np.diag(k), 1.3**2, rtol=3e-3)


@settings(max_examples=25, deadline=None)
@given(
    kind=st.sampled_from(KINDS),
    ls=st.floats(0.3, 3.0),
    shift=st.floats(-5.0, 5.0),
)
def test_stationarity_property(kind, ls, shift):
    """k(x, x') depends only on x − x' for stationary kernels."""
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 2))
    z = jax.random.normal(jax.random.PRNGKey(2), (8, 2))
    p = make_params(kind, lengthscale=ls, d=2)
    k1 = gram(p, x, z)
    k2 = gram(p, x + shift, z + shift)
    np.testing.assert_allclose(k1, k2, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 64), st.integers(1, 5))
def test_matvec_matches_dense(n, s):
    x = jax.random.normal(jax.random.PRNGKey(n), (n, 2))
    v = jax.random.normal(jax.random.PRNGKey(s), (n, s))
    p = make_params(SE, lengthscale=1.1, d=2, noise=0.4)
    dense = (gram(p, x) + p.noise * jnp.eye(n)) @ v
    chunked = matvec(p, x, v, row_chunk=16, jitter=p.noise)
    np.testing.assert_allclose(chunked, dense, rtol=2e-4, atol=2e-4)


def test_tanimoto_bounds_and_identity():
    x = (jax.random.uniform(jax.random.PRNGKey(0), (30, 64)) < 0.2).astype(jnp.float32)
    p = make_params(TANIMOTO, signal=1.0)
    k = gram(p, x)
    assert float(k.min()) >= 0.0 and float(k.max()) <= 1.0 + 1e-6
    nz = np.asarray(x.sum(1) > 0)
    np.testing.assert_allclose(np.diag(k)[nz], 1.0, atol=1e-6)
    # counts: MinMax, against a brute-force min/max sum
    counts = np.asarray(x) * np.random.default_rng(0).integers(1, 5, x.shape)
    brute = np.array([[np.minimum(a, b).sum() / max(np.maximum(a, b).sum(), 1.0)
                       for b in counts] for a in counts])
    np.testing.assert_allclose(gram(p, jnp.asarray(counts, jnp.float32)), brute,
                               atol=1e-6)


@pytest.mark.parametrize("kind", KINDS)
def test_rff_approximates_kernel(kind):
    """ΦΦᵀ → K as m grows (§2.2.2) — unbiasedness + variance decay."""
    d = 2
    x = jax.random.normal(jax.random.PRNGKey(3), (20, d))
    p = make_params(kind, lengthscale=1.0, signal=1.0, d=d)
    k_true = gram(p, x)
    ff = make_fourier_features(p, jax.random.PRNGKey(4), 8192, d)
    phi = ff.features(x)
    err = np.abs(np.asarray(phi @ phi.T - k_true)).max()
    assert err < 0.12, err


def test_prior_samples_cov():
    """Prior samples via RFF have covariance ≈ K (Eq. 2.63)."""
    d = 2
    x = jax.random.normal(jax.random.PRNGKey(5), (12, d))
    p = make_params(SE, lengthscale=1.0, signal=1.0, d=d)
    prior = sample_prior(p, jax.random.PRNGKey(6), 4096, 2048, d)
    f = np.asarray(prior(x))  # (12, 4096)
    cov = f @ f.T / f.shape[1]
    np.testing.assert_allclose(cov, gram(p, x), atol=0.15)


def test_spectral_sample_matches_kernel_curvature():
    """E[ωωᵀ] = −∇²k(0)/ℓ² : SE spectral variance = 1/ℓ²."""
    p = make_params(SE, lengthscale=2.0, d=3)
    w = spectral_sample(p, jax.random.PRNGKey(7), 40_000, 3)
    np.testing.assert_allclose(np.var(np.asarray(w), axis=0), 0.25, rtol=0.1)
