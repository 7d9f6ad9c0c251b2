"""Tanimoto (MinMax) GPs on count fingerprints: the fused kernels in interpret
mode against their oracles, Tanimoto random features, and GPEngine serving a
Tanimoto posterior (tiny sizes: n ≈ 256, d = 64)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernels_fn import TANIMOTO, gram, make_params
from repro.core.rff import TanimotoFeatures, sample_prior
from repro.core.solvers.spec import CG
from repro.kernels import ref
from repro.kernels.ops import (FEATURE_TRACE_COUNTS, MATVEC_TRACE_COUNTS, gram_mv,
                               gram_rows_pair, tanimoto_rf_mv)
from repro.kernels.tanimoto import MAX_COUNT, check_counts, hash_bit, rf_table
from repro.serve import GPEngine


def counts(rng, n, d=64, density=0.2):
    """Sparse count fingerprints: about ``density`` of the bits set, counts
    1..MAX_COUNT."""
    on = rng.random((n, d)) < density
    return (on * rng.integers(1, MAX_COUNT + 1, (n, d))).astype(np.float32)


def brute(x, z):
    return np.array([[np.minimum(a, b).sum() / max(np.maximum(a, b).sum(), 1.0)
                      for b in z] for a in x])


@pytest.mark.parametrize("density", [0.05, 0.2, 0.6])
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_pallas_matvec_matches_oracle(density, precision):
    rng = np.random.default_rng(int(100 * density))
    x = counts(rng, 90, density=density)
    z = counts(rng, 70, density=density)
    x[4] = 0.0  # an all-zero row: T = 0 against everything
    v = rng.normal(size=(70, 3)).astype(np.float32)
    p = make_params(TANIMOTO, signal=1.1)
    want = ref.tanimoto_matvec_ref(x, z, v, signal=float(p.signal))
    np.testing.assert_allclose(want, float(p.signal) * brute(x, z) @ v, atol=1e-5)
    out = gram_mv(p, x, v, z=z, backend="pallas", block=32, interpret=True,
                  precision=precision)
    tol = 1e-5 if precision == "fp32" else 3e-2  # bf16 rounds T and v, not Σmin
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol)


def test_pallas_matvec_refuses_what_it_cannot_take_exactly():
    rng = np.random.default_rng(1)
    x, z = counts(rng, 40), counts(rng, 48)
    v = rng.normal(size=(48, 2)).astype(np.float32)
    p = make_params(TANIMOTO)
    for bad in (MAX_COUNT + 1.0, 1.5, -1.0):
        xb = x.copy()
        xb[3, 7] = bad
        with pytest.raises(ValueError, match=f"integer counts 0..{MAX_COUNT}"):
            check_counts(xb)
        out = np.asarray(gram_mv(p, xb, v, z=z, backend="pallas", block=16,
                                 interpret=True))
        assert np.isnan(out[3]).all() and np.isfinite(np.delete(out, 3, 0)).all()
        out = np.asarray(gram_mv(p, z, v[:40], z=xb, backend="pallas", block=16,
                                 interpret=True))
        assert np.isnan(out).all()


def test_pallas_matvec_gradients():
    """σ_f², the noise and v differentiate; the count vectors refuse."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(counts(rng, 32))
    v = jnp.asarray(rng.normal(size=(32, 2)).astype(np.float32))
    p = make_params(TANIMOTO, signal=1.3, noise=0.4)

    def quad(p, v, backend):
        return jnp.sum(v * gram_mv(p, x, v, jitter=p.noise, backend=backend,
                                   block=16, interpret=True))

    gf = jax.grad(quad, argnums=(0, 1))(p, v, "pallas")
    gd = jax.grad(quad, argnums=(0, 1))(p, v, "dense")
    np.testing.assert_allclose(gf[0].log_signal, gd[0].log_signal, rtol=1e-5)
    np.testing.assert_allclose(gf[0].log_noise, gd[0].log_noise, rtol=1e-5)
    np.testing.assert_allclose(gf[1], gd[1], rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="no derivative"):
        jax.grad(lambda x: jnp.sum(gram_mv(p, x, v, backend="pallas", block=16,
                                           interpret=True)))(x)


def test_rows_pair_composes_fused_matvecs():
    rng = np.random.default_rng(3)
    x = counts(rng, 64)
    idx = jnp.arange(0, 64, 4)
    look = rng.normal(size=(64, 2)).astype(np.float32)
    b = rng.normal(size=(16, 2)).astype(np.float32)
    p = make_params(TANIMOTO, signal=0.9)
    before = MATVEC_TRACE_COUNTS["pallas"]
    err, g = gram_rows_pair(p, x, idx, look, b, backend="pallas", block=16,
                            interpret=True)
    assert MATVEC_TRACE_COUNTS["pallas"] == before + 2
    k = np.asarray(gram(p, x[np.asarray(idx)], x))
    np.testing.assert_allclose(err, k @ look - b, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g, k.T @ (k @ look - b), rtol=1e-5, atol=1e-4)


def ioffe_hashes(ff: TanimotoFeatures, x):
    """(n, m, 2) hashes (i*, t*) of each row by Ioffe's recipe, from the map's
    draws; -1 for an all-zero row."""
    x = np.asarray(x, np.float64)
    r, c, beta = (np.asarray(a, np.float64) for a in (ff.r, ff.c, ff.beta))
    out = np.full((x.shape[0], r.shape[0], 2), -1, np.int64)
    for row, xr in enumerate(x):
        nz = np.flatnonzero(xr)
        if nz.size == 0:
            continue
        t = np.floor(np.log(xr[nz]) / r[:, nz] + beta[:, nz])
        a = c[:, nz] / (np.exp(r[:, nz] * (t - beta[:, nz])) * np.exp(r[:, nz]))
        k = np.argmin(a, axis=1)
        out[row, :, 0] = nz[k]
        out[row, :, 1] = t[np.arange(len(k)), k]
    return out


def test_features_collide_with_tanimoto_probability_and_are_unbiased():
    rng = np.random.default_rng(4)
    x = counts(rng, 48)
    x[0] = 0.0
    m = 2048
    p = make_params(TANIMOTO, signal=1.0)
    ff = sample_prior(p, jax.random.PRNGKey(5), 1, m, x.shape[1]).ff
    assert isinstance(ff, TanimotoFeatures) and ff.num_features == m
    h = ioffe_hashes(ff, x)
    t = brute(x, x)
    a, b = np.triu_indices(len(x), 1)
    a, b = a[a > 0], b[a > 0]  # pairs of non-zero rows
    hits = np.all(h[a] == h[b], axis=-1).sum(axis=1)
    zs = (hits - m * t[a, b]) / np.sqrt(m * t[a, b] * (1 - t[a, b]) + 1e-12)
    assert abs(zs.mean() * np.sqrt(len(zs))) < 6.0 and np.abs(zs).max() < 6.0
    # the features are the hashes' signs: s_k(i*, t*), 0 for the zero row
    phi = np.sign(np.asarray(ff.features(x)))
    sign = 1 - 2 * np.asarray(hash_bit(np.asarray(ff.salt)[None, :], h[..., 0],
                                       h[..., 1]))
    agree = (phi[1:] == sign[1:]).mean()
    assert agree > 0.999, agree
    assert (phi[0] == 0).all()
    # Φ Φᵀ → T: unbiased, with the spread of m sign products
    gap = (phi @ phi.T / m - t)[a, b]
    assert abs(gap.mean()) < 4 * np.sqrt(1.0 / m / len(gap)) + 2e-3
    assert np.abs(gap).max() < 6 * np.sqrt(1.0 / m)


def test_rf_kernel_matches_materialised_features():
    rng = np.random.default_rng(6)
    x = counts(rng, 70)
    x[5] = 0.0
    p = make_params(TANIMOTO, signal=1.4)
    ff = sample_prior(p, jax.random.PRNGKey(7), 1, 200, x.shape[1]).ff
    w = rng.normal(size=(200, 3)).astype(np.float32)
    before = dict(FEATURE_TRACE_COUNTS)
    want = np.asarray(ff.features(x)) @ w
    got = tanimoto_rf_mv(x, ff.table, w, signal=ff.signal, backend="pallas",
                         block=32, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert FEATURE_TRACE_COUNTS["pallas"] == before["pallas"] + 1
    np.testing.assert_allclose(ff.phi_mv(x, w, backend="features"), want,
                               rtol=1e-5, atol=1e-5)
    xb = x.copy()
    xb[9, 2] = 2.5
    got = np.asarray(tanimoto_rf_mv(xb, ff.table, w, signal=ff.signal,
                                    backend="pallas", block=32, interpret=True))
    assert np.isnan(got[9]).all() and np.isfinite(np.delete(got, 9, 0)).all()


def recipe_signs(r, c, beta, salt, x):
    """The signs of Ioffe's hashes of the rows of x: the recipe per (row, hash,
    dim) in float64, rounded to float32, the first dim of least ``a``."""
    x, r, c, beta = (np.asarray(a, np.float64) for a in (x, r, c, beta))
    present = x[:, None, :] > 0
    lx = np.log(np.where(present, x[:, None, :], 1.0))
    t = np.floor(lx / r + beta)
    a = (c / (np.exp(r * (t - beta)) * np.exp(r))).astype(np.float32)
    i = np.argmin(np.where(present, a, np.inf), axis=-1)
    ti = np.take_along_axis(t, i[..., None], axis=-1)[..., 0].astype(np.int32)
    sign = 1 - 2 * np.asarray(hash_bit(jnp.asarray(salt)[None, :], jnp.asarray(i),
                                       jnp.asarray(ti)))
    return np.where(present.any(axis=-1), sign, 0)


def test_features_follow_the_recipe_exactly():
    """The table holds the recipe's ``a`` (float64, rounded to float32) and the
    kernels take the first dim of least ``a``: every hash equals the recipe
    evaluated per row, ties included, on both backends and under ``jit``."""
    rng = np.random.default_rng(9)
    x = counts(rng, 96)
    p = make_params(TANIMOTO, signal=1.0)
    ff = sample_prior(p, jax.random.PRNGKey(10), 1, 128, x.shape[1]).ff
    # dims 2 and 5 draw alike in every hash, so a row holding only those two
    # with equal counts ties exactly; the first dim names the hash
    r, c, beta = (a.at[:, 5].set(a[:, 2]) for a in (ff.r, ff.c, ff.beta))
    ff = dataclasses.replace(ff, r=r, c=c, beta=beta,
                             table=rf_table(r, c, beta, ff.salt))
    x[0] = 0.0
    x[0, 2] = x[0, 5] = 3.0
    x[1] = 0.0
    want = recipe_signs(r, c, beta, ff.salt, x)
    scale = np.sqrt(float(ff.signal) / ff.num_features)
    np.testing.assert_array_equal(np.rint(np.asarray(ff.features(x)) / scale), want)
    eye = jnp.eye(ff.num_features, dtype=jnp.float32)
    got = tanimoto_rf_mv(x, ff.table, eye, signal=ff.signal, backend="pallas",
                         block=32, interpret=True)
    np.testing.assert_array_equal(np.rint(np.asarray(got) / scale), want)
    jitted = jax.jit(lambda r, c, b, s: rf_table(r, c, b, s))(r, c, beta, ff.salt)
    np.testing.assert_array_equal(np.asarray(jitted), np.asarray(ff.table))
    t0 = np.floor(np.log(np.float32(3.0)) / np.asarray(r[:, 2]) + np.asarray(beta[:, 2]))
    bit = np.asarray(hash_bit(ff.salt, jnp.full(128, 2), jnp.asarray(t0, jnp.int32)))
    np.testing.assert_array_equal(want[0], 1 - 2 * bit)
    assert (want[1] == 0).all()


@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(8)
    x = counts(rng, 256)
    y = (x[:, :8].sum(1) - x[:, 8:16].sum(1)).astype(np.float32)
    y = (y - y.mean()) / y.std()
    p = make_params(TANIMOTO, signal=1.0, noise=0.3)
    eng = GPEngine(p, jnp.asarray(x), jnp.asarray(y), spec=CG(tol=1e-4, max_iters=200),
                   num_samples=8, num_features=256, seed=3)
    return eng, rng


def test_engine_serves_tanimoto_predict_against_reference(engine):
    eng, rng = engine
    st = eng.state
    assert isinstance(st.prior.ff, TanimotoFeatures)
    assert eng.stats()["feature_rows"] >= st.n
    xs = counts(rng, 20)
    h = eng.predict(xs)
    eng.run_until_idle()
    c = h.result()
    assert c.ok, c.error
    k = np.asarray(gram(st.params, xs, np.asarray(st.x)))
    v, alpha = np.asarray(st.post.v_mean), np.asarray(st.post.alpha)
    phi = np.asarray(st.prior.ff.features(xs))
    paths = phi @ np.asarray(st.prior.w) + k @ (v[:, None] - alpha)
    np.testing.assert_allclose(c.value["mean"], k @ v, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(c.value["var"], paths.var(axis=1), rtol=1e-4,
                               atol=1e-4)


def test_engine_serves_tanimoto_samples(engine):
    eng, rng = engine
    xs = counts(rng, 12)
    h = eng.sample(xs, num_samples=4, seed=11)
    eng.run_until_idle()
    c = h.result()
    assert c.ok, c.error
    assert c.value["samples"].shape == (12, 4)
    assert np.isfinite(np.asarray(c.value["samples"])).all()


def test_engine_refuses_thompson_and_bad_counts_on_tanimoto(engine):
    eng, rng = engine
    h = eng.thompson_step(num_samples=4, seed=5)
    assert h.done
    c = h.result()
    assert not c.ok and c.error["code"] == "unsupported_kernel"
    assert "tanimoto" in c.error["message"]
    bad = counts(rng, 3)
    bad[1, 0] = 9.0
    with pytest.raises(ValueError, match="integer counts"):
        eng.predict(bad)
    with pytest.raises(ValueError, match="integer counts"):
        GPEngine(eng.state.params, jnp.asarray(bad), jnp.zeros(3), num_features=16)


def test_engine_predict_spans_and_feature_rows(engine, tmp_path):
    """``gp.predict`` carries the kernel and row counts, ``gp.features`` the
    map's kind and rows, and ``feature_rows`` counts the padded block."""
    import glob

    from jax.profiler import ProfileData

    eng, rng = engine
    before = eng.stats()
    with jax.profiler.trace(str(tmp_path)):
        eng.predict(counts(rng, 5))
        eng.predict(counts(rng, 20))
        eng.run_until_idle()
    after = eng.stats()
    # one batch of two blocks, each padded to the row bucket of 20 (32)
    assert after["feature_rows"] - before["feature_rows"] == 2 * 32
    assert after["phases"]["features"]["calls"] == before["phases"]["features"]["calls"] + 1
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True))[-1]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("gp.predict", "gp.features"):
                    found[e.name] = dict(e.stats)
    assert found["gp.predict"]["kernel"] == "tanimoto"
    assert int(found["gp.predict"]["rows"]) == 25
    assert int(found["gp.predict"]["bucket_rows"]) == 32
    assert found["gp.features"]["kind"] == "tanimoto"
    assert int(found["gp.features"]["rows"]) == 64
