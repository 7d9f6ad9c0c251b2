"""Per-kernel allclose vs ref.py oracles (interpret mode), sweeping shapes/dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernels_fn import make_params
from repro.kernels.gram_matvec import PALLAS_KINDS, symmetric_fits
from repro.kernels.ops import (
    GRAM_TILING_TRACE_COUNTS, flash_attention, gram_matvec, gram_mv,
    reset_matvec_trace_counts, rff_matvec,
)
from repro.kernels.ref import flash_attention_ref, gram_matvec_ref, rff_matvec_ref


def _rel_err(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30)


@pytest.mark.parametrize("kind", ["se", "matern12", "matern32", "matern52"])
@pytest.mark.parametrize("n,m,s", [(64, 64, 1), (200, 130, 3), (256, 256, 8)])
def test_gram_matvec_kinds_shapes(kind, n, m, s):
    key = jax.random.PRNGKey(n + m + s)
    x = jax.random.normal(key, (n, 4))
    z = jax.random.normal(jax.random.fold_in(key, 1), (m, 4))
    v = jax.random.normal(jax.random.fold_in(key, 2), (m, s))
    p = make_params(kind, lengthscale=0.8, signal=1.4, d=4)
    out = gram_matvec(p, x, v, z=z, block=64, interpret=True)
    ref = gram_matvec_ref(x / p.lengthscale, z / p.lengthscale, v,
                          kind=kind, signal=float(p.signal))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kind", PALLAS_KINDS)
@pytest.mark.parametrize("nb", [1, 2, 3, 4])
@pytest.mark.parametrize("s", [1, 17])
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_gram_matvec_symmetric_tiling(kind, nb, s, precision):
    """K(x, x) @ v on the symmetric grid: odd and even block counts (the
    half-offset pair), n not a block multiple; against the dense reference and
    against the square grid of the same operator (z given). One block has no
    pair to share and stays on the square grid."""
    n = 64 * nb - 13
    key = jax.random.PRNGKey(100 * nb + s)
    x = jax.random.normal(key, (n, 4))
    v = jax.random.normal(jax.random.fold_in(key, 1), (n, s))
    p = make_params(kind, lengthscale=0.8, signal=1.4, d=4)
    kw = dict(block=64, interpret=True, precision=precision)
    reset_matvec_trace_counts()
    out = gram_matvec(p, x, v, **kw)
    sym = int(nb > 1)
    assert GRAM_TILING_TRACE_COUNTS == {"symmetric": sym, "square": 1 - sym}
    square = gram_matvec(p, x, v, z=x, **kw)
    assert GRAM_TILING_TRACE_COUNTS == {"symmetric": sym, "square": 2 - sym}
    ref = gram_matvec_ref(x / p.lengthscale, x / p.lengthscale, v,
                          kind=kind, signal=float(p.signal))
    # Matérn-1/2 takes √d² on the diagonal, where the matmul identity's
    # roundoff (d² ~ 1e-7 rather than 0) differs between kernel and reference
    tol = {"fp32": 2e-3 if kind == "matern12" else 2e-4, "bf16": 2e-2}[precision]
    assert _rel_err(out, ref) < tol
    assert _rel_err(out, square) < 1e-5


def test_gram_tiling_engagement_and_counter():
    """The symmetric grid is taken for K(x, x) within the VMEM budget only:
    a cross-Gram and an over-budget symmetric shape take the square grid."""
    p = make_params("matern32", lengthscale=1.0, d=3)
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 3))
    v = jnp.ones((96, 2))
    reset_matvec_trace_counts()
    gram_mv(p, x, v, backend="pallas", block=64, interpret=True)
    assert GRAM_TILING_TRACE_COUNTS == {"symmetric": 1, "square": 0}
    gram_mv(p, x, v, z=x[:40], backend="pallas", block=64, interpret=True)
    assert GRAM_TILING_TRACE_COUNTS == {"symmetric": 1, "square": 1}
    gram_mv(p, x[:64], v[:64], backend="pallas", block=64, interpret=True)
    assert GRAM_TILING_TRACE_COUNTS == {"symmetric": 1, "square": 2}  # one block
    n_big, s_big, block = 200_000, 64, 512
    assert not symmetric_fits(n_big, s_big, block)
    assert symmetric_fits(15_360, s_big, block)  # the pol solve
    big = jax.eval_shape(
        lambda x, v: gram_mv(p, x, v, backend="pallas", block=block,
                             interpret=True),
        jax.ShapeDtypeStruct((n_big, 3), jnp.float32),
        jax.ShapeDtypeStruct((n_big, s_big), jnp.float32),
    )
    assert big.shape == (n_big, s_big)
    assert GRAM_TILING_TRACE_COUNTS == {"symmetric": 1, "square": 3}
    reset_matvec_trace_counts()
    assert GRAM_TILING_TRACE_COUNTS == {"symmetric": 0, "square": 0}


def test_gram_matvec_jitter_square():
    key = jax.random.PRNGKey(0)
    n, s = 192, 4
    x = jax.random.normal(key, (n, 3))
    v = jax.random.normal(jax.random.fold_in(key, 1), (n, s))
    p = make_params("se", lengthscale=1.0, signal=1.0, d=3, noise=0.5)
    out = gram_matvec(p, x, v, jitter=float(p.noise), block=64, interpret=True)
    ref = gram_matvec_ref(x, x, v, kind="se", signal=1.0, jitter=float(p.noise))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_gram_matvec_1d_vector_rhs():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (100, 2))
    v = jax.random.normal(jax.random.fold_in(key, 1), (100,))
    p = make_params("matern32", lengthscale=1.2, d=2)
    out = gram_matvec(p, x, v, block=64, interpret=True)
    ref = gram_matvec_ref(x / p.lengthscale, x / p.lengthscale, v[:, None],
                          kind="matern32")[:, 0]
    assert out.shape == (100,)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,f,s", [(64, 64, 1), (100, 90, 2), (256, 512, 4)])
def test_rff_matvec_shapes(n, f, s):
    key = jax.random.PRNGKey(n + f)
    x = jax.random.normal(key, (n, 3))
    omega = jax.random.normal(jax.random.fold_in(key, 1), (f, 3))
    w = jax.random.normal(jax.random.fold_in(key, 2), (2 * f, s))
    out = rff_matvec(x, omega, w, signal=1.3, block=64, interpret=True)
    ref = rff_matvec_ref(x, omega, w, signal=1.3)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,s,hq,hkv,d", [(1, 128, 2, 2, 32), (2, 256, 4, 2, 64),
                                          (1, 130, 2, 1, 32)])
def test_flash_attention_vs_ref(causal, b, s, hq, hkv, d):
    key = jax.random.PRNGKey(s + hq)
    q = jax.random.normal(key, (b, s, hq, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    head_map = jnp.arange(hq) // (hq // hkv)
    ref = flash_attention_ref(q, k[:, :, head_map], v[:, :, head_map], causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (1, 128, 2, 32), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 128, 2, 32), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 128, 2, 32), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, rtol=3e-2, atol=3e-2)


def test_gram_matvec_bf16_inputs():
    key = jax.random.PRNGKey(10)
    x = jax.random.normal(key, (128, 4), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 1), (128, 2), jnp.bfloat16)
    p = make_params("se", lengthscale=1.0, d=4, dtype=jnp.float32)
    out = gram_matvec(p, x.astype(jnp.float32), v.astype(jnp.float32), block=64,
                      interpret=True)
    ref = gram_matvec_ref(x.astype(jnp.float32), x.astype(jnp.float32),
                          v.astype(jnp.float32), kind="se")
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Differentiability: jax.grad through the fused Pallas matvec must match
# autodiff through the dense gram() reference (interpret mode, CPU).
# ---------------------------------------------------------------------------

from repro.core.kernels_fn import gram  # noqa: E402
from repro.kernels.ops import gram_rows_matvec, resolve_backend  # noqa: E402


@pytest.mark.parametrize("kind", ["se", "matern12", "matern32", "matern52"])
@pytest.mark.parametrize("n,m", [(96, 96), (96, 130)])
def test_gram_matvec_vjp_matches_dense_autodiff(kind, n, m):
    """∂/∂{log ℓ, log σ_f, x, z, v} of uᵀ(σ_f²K)v: fused custom-VJP vs dense."""
    key = jax.random.PRNGKey(n + m)
    x = jax.random.normal(key, (n, 3))
    z = jax.random.normal(jax.random.fold_in(key, 1), (m, 3))
    v = jax.random.normal(jax.random.fold_in(key, 2), (m, 4))
    u = jax.random.normal(jax.random.fold_in(key, 3), (n, 4))
    p = make_params(kind, lengthscale=0.9, signal=1.3, d=3)

    def fused(p, x, z, v):
        return jnp.sum(u * gram_matvec(p, x, v, z=z, block=64, interpret=True))

    def dense(p, x, z, v):
        return jnp.sum(u * (gram(p, x, z) @ v))

    gf = jax.grad(fused, argnums=(0, 1, 2, 3))(p, x, z, v)
    gd = jax.grad(dense, argnums=(0, 1, 2, 3))(p, x, z, v)
    assert _rel_err(gf[0].log_lengthscale, gd[0].log_lengthscale) < 1e-4
    assert _rel_err(gf[0].log_signal, gd[0].log_signal) < 1e-4
    for a, b in zip(gf[1:], gd[1:]):
        assert _rel_err(a, b) < 1e-4


@pytest.mark.parametrize("kind", ["se", "matern32", "matern52"])
def test_gram_matvec_vjp_symmetric(kind):
    """z=None (K(X,X), duplicate diagonal): fused VJP still matches autodiff."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (100, 3))
    v = jax.random.normal(jax.random.fold_in(key, 1), (100, 2))
    u = jax.random.normal(jax.random.fold_in(key, 2), (100, 2))
    p = make_params(kind, lengthscale=1.1, signal=0.8, d=3)

    def fused(p, x):
        return jnp.sum(u * gram_matvec(p, x, v, block=64, interpret=True))

    def dense(p, x):
        return jnp.sum(u * (gram(p, x) @ v))

    reset_matvec_trace_counts()
    assert _rel_err(fused(p, x), dense(p, x)) < 1e-5  # the symmetric forward
    assert GRAM_TILING_TRACE_COUNTS["symmetric"] == 1
    gf = jax.grad(fused, argnums=(0, 1))(p, x)
    gd = jax.grad(dense, argnums=(0, 1))(p, x)
    assert _rel_err(gf[0].log_lengthscale, gd[0].log_lengthscale) < 1e-4
    assert _rel_err(gf[1], gd[1]) < 1e-4


def test_gram_matvec_vjp_matern12_diagonal_is_finite():
    """Matérn-1/2 is non-differentiable at coincident points: plain autodiff
    through sqrt(d²+ε) produces ~1/√ε garbage on the symmetric diagonal, while
    the fused VJP adopts the symmetric-limit convention (zero contribution) and
    stays finite and bounded."""
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (64, 3))
    v = jax.random.normal(jax.random.fold_in(key, 1), (64, 2))
    p = make_params("matern12", lengthscale=1.0, signal=1.0, d=3)
    g = jax.grad(
        lambda x: jnp.sum(gram_matvec(p, x, v, block=64, interpret=True))
    )(x)
    assert bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.max(jnp.abs(g))) < 1e3  # bounded, unlike the 1/√ε blow-up


def test_gram_matvec_grad_through_jitter():
    """∂/∂log σ_n of uᵀ(σ_f²K + σ²I)v flows through the jitter term (applied
    outside the custom-VJP core, in plain JAX)."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (64, 2))
    v = jax.random.normal(jax.random.fold_in(key, 1), (64,))
    p = make_params("se", lengthscale=1.0, noise=0.3, d=2)

    def fused(p):
        return jnp.sum(v * gram_mv(p, x, v, jitter=p.noise, backend="pallas",
                                   block=64, interpret=True))

    def dense(p):
        kmat = gram(p, x) + p.noise * jnp.eye(64)
        return jnp.sum(v * (kmat @ v))

    gf = jax.grad(fused)(p)
    gd = jax.grad(dense)(p)
    np.testing.assert_allclose(gf.log_noise, gd.log_noise, rtol=1e-4)
    np.testing.assert_allclose(gf.log_lengthscale, gd.log_lengthscale, rtol=1e-4)


# ---------------------------------------------------------------------------
# Backend selection: every kind, tanimoto included, has a fused kernel
# ---------------------------------------------------------------------------


def test_resolve_backend_auto_and_tanimoto():
    # auto: pallas on TPU only; CPU test container resolves to chunked, for
    # tanimoto as for the stationary kinds — nothing is rerouted by kind
    on_tpu = jax.default_backend() == "tpu"
    for kind in ("se", "tanimoto"):
        assert resolve_backend("auto", kind) == ("pallas" if on_tpu else "chunked")
        assert resolve_backend("pallas", kind) == "pallas"
    assert resolve_backend("chunked", "tanimoto") == "chunked"
    with pytest.raises(ValueError, match="no fused Pallas matvec"):
        resolve_backend("pallas", "cosine")
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda", "se")


def test_tanimoto_pallas_raises_auto_falls_back():
    """The fused Tanimoto matvec is MinMax on counts (interpret mode here);
    ``auto`` gives the same numbers, and values it cannot take exactly read
    NaN, never an approximation."""
    rng = np.random.default_rng(8)
    x = ((rng.random((50, 24)) < 0.3) * rng.integers(1, 5, (50, 24))).astype(np.float32)
    v = rng.normal(size=(50, 2)).astype(np.float32)
    p = make_params("tanimoto", signal=1.2, noise=0.3)
    want = np.asarray(gram(p, x)) @ v
    out = gram_mv(p, x, v, backend="pallas", block=16, interpret=True)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gram_mv(p, x, v, backend="auto"), want,
                               rtol=1e-5, atol=1e-5)
    x[7, 3] = 5.0  # above MAX_COUNT
    out = np.asarray(gram_mv(p, x, v, backend="pallas", block=16, interpret=True))
    assert np.isnan(out).all()  # an invalid row of the symmetric operator reaches every output


@pytest.mark.parametrize("backend", ["chunked", "dense", "pallas"])
def test_gram_mv_backends_agree(backend):
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (90, 3))
    v = jax.random.normal(jax.random.fold_in(key, 1), (90, 2))
    p = make_params("matern52", lengthscale=0.7, signal=1.1, noise=0.2, d=3)
    out = gram_mv(p, x, v, jitter=p.noise, backend=backend, block=64,
                  interpret=True)
    ref = (gram(p, x) + p.noise * jnp.eye(90)) @ v
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Fused row-block matvec (the SGD/SDD/AP primitive)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["pallas", "chunked"])
def test_gram_rows_matvec_vs_dense_panel(backend):
    key = jax.random.PRNGKey(11)
    n, p_rows, s = 200, 48, 3
    x = jax.random.normal(key, (n, 4))
    u = jax.random.normal(jax.random.fold_in(key, 1), (n, s))
    w = jax.random.normal(jax.random.fold_in(key, 2), (p_rows, s))
    idx = jax.random.randint(jax.random.fold_in(key, 3), (p_rows,), 0, n)
    p = make_params("matern32", lengthscale=0.9, signal=1.4, d=4)
    panel = gram(p, x[idx], x)  # (p, n) dense reference
    fwd = gram_rows_matvec(p, x, idx, u, backend=backend, block=64,
                           interpret=True)
    np.testing.assert_allclose(fwd, panel @ u, rtol=2e-4, atol=2e-4)
    bwd = gram_rows_matvec(p, x, idx, w, transpose=True, backend=backend,
                           block=64, interpret=True)
    np.testing.assert_allclose(bwd, panel.T @ w, rtol=2e-4, atol=2e-4)


def test_prior_samples_fused_matches_features():
    """PriorSamples backend='fused' (Pallas RFF matvec, interpret on CPU) agrees
    with the materialised-feature evaluation, including traced σ_f² handling."""
    import dataclasses as dc

    from repro.core.rff import sample_prior

    p = make_params("matern32", lengthscale=0.8, signal=1.4, d=3)
    prior = sample_prior(p, jax.random.PRNGKey(0), 5, 96, 3)
    x = jax.random.normal(jax.random.PRNGKey(1), (130, 3))
    via_features = prior(x)
    via_fused = dc.replace(prior, backend="fused")(x)
    np.testing.assert_allclose(via_features, via_fused, rtol=2e-4, atol=2e-4)


def test_gram_mv_rejects_jitter_on_cross_gram():
    """jitter·I is only meaningful for the symmetric operator; rectangular
    cross-Gram calls must refuse it instead of silently adding jitter·v."""
    key = jax.random.PRNGKey(12)
    x = jax.random.normal(key, (40, 2))
    z = jax.random.normal(jax.random.fold_in(key, 1), (30, 2))
    v = jax.random.normal(jax.random.fold_in(key, 2), (30,))
    p = make_params("se", d=2)
    with pytest.raises(ValueError, match="jitter"):
        gram_mv(p, x, v, z=z, jitter=0.1, backend="chunked")


def test_prior_samples_default_backend_is_differentiable():
    """User-facing posterior samples are differentiated through (Thompson
    gradient ascent). The default is now ``auto`` — safe on every resolution
    because the fused Pallas path carries a full custom VJP: its gradient
    matches the materialised-features gradient."""
    import dataclasses as dc

    from repro.core.rff import sample_prior

    p = make_params("se", lengthscale=1.0, d=2)
    prior = sample_prior(p, jax.random.PRNGKey(0), 3, 64, 2)
    assert prior.backend == "auto"
    xs = jax.random.normal(jax.random.PRNGKey(1), (5, 2))
    g_auto = jax.grad(lambda xs: jnp.sum(prior(xs)))(xs)
    g_fused = jax.grad(
        lambda xs: jnp.sum(dc.replace(prior, backend="pallas")(xs))
    )(xs)
    g_feat = jax.grad(
        lambda xs: jnp.sum(dc.replace(prior, backend="features")(xs))
    )(xs)
    assert bool(jnp.all(jnp.isfinite(g_auto)))
    np.testing.assert_allclose(g_fused, g_feat, rtol=1e-4, atol=1e-5)
