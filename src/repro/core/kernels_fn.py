"""Covariance functions (dissertation §2.1.3).

All kernels are expressed through a small dataclass carrying hyperparameters in
*unconstrained* (log) space so that MLL optimisation (core/mll.py) can take plain
gradients. Pairwise Gram blocks are computed with the distance-as-matmul identity
``||x - x'||^2 = ||x||^2 + ||x'||^2 - 2 x.x'`` so the dominant cost is a matmul
(MXU-shaped on TPU; the Pallas kernel in kernels/gram_matvec.py fuses this with the
elementwise map and the matvec).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

#: fp32 means fp32 on every backend: TPU dots default to reduced-precision
#: bf16 passes for float32 operands, CPU dots are full fp32 either way.
HIGHEST = jax.lax.Precision.HIGHEST

SE = "se"
MATERN12 = "matern12"
MATERN32 = "matern32"
MATERN52 = "matern52"
TANIMOTO = "tanimoto"

_STATIONARY = (SE, MATERN12, MATERN32, MATERN52)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class KernelParams:
    """Unconstrained GP hyperparameters θ = {log lengthscales, log signal, log noise}."""

    log_lengthscale: jax.Array  # (d,) ARD or scalar ()
    log_signal: jax.Array  # ()
    log_noise: jax.Array  # ()
    kind: str = dataclasses.field(default=SE, metadata=dict(static=True))

    @property
    def lengthscale(self) -> jax.Array:
        return jnp.exp(self.log_lengthscale)

    @property
    def signal(self) -> jax.Array:  # signal *variance*
        return jnp.exp(2.0 * self.log_signal)

    @property
    def noise(self) -> jax.Array:  # noise variance σ²
        return jnp.exp(2.0 * self.log_noise)


def make_params(
    kind: str = SE,
    lengthscale=1.0,
    signal: float = 1.0,
    noise: float = 0.1,
    d: Optional[int] = None,
    dtype=jnp.float32,
) -> KernelParams:
    ls = jnp.asarray(lengthscale, dtype)
    if d is not None and ls.ndim == 0:
        ls = jnp.full((d,), ls, dtype)
    return KernelParams(
        log_lengthscale=jnp.log(ls),
        log_signal=jnp.log(jnp.asarray(signal, dtype)),
        log_noise=jnp.log(jnp.asarray(noise, dtype)),
        kind=kind,
    )


def _sqdist(x: jax.Array, z: jax.Array) -> jax.Array:
    """Squared Euclidean distances via the matmul identity; clamped at 0."""
    xn = jnp.sum(x * x, axis=-1)[:, None]
    zn = jnp.sum(z * z, axis=-1)[None, :]
    d2 = xn + zn - 2.0 * jnp.matmul(x, z.T, precision=HIGHEST)
    return jnp.maximum(d2, 0.0)


def _stationary_map(d2: jax.Array, kind: str) -> jax.Array:
    """Elementwise covariance map applied to squared distances (lengthscale=1)."""
    if kind == SE:
        return jnp.exp(-0.5 * d2)
    r = jnp.sqrt(d2 + 1e-36)
    if kind == MATERN12:
        return jnp.exp(-r)
    if kind == MATERN32:
        s = jnp.sqrt(3.0) * r
        return (1.0 + s) * jnp.exp(-s)
    if kind == MATERN52:
        s = jnp.sqrt(5.0) * r
        return (1.0 + s + s * s / 3.0) * jnp.exp(-s)
    raise ValueError(f"unknown stationary kernel {kind!r}")


#: Stage the covariance map through ``lax.map`` row chunks once the d² block has
#: this many elements — below it the loop overhead outweighs the win.
_STAGED_MAP_MIN_ELEMENTS = 2 ** 18

#: Target elements per staged row chunk (~0.5 MB of fp32 — L2-resident).
_STAGED_MAP_CHUNK_ELEMENTS = 2 ** 17


def _stationary_apply(d2: jax.Array, kind: str) -> jax.Array:
    """``_stationary_map`` with large blocks staged through ``jax.lax.map``.

    On CPU, XLA emits *scalar* libm calls (~11 ns/element) for transcendentals
    that sit inside a large broadcast fusion — exactly what the exp in every
    Matérn/SE map becomes when fused with the distance matmul. Forcing the map
    to run as a ``lax.map`` over row chunks of the materialised d² array makes
    XLA emit the vectorised form (~2 ns/element), a 3–4× speedup on the panel
    shapes the stochastic solvers build every step. The restructure is purely
    elementwise — same ops on the same values — so results are bit-exact, and
    ``lax.map`` is differentiable, so gradients are unaffected. On TPU the
    fusion is fine; large blocks pass straight through.
    """
    n, m = d2.shape
    if jax.default_backend() == "tpu" or n * m < _STAGED_MAP_MIN_ELEMENTS:
        return _stationary_map(d2, kind)
    rows = max(1, min(n, _STAGED_MAP_CHUNK_ELEMENTS // max(m, 1)))
    pad = (-n) % rows
    d2p = jnp.pad(d2, ((0, pad), (0, 0)))
    chunks = d2p.reshape(-1, rows, m)
    out = jax.lax.map(partial(_stationary_map, kind=kind), chunks)
    return out.reshape(-1, m)[:n]


def tanimoto(x: jax.Array, z: jax.Array) -> jax.Array:
    """The Tanimoto (MinMax) kernel over non-negative count vectors (Ch. 4
    molecules): ``T(x, z) = Σᵢ min(xᵢ, zᵢ) / Σᵢ max(xᵢ, zᵢ)``, with
    ``Σ max = |x|₁ + |z|₁ − Σ min``; 0 between two all-zero vectors. On binary
    inputs it is the Jaccard form ``x·z / (|x|² + |z|² − x·z)``."""
    smin = jnp.sum(jnp.minimum(x[:, None, :], z[None, :, :]), axis=-1)
    smax = jnp.sum(x, axis=-1)[:, None] + jnp.sum(z, axis=-1)[None, :] - smin
    return smin / jnp.where(smax > 0, smax, 1.0)


def gram(params: KernelParams, x: jax.Array, z: Optional[jax.Array] = None) -> jax.Array:
    """Dense Gram matrix K(x, z) — the reference path (O(n m) memory)."""
    z = x if z is None else z
    if params.kind == TANIMOTO:
        return params.signal * tanimoto(x, z)
    ls = params.lengthscale
    d2 = _sqdist(x / ls, z / ls)
    return params.signal * _stationary_apply(d2, params.kind)


def gram_diag(params: KernelParams, x: jax.Array) -> jax.Array:
    if params.kind == TANIMOTO:  # T(x, x) = 1, but 0 for an all-zero vector
        return params.signal * (jnp.sum(x, axis=-1) > 0).astype(x.dtype)
    return params.signal * jnp.ones(x.shape[0], x.dtype)


def matvec(
    params: KernelParams,
    x: jax.Array,
    v: jax.Array,
    z: Optional[jax.Array] = None,
    row_chunk: int = 4096,
    jitter: Optional[jax.Array] = None,
) -> jax.Array:
    """(K(x,z) + jitter·I) @ v computed in row chunks — O(chunk·m) memory, never
    materialising K. This is the pure-JAX analogue of kernels/gram_matvec.py (which is
    the TPU Pallas version); both satisfy the same ref.py oracle.

    v may be (m,) or (m, s) for batched right-hand sides.
    """
    z_ = x if z is None else z
    n = x.shape[0]
    squeeze = v.ndim == 1
    v2 = v[:, None] if squeeze else v
    row_chunk = min(row_chunk, n)  # never pad small operands up to the chunk size
    pad = (-n) % row_chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    rows = xp.reshape(n // row_chunk + (pad > 0), row_chunk, x.shape[1])

    def chunk(xc):
        return jnp.matmul(gram(params, xc, z_), v2, precision=HIGHEST)

    out = jax.lax.map(chunk, rows).reshape(-1, v2.shape[1])[:n]
    if jitter is not None and z is None:
        out = out + jitter * v2
    return out[:, 0] if squeeze else out


def spectral_sample(params: KernelParams, key: jax.Array, m: int, d: int) -> jax.Array:
    """Sample m frequencies ω ~ spectral density of the kernel (§2.2.2).

    SE ↔ N(0, I/ℓ²); Matérn-ν ↔ multivariate Student-t with 2ν dof (scaled by 1/ℓ).
    """
    kind = params.kind
    knorm = jax.random.normal(key, (m, d))
    if kind == SE:
        w = knorm
    elif kind in (MATERN12, MATERN32, MATERN52):
        nu = {MATERN12: 0.5, MATERN32: 1.5, MATERN52: 2.5}[kind]
        kg = jax.random.fold_in(key, 1)
        # t_{2ν} = N(0,1) / sqrt(Gamma(ν, rate=ν))  (chi2_{2ν}/(2ν) = Gamma(ν, rate ν))
        g = jax.random.gamma(kg, nu, (m, 1)) / nu
        w = knorm / jnp.sqrt(g)
    else:
        raise ValueError(f"no spectral density for kernel {kind!r}")
    return w / params.lengthscale


# ---------------------------------------------------------------------------
# Product kernels over Cartesian grids (Ch. 6 latent Kronecker structure).


def kronecker_grams(
    params_list: list[KernelParams], grids: list[jax.Array]
) -> list[jax.Array]:
    """Per-factor Gram matrices K_j = k_j(X_j, X_j) of a product kernel (Eq. 2.68)."""
    return [gram(p, g) for p, g in zip(params_list, grids)]
