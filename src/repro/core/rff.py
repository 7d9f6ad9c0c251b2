"""Random features (§2.2.2): approximate prior function samples.

A prior sample is f(x) ≈ Φ(x) w with w ~ N(0, I), Φ(x)_j = sqrt(2σ_f²/m) cos(ω_jᵀx+b_j),
or the lower-variance paired sin/cos form (Sutherland & Schneider, 2015). Pathwise
conditioning (core/pathwise.py) consumes these to evaluate f_X (train) and f_X* (test)
*jointly* in O((n+n*) m), which is the paper's replacement for O((n+n*)³) conditional
sampling.

Both classes implement the :class:`~repro.core.operators.FeatureOperator` protocol
(``phi_mv``/``phi_t_mv``/``num_features``/``shape``) over the backend-dispatched
feature matvecs in kernels/ops.py: on the ``pallas`` backend the (n × 2m) feature
matrix never exists in HBM, and — since the fused kernels carry full custom VJPs
(forward, transpose, and input cotangents, kernels/rff_matvec.py) — the fused path
is differentiable w.r.t. inputs, frequencies, weights and σ_f². The historical
"must not differentiate through the fused path" restriction is gone: ``auto`` is
the default everywhere, so Thompson sampling's Adam ascent and the SGD regulariser
gradient run fused end to end on TPU and fall back to materialised features on CPU.
See docs/features.md.

The Tanimoto kernel is not stationary and has no spectral density, so its prior
samples use Tanimoto random features instead (:class:`TanimotoFeatures`; Tripp,
Bacallado, Singh and Hernández-Lobato, NeurIPS 2023): each feature is the random
sign of a consistent weighted-sampling hash of the count vector (Ioffe 2010),
two vectors' hashes collide with probability exactly T(x, z), so
E[φ(x)φ(z)] = T(x, z). :func:`sample_prior` picks the map by kernel kind.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .kernels_fn import TANIMOTO, KernelParams, spectral_sample
from .operators import FeatureOperator, shard_map_over


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FourierFeatures(FeatureOperator):
    """The feature map Φ itself — a :class:`FeatureOperator` with fused,
    differentiable contractions.

    ``backend`` selects the feature-matvec path (see kernels/ops.py):
    ``"auto"`` (fused Pallas on TPU, materialised features elsewhere),
    ``"pallas"`` (forced fused; interpret mode off-TPU), or ``"features"``
    (always materialise — reference path, any variant). The fused kernels only
    implement the paired sin/cos map; ``auto`` falls back to features for the
    cos-only variant, explicit ``pallas`` raises.

    ``precision`` selects the tile precision of the feature contractions —
    ``"fp32"`` (default) or ``"bf16"`` MXU operands with fp32 accumulation
    (kernels/ops.py PRECISIONS); solver specs pin it per solve like
    ``backend``. The sin/cos map itself always evaluates in fp32.
    """

    omega: jax.Array  # (m, d) frequencies
    phase: jax.Array  # (m,) phases (cos variant) — unused in paired variant
    signal: jax.Array  # σ_f² signal variance
    paired: bool = dataclasses.field(default=True, metadata=dict(static=True))
    backend: str = dataclasses.field(default="auto", metadata=dict(static=True))
    precision: str = dataclasses.field(default="fp32", metadata=dict(static=True))

    @property
    def num_features(self) -> int:
        m = self.omega.shape[0]
        return 2 * m if self.paired else m

    def with_backend(self, backend: str) -> "FourierFeatures":
        return dataclasses.replace(self, backend=backend)

    def _resolve(self, backend: Optional[str]) -> str:
        from ..kernels.ops import resolve_feature_backend  # deferred: pallas import

        return resolve_feature_backend(
            self.backend if backend is None else backend, paired=self.paired
        )

    def features(self, x: jax.Array) -> jax.Array:
        """Φ(x) materialised: (n, num_features) — the optional ``features``
        capability (reference path, RFF preconditioner factors)."""
        proj = x @ self.omega.T  # (n, m)
        m = self.omega.shape[0]
        if self.paired:
            scale = jnp.sqrt(self.signal / m)
            return scale * jnp.concatenate([jnp.sin(proj), jnp.cos(proj)], axis=-1)
        scale = jnp.sqrt(2.0 * self.signal / m)
        return scale * jnp.cos(proj + self.phase[None, :])

    def phi_mv(self, x: jax.Array, w: jax.Array, *, backend: Optional[str] = None,
               precision: Optional[str] = None) -> jax.Array:
        """Φ(x) @ w: (n, s-like). Differentiable on every backend."""
        from ..kernels.ops import FEATURE_TRACE_COUNTS, rff_mv  # deferred: pallas

        if not self.paired:  # cos-only: no fused form (``_resolve`` refuses pallas)
            self._resolve(backend)
            FEATURE_TRACE_COUNTS["features"] += 1  # materialises Φ below
            return self.features(x) @ w
        return rff_mv(x, self.omega, w, signal=self.signal,
                      backend=self._resolve(backend),
                      precision=precision or self.precision)

    def phi_t_mv(self, x: jax.Array, u: jax.Array, *, backend: Optional[str] = None,
                 precision: Optional[str] = None) -> jax.Array:
        """Φ(x)ᵀ @ u: (num_features, s-like) — the SGD regulariser pullback."""
        from ..kernels.ops import FEATURE_TRACE_COUNTS, rff_t_mv  # deferred: pallas

        if not self.paired:
            self._resolve(backend)
            FEATURE_TRACE_COUNTS["features"] += 1  # materialises Φ below
            return self.features(x).T @ u
        return rff_t_mv(x, self.omega, u, signal=self.signal,
                        backend=self._resolve(backend),
                        precision=precision or self.precision)

    def phi_pair_mv(self, x: jax.Array, u: jax.Array, *,
                    backend: Optional[str] = None,
                    precision: Optional[str] = None) -> jax.Array:
        """Φ(x) (Φ(x)ᵀ u): (n, s-like) — the SGD regulariser composition in ONE
        dispatch. On the ``features`` backend Φ(x) materialises once and serves
        both contractions; on ``pallas`` the two-phase ``rff_pair`` kernel keeps
        the (2m, s) intermediate in VMEM for its whole lifetime."""
        from ..kernels.ops import FEATURE_TRACE_COUNTS, rff_pair_mv  # deferred

        if not self.paired:
            self._resolve(backend)
            FEATURE_TRACE_COUNTS["features"] += 2  # materialises Φ below, used twice
            feats = self.features(x)
            return feats @ (feats.T @ u)
        return rff_pair_mv(x, self.omega, u, signal=self.signal,
                           backend=self._resolve(backend),
                           precision=precision or self.precision)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedFourierFeatures(FeatureOperator):
    """A :class:`FourierFeatures` shard_map-ped over a device mesh — the
    distributed SGD regulariser path (ROADMAP item 2a, closed).

    ``x`` arrives row-sharded over ``data_axes`` and every contraction runs the
    *fused* per-shard kernels — custom VJPs intact, so the regulariser gradient
    differentiates through the sharded path exactly like the single-device one:

    * ``phi_mv``      — embarrassingly parallel: Φ(x_local) @ w per shard with
      ``w`` replicated; the (n, s) result stays row-sharded, zero collectives;
    * ``phi_t_mv``    — Φ(x_local)ᵀ @ u_local per shard, one psum reduces the
      (F, s) partials (the transpose's only collective);
    * ``phi_pair_mv`` — the Eq. 3.3 composition: per-shard pullback, psum of
      the small (F, s) intermediate, per-shard push-forward — row-sharded out.

    The (n, 2q) feature matrix never materialises (per-shard the fused kernels
    keep features in VMEM; the ``features`` capability is deliberately absent),
    and the only data crossing the interconnect is the (F, s) intermediate.
    Constructed by ``ShardedGram.wrap_features`` — the mesh-awareness
    capability SGD discovers via ``supports(op, "wrap_features")``.
    """

    inner: FourierFeatures
    mesh: jax.sharding.Mesh = dataclasses.field(metadata=dict(static=True))
    data_axes: tuple = dataclasses.field(default=("data",), metadata=dict(static=True))

    @property
    def num_features(self) -> int:
        return self.inner.num_features

    def phi_mv(self, x: jax.Array, w: jax.Array, *, backend: Optional[str] = None,
               precision: Optional[str] = None) -> jax.Array:
        """Φ(x) @ w with x row-sharded, w replicated → row-sharded (n, s-like).
        No collective: each shard evaluates its own feature rows."""
        axes = self.data_axes
        squeeze = w.ndim == 1
        w2 = w[:, None] if squeeze else w

        def body(x_local, w_rep):
            return self.inner.phi_mv(x_local, w_rep, backend=backend,
                                     precision=precision)

        out = shard_map_over(
            self.mesh, body, in_specs=((axes, None), (None, None)), out_specs=(axes, None)
        )(x, w2)
        return out[:, 0] if squeeze else out

    def phi_t_mv(self, x: jax.Array, u: jax.Array, *, backend: Optional[str] = None,
                 precision: Optional[str] = None) -> jax.Array:
        """Φ(x)ᵀ @ u → replicated (F, s-like): per-shard fused pullback on the
        local rows, psum-reduced over the data axes."""
        axes = self.data_axes
        squeeze = u.ndim == 1
        u2 = u[:, None] if squeeze else u

        def body(x_local, u_local):
            t = self.inner.phi_t_mv(x_local, u_local, backend=backend,
                                    precision=precision)
            return jax.lax.psum(t, axes)

        out = shard_map_over(
            self.mesh, body, in_specs=((axes, None), (axes, None)), out_specs=(None, None)
        )(x, u2)
        return out[:, 0] if squeeze else out

    def phi_pair_mv(self, x: jax.Array, u: jax.Array, *,
                    backend: Optional[str] = None,
                    precision: Optional[str] = None) -> jax.Array:
        """Φ(x) (Φ(x)ᵀ u) in one shard_map: fused pullback, psum of the (F, s)
        intermediate — the only bytes on the wire — fused push-forward.
        Row-sharded in, row-sharded out."""
        axes = self.data_axes
        squeeze = u.ndim == 1
        u2 = u[:, None] if squeeze else u

        def body(x_local, u_local):
            t = self.inner.phi_t_mv(x_local, u_local, backend=backend,
                                    precision=precision)
            t = jax.lax.psum(t, axes)
            return self.inner.phi_mv(x_local, t, backend=backend,
                                     precision=precision)

        out = shard_map_over(
            self.mesh, body, in_specs=((axes, None), (axes, None)), out_specs=(axes, None)
        )(x, u2)
        return out[:, 0] if squeeze else out


def make_fourier_features(
    params: KernelParams, key: jax.Array, num_features: int, d: int, paired: bool = True
) -> FourierFeatures:
    m = num_features // 2 if paired else num_features
    omega = spectral_sample(params, key, m, d)
    phase = jax.random.uniform(jax.random.fold_in(key, 7), (m,), maxval=2.0 * jnp.pi)
    return FourierFeatures(omega=omega, phase=phase, signal=params.signal, paired=paired)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TanimotoFeatures(FeatureOperator):
    """Tanimoto random features: a :class:`FeatureOperator` whose k-th feature
    of a count vector x is ``sqrt(σ_f² / m) · s_k(h_k(x))``.

    ``h_k(x) = (i*, t*)`` is Ioffe's consistent weighted-sampling hash over the
    non-zero dims of x: per dim, ``t = ⌊ln x_i / r + β⌋``, ``y = e^{r (t − β)}``,
    ``a = c / (y e^r)`` from the draws ``r``, ``c`` ~ Gamma(2, 1) and ``β`` ~
    U(0, 1) (each (m, d)); ``i* = argmin a`` and ``t*`` its ``t``. Two vectors'
    hashes collide with probability T(x, z) (MinMax). ``s_k`` is the random
    sign ``1 − 2 · hash_bit(salt_k, i*, t*)`` (kernels/tanimoto.py), so
    E[φ(x)φ(z)] = σ_f² T(x, z); an all-zero vector has zero features.

    The draws and ``salt`` are the map itself (exposed like ``omega`` of
    :class:`FourierFeatures`); ``table`` is their (C, m, d) tabulation for
    counts 1..C that the kernels read (``tanimoto.rf_table``: Ioffe's ``a``
    with the hash's sign). Inputs must be integer counts 0..``MAX_COUNT``
    (kernels/tanimoto.py); a row with anything else evaluates to NaN.
    ``phi_mv`` dispatches through ``ops.tanimoto_rf_mv`` (``pallas``: signs
    built in VMEM; ``features``: materialised); there is no derivative with
    respect to x.
    """

    r: jax.Array  # (m, d) Gamma(2, 1)
    c: jax.Array  # (m, d) Gamma(2, 1)
    beta: jax.Array  # (m, d) U(0, 1)
    salt: jax.Array  # (m,) uint32, the sign function of each feature
    table: jax.Array  # (MAX_COUNT, m, d)
    signal: jax.Array  # σ_f²
    backend: str = dataclasses.field(default="auto", metadata=dict(static=True))
    precision: str = dataclasses.field(default="fp32", metadata=dict(static=True))

    @property
    def num_features(self) -> int:
        return self.r.shape[0]

    def with_backend(self, backend: str) -> "TanimotoFeatures":
        return dataclasses.replace(self, backend=backend)

    def features(self, x: jax.Array) -> jax.Array:
        """Φ(x) materialised: (n, m)."""
        from ..kernels.tanimoto import rf_signs  # deferred: pallas import

        scale = jnp.sqrt(self.signal / self.num_features)
        return scale * rf_signs(x, self.table)

    def phi_mv(self, x: jax.Array, w: jax.Array, *, backend: Optional[str] = None,
               precision: Optional[str] = None) -> jax.Array:
        """Φ(x) @ w: (n, s-like)."""
        from ..kernels.ops import tanimoto_rf_mv  # deferred: pallas import

        return tanimoto_rf_mv(
            x, self.table, w, signal=self.signal,
            backend=self.backend if backend is None else backend,
            precision=precision or self.precision,
        )

    def phi_t_mv(self, x: jax.Array, u: jax.Array, *, backend: Optional[str] = None,
                 precision: Optional[str] = None) -> jax.Array:
        """Φ(x)ᵀ @ u: (m, s-like), through the materialised features."""
        from ..kernels.ops import FEATURE_TRACE_COUNTS, resolve_feature_backend

        resolve_feature_backend(self.backend if backend is None else backend)
        FEATURE_TRACE_COUNTS["features"] += 1  # materialises Φ below
        return self.features(x).T @ u


def make_tanimoto_features(
    params: KernelParams, key: jax.Array, num_features: int, d: int
) -> TanimotoFeatures:
    """Draw ``num_features`` Tanimoto random features over d-dim counts."""
    from ..kernels.tanimoto import rf_table  # deferred: pallas import

    kr, kc, kb, ks = jax.random.split(key, 4)
    shape = (num_features, d)
    r = jax.random.gamma(kr, 2.0, shape)
    c = jax.random.gamma(kc, 2.0, shape)
    beta = jax.random.uniform(kb, shape)
    salt = jax.random.bits(ks, (num_features,), jnp.uint32)
    return TanimotoFeatures(
        r=r, c=c, beta=beta, salt=salt,
        table=rf_table(r, c, beta, salt), signal=params.signal,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PriorSamples(FeatureOperator):
    """s prior function samples f^(i)(·) = Φ(·) w_i, evaluable anywhere.

    A :class:`FeatureOperator` with bound weights: ``__call__(x)`` is
    ``phi_mv(x, w)`` through the map's backend dispatch. The default backend is
    ``"auto"`` — fused Pallas RFF matvecs on TPU (the (n × 2m) feature matrix
    never hits HBM), materialised features elsewhere — and because the fused
    kernels carry a full custom VJP this default is safe to differentiate
    *through*: Thompson sampling gradient-ascends posterior samples on the fused
    path. ``"features"`` forces materialisation; ``"pallas"`` (alias
    ``"fused"``) forces the fused kernel (interpret mode off-TPU).
    """

    ff: FeatureOperator  # FourierFeatures or TanimotoFeatures
    w: jax.Array  # (num_features, s)
    backend: str = dataclasses.field(default="auto", metadata=dict(static=True))

    @property
    def num_features(self) -> int:
        return self.ff.num_features

    @property
    def num_samples(self) -> int:
        return self.w.shape[1]

    def with_backend(self, backend: str) -> "PriorSamples":
        return dataclasses.replace(self, backend=backend)

    def features(self, x: jax.Array) -> jax.Array:
        return self.ff.features(x)

    def phi_mv(self, x: jax.Array, w: jax.Array) -> jax.Array:
        return self.ff.phi_mv(x, w, backend=self.backend)

    def phi_t_mv(self, x: jax.Array, u: jax.Array) -> jax.Array:
        return self.ff.phi_t_mv(x, u, backend=self.backend)

    def phi_pair_mv(self, x: jax.Array, u: jax.Array) -> jax.Array:
        return self.ff.phi_pair_mv(x, u, backend=self.backend)

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.phi_mv(x, self.w)  # (n, s)


def sample_prior(
    params: KernelParams,
    key: jax.Array,
    num_samples: int,
    num_features: int,
    d: int,
) -> PriorSamples:
    """``num_samples`` prior paths: random Fourier features for the stationary
    kinds, Tanimoto random features (``num_features`` hashes) for
    ``tanimoto``."""
    kf, kw = jax.random.split(key)
    if params.kind == TANIMOTO:
        ff = make_tanimoto_features(params, kf, num_features, d)
    else:
        ff = make_fourier_features(params, kf, num_features, d)
    w = jax.random.normal(kw, (ff.num_features, num_samples))
    return PriorSamples(ff=ff, w=w)
