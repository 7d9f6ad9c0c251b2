"""One operator abstraction from Gram to Kronecker to sharded: ``LinearOperator``
— and its feature-side twin, ``FeatureOperator``.

Every expensive GP computation in this library reduces to solving

    (K + σ²I) V = B

against a positive-definite coefficient matrix that is only ever *touched through
matvecs*.  This module makes "the matrix" a first-class protocol so the solver
layer (core/solvers) is operator-agnostic: dense-free Gram matvecs, inducing-point
normal equations, latent-Kronecker structure (Ch. 6) and mesh-sharded block-row
operators all flow through the same ``solve()`` entry point with the same
SolverSpec benefits (preconditioning, warm starts, matvec accounting, backend
pinning, JSON-drivable configs).

The protocol (see :class:`LinearOperator`):

required
    ``shape``        — ``(n, n)`` of the square system matrix A;
    ``mv(v)``        — ``A @ v`` for ``v`` of shape ``(n,)`` or ``(n, s)``;
    ``diag_part()``  — ``diag(A)`` (Jacobi preconditioning, diagnostics);
    ``noise``        — the σ² of the ``K + σ²I`` split (δ-channel folding).

optional capabilities (declared by *defining the method*; absence is detected by
``hasattr`` — the base class deliberately does not stub them out)
    ``rows_mv(idx, u)``    — ``K[idx, :] @ u`` (SGD/SDD data-fit primitive);
    ``rows_t_mv(idx, u)``  — ``K[idx, :]ᵀ @ u`` (SGD regulariser pullback, AP
                             residual update);
    ``rows_pair_mv(idx, look, b)`` — the fused pair step ``err = K[idx,:] @
                             look − b``, ``g = K[idx,:]ᵀ @ err`` with the panel
                             built once (SGD's fit gradient in one dispatch);
    ``block_at(idx)``      — ``K[idx, idx]`` principal block (AP's exact
                             sub-solve);
    ``precond_factor(rank, key=, method=)`` — an ``(n, m)`` low-rank factor L
                             with ``K ≈ L Lᵀ`` (Nyström / pivoted-Cholesky /
                             random-feature preconditioner construction).

Solver specs declare which capabilities they consume (``SolverSpec.needs``) and
``solve()`` verifies them up front — a spec requesting row blocks from a
matvec-only operator raises a :class:`TypeError` naming the missing capability
instead of an ``AttributeError`` deep inside a scan. Operators may additionally
define ``prepare_for_solve()`` — a per-solve setup hook ``solve()`` invokes once,
outside the solver's while_loop/scan (e.g. :class:`ShardedGram` gathers its
sharded inputs once instead of all-gathering per matvec).

Pathwise conditioning writes every posterior sample as ``f(·) + K(·)X w`` with
the prior ``f`` a *feature expansion* Φ(·)w (§2.2.2) — the feature side is the
dominant non-Gram cost at the paper's scales, and :class:`FeatureOperator` is its
protocol (required ``phi_mv``/``phi_t_mv``/``num_features``/``shape``; optional
``features``). ``FourierFeatures``/``PriorSamples`` (core/rff.py) implement it
over the fused differentiable RFF kernels, and :class:`RFFGram` closes the loop:
the feature surrogate ΦΦᵀ + σ²I *as* a LinearOperator, solvable and usable as a
feature-space preconditioner. See docs/features.md.

All concrete operators are frozen, pytree-registered dataclasses: hyperparameters
and inputs are traced leaves (same treedef + shapes ⇒ compiled solves are
reused), while meshes, backends and chunk sizes are static fields.
"""
from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.ops import gram_mv, gram_rows_matvec, gram_rows_pair
from .kernels_fn import KernelParams, gram, gram_diag, matvec

if TYPE_CHECKING:  # runtime imports would cycle: kronecker → solvers.spec → here,
    # and rff → here (for the FeatureOperator protocol base)
    from .kronecker import LatentKroneckerGP
    from .rff import FourierFeatures


# ---------------------------------------------------------------------------
# Capability dispatch
# ---------------------------------------------------------------------------

#: Capabilities beyond the required ``mv``/``shape``/``diag_part``/``noise``.
#: ``rows_pair_mv`` is the fused err/gradient pair step (one panel build for
#: both contractions); SGD uses it when present and composes ``rows_mv``/
#: ``rows_t_mv`` otherwise, so operators without it still run every spec.
OPTIONAL_CAPABILITIES = (
    "rows_mv", "rows_t_mv", "rows_pair_mv", "block_at", "precond_factor"
)

#: FeatureOperator capabilities beyond the required ``phi_mv``/``phi_t_mv``/
#: ``num_features``/``shape``: ``features`` materialises Φ(x) (reference path,
#: RFF preconditioner factors).
OPTIONAL_FEATURE_CAPABILITIES = ("features",)


def supports(op, *caps: str) -> bool:
    """True iff ``op`` provides every named capability (method or attribute)."""
    return all(callable(getattr(op, c, None)) or hasattr(op, c) for c in caps)


def capabilities(op, optional: tuple = OPTIONAL_CAPABILITIES) -> tuple:
    """The optional capabilities ``op`` provides (sorted, for error messages)."""
    return tuple(c for c in optional if supports(op, c))


def feature_capabilities(op) -> tuple:
    """The optional :class:`FeatureOperator` capabilities ``op`` provides."""
    return capabilities(op, OPTIONAL_FEATURE_CAPABILITIES)


def require_capabilities(op, caps, *, consumer: str) -> None:
    """Raise a clear ``TypeError`` if ``op`` lacks any of ``caps``.

    ``consumer`` names who is asking (a solver spec, a preconditioner build) so
    the error reads as a capability mismatch, not a missing attribute.
    """
    missing = tuple(c for c in caps if not supports(op, c))
    if missing:
        feature_side = all(c in OPTIONAL_FEATURE_CAPABILITIES for c in missing)
        have = capabilities(
            op, OPTIONAL_FEATURE_CAPABILITIES if feature_side else OPTIONAL_CAPABILITIES
        )
        hint = (
            "Fused feature operators need the 'features' capability only for "
            "materialised reference paths and RFF preconditioner factors."
            if feature_side
            else "Matvec-only operators support CG-family specs; SGD/SDD/AP "
            "need row-block access (rows_mv/rows_t_mv/block_at)."
        )
        raise TypeError(
            f"{consumer} needs operator capabilities {missing} that "
            f"{type(op).__name__} does not provide (optional capabilities it "
            f"has: {have or '()'}). {hint}"
        )


class LinearOperator:
    """Protocol base for the square operators ``solve()`` accepts.

    Subclasses are frozen ``@jax.tree_util.register_dataclass`` dataclasses.
    They must implement ``shape``, ``mv``, ``diag_part`` and ``noise``; the
    optional capabilities in :data:`OPTIONAL_CAPABILITIES` are declared simply
    by defining the method (absence is how ``solve()`` knows to refuse a spec
    that needs them). Duck-typed operators that never subclass this also work —
    the protocol is structural, the base class is documentation plus default
    errors.
    """

    @property
    def shape(self) -> tuple:
        raise NotImplementedError(f"{type(self).__name__} must define shape")

    @property
    def noise(self) -> jax.Array:
        raise NotImplementedError(f"{type(self).__name__} must define noise")

    def mv(self, v: jax.Array) -> jax.Array:
        raise NotImplementedError(f"{type(self).__name__} must define mv")

    def diag_part(self) -> jax.Array:
        raise NotImplementedError(f"{type(self).__name__} must define diag_part")

    def dense(self) -> jax.Array:
        """Materialised A — O(n²); reference/tests only. Default: n matvecs."""
        n = self.shape[0]
        return self.mv(jnp.eye(n))


class FeatureOperator:
    """Protocol base for feature maps Φ: the rectangular twin of
    :class:`LinearOperator`.

    A feature operator is a map Φ(·) into ``num_features`` dimensions, touched
    only through its two contractions — never through a materialised feature
    matrix. Required surface:

    * ``num_features``   — the feature dimension F of Φ(x): (n, F);
    * ``shape``          — ``(None, F)``: the row count is input-dependent
                           (feature maps are evaluable anywhere, unlike the
                           square operators bound to training rows);
    * ``phi_mv(x, w)``   — Φ(x) @ w, the prior-sample evaluation primitive
                           (pathwise conditioning, Thompson ascent);
    * ``phi_t_mv(x, u)`` — Φ(x)ᵀ @ u, the SGD regulariser pullback (Eq. 3.3).

    Optional capability (absence detected by ``hasattr``, exactly like the
    LinearOperator capabilities): ``features(x)`` materialises Φ(x) — the
    reference path and the RFF preconditioner-factor build. Consumers verify
    with ``require_capabilities(op, ("features",), consumer=...)``.

    Both primitives must be differentiable w.r.t. ``x`` and the map's own
    parameters on every backend — the fused Pallas implementations carry custom
    VJPs (kernels/rff_matvec.py), so Thompson's Adam ascent and the SGD
    regulariser gradient run fused end to end.

    Implementations are frozen, pytree-registered dataclasses
    (``FourierFeatures``, ``PriorSamples`` — core/rff.py): same treedef + shapes
    ⇒ compiled consumers are reused across fresh feature draws.
    """

    @property
    def num_features(self) -> int:
        raise NotImplementedError(f"{type(self).__name__} must define num_features")

    @property
    def shape(self) -> tuple:
        return (None, self.num_features)

    def phi_mv(self, x: jax.Array, w: jax.Array) -> jax.Array:
        raise NotImplementedError(f"{type(self).__name__} must define phi_mv")

    def phi_t_mv(self, x: jax.Array, u: jax.Array) -> jax.Array:
        raise NotImplementedError(f"{type(self).__name__} must define phi_t_mv")

    def phi_pair_mv(self, x: jax.Array, u: jax.Array) -> jax.Array:
        """Φ(x) (Φ(x)ᵀ u) — the SGD regulariser composition (Eq. 3.3) as one
        primitive. Default: the two contractions in sequence; fused
        implementations override with a single dispatch whose (F, s)
        intermediate never leaves VMEM (``FourierFeatures``)."""
        return self.phi_mv(x, self.phi_t_mv(x, u))


# ---------------------------------------------------------------------------
# Runtime (post-compilation) matvec counters, bumped via jax.debug.callback from
# instrumented operators — unlike trace-time counts these reflect what the
# hardware actually executed, including every while_loop/scan iteration.
# ---------------------------------------------------------------------------

_RUNTIME_COUNTS = {"mv": 0, "rows": 0}


def reset_matvec_counts() -> None:
    for k in _RUNTIME_COUNTS:
        _RUNTIME_COUNTS[k] = 0


def matvec_counts() -> dict:
    """{"mv": full operator matvecs, "rows": row-block matvecs} executed by
    instrumented operators since the last reset."""
    return dict(_RUNTIME_COUNTS)


def _bump_mv(_):
    _RUNTIME_COUNTS["mv"] += 1


def _bump_rows(_):
    _RUNTIME_COUNTS["rows"] += 1


class _InstrumentedOp(LinearOperator):
    """Shared ``instrument=True`` plumbing (host-callback matvec counters)."""

    def _count(self, fn, out: jax.Array) -> None:
        if self.instrument:
            # operand-dependent so the callback stays inside loop bodies
            jax.debug.callback(fn, out.ravel()[0])


# ---------------------------------------------------------------------------
# Gram — the workhorse (K(X,X) + σ²I) operator
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Gram(_InstrumentedOp):
    """The linear operator A = K(X,X) + σ² I, touched only through matvecs.

    Implements the full capability set: fused row-block matvecs (``rows_mv``/
    ``rows_t_mv``/``block_at``) back the stochastic solvers, and
    ``precond_factor`` backs Nyström / pivoted-Cholesky preconditioner specs.

    ``backend`` selects the matvec implementation (see kernels/ops.py):
    ``"auto"`` (fused Pallas on TPU, chunked JAX elsewhere), ``"pallas"``,
    ``"chunked"``, or ``"dense"``. Solver specs can pin it per solve
    (``CG(backend="pallas")``), and likewise ``precision`` — ``"fp32"``
    (default) or ``"bf16"`` tile contractions with fp32 accumulation (see
    kernels/ops.py PRECISIONS). ``block`` is the Pallas tile size; the
    ``"auto"`` default resolves per shape at trace time
    (kernels/ops.py ``stationary_block``).
    ``instrument=True`` counts executed matvecs via ``matvec_counts()``
    (tests/benchmarks; adds a host callback per matvec).
    """

    x: jax.Array  # (n, d) training inputs
    params: KernelParams
    row_chunk: int = dataclasses.field(default=2048, metadata=dict(static=True))
    backend: str = dataclasses.field(default="auto", metadata=dict(static=True))
    block: "int | str" = dataclasses.field(default="auto", metadata=dict(static=True))
    precision: str = dataclasses.field(default="fp32", metadata=dict(static=True))
    instrument: bool = dataclasses.field(default=False, metadata=dict(static=True))

    @property
    def n(self) -> int:
        return self.x.shape[0]

    @property
    def shape(self) -> tuple:
        return (self.x.shape[0], self.x.shape[0])

    @property
    def noise(self) -> jax.Array:
        return self.params.noise

    def mv(self, v: jax.Array) -> jax.Array:
        """(K + σ²I) @ v without materialising K. v: (n,) or (n,s)."""
        out = gram_mv(
            self.params, self.x, v, jitter=self.noise, backend=self.backend,
            block=self.block, row_chunk=self.row_chunk, precision=self.precision,
        )
        self._count(_bump_mv, out)
        return out

    def mv_k(self, v: jax.Array) -> jax.Array:
        """K @ v (no jitter)."""
        out = gram_mv(
            self.params, self.x, v, backend=self.backend, block=self.block,
            row_chunk=self.row_chunk, precision=self.precision,
        )
        self._count(_bump_mv, out)
        return out

    def diag_part(self) -> jax.Array:
        """diag(K + σ²I) — (n,)."""
        return gram_diag(self.params, self.x) + self.noise

    def rows_mv(self, idx: jax.Array, u: jax.Array) -> jax.Array:
        """K[idx, :] @ u — fused row-block matvec, the panel never materialised.

        The SGD/SDD/AP data-fit primitive: O(|idx|·d) gathered inputs instead of
        an O(|idx|·n) HBM panel. u: (n,) or (n, s) → (|idx|, s-like).
        """
        out = gram_rows_matvec(
            self.params, self.x, idx, u, backend=self.backend, block=self.block,
            row_chunk=self.row_chunk, precision=self.precision,
        )
        self._count(_bump_rows, out)
        return out

    def rows_t_mv(self, idx: jax.Array, u: jax.Array) -> jax.Array:
        """K[idx, :]ᵀ @ u = K[:, idx] @ u — transposed fused row-block matvec.
        u: (|idx|,) or (|idx|, s) → (n, s-like)."""
        out = gram_rows_matvec(
            self.params, self.x, idx, u, transpose=True, backend=self.backend,
            block=self.block, row_chunk=self.row_chunk, precision=self.precision,
        )
        self._count(_bump_rows, out)
        return out

    def rows_pair_mv(self, idx: jax.Array, look: jax.Array, b: jax.Array) -> tuple:
        """The fused pair step: ``err = K[idx,:] @ look − b`` and
        ``g = K[idx,:]ᵀ @ err`` with the kernel panel built ONCE.

        SGD's fit gradient in a single dispatch — the unfused ``rows_mv`` +
        ``rows_t_mv`` composition rebuilds the same |idx|×n panel twice per
        step. Counts as two row-block matvecs (the work it replaces), keeping
        ``matvec_counts()`` comparable across the fused and unfused paths.
        look: (n, s); b: (|idx|, s) → ((|idx|, s), (n, s)).
        """
        err, g = gram_rows_pair(
            self.params, self.x, idx, look, b, backend=self.backend,
            block=self.block, precision=self.precision,
        )
        self._count(_bump_rows, err)
        self._count(_bump_rows, g)
        return err, g

    def block_at(self, idx: jax.Array) -> jax.Array:
        """K[idx, idx] — the |idx|×|idx| principal block (AP's exact sub-solve)."""
        return gram(self.params, self.x[idx], self.x[idx])

    def rows(self, idx: jax.Array) -> jax.Array:
        """K[idx, :] materialised — O(|idx|·n) memory. Legacy primitive; solvers
        use the fused ``rows_mv``/``rows_t_mv``/``block_at`` instead."""
        return gram(self.params, self.x[idx], self.x)

    def precond_factor(
        self, rank: int, key: Optional[jax.Array] = None, method: str = "nystrom"
    ) -> jax.Array:
        """(n, rank) factor L with K ≈ L Lᵀ for Woodbury preconditioning."""
        from .precond import low_rank_factor  # deferred: precond imports operators

        return low_rank_factor(self.params, self.x, rank, key=key, method=method)

    def dense(self) -> jax.Array:
        """Materialised K + σ²I (tests / small-n reference only)."""
        return gram(self.params, self.x) + self.noise * jnp.eye(self.n, dtype=self.x.dtype)


# ---------------------------------------------------------------------------
# RFFGram — the feature-space surrogate ΦΦᵀ + σ²I as a LinearOperator
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RFFGram(_InstrumentedOp):
    """The operator A = Φ(X) Φ(X)ᵀ + σ² I — the random-feature surrogate of the
    Gram operator (ΦΦᵀ is an unbiased K estimate, §2.2.2), touched only through
    two fused feature matvecs per ``mv``.

    Bridges the two protocols: any :class:`FeatureOperator` (a ``FourierFeatures``
    draw) becomes a solvable :class:`LinearOperator` — ``solve(RFFGram(...), b,
    spec)`` runs any CG-family spec with O(n·(d+s)) memory per matvec on the
    Pallas backend — and its ``precond_factor`` exposes the materialised Φ as an
    exact low-rank factor (A = LLᵀ + σ²I with L = Φ), making it a feature-space
    preconditioner / surrogate for full Gram solves (the ``"rff"`` precond spec).
    """

    x: jax.Array  # (n, d) training inputs
    ff: "FourierFeatures"  # the feature map (a FeatureOperator)
    sigma2: jax.Array  # () noise variance σ²
    # feature-matvec backend/precision overrides; None inherits the ff's own.
    # A spec's ``backend``/``precision`` fields pin them through solve(), like
    # Gram/ShardedGram.
    backend: Optional[str] = dataclasses.field(default=None, metadata=dict(static=True))
    precision: Optional[str] = dataclasses.field(default=None, metadata=dict(static=True))
    instrument: bool = dataclasses.field(default=False, metadata=dict(static=True))

    @property
    def n(self) -> int:
        return self.x.shape[0]

    @property
    def shape(self) -> tuple:
        return (self.x.shape[0], self.x.shape[0])

    @property
    def noise(self) -> jax.Array:
        return self.sigma2

    def mv(self, v: jax.Array) -> jax.Array:
        """(ΦΦᵀ + σ²I) @ v = Φ(Φᵀv) + σ²v — two fused feature matvecs."""
        bk, pr = self.backend, self.precision
        out = self.ff.phi_mv(
            self.x, self.ff.phi_t_mv(self.x, v, backend=bk, precision=pr),
            backend=bk, precision=pr,
        ) + self.sigma2 * v
        self._count(_bump_mv, out)
        return out

    def diag_part(self) -> jax.Array:
        """diag(ΦΦᵀ) + σ². Paired sin/cos features satisfy Σ_j Φ_ij² = σ_f²
        exactly (sin² + cos² = 1 per frequency); the cos-only variant needs the
        materialised rows."""
        if self.ff.paired:
            diag = jnp.broadcast_to(self.ff.signal, (self.n,))
        else:
            diag = jnp.sum(self.ff.features(self.x) ** 2, axis=1)
        return diag + self.sigma2

    def precond_factor(
        self, rank: int, key: Optional[jax.Array] = None, method: str = "rff"
    ) -> jax.Array:
        """The materialised feature matrix Φ — an *exact* factor (A = ΦΦᵀ + σ²I,
        no approximation), so Woodbury preconditioning of this operator is an
        exact inverse. Only ``method="rff"`` is meaningful here (the ``RFF``
        precond spec): a Nyström/pivoted-Cholesky request would silently get a
        factor of the operator's full feature count instead of the requested
        low-rank approximation, so it raises. ``rank``/``key`` are accepted for
        interface parity and ignored: the factor's rank is the operator's
        feature count.
        """
        if method != "rff":
            raise ValueError(
                f"RFFGram's only factor is its own feature matrix (method "
                f"'rff', {self.ff.num_features} columns); a {method!r} factor "
                f"of rank {rank} is not available — use CG(precond=RFF()) or "
                f"Jacobi() on this operator"
            )
        require_capabilities(
            self.ff, ("features",), consumer="RFFGram.precond_factor"
        )
        return self.ff.features(self.x)

    def dense(self) -> jax.Array:
        """Materialised ΦΦᵀ + σ²I (tests / small-n reference only)."""
        phi = self.ff.features(self.x)
        return phi @ phi.T + self.sigma2 * jnp.eye(self.n, dtype=self.x.dtype)


# ---------------------------------------------------------------------------
# NormalEq — inducing-point normal equations (§3.2.3), matvec-only
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class NormalEq(LinearOperator):
    """The m×m operator K_ZX K_XZ + σ² K_ZZ, touched only through matvecs.

    A matvec-only operator (no kernel-row capabilities), so only CG-family specs
    can drive it through ``solve()`` — the stochastic solvers raise a capability
    error. Used by ``inducing_posterior`` (Eqs. 3.23/3.24) and the iterative
    SGPR path (``svgp.sgpr_iterative``): note (K_ZX K_XZ + σ²K_ZZ) = σ²·B with
    B the Titsias matrix K_ZZ + σ⁻²K_ZX K_XZ.

    ``ridge`` adds ridge·I to the operator (a traced leaf, so changing it does
    not retrace solves) — the iterative SGPR path uses it to reproduce the dense
    path's fp32-stabilising ridge exactly, since the two would otherwise
    converge to visibly different solutions in the κ(K_XZ)²-amplified
    directions.
    """

    x: jax.Array  # (n, d) training inputs
    z: jax.Array  # (m, d) inducing inputs
    params: KernelParams
    ridge: jax.Array = 0.0  # additive ridge·I (traced; 0 = the pure operator)
    row_chunk: int = dataclasses.field(default=4096, metadata=dict(static=True))

    @property
    def shape(self) -> tuple:
        return (self.z.shape[0], self.z.shape[0])

    @property
    def noise(self) -> jax.Array:
        return self.params.noise

    def mv(self, u: jax.Array) -> jax.Array:
        """(K_ZX K_XZ + σ² K_ZZ + ridge·I) @ u without materialising K_XZ (n×m)."""
        kxz_u = matvec(self.params, self.x, u, z=self.z, row_chunk=self.row_chunk)
        kzx_kxz_u = matvec(self.params, self.z, kxz_u, z=self.x, row_chunk=self.row_chunk)
        kzz_u = matvec(self.params, self.z, u, z=self.z, row_chunk=self.row_chunk)
        return kzx_kxz_u + self.params.noise * kzz_u + self.ridge * u

    def diag_part(self) -> jax.Array:
        """diag(K_ZX K_XZ) + σ²·diag(K_ZZ) + ridge, in row chunks of X."""
        n = self.x.shape[0]
        chunk = min(self.row_chunk, n)
        pad = (-n) % chunk
        xp = jnp.pad(self.x, ((0, pad), (0, 0)))
        rows = xp.reshape(-1, chunk, self.x.shape[1])

        def col_sq(xc):  # Σ_i k(x_i, z_j)² over the chunk (padded rows: see below)
            return jnp.sum(gram(self.params, xc, self.z) ** 2, axis=0)

        sq = jnp.sum(jax.lax.map(col_sq, rows), axis=0)
        if pad:  # padded (zero) rows contribute k(0, z_j)² — subtract them
            sq = sq - pad * gram(self.params, jnp.zeros((1, self.x.shape[1])), self.z)[0] ** 2
        return sq + self.params.noise * gram_diag(self.params, self.z) + self.ridge


# ---------------------------------------------------------------------------
# LatentKroneckerOp — Ch. 6 structured operator
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LatentKroneckerOp(_InstrumentedOp):
    """(P_M (K₁ ⊗ K₂) P_Mᵀ + σ²I) as a LinearOperator (§6.2.2–6.2.3).

    Wraps a :class:`~repro.core.kronecker.LatentKroneckerGP`: the matvec costs
    O(n₁n₂(n₁+n₂)) through the latent Kronecker identity instead of O(n_obs²),
    and the whole solver layer (CG warm starts, matvec accounting, spec configs)
    applies unchanged. Matvec-only: row gathers of the projected product kernel
    would each cost a full structured matvec, so SGD/SDD/AP specs are refused
    with a capability error.
    """

    gp: "LatentKroneckerGP"
    instrument: bool = dataclasses.field(default=False, metadata=dict(static=True))

    @property
    def shape(self) -> tuple:
        n_obs = self.gp.obs_idx.shape[0]
        return (n_obs, n_obs)

    @property
    def noise(self) -> jax.Array:
        return self.gp.noise

    def mv(self, v: jax.Array) -> jax.Array:
        """(K_obs + σ²I) @ v via the latent Kronecker matvec (§6.2.3)."""
        out = self.gp.mv(v)
        self._count(_bump_mv, out)
        return out

    def diag_part(self) -> jax.Array:
        """diag(K_obs) + σ² = d₁[i₁]·d₂[i₂] at each observed grid index + σ²."""
        n1, n2 = self.gp.shape
        d1 = gram_diag(self.gp.params1, self.gp.grid1)
        d2 = gram_diag(self.gp.params2, self.gp.grid2)
        i1 = self.gp.obs_idx // n2
        i2 = self.gp.obs_idx % n2
        return d1[i1] * d2[i2] + self.gp.noise


# ---------------------------------------------------------------------------
# ShardedGram — mesh-aware block-row Gram operator
# ---------------------------------------------------------------------------

#: Communication strategies for :class:`ShardedGram` (docs/distributed.md).
#: ``gather`` all-gathers the sharded inputs (or vectors) around each matvec;
#: ``ring`` pipelines ``ppermute`` shard rotations against the per-shard fused
#: contraction so the O(n·d) replicated panel never exists and no per-matvec
#: ``all_gather`` is staged; ``auto`` picks ring once the replicated panel
#: would exceed the operator's per-device byte budget.
COMM_STRATEGIES = ("gather", "ring", "auto")


def shard_map_over(mesh: Mesh, body, in_specs, out_specs):
    """``jax.shard_map(body)`` over ``mesh`` with each spec written as a tuple
    of ``PartitionSpec`` entries (``(axes, None)`` → ``P(axes, None)``).

    The varying-manual-axes check is off: every body ends in an explicit
    collective (psum / all_gather / ppermute) or returns a sharded block whose
    out spec says so."""
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(P(*s) for s in in_specs),
        out_specs=P(*out_specs), check_vma=False,
    )


def _psum_row_gather(x_local, idx, axes):
    """``x_full[idx]`` without replicating x: every device contributes the
    ``idx`` rows that live in its shard (others zeroed) and a psum reduces.

    The collective moves O(|idx|·d) bytes instead of the O(n·d) ``all_gather``
    the gather strategy pays to index the global inputs. Assumes the canonical
    block-row layout (device i holds rows [i·n_local, (i+1)·n_local))."""
    i = jax.lax.axis_index(axes)
    n_local = x_local.shape[0]
    rel = idx - i * n_local
    mask = (rel >= 0) & (rel < n_local)
    safe = jnp.clip(rel, 0, n_local - 1)
    part = jnp.where(mask[:, None], x_local[safe], jnp.zeros((1, 1), x_local.dtype))
    return jax.lax.psum(part, axes)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedGram(_InstrumentedOp):
    """(K(X,X) + σ²I) with training rows sharded over mesh ``data_axes``.

    A block-row distribution of K: each device computes its K-block matvec
    without materialising the block — the local contraction runs through the
    same backend dispatch as :class:`Gram` (``pallas``/``chunked``/``dense``),
    so the fused Pallas kernel is threaded through the shards — and results are
    combined with mesh collectives.

    ``comm`` selects the collective schedule (see :data:`COMM_STRATEGIES` and
    docs/distributed.md):

    * ``"gather"`` (default) — every ``mv`` all-gathers the sharded inputs
      before contracting its block row; vectors (RHS batches, iterates) are
      replicated. The O(n·d) input panel transits the interconnect per matvec
      (or is cached by ``gather_once``), and communication strictly precedes
      compute.
    * ``"ring"`` — the collective-matmul idiom: ``(K+σ²I)v`` decomposes into P
      pipeline stages, each device contracting K(x_local, x_peer) @ v_peer
      against the shard pair it currently holds while ``jax.lax.ppermute``
      rotates the next (x_peer, v_peer) around the ring — the stage-t+1
      permute overlaps the stage-t contraction under XLA's latency-hiding
      scheduler, the replicated panel never exists, and no per-matvec
      ``all_gather`` is staged. Inputs AND vectors stay row-sharded end to
      end (``mv`` maps sharded → sharded), so solver iterates threaded through
      it keep per-device O(n·s/P) footprint; row gathers go through an
      O(|idx|·d) masked psum instead of replicating x.
    * ``"auto"`` — ring once the replicated (n, d) panel exceeds
      ``comm_budget_bytes`` per device, gather otherwise.

    Implements the full capability set, including the *sharded row-gather*
    primitives that let SGD/SDD/AP specs run distributed: ``rows_mv`` psum-
    reduces per-device column-block contributions K(x[idx], x_local) @ u_local,
    ``rows_t_mv`` computes per-device row blocks (all-gathered under ``gather``,
    left row-sharded under ``ring``), and ``block_at`` gathers the |idx|×|idx|
    principal block from the global (sharded) inputs. ``wrap_features`` is the
    mesh-awareness capability the SGD regulariser consumes: it shard_map-wraps
    a :class:`FeatureOperator` over this operator's mesh so the fused RFF pair
    step runs distributed without materialising the (n, 2q) feature matrix
    (see :class:`~repro.core.rff.ShardedFourierFeatures`).

    ``gather_once=True`` trades memory for collectives: instead of all-gathering
    the sharded inputs on *every* matvec (an O(n·d) collective per solver
    iteration), ``prepare_for_solve()`` — invoked once per solve by ``solve()``,
    outside the solver's while_loop/scan — replicates them into ``x_full``, and
    every subsequent ``mv``/``rows_mv``/``rows_t_mv`` reads the cached panel.
    Use it when the replicated (n, d) panel fits device memory (d is small; the
    K blocks still never materialise). Incompatible with ``comm="ring"`` (whose
    whole point is that the replicated panel never exists) — the combination
    raises ``ValueError``; ``comm="auto"`` + ``gather_once`` resolves to gather.

    Memory per device: O(n_local · chunk) — the paper's linear-memory claim,
    per device (plus O(n·d) with ``gather_once``; O(n·s/P) solver vectors
    under ``ring`` vs O(n·s) replicated under ``gather``).
    """

    x: jax.Array  # (n, d) training inputs, row-sharded over data_axes
    params: KernelParams
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    data_axes: tuple = dataclasses.field(default=("data",), metadata=dict(static=True))
    row_chunk: int = dataclasses.field(default=2048, metadata=dict(static=True))
    backend: str = dataclasses.field(default="auto", metadata=dict(static=True))
    block: "int | str" = dataclasses.field(default="auto", metadata=dict(static=True))
    precision: str = dataclasses.field(default="fp32", metadata=dict(static=True))
    instrument: bool = dataclasses.field(default=False, metadata=dict(static=True))
    # replicated input panel, populated by prepare_for_solve() when gather_once
    x_full: Optional[jax.Array] = None
    gather_once: bool = dataclasses.field(default=False, metadata=dict(static=True))
    comm: str = dataclasses.field(default="gather", metadata=dict(static=True))
    # "auto" switches to ring when the replicated (n, d) panel exceeds this
    comm_budget_bytes: int = dataclasses.field(
        default=128 * 2**20, metadata=dict(static=True)
    )

    def __post_init__(self):
        if self.comm not in COMM_STRATEGIES:
            raise ValueError(
                f"unknown comm strategy {self.comm!r}; expected one of "
                f"{COMM_STRATEGIES}"
            )
        if self.comm == "ring" and self.gather_once:
            raise ValueError(
                "gather_once=True replicates the O(n·d) input panel that "
                "comm='ring' exists to avoid — pick one: gather_once with "
                "comm='gather', or comm='ring' alone (comm='auto' resolves "
                "to gather when gather_once is set)"
            )

    @property
    def n(self) -> int:
        return self.x.shape[0]

    @property
    def shape(self) -> tuple:
        return (self.x.shape[0], self.x.shape[0])

    @property
    def noise(self) -> jax.Array:
        return self.params.noise

    def _local_mv(self, x_local, x_other, v):
        """K(x_local, x_other) @ v through the backend dispatch (no jitter)."""
        return gram_mv(
            self.params, x_local, v, z=x_other, backend=self.backend,
            block=self.block, row_chunk=self.row_chunk, precision=self.precision,
        )

    def _mesh_size(self) -> int:
        """Number of shards along ``data_axes`` (the ring's pipeline depth P)."""
        return math.prod(self.mesh.shape[a] for a in self.data_axes)

    def _resolve_comm(self) -> str:
        """The effective strategy: ``auto`` → ring once the replicated (n, d)
        panel would exceed ``comm_budget_bytes`` per device (with ``gather_once``
        the user already asked for the panel, so auto resolves to gather)."""
        if self.comm != "auto":
            return self.comm
        if self.gather_once or self._mesh_size() == 1:
            return "gather"
        panel_bytes = self.x.shape[0] * self.x.shape[1] * self.x.dtype.itemsize
        return "ring" if panel_bytes > self.comm_budget_bytes else "gather"

    def _panel_map(self, body, in_specs, out_specs, *args):
        """shard_map ``body(x_local, x_all, *args)`` with ``x_all`` the
        replicated (n, d) input panel: the cached ``x_full`` when gathered
        once per solve, else an ``all_gather`` of the shards on every call
        (the gather strategy). ``in_specs`` cover ``args`` only."""
        axes = self.data_axes
        if self.x_full is not None:
            return shard_map_over(
                self.mesh, body, ((axes, None), (None, None), *in_specs), out_specs
            )(self.x, self.x_full, *args)

        def gathered(x_local, *rest):
            x_all = jax.lax.all_gather(x_local, axes, tiled=True)
            return body(x_local, x_all, *rest)

        return shard_map_over(
            self.mesh, gathered, ((axes, None), *in_specs), out_specs
        )(self.x, *args)

    def _gather_rows(self, idx: jax.Array) -> jax.Array:
        """x[idx] as a replicated (|idx|, d) panel: the cached ``x_full`` when
        gathered once, else a masked psum over the canonical block-row layout
        — O(|idx|·d) bytes on the wire under either comm strategy, and no
        global indexing of the sharded x (which explicit-sharding meshes
        refuse without an output sharding)."""
        if self.x_full is not None:
            return jnp.take(self.x_full, idx, axis=0)
        axes = self.data_axes
        return shard_map_over(
            self.mesh, lambda x_local, idx_rep: _psum_row_gather(x_local, idx_rep, axes),
            ((axes, None), (None,)), (None, None),
        )(self.x, idx)

    def prepare_for_solve(self) -> "ShardedGram":
        """Per-solve setup hook (called once by ``solve()``, outside the solver
        loop): with ``gather_once``, replicate the sharded inputs into
        ``x_full`` so no matvec inside the loop pays the O(n·d) all_gather."""
        if not self.gather_once or self.x_full is not None:
            return self
        x_full = jax.device_put(
            self.x, NamedSharding(self.mesh, P(None, None))
        )
        return dataclasses.replace(self, x_full=x_full)

    def mv(self, v: jax.Array) -> jax.Array:
        """(K + σ²I) @ v under the resolved comm strategy.

        gather: per-device block-row matvec + all_gather of the result. v
        replicated; the input panel comes from ``x_full`` when pre-gathered,
        else a per-matvec all_gather. ring: P pipeline stages of
        K(x_local, x_peer) @ v_peer with ``ppermute`` rotating the next shard
        pair while the current one contracts — zero ``all_gather`` in the
        jaxpr, and the result stays row-sharded."""
        axes = self.data_axes
        squeeze = v.ndim == 1
        v2 = v[:, None] if squeeze else v

        if self._resolve_comm() == "ring":
            p_size = self._mesh_size()
            perm = [((j + 1) % p_size, j) for j in range(p_size)]

            def ring_body(x_local, v_local):
                # Stage t contracts the shard pair this device holds while the
                # permute for stage t+1 is already in flight — issuing the
                # ppermute *before* the contraction lets XLA's latency-hiding
                # scheduler overlap the rotation with the fused block matvec.
                acc = self.params.noise * v_local
                x_peer, v_peer = x_local, v_local
                for t in range(p_size):
                    if t + 1 < p_size:
                        nxt = jax.lax.ppermute((x_peer, v_peer), axes, perm)
                    acc = acc + self._local_mv(x_local, x_peer, v_peer)
                    if t + 1 < p_size:
                        x_peer, v_peer = nxt
                return acc

            out = shard_map_over(
                self.mesh, ring_body, ((axes, None), (axes, None)), (axes, None)
            )(self.x, v2)
        else:
            def block_row(x_local, x_all, v_all):
                i = jax.lax.axis_index(axes)
                n_local = x_local.shape[0]
                out = self._local_mv(x_local, x_all, v_all)
                v_local = jax.lax.dynamic_slice_in_dim(
                    v_all, i * n_local, n_local, axis=0
                )
                out = out + self.params.noise * v_local
                return jax.lax.all_gather(out, axes, tiled=True)

            out = self._panel_map(block_row, ((None, None),), (None, None), v2)
        self._count(_bump_mv, out)
        return out[:, 0] if squeeze else out

    def rows_mv(self, idx: jax.Array, u: jax.Array) -> jax.Array:
        """K[idx, :] @ u — sharded row-gather: each device contracts its column
        block K(x[idx], x_local) @ u_local; a psum over the data axes reduces.
        idx is replicated; output is replicated (|idx|, s-like). Under ring the
        idx panel comes from an O(|idx|·d) masked psum instead of an all_gather
        of x, and u may arrive row-sharded (SGD iterates)."""
        axes = self.data_axes
        squeeze = u.ndim == 1
        u2 = u[:, None] if squeeze else u

        if self._resolve_comm() == "ring":
            def body_ring(x_local, idx_rep, u_local):
                xi = _psum_row_gather(x_local, idx_rep, axes)
                part = self._local_mv(xi, x_local, u_local)
                return jax.lax.psum(part, axes)

            out = shard_map_over(
                self.mesh, body_ring, ((axes, None), (None,), (axes, None)), (None, None)
            )(self.x, idx, u2)
        else:
            def contract(x_local, x_all, idx_rep, u_all):
                i = jax.lax.axis_index(axes)
                n_local = x_local.shape[0]
                u_local = jax.lax.dynamic_slice_in_dim(
                    u_all, i * n_local, n_local, axis=0
                )
                part = self._local_mv(x_all[idx_rep], x_local, u_local)
                return jax.lax.psum(part, axes)

            out = self._panel_map(
                contract, ((None,), (None, None)), (None, None), idx, u2
            )
        self._count(_bump_rows, out)
        return out[:, 0] if squeeze else out

    def rows_t_mv(self, idx: jax.Array, u: jax.Array) -> jax.Array:
        """K[idx, :]ᵀ @ u = K[:, idx] @ u — each device computes its row block
        K(x_local, x[idx]) @ u. Under gather the blocks are all-gathered to a
        replicated (n, s-like); under ring the idx panel comes from the masked
        psum and the output *stays row-sharded* — the all_gather this
        primitive used to pay per SGD step is gone, and downstream axpys on
        the iterate run shard-local."""
        axes = self.data_axes
        squeeze = u.ndim == 1
        u2 = u[:, None] if squeeze else u

        if self._resolve_comm() == "ring":
            def body_ring(x_local, idx_rep, u_rep):
                xi = _psum_row_gather(x_local, idx_rep, axes)
                return self._local_mv(x_local, xi, u_rep)

            out = shard_map_over(
                self.mesh, body_ring, ((axes, None), (None,), (None, None)), (axes, None)
            )(self.x, idx, u2)
        else:
            def row_block(x_local, x_all, idx_rep, u_rep):
                out_local = self._local_mv(x_local, x_all[idx_rep], u_rep)
                return jax.lax.all_gather(out_local, axes, tiled=True)

            out = self._panel_map(
                row_block, ((None,), (None, None)), (None, None), idx, u2
            )
        self._count(_bump_rows, out)
        return out[:, 0] if squeeze else out

    def rows_pair_mv(self, idx: jax.Array, look: jax.Array, b: jax.Array):
        """err = K[idx,:] @ look − b, then g = K[idx,:]ᵀ @ err — composed from
        the sharded row primitives. No VMEM fusion applies across the mesh
        collectives, but exposing the capability keeps the operator drop-in for
        the fused SGD step; the counters still record two row-block matvecs."""
        err = self.rows_mv(idx, look) - b
        return err, self.rows_t_mv(idx, err)

    def block_at(self, idx: jax.Array) -> jax.Array:
        """K[idx, idx] — gathered from the global (sharded) inputs; the |idx|×d
        panel and |idx|² block are small and land replicated. The panel comes
        from the masked psum (no all_gather of x)."""
        xi = self._gather_rows(idx)
        return gram(self.params, xi, xi)

    def wrap_features(self, ff: "FourierFeatures"):
        """Mesh-awareness capability (``supports(op, "wrap_features")``): wrap a
        feature operator so its phi_mv/phi_t_mv/phi_pair_mv run shard_map-ped
        over this operator's mesh — row-sharded x, psum-reduced transposes, the
        fused per-shard kernels (and their custom VJPs) intact, and the (n, 2q)
        feature matrix never materialised. SGD's regulariser consumes this to
        run its Eq. 3.3 pair step distributed."""
        from .rff import ShardedFourierFeatures  # deferred: rff imports this module

        return ShardedFourierFeatures(
            inner=ff, mesh=self.mesh, data_axes=self.data_axes
        )

    def diag_part(self) -> jax.Array:
        return gram_diag(self.params, self.x) + self.noise

    def precond_factor(
        self, rank: int, key: Optional[jax.Array] = None, method: str = "nystrom"
    ) -> jax.Array:
        """(n, rank) factor for Woodbury preconditioning; computed under global
        sharding semantics (the n×rank factor is the preconditioner's memory
        footprint either way)."""
        from .precond import low_rank_factor  # deferred: precond imports operators

        return low_rank_factor(self.params, self.x, rank, key=key, method=method)

    def dense(self) -> jax.Array:
        """Materialised K + σ²I (tests / small-n reference only)."""
        return gram(self.params, self.x) + self.noise * jnp.eye(self.n, dtype=self.x.dtype)
