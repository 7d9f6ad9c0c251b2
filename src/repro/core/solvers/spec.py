"""Declarative solver configuration and the library's single ``solve()`` entry point.

The paper's thesis is that *every* expensive GP computation — pathwise posterior
samples (Ch. 3), MLL gradients (Ch. 5), Thompson steps (§3.3.2), latent-Kronecker
posteriors (Ch. 6), distributed solves — reduces to one batched multi-RHS linear
solve against interchangeable iterative solvers. This module makes that
interchangeability a first-class API instead of an accident of call sites:

* frozen, pytree-registered spec dataclasses describe *how* to solve
  (``CG``, ``SGD``, ``SDD``, ``AP``) and how to precondition (``Nystrom``,
  ``PivotedCholesky``);
* a registry maps string names (``"cg"``/``"sgd"``/``"sdd"``/``"ap"``) to spec
  classes so configs, CLIs and serialized runs can name solvers;
* ``solve(op, b, spec, key=..., x0=..., delta=...)`` uniformly handles PRNG keys,
  warm starts and preconditioner construction for all of them, for ANY
  :class:`~repro.core.operators.LinearOperator` — ``Gram``, ``NormalEq``,
  ``LatentKroneckerOp``, ``ShardedGram``, or a third-party operator.

Specs declare the operator capabilities they consume (``SolverSpec.needs``) and
``solve()`` verifies them up front: a spec requesting row blocks from a
matvec-only operator raises a clear ``TypeError`` naming the missing capability.

The system solved is always

    (K + σ²I) V = b + σ² δ

where ``delta`` is an optional extra channel: pathwise sampling passes δ = ε/σ² so
SGD can keep the noise draw out of its mini-batch data-fit term (the Eq. 3.6
variance-reduction shift); solvers without a native δ channel fold σ²δ into the
right-hand side, which is algebraically identical.

Specs carry only static (hashable) configuration, so they can cross ``jax.jit``
boundaries as static arguments and serve as cache keys for compiled solves.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, ClassVar, Dict, Optional, Tuple, Type, Union

import jax

import jax.numpy as jnp
from jax.scipy.linalg import cho_factor, cho_solve

from ...kernels.ops import BACKENDS, FEATURE_BACKENDS, PRECISIONS
from ..kernels_fn import HIGHEST
from ..operators import require_capabilities
from ..precond import jacobi_preconditioner, woodbury_from_factor
from .ap import solve_ap
from .base import SolveResult, as_matrix_rhs
from .cg import solve_cg
from .sdd import solve_sdd
from .sgd import solve_sgd


def _static(default):
    return dataclasses.field(default=default, metadata=dict(static=True))


# ---------------------------------------------------------------------------
# Preconditioner specs (§2.2.4; built on core/precond.py)
# ---------------------------------------------------------------------------

_PRECOND_REGISTRY: Dict[str, type] = {}


def register_precond(name: str, cls: Optional[type] = None):
    """Register a preconditioner spec class under a string name (decorator)."""

    def deco(c: type) -> type:
        c.name = name
        _PRECOND_REGISTRY[name] = c
        return c

    return deco(cls) if cls is not None else deco


def get_precond(name: str) -> type:
    try:
        return _PRECOND_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown preconditioner {name!r}; registered: {sorted(_PRECOND_REGISTRY)}"
        ) from None


def registered_preconds() -> tuple:
    return tuple(sorted(_PRECOND_REGISTRY))


class _JsonSpecMixin:
    """``to_json``/``from_json`` shared by solver and preconditioner specs.

    Specs are static dataclasses, so serialization is just their fields; nested
    preconditioner specs are tagged dicts. Prebuilt apply callables are runtime
    objects and refuse to serialize.
    """

    def to_json(self, **dumps_kwargs: Any) -> str:
        return json.dumps(_spec_to_dict(self), **dumps_kwargs)

    @staticmethod
    def from_json(s: str) -> "Any":
        return spec_from_dict(json.loads(s))


class _FactorPrecondSpec(_JsonSpecMixin):
    """Preconditioner specs built from an operator's ``precond_factor``
    capability: L = op.precond_factor(rank, method=...) with K ≈ LLᵀ, wrapped in
    the Woodbury apply (LLᵀ + σ²I)⁻¹."""

    method: ClassVar[str] = "?"

    def build(self, op, key: Optional[jax.Array] = None) -> Callable:
        require_capabilities(
            op, ("precond_factor",), consumer=f"the {self.name!r} preconditioner"
        )
        l = op.precond_factor(self.rank, key=key, method=self.method)
        return woodbury_from_factor(l, op.noise)


@register_precond("nystrom")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Nystrom(_FactorPrecondSpec):
    """Uniform-subset Nyström preconditioner: rank-m surrogate + Woodbury apply."""

    method: ClassVar[str] = "nystrom"
    rank: int = _static(100)


@register_precond("pivoted_cholesky")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PivotedCholesky(_FactorPrecondSpec):
    """Greedy pivoted-Cholesky preconditioner (paper fidelity; sequential build)."""

    method: ClassVar[str] = "pivoted_cholesky"
    rank: int = _static(100)


@register_precond("rff")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RFF(_FactorPrecondSpec):
    """Random-feature (feature-space) preconditioner: L = Φ(x), E[LLᵀ] = K.

    The surrogate is the same feature expansion pathwise conditioning uses for
    the prior (§2.2.2), so the preconditioner and the sampler share one
    approximation family. ``rank`` counts feature *columns* (must be even:
    paired sin/cos). On an :class:`~repro.core.operators.RFFGram` operator the
    factor is the operator's own Φ and Woodbury becomes the exact inverse.
    """

    method: ClassVar[str] = "rff"
    rank: int = _static(256)


@register_precond("jacobi")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Jacobi(_JsonSpecMixin):
    """Diagonal (Jacobi) preconditioner built from the protocol's *required*
    ``diag_part()`` — the cheap fallback for operators without a
    ``precond_factor`` capability (``LatentKroneckerOp``, ``NormalEq``). Needs
    no optional capability, so ``CG(precond=Jacobi())`` works on every operator
    ``solve()`` accepts."""

    def build(self, op, key: Optional[jax.Array] = None) -> Callable:
        return jacobi_preconditioner(op)


PrecondSpec = Union[Nystrom, PivotedCholesky, RFF, Jacobi]
# a raw ``r -> M⁻¹r`` callable is also accepted wherever a PrecondSpec fits
PrecondLike = Union[Nystrom, PivotedCholesky, RFF, Jacobi, Callable]


# ---------------------------------------------------------------------------
# Solver specs + registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Type["SolverSpec"]] = {}


def register_solver(name: str, cls: Optional[type] = None):
    """Register a spec class under a string name (usable as a decorator).

    Third-party solvers plug in the same way the built-ins do: subclass
    ``SolverSpec``, implement ``run``, and ``register_solver("mine", MySpec)`` —
    every consumer (``posterior_functions``, ``mll_grad``, ``thompson_step``, …)
    then accepts ``spec="mine"`` without being edited.
    """

    def deco(c: type) -> type:
        c.name = name
        _REGISTRY[name] = c
        return c

    return deco(cls) if cls is not None else deco


def get_solver(name: str) -> Type["SolverSpec"]:
    """String → spec class lookup for configs/CLIs; raises on unknown names."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown solver {name!r}; registered solvers: {sorted(_REGISTRY)}"
        ) from None


def registered_solvers() -> tuple:
    return tuple(sorted(_REGISTRY))


class SolverSpec(_JsonSpecMixin):
    """Base class for declarative solver configs.

    Subclasses are frozen dataclasses whose fields are all static (hashable), so a
    spec instance can be a ``jax.jit`` static argument or a dict key. ``run`` maps
    the spec onto the underlying solver function; consumers never call it directly
    — they go through ``solve()``.

    ``needs`` declares the operator capabilities the solver consumes beyond the
    required ``mv``/``shape``/``diag_part``/``noise`` (see core/operators.py) —
    ``solve()`` verifies them before dispatch, so an SGD spec pointed at a
    matvec-only operator fails with a capability error, not an ``AttributeError``
    inside a scan.

    All built-in specs carry a ``backend`` field pinning the kernel-matvec
    backend (``"pallas"``/``"chunked"``/``"dense"``/``"auto"``; ``None`` inherits
    the operator's own setting) — ``solve()`` applies it to any operator with a
    ``backend`` field (``Gram``, ``ShardedGram``), so ``CG(backend="pallas")``
    runs every matvec of the solve through the fused differentiable Pallas
    kernel, including through the shards of a distributed solve.

    ``precision`` pins the tile precision of the kernel contractions the same
    way (``"fp32"``/``"bf16"``; ``None`` inherits the operator's setting —
    fp32 everywhere by default). bf16 tiles halve MXU operand traffic while
    accumulating in fp32; the stochastic solvers tolerate the extra tile noise
    (it is dominated by mini-batch variance), so ``SGD(precision="bf16")`` is
    the intended opt-in — exact CG convergence is precision-sensitive and
    stays fp32 unless explicitly pinned. See docs/kernels.md.
    """

    name: ClassVar[str] = "?"
    requires_key: ClassVar[bool] = False  # stochastic solvers need a PRNG key
    needs: ClassVar[Tuple[str, ...]] = ()  # operator capabilities beyond mv

    def run(
        self,
        op,
        b: jax.Array,
        *,
        key: Optional[jax.Array] = None,
        x0: Optional[jax.Array] = None,
        delta: Optional[jax.Array] = None,
    ) -> SolveResult:
        raise NotImplementedError


def _fold_delta(op, b: jax.Array, delta: Optional[jax.Array]) -> jax.Array:
    """Fold the δ channel into the RHS: (K+σ²I)V = b + σ²δ."""
    return b if delta is None else b + op.noise * delta


@register_solver("cg")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CG(SolverSpec):
    """Conjugate gradients (§2.2.4), optionally preconditioned.

    ``precond`` is a preconditioner spec (built fresh per solve, since it depends
    on the hyperparameters) or a prebuilt ``r -> M⁻¹r`` apply. Spec builds
    call the operator's ``precond_factor`` capability and return
    ``WoodburyPrecond`` pytrees, which ride through the jitted CG as traced
    arguments — rebuilding one of the same rank reuses the compiled solve, so
    spec-valued preconds are safe inside hot outer loops. Only raw closures
    (legacy) are static arguments and recompile per identity.
    """

    max_iters: int = _static(1000)
    tol: float = _static(1e-2)
    precond: Optional[PrecondLike] = _static(None)
    backend: Optional[str] = _static(None)
    precision: Optional[str] = _static(None)
    # iterations without relative residual improvement before FLAG_STAGNATION
    # is raised on a column (advisory — see docs/robustness.md)
    stall_window: int = _static(100)

    def run(self, op, b, *, key=None, x0=None, delta=None) -> SolveResult:
        pc = self.precond
        if pc is not None and not callable(pc):
            pc = pc.build(op, key)
        return solve_cg(
            op, _fold_delta(op, b, delta), x0,
            max_iters=self.max_iters, tol=self.tol, precond=pc,
            stall_window=self.stall_window,
        )


@register_solver("sgd")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SGD(SolverSpec):
    """Primal stochastic gradient descent (Ch. 3).

    The only solver with a *native* δ channel: δ stays in the regulariser
    (Eq. 3.6) instead of being folded into the data-fit targets, which is the
    paper's variance-reduction trick for posterior sampling.

    Beyond row-block access, the RFF regulariser samples frequencies from the
    operator's kernel and evaluates features on its inputs, so the operator must
    also expose ``x`` and ``params`` (``Gram`` and ``ShardedGram`` do).
    """

    requires_key: ClassVar[bool] = True
    needs: ClassVar[Tuple[str, ...]] = ("rows_mv", "rows_t_mv", "x", "params")

    num_steps: int = _static(20_000)
    batch_size: int = _static(512)
    num_features: int = _static(100)
    step_size_times_n: float = _static(0.5)
    momentum: float = _static(0.9)
    average_tail: float = _static(0.5)
    grad_clip: float = _static(0.1)
    tol: float = _static(1e-2)
    backend: Optional[str] = _static(None)
    precision: Optional[str] = _static(None)

    def run(self, op, b, *, key=None, x0=None, delta=None) -> SolveResult:
        return solve_sgd(
            op, b, x0, key=key,
            num_steps=self.num_steps, batch_size=self.batch_size,
            num_features=self.num_features,
            step_size_times_n=self.step_size_times_n, momentum=self.momentum,
            average_tail=self.average_tail, grad_clip=self.grad_clip,
            delta=delta, tol=self.tol,
        )


@register_solver("sdd")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SDD(SolverSpec):
    """Stochastic dual descent (Ch. 4, Algorithm 4.1)."""

    requires_key: ClassVar[bool] = True
    needs: ClassVar[Tuple[str, ...]] = ("rows_mv",)

    num_steps: int = _static(20_000)
    batch_size: int = _static(512)
    step_size_times_n: float = _static(50.0)
    momentum: float = _static(0.9)
    averaging: Optional[float] = _static(None)
    tol: float = _static(1e-2)
    backend: Optional[str] = _static(None)
    precision: Optional[str] = _static(None)

    def run(self, op, b, *, key=None, x0=None, delta=None) -> SolveResult:
        return solve_sdd(
            op, _fold_delta(op, b, delta), x0, key=key,
            num_steps=self.num_steps, batch_size=self.batch_size,
            step_size_times_n=self.step_size_times_n, momentum=self.momentum,
            averaging=self.averaging, tol=self.tol,
        )


@register_solver("ap")
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AP(SolverSpec):
    """Alternating projections / randomised block-coordinate descent (§5.1.1)."""

    requires_key: ClassVar[bool] = True
    needs: ClassVar[Tuple[str, ...]] = ("rows_t_mv", "block_at")

    num_steps: int = _static(2000)
    block_size: int = _static(512)
    tol: float = _static(1e-2)
    backend: Optional[str] = _static(None)
    precision: Optional[str] = _static(None)

    def run(self, op, b, *, key=None, x0=None, delta=None) -> SolveResult:
        return solve_ap(
            op, _fold_delta(op, b, delta), x0, key=key,
            num_steps=self.num_steps, block_size=self.block_size, tol=self.tol,
        )


# ---------------------------------------------------------------------------
# JSON serialization — run configs, CLIs and the benchmark harness are
# file-drivable (ROADMAP item): every spec is a tagged dict of its fields.
# ---------------------------------------------------------------------------


def _spec_to_dict(spec) -> Dict[str, Any]:
    if not dataclasses.is_dataclass(spec):
        raise TypeError(f"expected a spec dataclass, got {spec!r}")
    tag = "precond" if type(spec) in _PRECOND_REGISTRY.values() else "solver"
    if spec.name not in (_PRECOND_REGISTRY if tag == "precond" else _REGISTRY):
        raise TypeError(
            f"{type(spec).__name__} is not a registered spec; register it with "
            f"register_{tag}(name) before serializing"
        )
    d: Dict[str, Any] = {tag: spec.name}
    for f in dataclasses.fields(spec):
        v = getattr(spec, f.name)
        if f.name == "precond" and v is not None:
            if callable(v) and not dataclasses.is_dataclass(v):
                raise TypeError(
                    "a prebuilt preconditioner apply is a runtime object and "
                    "cannot be serialized; use a Nystrom/PivotedCholesky spec"
                )
            v = _spec_to_dict(v)
        d[f.name] = v
    return d


def spec_to_dict(spec) -> Dict[str, Any]:
    """Spec (solver or preconditioner) → plain JSON-compatible dict."""
    return _spec_to_dict(spec)


def spec_from_dict(d: Dict[str, Any]):
    """Tagged dict → spec instance (inverse of :func:`spec_to_dict`)."""
    d = dict(d)
    if "solver" in d:
        cls: type = get_solver(d.pop("solver"))
    elif "precond" in d:
        cls = get_precond(d.pop("precond"))
    else:
        raise ValueError(
            "spec dict must be tagged with a 'solver' or 'precond' name; "
            f"got keys {sorted(d)}"
        )
    if isinstance(d.get("precond"), dict):
        d["precond"] = spec_from_dict(d["precond"])
    return cls(**d)


def spec_to_json(spec, **dumps_kwargs: Any) -> str:
    return json.dumps(_spec_to_dict(spec), **dumps_kwargs)


def spec_from_json(s: str):
    return spec_from_dict(json.loads(s))


# ---------------------------------------------------------------------------
# Normalisation: names / classes / instances
# ---------------------------------------------------------------------------

SpecLike = Union[str, SolverSpec, Type[SolverSpec]]


def as_spec(spec: SpecLike, **overrides: Any) -> SolverSpec:
    """Normalise a spec instance, spec class, or registered name to an instance.

    ``overrides`` are spec fields applied on top (``as_spec("cg", max_iters=50)``).
    """
    if isinstance(spec, str):
        spec = get_solver(spec)
    if isinstance(spec, type) and issubclass(spec, SolverSpec):
        return spec(**overrides)
    if isinstance(spec, SolverSpec):
        return dataclasses.replace(spec, **overrides) if overrides else spec
    raise TypeError(
        f"expected a SolverSpec, spec class, or registered solver name; got {spec!r}"
    )


# ---------------------------------------------------------------------------
# The single entry point
# ---------------------------------------------------------------------------


def _validate_x0(op, b: jax.Array, x0: jax.Array) -> None:
    """Warm-start sanity checks, up front and with a clear error.

    A stale warm-start cache (serving engine, MLL outer loop) otherwise surfaces
    as an opaque XLA broadcast/shape error deep inside the solver's
    while_loop/scan; here it names the mismatch at the ``solve()`` boundary.
    The rule is strict: ``x0`` must match ``b``'s shape exactly — a 1-D ``x0``
    against a multi-column ``b`` is refused rather than silently broadcast,
    because it almost always means a cached single-RHS solution is being reused
    for a differently-batched solve.
    """
    b_shape, x_shape = jnp.shape(b), jnp.shape(x0)
    if x_shape != b_shape:
        n = op.shape[0]
        raise ValueError(
            f"warm start x0 has shape {x_shape} but the right-hand side has "
            f"shape {b_shape} (operator is {n}×{n}); x0 must match b exactly — "
            f"a stale warm-start cache entry (old n after new observations, or "
            f"a different RHS column batch) is the usual cause. Drop x0 for a "
            f"cold solve, or re-key the cache."
        )
    b_dtype = jnp.result_type(b)
    x_dtype = jnp.result_type(x0)
    if x_dtype != b_dtype:
        raise TypeError(
            f"warm start x0 has dtype {x_dtype.name} but the right-hand side "
            f"has dtype {b_dtype.name}; pass x0 in the RHS dtype — a silent "
            f"promotion here would retrace the compiled solve and mask cache "
            f"bugs."
        )


def solve(
    op,
    b: jax.Array,
    spec: SpecLike = "cg",
    *,
    key: Optional[jax.Array] = None,
    x0: Optional[jax.Array] = None,
    delta: Optional[jax.Array] = None,
    **overrides: Any,
) -> SolveResult:
    """Solve (K+σ²I)V = b + σ²δ with any registered solver on any operator.

    Args:
        op: a :class:`~repro.core.operators.LinearOperator` — ``Gram``,
            ``NormalEq``, ``LatentKroneckerOp``, ``ShardedGram``, or any
            operator implementing the protocol. Capability dispatch: the spec's
            ``needs`` (row-block access for SGD/SDD/AP, ``precond_factor`` for
            preconditioner builds) are verified up front with a clear error.
        b: right-hand side(s), ``(n,)`` or ``(n, s)``.
        spec: a ``SolverSpec`` instance, spec class, or registered name
            (``"cg"``, ``"sgd"``, ``"sdd"``, ``"ap"``).
        key: PRNG key; required by stochastic solvers, used by CG only to draw the
            Nyström preconditioner subset.
        x0: optional warm start (Ch. 5 §5.3), same shape as ``b``.
        delta: optional δ channel, same shape as ``b`` — the system solved becomes
            ``(K+σ²I)V = b + σ²δ``, with SGD keeping δ in its regulariser
            (Eq. 3.6) and everything else folding it into the RHS.
        **overrides: spec-field overrides, e.g. ``solve(op, b, "cg", max_iters=50)``.
    """
    s = as_spec(spec, **overrides)
    backend = getattr(s, "backend", None)
    if backend is not None:
        # Gram backend names plus the feature names ("features" pins the
        # materialised path on feature-backed operators like RFFGram; the Gram
        # dispatch rejects it with its own error if pinned on a Gram operator)
        known = BACKENDS + tuple(b for b in FEATURE_BACKENDS if b not in BACKENDS)
        if backend not in known:
            raise ValueError(f"unknown backend {backend!r}; expected one of {known}")
        if (
            dataclasses.is_dataclass(op)
            and getattr(op, "backend", backend) != backend
        ):
            # the spec pins the kernel-matvec backend for this solve
            op = dataclasses.replace(op, backend=backend)
    precision = getattr(s, "precision", None)
    if precision is not None:
        if precision not in PRECISIONS:
            raise ValueError(
                f"unknown precision {precision!r}; expected one of {PRECISIONS}"
            )
        if (
            dataclasses.is_dataclass(op)
            and getattr(op, "precision", precision) != precision
        ):
            # the spec pins the kernel tile precision for this solve
            op = dataclasses.replace(op, precision=precision)
    if s.requires_key and key is None:
        raise ValueError(
            f"solver {s.name!r} is stochastic: solve(..., key=jax.random.PRNGKey(...))"
            " is required"
        )
    if x0 is not None:
        _validate_x0(op, b, x0)
    require_capabilities(op, s.needs, consumer=f"solver {s.name!r}")
    prep = getattr(op, "prepare_for_solve", None)
    if callable(prep):
        # per-solve setup hook, run once outside the solver's while_loop/scan —
        # e.g. ShardedGram(gather_once=True) gathers its sharded inputs here
        # instead of all-gathering on every matvec
        op = prep()
    return s.run(op, b, key=key, x0=x0, delta=delta)


# ---------------------------------------------------------------------------
# Multi-RHS coalescing on top of solve() — the serving engine's primitive
# ---------------------------------------------------------------------------


def solve_batched(
    op,
    blocks,
    spec: SpecLike = "cg",
    *,
    key: Optional[jax.Array] = None,
    x0_blocks=None,
    delta_blocks=None,
    pad_columns_to: Optional[int] = None,
    **overrides: Any,
) -> list:
    """Coalesce per-consumer RHS column blocks into ONE multi-RHS solve.

    This is the paper's continuous-batching primitive made explicit: k callers
    each bring a small RHS block against the *same* operator, the blocks are
    stacked column-wise, solved in one call to :func:`solve` (one matvec stream
    serves everyone — CG's per-iteration cost is one fused multi-RHS matvec
    regardless of k), and the result is scattered back as one ``SolveResult``
    per block. ``iterations``/``matvecs`` on each returned result are the
    *shared* batch totals — that sharing is the whole point — while
    ``residual_norm``/``rel_residual``/``converged`` are per-block.

    Args:
        blocks: sequence of RHS blocks, each ``(n,)`` or ``(n, s_i)``.
        x0_blocks: optional warm starts, one per block (``None`` entries are
            cold and solved from zero); if every entry is ``None`` the batch is
            a cold solve.
        delta_blocks: optional δ channels, one per block (``None`` entries get
            δ = 0).
        pad_columns_to: pad the stacked RHS with zero columns up to this count —
            the serving engine's fixed bucket shapes, so batches of 3 and 5
            requests hit the same compiled solve. Zero columns converge
            immediately (CG freezes them on the spot) and are sliced off.

    Returns:
        ``[SolveResult, ...]``, one per input block, in order; solutions are
        squeezed back to 1-D for 1-D input blocks.
    """
    s = as_spec(spec, **overrides)
    blocks = list(blocks)
    if not blocks:
        return []
    mats, squeezes = [], []
    for blk in blocks:
        m, sq = as_matrix_rhs(jnp.asarray(blk))
        mats.append(m)
        squeezes.append(sq)
    widths = [m.shape[1] for m in mats]
    offsets = [0]
    for w in widths:
        offsets.append(offsets[-1] + w)
    total = offsets[-1]
    n = mats[0].shape[0]

    def _stack(maybe_blocks, what):
        if maybe_blocks is None:
            return None
        maybe_blocks = list(maybe_blocks)
        if len(maybe_blocks) != len(blocks):
            raise ValueError(
                f"{what} has {len(maybe_blocks)} blocks for {len(blocks)} RHS "
                f"blocks; pass one entry per block (None for missing)"
            )
        if all(e is None for e in maybe_blocks):
            return None
        cols = []
        for e, w in zip(maybe_blocks, widths):
            if e is None:
                cols.append(jnp.zeros((n, w), dtype=mats[0].dtype))
            else:
                cols.append(as_matrix_rhs(jnp.asarray(e))[0])
        return jnp.concatenate(cols, axis=1)

    b = jnp.concatenate(mats, axis=1)
    x0 = _stack(x0_blocks, "x0_blocks")
    delta = _stack(delta_blocks, "delta_blocks")
    if pad_columns_to is not None and pad_columns_to > total:
        pad = pad_columns_to - total
        zeros = jnp.zeros((n, pad), dtype=b.dtype)
        b = jnp.concatenate([b, zeros], axis=1)
        if x0 is not None:
            x0 = jnp.concatenate([x0, zeros], axis=1)
        if delta is not None:
            delta = jnp.concatenate([delta, zeros], axis=1)

    res = solve(op, b, s, key=key, x0=x0, delta=delta)
    tol = float(getattr(s, "tol", 1e-2))
    flags_full = jnp.asarray(res.flags, dtype=jnp.int32)
    if flags_full.ndim == 0:
        flags_full = jnp.broadcast_to(flags_full, res.rel_residual.shape)
    out = []
    for (lo, hi), sq in zip(zip(offsets[:-1], offsets[1:]), squeezes):
        sol = res.solution[:, lo:hi]
        rel = res.rel_residual[lo:hi]
        fl = flags_full[lo:hi]
        out.append(
            SolveResult(
                solution=sol[:, 0] if sq else sol,
                residual_norm=res.residual_norm[lo:hi],
                rel_residual=rel,
                iterations=res.iterations,
                # per-block convergence is flag-aware, like finalize():
                # a flagged column in THIS block fails this block only
                converged=jnp.all((rel <= tol) & (fl == 0)),
                matvecs=res.matvecs,
                flags=fl,
            )
        )
    return out


# ---------------------------------------------------------------------------
# Bordered-system (rank-k) extension on top of solve_batched — the serving
# engine's incremental-update primitive
# ---------------------------------------------------------------------------


def solve_bordered(
    op,
    b_cols: jax.Array,
    c_new: jax.Array,
    rhs_new: jax.Array,
    sol_old: jax.Array,
    spec: SpecLike = "cg",
    *,
    key: Optional[jax.Array] = None,
    x0: Optional[jax.Array] = None,
    **overrides: Any,
) -> Tuple[jax.Array, SolveResult]:
    """Extend a solved system by k rows via the bordered-system identity.

    Given ``sol_old`` with (A = K_old + σ²I)·sol_old ≈ rhs_old already solved,
    and k appended inputs with cross-covariance block B = K(X_old, X_new)
    (``b_cols``, (n, k)), new-block covariance C = K(X_new, X_new) (``c_new``,
    (k, k), WITHOUT noise — σ²I is added here, from ``op.noise``), and bottom
    RHS rows ``rhs_new`` ((k, m)), the extended system

        [ A   B ] [u]   [rhs_old]
        [ Bᵀ  C+σ²I ] [w] = [rhs_new]

    is solved without ever touching the (n+k)-operator:

        Z = A⁻¹ B                       (ONE multi-RHS solve, k columns, old n)
        S = (C + σ²I) − Bᵀ Z            (k×k Schur complement, Cholesky)
        w = S⁻¹ (rhs_new − Bᵀ sol_old)  (closed-form back-substitution)
        u = sol_old − Z w

    so the iterative cost is k correction columns against the OLD operator —
    independent of how many RHS columns m ride the update (they all share Z) —
    instead of a fresh m-column solve at n+k. The Z solve goes through
    :func:`solve_batched`, so it is warm-startable (``x0``, e.g. the Z of a
    previous update at nearby hyperparameters) and its iteration/matvec
    accounting comes back as a standard per-block :class:`SolveResult`.

    Exactness: with Z and sol_old exact, the returned solution satisfies the
    extended system exactly (the identity is algebra, not approximation); with
    iterative Z/sol_old, the top-block residual is r_old − (B − AZ)·w, so
    accumulated drift is observable with ONE extended-operator matvec — see
    ``serve.state.update_state_lowrank``, which certifies exactly that way.

    Returns ``(solution (n+k, m), z_result)`` where ``z_result`` is the
    correction solve's :class:`SolveResult` (its per-column ``flags``/
    ``rel_residual`` refer to the k Z columns).
    """
    s = as_spec(spec, **overrides)
    b_cols = jnp.asarray(b_cols)
    if b_cols.ndim != 2:
        raise ValueError(f"b_cols must be (n, k); got shape {jnp.shape(b_cols)}")
    n, k = b_cols.shape
    c_new = jnp.asarray(c_new)
    if c_new.shape != (k, k):
        raise ValueError(
            f"c_new must be ({k}, {k}) to match b_cols' {k} columns; got "
            f"{c_new.shape}"
        )
    sol_old, _ = as_matrix_rhs(jnp.asarray(sol_old))
    rhs_new, _ = as_matrix_rhs(jnp.asarray(rhs_new))
    if sol_old.shape[0] != n or rhs_new.shape[0] != k:
        raise ValueError(
            f"sol_old rows ({sol_old.shape[0]}) must match the old n ({n}) and "
            f"rhs_new rows ({rhs_new.shape[0]}) the k new rows ({k})"
        )
    (z_result,) = solve_batched(
        op, [b_cols], s, key=key,
        x0_blocks=None if x0 is None else [x0],
    )
    z = z_result.solution  # (n, k) = A⁻¹ B
    def dot(a, b):  # full fp32 on TPU too (its default is a bf16 pass)
        return jnp.matmul(a, b, precision=HIGHEST)

    schur = c_new + op.noise * jnp.eye(k, dtype=b_cols.dtype) - dot(b_cols.T, z)
    # symmetrise the fp drift from the iterative Z before factorizing — S is
    # S.P.D. by the Schur-complement theorem whenever the extended Gram is
    schur = 0.5 * (schur + schur.T)
    cho = cho_factor(schur, lower=True)
    w = cho_solve(cho, rhs_new - dot(b_cols.T, sol_old))  # (k, m)
    u = sol_old - dot(z, w)  # (n, m)
    return jnp.concatenate([u, w], axis=0), z_result
