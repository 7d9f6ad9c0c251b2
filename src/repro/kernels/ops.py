"""Jit'd public wrappers around the Pallas kernels — and the library's matvec
backend-selection layer, for Gram *and* feature-map contractions.

Every Gram-matvec in the library routes through :func:`gram_mv` (full matvecs) or
:func:`gram_rows_matvec` (row-block matvecs), which dispatch on a ``backend``
string:

* ``"pallas"``  — the fused, Pallas kernel of the kernel kind, K tiles built in
  VMEM and never materialised in HBM: ``gram_matvec.py`` for the stationary
  kinds (differentiable throughout), ``tanimoto.py`` for ``tanimoto`` (exact on
  integer counts 0..``tanimoto.MAX_COUNT``, Σmin on the MXU through count planes;
  no derivative with respect to the points). Compiled on TPU, interpret mode
  elsewhere.
* ``"chunked"`` — the pure-JAX row-chunked matvec (core/kernels_fn.py):
  O(chunk·m) memory, any kernel kind, autodiff throughout.
* ``"dense"``   — materialise K and multiply (small-n reference / tests).
* ``"auto"``    — Pallas when running on TPU (interpret mode is slower than
  chunked XLA on CPU), chunked otherwise, for every kind.

Every feature-map matvec routes through :func:`rff_mv` (Φ(x) @ w) or
:func:`rff_t_mv` (Φ(x)ᵀ @ u) for random Fourier features and
:func:`tanimoto_rf_mv` for Tanimoto random features — the ``FeatureOperator``
twins of ``gram_mv``, dispatching on ``"pallas"`` (fused, (n × F) feature
matrix never in HBM) / ``"features"`` (materialise Φ and matmul) / ``"auto"``.
The Gram backend names ``"chunked"``/``"dense"`` coerce to ``"features"``, so
one spec-level ``backend`` field pins both sides of a solve.

All stationary paths are differentiable w.r.t. the hyperparameters: the Pallas
paths wrap ``jax.custom_vjp``\\ s whose backward passes are themselves fused
Pallas contractions, with σ_f², lengthscale and jitter folded in *outside* the
custom-VJP cores so their gradients flow through ordinary autodiff (σ_f² and
the jitter likewise for ``tanimoto``).

``MATVEC_TRACE_COUNTS`` / ``FEATURE_TRACE_COUNTS`` record how many Gram/feature
matvecs each backend dispatched (counted when the op is staged, i.e. per trace or
eager call) — used by tests and benchmarks to prove the hot paths never silently
fall back (see tests/test_backends_and_counts.py, tests/test_features.py).
``GRAM_TILING_TRACE_COUNTS`` splits the fused stationary Gram matvecs by grid:
``K(x, x) @ v`` takes the symmetric grid when it spans more than one block and
its resident accumulator fits the kernel's VMEM budget
(``gram_matvec.symmetric_fits``), everything else the square one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .gram_matvec import (
    PALLAS_KINDS,
    TILE_PRECISIONS,
    gram_matvec_fused,
    gram_rows_pair_fused,
    symmetric_fits,
)
from .rff_matvec import rff_matvec_fused, rff_pair_fused, rff_t_matvec_fused
from .tanimoto import ABSENT, rf_signs, tanimoto_matvec_fused, tanimoto_rf_pallas
from .flash_attention import flash_attention_pallas

BACKENDS = ("auto", "pallas", "chunked", "dense")

#: kernel kinds with a fused Pallas matvec
FUSED_KINDS = PALLAS_KINDS + ("tanimoto",)

#: Tanimoto tiles: (512, 1024) float32 count tiles keep the MXU busy with
#: C·1024-deep plane products; smaller operands take one tile of their own
#: size (rounded up to 16 rows, the bfloat16 plane tile)
TANIMOTO_BLOCK = 512
#: Tanimoto random features: rows and hashes per grid step
TANIMOTO_RF_BLOCK = 256

#: VMEM one step of a stationary Gram or RFF tile may take (half of the
#: core's 16 MiB, leaving room for double buffering) ...
_TILE_VMEM = 8 * 2 ** 20
#: ... at this many right-hand sides (the pathwise batch, num_samples + 1 ≈ 16)
_TILE_RHS = 16

#: Tile/contration precisions (re-exported from gram_matvec): ``"fp32"``
#: everywhere by default; ``"bf16"`` casts contraction operands to bfloat16
#: with fp32 accumulation. Threaded ``SolverSpec`` → ``solve()`` → operators →
#: here, exactly like ``backend``. On the chunked/dense backends precision
#: applies to the panel/feature *contractions* (the panel itself and the
#: covariance map stay fp32); on Pallas it also covers the distance matmul.
PRECISIONS = TILE_PRECISIONS

#: Feature-map (RFF) backends: fused Pallas vs materialised features. ``auto``
#: is pallas on TPU, features elsewhere; Gram backend names coerce (see
#: :func:`resolve_feature_backend`).
FEATURE_BACKENDS = ("auto", "pallas", "features")

# backend -> number of Gram matvecs dispatched (staged into a trace or run
# eagerly). A solve that never touches "chunked" proves the fused path is the
# hot path — see tests/test_backends_and_counts.py.
MATVEC_TRACE_COUNTS = {"pallas": 0, "chunked": 0, "dense": 0}

# grid -> number of fused stationary Gram matvecs staged on it: "symmetric"
# (K(x, x) within the kernel's VMEM budget, each off-diagonal tile built once
# and contracted both ways) or "square" (a cross-Gram, one block, or over the
# budget).
GRAM_TILING_TRACE_COUNTS = {"symmetric": 0, "square": 0}

# backend -> number of feature matvecs (Φw / Φᵀu) dispatched. A solve whose
# "features" count stays zero provably never materialised an (n, 2m) feature
# matrix — the acceptance check for the fused SGD regulariser.
FEATURE_TRACE_COUNTS = {"pallas": 0, "features": 0}


def reset_matvec_trace_counts() -> None:
    for k in MATVEC_TRACE_COUNTS:
        MATVEC_TRACE_COUNTS[k] = 0
    for k in GRAM_TILING_TRACE_COUNTS:
        GRAM_TILING_TRACE_COUNTS[k] = 0


def reset_feature_trace_counts() -> None:
    for k in FEATURE_TRACE_COUNTS:
        FEATURE_TRACE_COUNTS[k] = 0


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str = "auto", kind: str = "se") -> str:
    """Normalise a backend request to a concrete backend for kernel ``kind``.

    ``auto`` picks the fused Pallas kernel on TPU and the chunked JAX matvec
    elsewhere. Every kind in ``FUSED_KINDS`` has a Pallas kernel; any other
    kind is refused by ``pallas`` and ``auto`` alike, never rerouted.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if backend in ("auto", "pallas") and kind not in FUSED_KINDS:
        raise ValueError(
            f"kernel kind {kind!r} has no fused Pallas matvec; supported kinds: "
            f"{FUSED_KINDS}. Use backend='chunked'."
        )
    if backend == "auto":
        return "pallas" if _on_tpu() else "chunked"
    return backend


def _pad_rows(a: jax.Array, mult: int) -> jax.Array:
    pad = (-a.shape[0]) % mult
    return a if pad == 0 else jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))


def _check_precision(precision: str) -> str:
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; expected one of {PRECISIONS}"
        )
    return precision


def stationary_block(family: str, rows: int, d: int, precision: str) -> int:
    """The ``block="auto"`` tile of the stationary Gram (``family="gram"``)
    and RFF (``"rff"``) kernels over an operand of ``rows`` rows.

    512 from 512 rows; below that 256 from 256 rows if one 256-tile step fits
    ``_TILE_VMEM``; else 128. Only the 128 floor ever out-pads the operand.
    A step holds the two point tiles and the RHS tile (both weight
    halves for ``rff``) at the tile precision, and the fp32 (b, b) tile and
    (b, s) accumulator.
    """
    if rows >= 512:
        return 512
    b, s, el = 256, _TILE_RHS, 2 if precision == "bf16" else 4
    rhs = 2 * b * s if family == "rff" else b * s
    step = el * (2 * b * d + rhs) + 4 * (b * b + b * s)
    return 256 if rows >= 256 and step <= _TILE_VMEM else 128


def _resolve_block(block, family: str, n: int, d: int, precision: str) -> int:
    """``"auto"`` → :func:`stationary_block`; ints pass through.

    Runs at trace time on static shapes, so the result is a plain Python int
    and a repeated call with the same shapes re-traces nothing.
    """
    if block == "auto":
        return stationary_block(family, n, d, precision)
    return int(block)


def _dot(a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    """a @ b honouring the tile precision: bf16 operands, fp32 accumulation.

    The fp32 path is a full-fp32 dot (``HIGHEST``): the TPU default would
    run float32 operands through a reduced-precision bf16 pass.
    """
    if precision == "bf16":
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _tile(block, rows: int) -> int:
    """``block`` rows, or one tile of the operand's own rows (rounded up to 16)
    when it has fewer."""
    return min(int(block), -(-rows // 16) * 16)


def _pallas_tanimoto_mv(params, x, v2, z, block, interpret, precision):
    interpret = (not _on_tpu()) if interpret is None else interpret
    block = TANIMOTO_BLOCK if block == "auto" else block
    n = x.shape[0]
    bm = _tile(block, n)
    bn = bm if z is None else _tile(block, z.shape[0])
    xp = _pad_rows(x, bm)
    zp = xp if z is None else _pad_rows(z, bn)
    vp = _pad_rows(v2, bn)
    out = tanimoto_matvec_fused(bm, bn, bool(interpret), precision, xp, zp, vp)
    return params.signal * out[:n]


def _pallas_gram_mv(params, x, v2, z, block, interpret, precision="fp32"):
    if params.kind == "tanimoto":
        return _pallas_tanimoto_mv(params, x, v2, z, block, interpret, precision)
    interpret = (not _on_tpu()) if interpret is None else interpret
    ls = params.lengthscale
    xs = x / ls
    zs = None if z is None else z / ls
    n = xs.shape[0]
    block = _resolve_block(block, "gram", n, x.shape[1], precision)
    xp = _pad_rows(xs, block)
    zp = xp if zs is None else _pad_rows(zs, block)
    vp = _pad_rows(v2, block)
    symmetric = zs is None and symmetric_fits(xp.shape[0], vp.shape[1], block)
    GRAM_TILING_TRACE_COUNTS["symmetric" if symmetric else "square"] += 1
    out = gram_matvec_fused(
        params.kind, block, block, bool(interpret), precision, symmetric,
        xp, zp, vp,
    )
    return params.signal * out[:n]


def gram_mv(
    params,
    x: jax.Array,
    v: jax.Array,
    z=None,
    *,
    jitter=None,
    backend: str = "auto",
    block="auto",
    row_chunk: int = 2048,
    interpret=None,
    precision: str = "fp32",
) -> jax.Array:
    """(σ_f² k(x, z) + jitter·I) @ v through the selected backend — THE Gram
    matvec entry point; differentiable w.r.t. ``params`` on every backend.

    params: core.kernels_fn.KernelParams. v: (m,) or (m, s). ``jitter`` (typically
    σ²) is applied as ``out + jitter·v`` outside the kernels, valid only for the
    symmetric z-is-None case.
    """
    from ..core.kernels_fn import gram, matvec  # deferred: avoid core<->kernels cycle

    if jitter is not None and z is not None:
        raise ValueError(
            "jitter adds jitter·I, which only makes sense for the symmetric "
            "K(x, x) operator — drop jitter for cross-Gram matvecs (z given)"
        )
    bk = resolve_backend(backend, params.kind)
    _check_precision(precision)
    MATVEC_TRACE_COUNTS[bk] += 1
    squeeze = v.ndim == 1
    v2 = v[:, None] if squeeze else v
    if bk == "pallas":
        out = _pallas_gram_mv(params, x, v2, z, block, interpret, precision)
    elif bk == "chunked":
        out = matvec(params, x, v2, z=z, row_chunk=row_chunk)
    else:
        out = _dot(gram(params, x, z), v2, "fp32")
    if jitter is not None:
        out = out + jitter * v2
    return out[:, 0] if squeeze else out


def gram_rows_matvec(
    params,
    x: jax.Array,
    idx: jax.Array,
    u: jax.Array,
    *,
    transpose: bool = False,
    backend: str = "auto",
    block="auto",
    row_chunk: int = 2048,
    interpret=None,
    precision: str = "fp32",
) -> jax.Array:
    """Fused row-block matvec: K[idx, :] @ u, or K[idx, :]ᵀ @ u with ``transpose``.

    The SGD/SDD/AP primitive (Wu et al. 2023). On the Pallas backend the |idx|×n
    row panel never exists in HBM — only the gathered x[idx] (|idx|×d) does, and
    the panel is built tile-by-tile in VMEM. The chunked/dense backends
    materialise the panel once per call (a solver batch is small, |idx| ≪ n, so
    this is the seed's memory envelope and avoids recomputing kernel entries —
    fusion only pays when HBM bandwidth is the bottleneck). u: (n, s) (or
    (|idx|, s) with ``transpose``).
    """
    from ..core.kernels_fn import gram  # deferred: avoid core<->kernels cycle

    bk = resolve_backend(backend, params.kind)
    _check_precision(precision)
    xi = x[idx]
    if bk == "pallas":
        if transpose:
            return gram_mv(
                params, x, u, z=xi, backend="pallas", block=block,
                interpret=interpret, precision=precision,
            )
        return gram_mv(
            params, xi, u, z=x, backend="pallas", block=block,
            interpret=interpret, precision=precision,
        )
    MATVEC_TRACE_COUNTS[bk] += 1
    panel = gram(params, xi, x)  # (|idx|, n)
    return _dot(panel.T, u, precision) if transpose else _dot(panel, u, precision)


def gram_rows_pair(
    params,
    x: jax.Array,
    idx: jax.Array,
    look: jax.Array,
    b: jax.Array,
    *,
    backend: str = "auto",
    block="auto",
    interpret=None,
    precision: str = "fp32",
) -> tuple:
    """Fused stochastic pair step: err = K[idx,:] @ look − b and
    g = K[idx,:]ᵀ @ err in one dispatch — the SGD fit-gradient primitive.

    The unfused path launches two independent row-block matvecs that each
    rebuild the same kernel panel from scratch; here the chunked/dense backends
    build the panel ONCE and reuse it for both contractions, and the Pallas
    backend runs the two-phase ``gram_rows_pair`` kernel (gram_matvec.py) whose
    (|idx|, s) error block never leaves VMEM between the two passes. Counts as
    TWO row-block matvecs — the work of the two calls it replaces — so the
    solver-layer accounting is unchanged. look: (n, s); b: (|idx|, s).
    Differentiable w.r.t. ``params`` on every backend.
    """
    from ..core.kernels_fn import gram  # deferred: avoid core<->kernels cycle

    bk = resolve_backend(backend, params.kind)
    _check_precision(precision)
    MATVEC_TRACE_COUNTS[bk] += 2
    xi = x[idx]
    if bk == "pallas" and params.kind == "tanimoto":
        # no two-phase Tanimoto kernel: the two fused matvecs in sequence
        kw = dict(block=block, interpret=interpret, precision=precision)
        err = _pallas_tanimoto_mv(params, xi, look, x, **kw) - b
        return err, _pallas_tanimoto_mv(params, x, err, xi, **kw)
    if bk == "pallas":
        interpret = (not _on_tpu()) if interpret is None else interpret
        ls = params.lengthscale
        p, n = xi.shape[0], x.shape[0]
        bn = _resolve_block(block, "gram", n, x.shape[1], precision)
        xip = _pad_rows(xi / ls, 128)
        xp = _pad_rows(x / ls, bn)
        lookp = _pad_rows(look, bn)
        # unit-signal core: err = σ_f²·err_u with err_u = A_u@look − b/σ_f²,
        # and g = Aᵀ err = σ_f²·A_uᵀ·(σ_f²·err_u) = σ_f⁴·g_u — σ_f² gradients
        # flow through the plain-JAX scaling, like every other fused core
        bp = _pad_rows(b / params.signal, 128)
        err_u, g_u = gram_rows_pair_fused(
            params.kind, bn, bool(interpret), precision, p, xip, xp, lookp, bp
        )
        return params.signal * err_u[:p], (params.signal ** 2) * g_u[:n]
    panel = gram(params, xi, x)  # (|idx|, n) — built once, used twice
    err = _dot(panel, look, precision) - b
    return err, _dot(panel.T, err, precision)


def gram_matvec(params, x, v, z=None, *, jitter=None, block="auto", interpret=None,
                precision: str = "fp32"):
    """(σ_f² k(x,z) + jitter I) @ v — Pallas fused Gram matvec (see gram_matvec.py).

    Thin ``backend="pallas"`` pin over :func:`gram_mv`, kept as the conventional
    name for kernel tests and benchmarks.
    """
    return gram_mv(
        params, x, v, z=z, jitter=jitter, backend="pallas", block=block,
        interpret=interpret, precision=precision,
    )


def resolve_feature_backend(backend: str = "auto", paired: bool = True) -> str:
    """Normalise a backend request to a concrete feature-matvec backend.

    Accepts the feature names (``auto``/``pallas``/``features``) plus the Gram
    names — ``chunked``/``dense`` coerce to ``features`` and the legacy
    ``fused`` alias to ``pallas`` — so a solver spec's single ``backend`` field
    pins the Gram *and* feature sides of a solve consistently. The fused kernel
    only implements the paired sin/cos map: ``auto`` silently falls back to
    ``features`` for the cos-only variant; explicit ``pallas`` raises.
    """
    if backend in ("chunked", "dense"):
        backend = "features"
    elif backend == "fused":
        backend = "pallas"
    if backend not in FEATURE_BACKENDS:
        raise ValueError(
            f"unknown feature backend {backend!r}; expected one of "
            f"{FEATURE_BACKENDS} (or a Gram backend name, coerced to 'features')"
        )
    if backend == "auto":
        return "pallas" if (_on_tpu() and paired) else "features"
    if backend == "pallas" and not paired:
        raise ValueError(
            "the fused RFF kernels only implement the paired sin/cos feature "
            "map; use paired features or backend='features'"
        )
    return backend


def _pad_rff_operands(x, omega, halves, block):
    """Zero-pad x rows, the ω feature rows, and any per-frequency ``halves``
    (sin/cos weight blocks) to block multiples. Padded ω rows give cos→1
    features, but the matching padded weight/cotangent rows are zero, so their
    contribution vanishes; only the 1/m normalisation needs fixing (the caller
    rescales by √(m_pad/m_true)). All pads are plain ``jnp.pad``, so their
    transposes slice the padded cotangents off again under autodiff."""
    m_true = omega.shape[0]
    pad_f = (-m_true) % block
    if pad_f:
        omega = jnp.pad(omega, ((0, pad_f), (0, 0)))
        halves = tuple(jnp.pad(h, ((0, pad_f), (0, 0))) for h in halves)
    return _pad_rows(x, block), omega, halves, m_true + pad_f


def rff_matvec(x, omega, w, *, signal=1.0, block="auto", interpret=None,
               precision: str = "fp32"):
    """Φ(x) @ w (paired sin/cos RFF) — fused, feature matrix never in HBM;
    differentiable w.r.t. ``x``, ``omega``, ``w`` and ``signal`` (custom VJP,
    every pass a fused Pallas contraction — kernels/rff_matvec.py).

    ``signal`` (σ_f²) may be a traced array: the kernel runs with unit signal
    and the √(σ_f²/m) normalisation is applied outside, in plain JAX.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    n = x.shape[0]
    m_true = omega.shape[0]
    block = _resolve_block(block, "rff", n, x.shape[1], precision)
    xp, omega, (w_sin, w_cos), m_pad = _pad_rff_operands(
        x, omega, (w[:m_true], w[m_true:]), block
    )
    wp = jnp.concatenate([w_sin, w_cos], axis=0)
    out = rff_matvec_fused(
        block, block, bool(interpret), precision, xp, omega, wp
    )[:n]
    # kernel scale is sqrt(1/m_pad); rescale to sqrt(signal/m_true)
    return out * jnp.sqrt(signal * (m_pad / m_true))


def rff_t_matvec(x, omega, u, *, signal=1.0, block="auto", interpret=None,
                 precision: str = "fp32"):
    """Φ(x)ᵀ @ u (paired sin/cos RFF) → (2m, s) — the transposed fused matvec,
    sin/cos halves accumulated per feature tile; differentiable throughout.

    The SGD regulariser pullback primitive (Eq. 3.3): Φᵀ(v − δ) without the
    (n × 2m) feature matrix in HBM.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    m_true = omega.shape[0]
    block = _resolve_block(block, "rff", x.shape[0], x.shape[1], precision)
    xp, omega, _, m_pad = _pad_rff_operands(x, omega, (), block)
    up = _pad_rows(u, block)  # padded rows are zero ⇒ contribute nothing to Φᵀu
    out = rff_t_matvec_fused(block, block, bool(interpret), precision, xp, omega, up)
    out = jnp.concatenate([out[:m_true], out[m_pad:m_pad + m_true]], axis=0)
    return out * jnp.sqrt(signal * (m_pad / m_true))


def _materialised_features(x, omega, signal):
    m = omega.shape[0]
    proj = x @ omega.T  # (n, m)
    return jnp.sqrt(signal / m) * jnp.concatenate(
        [jnp.sin(proj), jnp.cos(proj)], axis=-1
    )  # (n, 2m)


def rff_mv(
    x: jax.Array,
    omega: jax.Array,
    w: jax.Array,
    *,
    signal=1.0,
    backend: str = "auto",
    block="auto",
    interpret=None,
    precision: str = "fp32",
) -> jax.Array:
    """Φ(x) @ w through the selected feature backend — THE feature matvec entry
    point (the ``FeatureOperator`` twin of :func:`gram_mv`); differentiable on
    every backend. x:(n,d) ω:(m,d) w:(2m,) or (2m,s) → (n, s-like)."""
    bk = resolve_feature_backend(backend)
    _check_precision(precision)
    FEATURE_TRACE_COUNTS[bk] += 1
    squeeze = w.ndim == 1
    w2 = w[:, None] if squeeze else w
    if bk == "pallas":
        out = rff_matvec(x, omega, w2, signal=signal, block=block,
                         interpret=interpret, precision=precision)
    else:
        out = _dot(_materialised_features(x, omega, signal), w2, precision)
    return out[:, 0] if squeeze else out


def rff_t_mv(
    x: jax.Array,
    omega: jax.Array,
    u: jax.Array,
    *,
    signal=1.0,
    backend: str = "auto",
    block="auto",
    interpret=None,
    precision: str = "fp32",
) -> jax.Array:
    """Φ(x)ᵀ @ u through the selected feature backend — the transposed feature
    matvec entry point. x:(n,d) ω:(m,d) u:(n,) or (n,s) → (2m, s-like)."""
    bk = resolve_feature_backend(backend)
    _check_precision(precision)
    FEATURE_TRACE_COUNTS[bk] += 1
    squeeze = u.ndim == 1
    u2 = u[:, None] if squeeze else u
    if bk == "pallas":
        out = rff_t_matvec(x, omega, u2, signal=signal, block=block,
                           interpret=interpret, precision=precision)
    else:
        out = _dot(_materialised_features(x, omega, signal).T, u2, precision)
    return out[:, 0] if squeeze else out


def rff_pair_mv(
    x: jax.Array,
    omega: jax.Array,
    u: jax.Array,
    *,
    signal=1.0,
    backend: str = "auto",
    block="auto",
    interpret=None,
    precision: str = "fp32",
) -> jax.Array:
    """Φ(x) (Φ(x)ᵀ u) — the SGD regulariser composition (Eq. 3.3) in ONE
    dispatch. On the features backend Φ is materialised once and reused for
    both contractions; on Pallas the two-phase ``rff_pair`` kernel keeps the
    (2m, s) intermediate in VMEM for its whole lifetime (rff_matvec.py).
    Counts as TWO feature matvecs — the work of the Φᵀ/Φ pair it replaces.
    x:(n,d) ω:(m,d) u:(n,) or (n,s) → (n, s-like); differentiable throughout.
    """
    bk = resolve_feature_backend(backend)
    _check_precision(precision)
    FEATURE_TRACE_COUNTS[bk] += 2
    squeeze = u.ndim == 1
    u2 = u[:, None] if squeeze else u
    if bk == "pallas":
        interpret = (not _on_tpu()) if interpret is None else interpret
        n = x.shape[0]
        m_true = omega.shape[0]
        bm = _resolve_block(block, "rff", n, x.shape[1], precision)
        xp = _pad_rows(x, bm)
        om = _pad_rows(omega, 128)
        up = _pad_rows(u2, bm)
        raw = rff_pair_fused(bm, bool(interpret), precision, m_true, xp, om, up)
        # core normalisation is 1/m_pad (both Φ̃ factors); want signal/m_true
        out = raw[:n] * (signal * (om.shape[0] / m_true))
    else:
        feats = _materialised_features(x, omega, signal)  # built once, used twice
        out = _dot(feats, _dot(feats.T, u2, precision), precision)
    return out[:, 0] if squeeze else out


def tanimoto_rf_mv(
    x: jax.Array,
    table: jax.Array,
    w: jax.Array,
    *,
    signal=1.0,
    backend: str = "auto",
    block="auto",
    interpret=None,
    precision: str = "fp32",
) -> jax.Array:
    """Φ(x) @ w for Tanimoto random features (core/rff.py
    ``TanimotoFeatures``): x:(n,d) counts, table:(C,m,d) from
    ``tanimoto.rf_table``, w:(m,) or (m,s) → (n, s-like). ``pallas`` runs the
    fused ``tanimoto_rf_pallas`` kernel (signs built in VMEM); ``features``
    materialises the (n, m) sign matrix. ``precision="bf16"`` rounds ``w`` to
    bfloat16 before the contraction on both."""
    bk = resolve_feature_backend(backend)
    _check_precision(precision)
    FEATURE_TRACE_COUNTS[bk] += 1
    squeeze = w.ndim == 1
    w2 = w[:, None] if squeeze else w
    m = table.shape[1]
    if bk == "pallas":
        interpret = (not _on_tpu()) if interpret is None else interpret
        block = TANIMOTO_RF_BLOCK if block == "auto" else block
        n = x.shape[0]
        bm, bq = _tile(block, n), _tile(block, m)
        if precision == "bf16":
            w2 = w2.astype(jnp.bfloat16).astype(jnp.float32)
        pad_k = (-m) % bq
        if pad_k:  # padded hashes hold no finite entry: their signs decode to 0
            table = jnp.pad(table, ((0, 0), (0, pad_k), (0, 0)),
                            constant_values=ABSENT)
        out = tanimoto_rf_pallas(
            _pad_rows(x, bm), table, _pad_rows(w2, bq), block_m=bm, block_k=bq,
            interpret=bool(interpret),
        )[:n]
    else:
        out = _dot(rf_signs(x, table), w2, precision)
    out = out * jnp.sqrt(signal / m)
    return out[:, 0] if squeeze else out


def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128, interpret=None):
    """q: (b, s, hq, d), k/v: (b, s, hkv, d) with hq % hkv == 0 (GQA) → (b, s, hq, d)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    assert hq % hkv == 0
    group = hq // hkv
    # GQA: index kv heads per q head (gather, no broadcast materialisation pre-kernel)
    head_map = jnp.arange(hq) // group
    kq = k[:, :, head_map]  # (b, s, hq, d)
    vq = v[:, :, head_map]
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kf = kq.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    vf = vq.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    pad = (-s) % max(block_q, block_k)
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))
    out = flash_attention_pallas(
        qf, kf, vf, causal=causal, block_q=block_q, block_k=block_k,
        kv_len=(s if pad else None), interpret=interpret,
    )[:, :s]
    return out.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
