"""Fused random-Fourier-feature matvec Pallas kernels — forward, transpose, backward.

Forward: O = Φ(X) @ W with Φ(x) = sqrt(σ_f²/m)·[sin(xΩᵀ) | cos(xΩᵀ)] without
materialising the (n × 2m) feature matrix in HBM: each (bm × bf) projection tile is
built on the MXU, the sin/cos map applied in VREGs, and both halves contracted
against the corresponding W rows into a VMEM accumulator.

Transpose (``rff_t_matvec_pallas``): Φ(X)ᵀ @ U with the sin/cos halves accumulated
per feature tile — the SGD regulariser pullback (Eq. 3.3) and the ∂W rule of the
forward. Backward (``rff_bwd_pallas``): cotangents w.r.t. X and Ω via the identity

    ∂L/∂proj_ij = scale·(cos(proj_ij)·(ḡ_i·Wsin_j) − sin(proj_ij)·(ḡ_i·Wcos_j))
    ∂x_i = Σ_j (∂L/∂proj_ij)·ω_j        ∂ω_j = Σ_i (∂L/∂proj_ij)·x_i

— one kernel accumulating the cos/sin-weighted contractions per tile, the n×m
weight matrix never leaving VMEM (same design as the Gram backward kernel).

``rff_matvec_fused`` / ``rff_t_matvec_fused`` wrap the kernels in ``jax.custom_vjp``
so every pass — forward, transpose, and both input cotangents — runs through fused
tiles. The σ_f² signal scale is folded *outside* the cores (ops.py), like the Gram
kernel, so its gradient flows through ordinary autodiff; the cores carry only the
static √(1/m) normalisation.

Used by RFF prior-function evaluation (core/rff.py), the SGD regulariser term
(core/solvers/sgd.py), and every differentiated posterior-sample evaluation
(Thompson ascent) — the dominant non-Gram cost at the paper's scales.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gram_matvec import _mxu_dot


def _proj(x, om, precision):
    """The (bm, bf) projection tile x Ωᵀ — MXU operands cast per the tile
    precision, fp32 accumulation (see gram_matvec.TILE_PRECISIONS)."""
    return _mxu_dot(x, om, ((1,), (1,)), precision)


def _rff_kernel(
    x_ref, om_ref, wsin_ref, wcos_ref, o_ref, acc_ref, *, scale, nfeat, precision
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    proj = _proj(x_ref[...], om_ref[...], precision)  # (bm, bf)
    wsin, wcos = wsin_ref[...], wcos_ref[...]
    acc_ref[...] += scale * (
        _mxu_dot(jnp.sin(proj), wsin, ((1,), (0,)), precision)
        + _mxu_dot(jnp.cos(proj), wcos, ((1,), (0,)), precision)
    )

    @pl.when(j == nfeat - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("signal", "block_m", "block_f", "interpret", "precision")
)
def rff_matvec_pallas(
    x: jax.Array,
    omega: jax.Array,
    w: jax.Array,
    *,
    signal: float = 1.0,
    block_m: int = 256,
    block_f: int = 256,
    interpret: bool = False,
    precision: str = "fp32",
) -> jax.Array:
    """x:(n,d) ω:(m,d) w:(2m,s) (sin rows then cos rows) → (n,s). Pre-padded."""
    n, d = x.shape
    m = omega.shape[0]
    s = w.shape[1]
    assert n % block_m == 0 and m % block_f == 0
    assert w.shape[0] == 2 * m
    w_sin, w_cos = w[:m], w[m:]
    nfeat = m // block_f
    scale = (signal / m) ** 0.5
    return pl.pallas_call(
        functools.partial(
            _rff_kernel, scale=scale, nfeat=nfeat, precision=precision
        ),
        grid=(n // block_m, nfeat),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_f, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_f, s), lambda i, j: (j, 0)),
            pl.BlockSpec((block_f, s), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, s), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s), w.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, s), jnp.float32)],
        interpret=interpret,
    )(x, omega, w_sin, w_cos)


# ---------------------------------------------------------------------------
# Transposed fused matvec: Φ(X)ᵀ @ U, sin/cos halves accumulated per feature tile
# ---------------------------------------------------------------------------


def _rff_t_kernel(
    x_ref, om_ref, u_ref, osin_ref, ocos_ref, accs_ref, accc_ref,
    *, scale, nrows, precision
):
    i = pl.program_id(1)  # row tile (innermost: the feature-tile output stays
    # resident in VMEM across the full row accumulation)

    @pl.when(i == 0)
    def _init():
        accs_ref[...] = jnp.zeros_like(accs_ref)
        accc_ref[...] = jnp.zeros_like(accc_ref)

    proj = _proj(x_ref[...], om_ref[...], precision)  # (bm, bf)
    u = u_ref[...]  # (bm, s)
    # sin(proj)ᵀ @ u and cos(proj)ᵀ @ u (each (bf, s)) — contract the row
    # dimension on the MXU
    accs_ref[...] += scale * _mxu_dot(jnp.sin(proj), u, ((0,), (0,)), precision)
    accc_ref[...] += scale * _mxu_dot(jnp.cos(proj), u, ((0,), (0,)), precision)

    @pl.when(i == nrows - 1)
    def _flush():
        osin_ref[...] = accs_ref[...].astype(osin_ref.dtype)
        ocos_ref[...] = accc_ref[...].astype(ocos_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("signal", "block_m", "block_f", "interpret", "precision")
)
def rff_t_matvec_pallas(
    x: jax.Array,
    omega: jax.Array,
    u: jax.Array,
    *,
    signal: float = 1.0,
    block_m: int = 256,
    block_f: int = 256,
    interpret: bool = False,
    precision: str = "fp32",
) -> jax.Array:
    """Φ(x)ᵀ @ u: x:(n,d) ω:(m,d) u:(n,s) → (2m,s) (sin rows then cos rows).

    Pre-padded; padded u rows must be zero (they are — ops.py zero-pads).
    """
    n, d = x.shape
    m = omega.shape[0]
    s = u.shape[1]
    assert n % block_m == 0 and m % block_f == 0
    nrows = n // block_m
    scale = (signal / m) ** 0.5
    osin, ocos = pl.pallas_call(
        functools.partial(
            _rff_t_kernel, scale=scale, nrows=nrows, precision=precision
        ),
        grid=(m // block_f, nrows),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_f, d), lambda j, i: (j, 0)),
            pl.BlockSpec((block_m, s), lambda j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_f, s), lambda j, i: (j, 0)),
            pl.BlockSpec((block_f, s), lambda j, i: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, s), u.dtype),
            jax.ShapeDtypeStruct((m, s), u.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_f, s), jnp.float32),
            pltpu.VMEM((block_f, s), jnp.float32),
        ],
        interpret=interpret,
    )(x, omega, u)
    return jnp.concatenate([osin, ocos], axis=0)


# ---------------------------------------------------------------------------
# Backward kernel: input cotangents of the projection proj = R Cᵀ.
#
# The cotangent of L through proj is the (rows × cols) matrix
#     W = cos(proj) ⊙ (P₁ Q₁ᵀ) − sin(proj) ⊙ (P₂ Q₂ᵀ)
# and the output is  dR = scale · W @ C.  Instantiations:
#   * ∂x of Φ(x)w:  R=x, C=ω, P₁=P₂=ḡ, Q₁=w_sin, Q₂=w_cos;
#   * ∂ω of Φ(x)w:  R=ω, C=x, P₁=w_sin, P₂=w_cos, Q₁=Q₂=ḡ  (Wᵀ by symmetry);
#   * ∂x/∂ω of Φ(x)ᵀu: same with ḡ ↦ u and w_sin/w_cos ↦ the sin/cos halves of
#     the (2m, s) cotangent.
# W never exists in HBM — per tile it is three MXU contractions in VMEM.
# ---------------------------------------------------------------------------


def _rff_bwd_kernel(
    r_ref, c_ref, p1_ref, p2_ref, q1_ref, q2_ref, o_ref, acc_ref,
    *, scale, ncols, precision
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[...]  # (bn, d)
    proj = _proj(r_ref[...], c, precision)  # (bm, bn)
    # (bm, bn) = P₁_i · Q₁_j
    a = _mxu_dot(p1_ref[...], q1_ref[...], ((1,), (1,)), precision)
    b = _mxu_dot(p2_ref[...], q2_ref[...], ((1,), (1,)), precision)
    w = jnp.cos(proj) * a - jnp.sin(proj) * b
    acc_ref[...] += scale * _mxu_dot(w, c, ((1,), (0,)), precision)  # (bm, d)

    @pl.when(j == ncols - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_m", "block_n", "interpret", "precision")
)
def rff_bwd_pallas(
    r: jax.Array,
    c: jax.Array,
    p1: jax.Array,
    p2: jax.Array,
    q1: jax.Array,
    q2: jax.Array,
    *,
    scale: float,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    precision: str = "fp32",
) -> jax.Array:
    """dR = scale · (cos(RCᵀ)⊙(P₁Q₁ᵀ) − sin(RCᵀ)⊙(P₂Q₂ᵀ)) @ C — (rows, d)."""
    n, d = r.shape
    m = c.shape[0]
    assert n % block_m == 0 and m % block_n == 0, (n, m, block_m, block_n)
    ncols = m // block_n
    s = p1.shape[1]
    return pl.pallas_call(
        functools.partial(
            _rff_bwd_kernel, scale=scale, ncols=ncols, precision=precision
        ),
        grid=(n // block_m, ncols),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_m, s), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, s), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, s), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, s), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), r.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, d), jnp.float32)],
        interpret=interpret,
    )(r, c, p1, p2, q1, q2)


# ---------------------------------------------------------------------------
# Differentiable fused cores (unit signal; ops.py folds σ_f² outside so its
# gradient is plain autodiff, exactly like the Gram kernel).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def rff_matvec_fused(block_m, block_f, interpret, precision, x, omega, w):
    """Φ̃(x) @ w with Φ̃ = sqrt(1/m)·[sin(xΩᵀ) | cos(xΩᵀ)], differentiable w.r.t.
    x, ω and w — every pass a fused Pallas kernel. Operands pre-padded to block
    multiples (ops.py pads; padded w/u rows are zero so cotangents vanish there
    and the surrounding ``jnp.pad`` transposes slice them off)."""
    return rff_matvec_pallas(
        x, omega, w, signal=1.0, block_m=block_m, block_f=block_f,
        interpret=interpret, precision=precision,
    )


def _rff_matvec_fused_fwd(block_m, block_f, interpret, precision, x, omega, w):
    out = rff_matvec_fused(block_m, block_f, interpret, precision, x, omega, w)
    return out, (x, omega, w)


def _rff_matvec_fused_bwd(block_m, block_f, interpret, precision, res, g):
    x, omega, w = res
    m = omega.shape[0]
    scale = (1.0 / m) ** 0.5
    w_sin, w_cos = w[:m], w[m:]
    # ∂w = Φ̃ᵀ ḡ — the transposed fused matvec
    dw = rff_t_matvec_pallas(
        x, omega, g, signal=1.0, block_m=block_m, block_f=block_f,
        interpret=interpret, precision=precision,
    )
    # ∂x_i = Σ_j [cos(x_i·ω_j)(ḡ_i·ws_j) − sin(x_i·ω_j)(ḡ_i·wc_j)]·scale·ω_j
    dx = rff_bwd_pallas(
        x, omega, g, g, w_sin, w_cos, scale=scale, block_m=block_m,
        block_n=block_f, interpret=interpret, precision=precision,
    )
    # ∂ω_j — the same kernel with rows/cols and factor roles swapped (Wᵀ)
    dom = rff_bwd_pallas(
        omega, x, w_sin, w_cos, g, g, scale=scale, block_m=block_f,
        block_n=block_m, interpret=interpret, precision=precision,
    )
    return dx, dom, dw


rff_matvec_fused.defvjp(_rff_matvec_fused_fwd, _rff_matvec_fused_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def rff_t_matvec_fused(block_m, block_f, interpret, precision, x, omega, u):
    """Φ̃(x)ᵀ @ u (unit signal), differentiable w.r.t. x, ω and u."""
    return rff_t_matvec_pallas(
        x, omega, u, signal=1.0, block_m=block_m, block_f=block_f,
        interpret=interpret, precision=precision,
    )


def _rff_t_matvec_fused_fwd(block_m, block_f, interpret, precision, x, omega, u):
    out = rff_t_matvec_fused(block_m, block_f, interpret, precision, x, omega, u)
    return out, (x, omega, u)


def _rff_t_matvec_fused_bwd(block_m, block_f, interpret, precision, res, g):
    x, omega, u = res
    m = omega.shape[0]
    scale = (1.0 / m) ** 0.5
    g_sin, g_cos = g[:m], g[m:]  # (2m, s) cotangent split into halves
    # ∂u = Φ̃ ḡ — the forward fused matvec against the cotangent
    du = rff_matvec_pallas(
        x, omega, g, signal=1.0, block_m=block_m, block_f=block_f,
        interpret=interpret, precision=precision,
    )
    # L = Σ ḡ ⊙ (Φ̃ᵀu) = Σ u ⊙ (Φ̃ḡ): same projection cotangent with ḡ ↦ u
    dx = rff_bwd_pallas(
        x, omega, u, u, g_sin, g_cos, scale=scale, block_m=block_m,
        block_n=block_f, interpret=interpret, precision=precision,
    )
    dom = rff_bwd_pallas(
        omega, x, g_sin, g_cos, u, u, scale=scale, block_m=block_f,
        block_n=block_m, interpret=interpret, precision=precision,
    )
    return dx, dom, du


rff_t_matvec_fused.defvjp(_rff_t_matvec_fused_fwd, _rff_t_matvec_fused_bwd)


# ---------------------------------------------------------------------------
# Fused regulariser pair: Φ̃(x) (Φ̃(x)ᵀ u) in ONE launch — the SGD regulariser
# (Eq. 3.3) composition. The (2m, s) intermediate t = Φ̃ᵀu lives in a VMEM
# scratch spanning the whole (padded) feature axis and never touches HBM.
# ---------------------------------------------------------------------------


def _rff_pair_kernel(
    x_ref, om_ref, u_ref, o_ref, ts_ref, tc_ref, *, scale, nrows, m_true, precision
):
    """Two-phase grid (phase outermost, row tiles innermost).

    Phase 0 sweeps the row tiles, accumulating the sin/cos halves of
    t = Φ̃ᵀu into VMEM scratches covering the full feature axis; at the last
    row tile the rows belonging to feature padding are zeroed (padded ω rows
    are zero frequencies, whose cos features are identically 1 — their tᵀu
    accumulations are Σᵢuᵢ garbage, not zero). Phase 1 revisits the row tiles,
    rebuilds each projection tile and writes o_i = Φ̃_i t straight out; blocks
    flushed during phase 0 hold dead data that phase 1 fully overwrites.
    """
    ph, i = pl.program_id(0), pl.program_id(1)
    proj = _proj(x_ref[...], om_ref[...], precision)  # (bm, m_pad)
    sn, cs = jnp.sin(proj), jnp.cos(proj)

    @pl.when(ph == 0)
    def _accumulate():
        @pl.when(i == 0)
        def _init():
            ts_ref[...] = jnp.zeros_like(ts_ref)
            tc_ref[...] = jnp.zeros_like(tc_ref)

        u = u_ref[...]  # (bm, s)
        ts_ref[...] += scale * _mxu_dot(sn, u, ((0,), (0,)), precision)  # (m_pad, s)
        tc_ref[...] += scale * _mxu_dot(cs, u, ((0,), (0,)), precision)

        @pl.when(i == nrows - 1)
        def _finalize():
            rows = jax.lax.broadcasted_iota(jnp.int32, ts_ref.shape, 0)
            keep = rows < m_true
            ts_ref[...] = jnp.where(keep, ts_ref[...], 0.0)
            tc_ref[...] = jnp.where(keep, tc_ref[...], 0.0)

    @pl.when(ph == 1)
    def _apply():
        o_ref[...] = (
            scale * (
                _mxu_dot(sn, ts_ref[...], ((1,), (0,)), precision)
                + _mxu_dot(cs, tc_ref[...], ((1,), (0,)), precision)
            )
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "interpret", "precision", "m_true")
)
def rff_pair_pallas(
    x: jax.Array,
    omega: jax.Array,
    u: jax.Array,
    *,
    block_m: int = 256,
    interpret: bool = False,
    precision: str = "fp32",
    m_true: int | None = None,
) -> jax.Array:
    """Φ̃(x) (Φ̃(x)ᵀ u) with Φ̃ = sqrt(1/m)·[sin|cos] — x:(n,d) ω:(m,d) u:(n,s)
    → (n,s), pre-padded (n to block_m, m to 128 multiples; padded u rows zero).
    The feature axis is NOT tiled: both (m, s) halves of the intermediate stay
    resident in VMEM across the whole grid. ``m_true`` masks feature padding.
    """
    n, d = x.shape
    m = omega.shape[0]
    s = u.shape[1]
    assert n % block_m == 0 and m % 128 == 0, (n, m, block_m)
    m_true = m if m_true is None else m_true
    nrows = n // block_m
    scale = (1.0 / m) ** 0.5
    return pl.pallas_call(
        functools.partial(
            _rff_pair_kernel, scale=scale, nrows=nrows, m_true=m_true,
            precision=precision,
        ),
        grid=(2, nrows),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda ph, i: (i, 0)),
            pl.BlockSpec((m, d), lambda ph, i: (0, 0)),
            pl.BlockSpec((block_m, s), lambda ph, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, s), lambda ph, i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s), u.dtype),
        scratch_shapes=[
            pltpu.VMEM((m, s), jnp.float32),
            pltpu.VMEM((m, s), jnp.float32),
        ],
        interpret=interpret,
    )(x, omega, u)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def rff_pair_fused(block_m, interpret, precision, m_true, x, omega, u):
    """Differentiable fused pair Φ̃(Φ̃ᵀu) (unit signal; ops.py folds σ_f²·m_pad/m
    outside). The VJP composes existing fused primitives: du is the pair itself
    (the operator is symmetric PSD), and dx/dω run through ``rff_bwd_pallas``
    on the concatenated factors of dΦ̃ = ō tᵀ + u t̃ᵀ (t = Φ̃ᵀu, t̃ = Φ̃ᵀō)."""
    return rff_pair_pallas(
        x, omega, u, block_m=block_m, interpret=interpret, precision=precision,
        m_true=m_true,
    )


def _rff_pair_fused_fwd(block_m, interpret, precision, m_true, x, omega, u):
    out = rff_pair_fused(block_m, interpret, precision, m_true, x, omega, u)
    return out, (x, omega, u)


def _rff_pair_fused_bwd(block_m, interpret, precision, m_true, res, g):
    x, omega, u = res
    m = omega.shape[0]
    scale = (1.0 / m) ** 0.5
    kw = dict(block_m=block_m, block_f=min(128, m), interpret=interpret,
              precision=precision)
    # ∂u = Φ̃ Φ̃ᵀ ḡ — the pair itself (symmetric operator)
    du = rff_pair_fused(block_m, interpret, precision, m_true, x, omega, g)
    # t = Φ̃ᵀu and t̃ = Φ̃ᵀḡ, masked to the true feature rows exactly like the
    # forward masks its VMEM intermediate
    keep = (jnp.arange(m) < m_true)[:, None]
    t = rff_t_matvec_pallas(x, omega, u, signal=1.0, **kw)
    tt = rff_t_matvec_pallas(x, omega, g, signal=1.0, **kw)
    t_s, t_c = jnp.where(keep, t[:m], 0.0), jnp.where(keep, t[m:], 0.0)
    tt_s, tt_c = jnp.where(keep, tt[:m], 0.0), jnp.where(keep, tt[m:], 0.0)
    # dL/dΦ̃ = ḡ tᵀ + u t̃ᵀ — rank-2s factors for the projection cotangent
    pp = jnp.concatenate([g, u], axis=1)  # (n, 2s)
    q1 = jnp.concatenate([t_s, tt_s], axis=1)  # (m, 2s)
    q2 = jnp.concatenate([t_c, tt_c], axis=1)
    dx = rff_bwd_pallas(
        x, omega, pp, pp, q1, q2, scale=scale, block_m=block_m,
        block_n=min(128, m), interpret=interpret, precision=precision,
    )
    dom = rff_bwd_pallas(
        omega, x, q1, q2, pp, pp, scale=scale, block_m=min(128, m),
        block_n=block_m, interpret=interpret, precision=precision,
    )
    return dx, dom, du


rff_pair_fused.defvjp(_rff_pair_fused_fwd, _rff_pair_fused_bwd)
