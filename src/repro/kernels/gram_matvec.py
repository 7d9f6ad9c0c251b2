"""Fused Gram matvec Pallas TPU kernel (DESIGN.md §2) — forward AND backward.

Computes O = (σ_f²·k(X, Z) + jitter·I) @ V *without materialising K in HBM*:
each (bm × bn) tile of K is built in VMEM — the −2·x·zᵀ inner-product term on the MXU
(distance-as-matmul), the elementwise covariance map on the VPU — and immediately
contracted against the V tile into a VMEM accumulator. HBM traffic is O(n(d+s))
instead of O(n·m); arithmetic intensity rises from ~0.5 flop/byte (materialised K,
memory-bound) to ~bn·s/(d+s) — compute-bound for the solver's multi-RHS batches.

Grid: (rows n/bm, cols m/bn), cols innermost ("arbitrary") so the output tile stays
resident in VMEM across the full accumulation. Block shapes default to 256×256
(MXU-aligned multiples of 128; VMEM footprint ≈ bm·bn·4 + (bm+bn)·(d+s)·4 ≈ 0.5 MB).

For the symmetric operator K(x, x) @ v the forward can take a symmetric grid
instead (``symmetric=True``, chosen by ops.py from the shapes): each unordered
pair of blocks builds its tile once and contracts it both ways, K_ij v_j into
row block i and K_ijᵀ v_i into row block j, so nb(nb + 1)/2 tiles are built
instead of nb². Contributions land anywhere, so the whole accumulator stays
resident in VMEM (``symmetric_fits`` bounds it) and is written once.

``gram_matvec_fused`` wraps the kernel in a ``jax.custom_vjp`` so MLL gradients
(Lin et al. 2024) run end-to-end through fused tiles. The backward pass is itself
two fused Pallas contractions over the same tiling:

  * ∂/∂v  = K̃(z, x) @ ḡ               — the forward kernel, transposed operands;
  * ∂/∂x  = 2·(x ⊙ Σⱼ W − W @ z),  W_ij = κ'(d²_ij)·(ḡ_i·v_j)
    (and ∂/∂z by symmetry with x↔z, ḡ↔v swapped) — ``_gram_matvec_bwd_kernel``
    builds the κ' tile exactly like the forward builds the κ tile and contracts
    it against z on the MXU; the n×m matrix W never exists in HBM.

σ_f² and the jitter are *not* baked into the fused core: the callers in ops.py
apply ``signal * core(x/ℓ, z/ℓ, v) + jitter·v`` in plain JAX, so gradients w.r.t.
signal, noise, and lengthscale flow through ordinary autodiff around the VJP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SQRT3 = 1.7320508075688772
_SQRT5 = 2.23606797749979

# kernel kinds of this distance-as-matmul kernel; tanimoto has its own (tanimoto.py)
PALLAS_KINDS = ("se", "matern12", "matern32", "matern52")

#: Tile-operand precisions. ``"fp32"`` is the default everywhere; ``"bf16"``
#: casts the MXU contraction operands (points, kernel tiles, RHS tiles) to
#: bfloat16 on load while every accumulation stays fp32
#: (``preferred_element_type``) and the elementwise covariance map runs on fp32
#: squared distances. Halves tile memory traffic and doubles MXU throughput;
#: the stochastic solvers opt in (their estimators are minibatch-noise
#: dominated — see docs/kernels.md for accuracy guidance).
TILE_PRECISIONS = ("fp32", "bf16")


def _cast_mxu(a, precision: str):
    """Cast an MXU contraction operand per the tile precision (no-op on fp32)."""
    if precision == "bf16":
        return a.astype(jnp.bfloat16)
    if precision != "fp32":
        raise ValueError(
            f"unknown tile precision {precision!r}; expected one of {TILE_PRECISIONS}"
        )
    return a


def _mxu_dot(a, b, contract, precision: str):
    """``dot_general`` of two tiles over the ``contract`` dims, operands cast
    per the tile precision, accumulated in fp32. fp32 tiles contract at full
    fp32 (``HIGHEST``): Mosaic's default takes one bf16 pass for float32
    operands, and a solver's representer weights cancel so heavily in
    K @ v that the rounding of K's entries dominates the result."""
    return jax.lax.dot_general(
        _cast_mxu(a, precision), _cast_mxu(b, precision), (contract, ((), ())),
        precision=jax.lax.Precision.HIGHEST if precision == "fp32" else None,
        preferred_element_type=jnp.float32,
    )


def _pair_dists(x, z, precision: str):
    """Squared-distance tile via the matmul identity, honouring the precision.

    The inner product runs on (possibly bf16-cast) MXU operands with fp32
    accumulation; the norms are computed in fp32 *from the cast values* so the
    three terms of ||x−z||² = ||x||² + ||z||² − 2x·z see the same rounding and
    the cancellation stays consistent (d² ≥ 0 up to fp32 roundoff, as in fp32).
    """
    xc = _cast_mxu(x, precision)
    zc = _cast_mxu(z, precision)
    xf = xc.astype(jnp.float32)
    zf = zc.astype(jnp.float32)
    xn = jnp.sum(xf * xf, axis=-1)[:, None]
    zn = jnp.sum(zf * zf, axis=-1)[None, :]
    inner = _mxu_dot(xc, zc, ((1,), (1,)), precision)
    return xn + zn - 2.0 * inner


def _cov_map(d2, kind: str):
    if kind == "se":
        return jnp.exp(-0.5 * d2)
    r = jnp.sqrt(d2 + 1e-36)
    if kind == "matern12":
        return jnp.exp(-r)
    if kind == "matern32":
        s = _SQRT3 * r
        return (1.0 + s) * jnp.exp(-s)
    if kind == "matern52":
        s = _SQRT5 * r
        return (1.0 + s + s * s / 3.0) * jnp.exp(-s)
    raise ValueError(
        f"kernel kind {kind!r} has no fused Pallas covariance map; "
        f"supported kinds: {PALLAS_KINDS} — use the chunked backend instead"
    )


def _dcov_map(d2, kind: str):
    """dκ/d(d²) — same ε-regularised r as ``_cov_map`` so the VJP matches plain
    autodiff through the dense reference bit-for-bit in structure."""
    if kind == "se":
        return -0.5 * jnp.exp(-0.5 * d2)
    r = jnp.sqrt(d2 + 1e-36)
    if kind == "matern12":
        return -jnp.exp(-r) / (2.0 * r)
    if kind == "matern32":
        return -1.5 * jnp.exp(-_SQRT3 * r)
    if kind == "matern52":
        s = _SQRT5 * r
        return -(5.0 / 6.0) * (1.0 + s) * jnp.exp(-s)
    raise ValueError(
        f"kernel kind {kind!r} has no fused Pallas covariance derivative; "
        f"supported kinds: {PALLAS_KINDS} — use the chunked backend instead"
    )


def _gram_matvec_kernel(
    x_ref, z_ref, v_ref, o_ref, acc_ref, *, kind, signal, jitter, ncols, precision
):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v = v_ref[...]  # (bn, s)
    d2 = jnp.maximum(_pair_dists(x_ref[...], z_ref[...], precision), 0.0)
    k = signal * _cov_map(d2, kind)
    acc_ref[...] += _mxu_dot(k, v, ((1,), (0,)), precision)
    if jitter:
        # square blocking (bm == bn): diagonal tiles contribute jitter·I @ v = jitter·v
        @pl.when(i == j)
        def _diag():
            acc_ref[...] += jitter * v

    @pl.when(j == ncols - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


#: the symmetric grid's scoped VMEM: its whole (s, n) accumulator stays resident
_SYM_VMEM_LIMIT = 64 * 1024 * 1024
#: of which one grid step's tiles take at most this: about six (512, 512)
#: float32 temporaries (distances, covariances, their casts) and the
#: double-buffered point and RHS blocks
_SYM_TILE_VMEM = 16 * 1024 * 1024


def symmetric_fits(n: int, s: int, block: int) -> bool:
    """Whether ``K(x, x) @ v`` at padded ``n`` rows and ``s`` columns takes the
    symmetric grid at ``block``: it has more than one block (one block has no
    pair to share) and its (s, n) float32 accumulator, laid out in (8, 128)
    tiles and double-buffered, fits the VMEM budget beside one step's tiles.
    Shapes are static, so this is a trace-time decision."""
    acc = 2 * (-(-s // 8) * 8) * n * 4
    return (n % block == 0 and n > block
            and acc + _SYM_TILE_VMEM <= _SYM_VMEM_LIMIT)


def _sym_col(i, t, nb):
    """Column block of step (i, t) on the symmetric grid: (i + t) mod nb."""
    return jnp.where(i + t >= nb, i + t - nb, i + t)


def _gram_matvec_sym_kernel(
    xi_ref, xj_ref, v_hbm, vtj_ref, vti_ref, o_ref,
    *, kind, signal, jitter, nb, precision
):
    """Step (i, t) builds the tile K_ij of row block i against column block
    j = (i + t) mod nb once and contracts it both ways into the resident
    (nb, s, bn) accumulator, which holds (K v)ᵀ block by block: the RHS enters
    transposed, as (s, bn) tiles, so K_ij itself is never transposed. t = 0 is
    the diagonal tile, taken once; for even nb the offset nb/2 meets each pair
    from both ends, so only i < nb/2 takes it. ``v_hbm`` is not read."""
    i, t = pl.program_id(0), pl.program_id(1)
    j = _sym_col(i, t, nb)

    @pl.when((i == 0) & (t == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def tile():
        d2 = jnp.maximum(_pair_dists(xi_ref[...], xj_ref[...], precision), 0.0)
        return signal * _cov_map(d2, kind)

    @pl.when(t == 0)
    def _diag():
        # (K_ii v_i)ᵀ = v_iᵀ K_ii: the diagonal tile is symmetric
        o_ref[i] += _mxu_dot(vti_ref[...], tile(), ((1,), (0,)), precision)
        if jitter:
            o_ref[i] += jitter * vti_ref[...]

    @pl.when((t > 0) & ((2 * t != nb) | (i < t)))
    def _pair():
        k = tile()
        o_ref[i] += _mxu_dot(vtj_ref[...], k, ((1,), (1,)), precision)  # (K_ij v_j)ᵀ
        o_ref[j] += _mxu_dot(vti_ref[...], k, ((1,), (0,)), precision)  # (K_ijᵀ v_i)ᵀ


def _gram_matvec_sym(x, z, v, *, kind, signal, jitter, block, interpret, precision):
    """K(x, x) @ v on the symmetric grid (``z`` is ``x``): nb (nb + 1) / 2
    tiles built instead of nb², the output written once at the end."""
    n, d = x.shape
    s = v.shape[1]
    nb = n // block
    vt = v.T
    out = pl.pallas_call(
        functools.partial(
            _gram_matvec_sym_kernel, kind=kind, signal=signal, jitter=jitter,
            nb=nb, precision=precision,
        ),
        grid=(nb, nb // 2 + 1),
        in_specs=[
            pl.BlockSpec((block, d), lambda i, t: (i, 0)),
            pl.BlockSpec((block, d), lambda i, t: (_sym_col(i, t, nb), 0)),
            # v stays in HBM, unread: the operands open (x, z, v) as on the
            # square grid, which the benchmark's trace reader counts work from
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((s, block), lambda i, t: (0, _sym_col(i, t, nb))),
            pl.BlockSpec((s, block), lambda i, t: (0, i)),
        ],
        out_specs=pl.BlockSpec((nb, s, block), lambda i, t: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, s, block), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_SYM_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(x, z, v, vt, vt)
    return out.transpose(0, 2, 1).reshape(n, s).astype(v.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "kind", "signal", "jitter", "block_m", "block_n", "interpret", "precision",
        "symmetric",
    ),
)
def gram_matvec_pallas(
    x: jax.Array,
    z: jax.Array,
    v: jax.Array,
    *,
    kind: str = "se",
    signal: float = 1.0,
    jitter: float = 0.0,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    precision: str = "fp32",
    symmetric: bool = False,
) -> jax.Array:
    """x:(n,d) z:(m,d) v:(m,s) → (n,s). Inputs pre-scaled by 1/lengthscale.

    Caller must pad n,m to multiples of the block sizes (ops.py does this).
    ``symmetric`` promises ``z`` is ``x`` and takes the symmetric grid.
    """
    n, d = x.shape
    m, s = z.shape[0], v.shape[1]
    assert n % block_m == 0 and m % block_n == 0, (n, m, block_m, block_n)
    if symmetric:
        assert x.shape == z.shape and block_m == block_n, (x.shape, z.shape)
        return _gram_matvec_sym(
            x, z, v, kind=kind, signal=signal, jitter=jitter, block=block_m,
            interpret=interpret, precision=precision,
        )
    if jitter:
        assert block_m == block_n and n == m, "jitter requires square blocking"
    ncols = m // block_n
    grid = (n // block_m, ncols)
    return pl.pallas_call(
        functools.partial(
            _gram_matvec_kernel,
            kind=kind,
            signal=signal,
            jitter=jitter,
            ncols=ncols,
            precision=precision,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, s), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, s), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s), v.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, s), jnp.float32)],
        interpret=interpret,
    )(x, z, v)


def _gram_matvec_bwd_kernel(
    x_ref, z_ref, rowv_ref, colv_ref, o_ref, acc_wz_ref, acc_ws_ref,
    *, kind, ncols, precision
):
    """Accumulates dx_i = 2 Σ_j W_ij (x_i − z_j) with W_ij = κ'(d²_ij)·(rowv_i·colv_j).

    Per tile: the κ' block on the VPU (same distance-as-matmul trick as the
    forward), the rank-s outer product rowv·colvᵀ on the MXU, then W @ z on the
    MXU — three fused contractions, W never leaves VMEM.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_wz_ref[...] = jnp.zeros_like(acc_wz_ref)
        acc_ws_ref[...] = jnp.zeros_like(acc_ws_ref)

    x = x_ref[...]  # (bm, d)
    z = z_ref[...]  # (bn, d)
    raw = _pair_dists(x, z, precision)
    kp = _dcov_map(jnp.maximum(raw, 0.0), kind)
    if kind == "matern12":
        # Matérn-1/2 is non-differentiable at coincident points (κ' ~ 1/r → ∞);
        # plain autodiff through sqrt(d²+ε) yields unbounded garbage on the
        # diagonal of symmetric Grams. Adopt the symmetric-limit convention: the
        # pair contributes nothing at exactly zero distance.
        mask = (raw > 0.0).astype(jnp.float32)
    else:
        # replicate autodiff's max(·, 0) clamp convention: 1 above, ½ at, 0 below
        mask = jnp.where(raw > 0.0, 1.0, jnp.where(raw == 0.0, 0.5, 0.0))
    # (bm, bn) = ḡ_i · v_j
    gv = _mxu_dot(rowv_ref[...], colv_ref[...], ((1,), (1,)), precision)
    w = kp * mask * gv
    acc_ws_ref[...] += jnp.sum(w, axis=1, keepdims=True)
    acc_wz_ref[...] += _mxu_dot(w, z, ((1,), (0,)), precision)

    @pl.when(j == ncols - 1)
    def _flush():
        o_ref[...] = (2.0 * (x * acc_ws_ref[...] - acc_wz_ref[...])).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("kind", "block_m", "block_n", "interpret", "precision")
)
def gram_matvec_bwd_pallas(
    x: jax.Array,
    z: jax.Array,
    rowv: jax.Array,
    colv: jax.Array,
    *,
    kind: str = "se",
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    precision: str = "fp32",
) -> jax.Array:
    """Input cotangent dx (n,d) of v ↦ K̃(x,z)@v at rowv=ḡ (n,s), colv=v (m,s).

    With (x,z,rowv,colv) = (z,x,v,ḡ) the same kernel yields dz by symmetry.
    """
    n, d = x.shape
    m = z.shape[0]
    assert n % block_m == 0 and m % block_n == 0, (n, m, block_m, block_n)
    ncols = m // block_n
    return pl.pallas_call(
        functools.partial(
            _gram_matvec_bwd_kernel, kind=kind, ncols=ncols, precision=precision
        ),
        grid=(n // block_m, ncols),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_m, rowv.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, colv.shape[1]), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, d), jnp.float32),
            pltpu.VMEM((block_m, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, z, rowv, colv)


# ---------------------------------------------------------------------------
# Differentiable fused core: K̃(x, z) @ v with a custom VJP (signal/jitter-free;
# ops.py scales by σ_f² and adds jitter·v outside, in plain JAX).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def gram_matvec_fused(kind, block_m, block_n, interpret, precision, symmetric,
                      x, z, v):
    """K̃(x, z) @ v (unit signal, no jitter), differentiable w.r.t. x, z, v.

    x:(n,d) z:(m,d) v:(m,s), all pre-scaled by 1/lengthscale and pre-padded to
    block multiples. Every pass — forward and both backward contractions — runs
    through fused Pallas tiles; the n×m Gram block never exists in HBM.
    ``symmetric`` (z is x) runs the forward on the symmetric grid; the
    backward passes are the same either way.
    """
    return gram_matvec_pallas(
        x, z, v, kind=kind, signal=1.0, jitter=0.0,
        block_m=block_m, block_n=block_n, interpret=interpret,
        precision=precision, symmetric=symmetric,
    )


def _gram_matvec_fused_fwd(kind, block_m, block_n, interpret, precision, symmetric,
                           x, z, v):
    out = gram_matvec_fused(
        kind, block_m, block_n, interpret, precision, symmetric, x, z, v
    )
    return out, (x, z, v)


def _gram_matvec_fused_bwd(kind, block_m, block_n, interpret, precision, symmetric,
                           res, g):
    x, z, v = res
    kw = dict(kind=kind, interpret=interpret, precision=precision)
    # ∂v: the transposed fused matvec K̃(z, x) @ ḡ — note the swapped block sizes
    dv = gram_matvec_pallas(
        z, x, g, signal=1.0, jitter=0.0,
        block_m=block_n, block_n=block_m, **kw,
    )
    dx = gram_matvec_bwd_pallas(x, z, g, v, block_m=block_m, block_n=block_n, **kw)
    dz = gram_matvec_bwd_pallas(z, x, v, g, block_m=block_n, block_n=block_m, **kw)
    return dx, dz, dv


gram_matvec_fused.defvjp(_gram_matvec_fused_fwd, _gram_matvec_fused_bwd)


# ---------------------------------------------------------------------------
# Fused stochastic pair step: err = K̃(xi, x) @ look − b and g = K̃(xi, x)ᵀ @ err
# in ONE kernel launch — the SGD fit-term primitive (Lin et al. 2024 run the
# row-panel forward and its pullback as separate passes; fusing them keeps the
# (p, s) error block in VMEM between the two contractions).
# ---------------------------------------------------------------------------


def _gram_rows_pair_kernel(
    xi_ref, x_ref, look_ref, b_ref, err_ref, o_ref, acc_ref,
    *, kind, ncols, p_true, precision
):
    """Two-phase grid (phase outermost, column tiles innermost).

    Phase 0 sweeps the column tiles of the panel A = K̃(xi, x), accumulating
    err = A @ look − b into a VMEM scratch that persists across the whole grid;
    at the last column tile the rows belonging to row padding are zeroed
    (padded xi rows are all-zero points, whose kernel values k(0, ·) ≠ 0 would
    otherwise leak garbage into phase 1) and the finished error block is
    emitted. Phase 1 revisits the same column tiles, rebuilding each A tile and
    writing g_j = A_jᵀ @ err straight to the j-th output block — err never
    round-trips HBM, and the launch (plus its operand DMAs) happens once
    instead of twice. Output blocks mapped during phase 0 flush whatever the
    buffer holds, which is dead: phase 1 fully overwrites every block.
    """
    ph, j = pl.program_id(0), pl.program_id(1)
    d2 = jnp.maximum(_pair_dists(xi_ref[...], x_ref[...], precision), 0.0)
    k = _cov_map(d2, kind)  # (bp, bn) panel tile

    @pl.when(ph == 0)
    def _accumulate():
        @pl.when(j == 0)
        def _init():
            acc_ref[...] = -b_ref[...].astype(jnp.float32)

        acc_ref[...] += _mxu_dot(k, look_ref[...], ((1,), (0,)), precision)

        @pl.when(j == ncols - 1)
        def _finalize():
            rows = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
            acc_ref[...] = jnp.where(rows < p_true, acc_ref[...], 0.0)
            err_ref[...] = acc_ref[...].astype(err_ref.dtype)

    @pl.when(ph == 1)
    def _contract():
        o_ref[...] = _mxu_dot(
            k, acc_ref[...], ((0,), (0,)), precision
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("kind", "block_n", "interpret", "precision", "p_true")
)
def gram_rows_pair_pallas(
    xi: jax.Array,
    x: jax.Array,
    look: jax.Array,
    b: jax.Array,
    *,
    kind: str = "se",
    block_n: int = 256,
    interpret: bool = False,
    precision: str = "fp32",
    p_true: int | None = None,
) -> tuple:
    """err = K̃(xi, x) @ look − b, g = K̃(xi, x)ᵀ @ err — one fused launch.

    xi:(p,d) x:(n,d) look:(n,s) b:(p,s) → (err:(p,s), g:(n,s)). Unit signal;
    inputs pre-scaled by 1/lengthscale, n pre-padded to a block_n multiple and
    p to a 128 multiple (the whole row block is one tile). ``p_true`` masks the
    padded error rows (default: no padding).
    """
    p, d = xi.shape
    n, s = look.shape
    assert n % block_n == 0 and p % 128 == 0, (n, p, block_n)
    assert b.shape == (p, s)
    p_true = p if p_true is None else p_true
    ncols = n // block_n
    return pl.pallas_call(
        functools.partial(
            _gram_rows_pair_kernel,
            kind=kind, ncols=ncols, p_true=p_true, precision=precision,
        ),
        grid=(2, ncols),
        in_specs=[
            pl.BlockSpec((p, d), lambda ph, j: (0, 0)),
            pl.BlockSpec((block_n, d), lambda ph, j: (j, 0)),
            pl.BlockSpec((block_n, s), lambda ph, j: (j, 0)),
            pl.BlockSpec((p, s), lambda ph, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((p, s), lambda ph, j: (0, 0)),
            pl.BlockSpec((block_n, s), lambda ph, j: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, s), look.dtype),
            jax.ShapeDtypeStruct((n, s), look.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((p, s), jnp.float32)],
        interpret=interpret,
    )(xi, x, look, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def gram_rows_pair_fused(kind, block_n, interpret, precision, p_true, xi, x, look, b):
    """Differentiable fused pair step (unit signal — ops.py folds σ_f² outside).

    Returns (err, g) = (A@look − b, Aᵀ@err) for A = K̃(xi, x). The VJP is a
    composition of the existing fused primitives: with ê = ē + A ḡ (masked to
    the true rows), dlook = Aᵀ ê, db = −ê, and dA = ê lookᵀ + err ḡᵀ — a
    rank-2s outer-product pair handled by ``gram_matvec_bwd_pallas`` on the
    concatenated factors. No pass materialises the panel in HBM.
    """
    return gram_rows_pair_pallas(
        xi, x, look, b, kind=kind, block_n=block_n, interpret=interpret,
        precision=precision, p_true=p_true,
    )


def _gram_rows_pair_fused_fwd(kind, block_n, interpret, precision, p_true,
                              xi, x, look, b):
    err, g = gram_rows_pair_fused(
        kind, block_n, interpret, precision, p_true, xi, x, look, b
    )
    return (err, g), (xi, x, look, b, err)


def _gram_rows_pair_fused_bwd(kind, block_n, interpret, precision, p_true, res, cts):
    xi, x, look, b = res[:4]
    err = res[4]
    e_bar, g_bar = cts
    p = xi.shape[0]
    kw = dict(kind=kind, interpret=interpret, precision=precision)
    # ê = ē + A ḡ — cotangent of err through BOTH outputs (g = Aᵀ err depends
    # on err); masked exactly like the forward masks the padded error rows
    ag = gram_matvec_pallas(
        xi, x, g_bar, signal=1.0, jitter=0.0,
        block_m=p, block_n=block_n, **kw,
    )
    rows = jnp.arange(p)[:, None]
    ehat = jnp.where(rows < p_true, e_bar + ag, 0.0)
    dlook = gram_matvec_pallas(
        x, xi, ehat, signal=1.0, jitter=0.0,
        block_m=block_n, block_n=p, **kw,
    )
    db = -ehat
    # dA = ê lookᵀ + err ḡᵀ: stack the rank-s factors and reuse the Gram
    # backward kernel on the (·, 2s) concatenations
    rowv = jnp.concatenate([ehat, err], axis=1)  # (p, 2s)
    colv = jnp.concatenate([look, g_bar], axis=1)  # (n, 2s)
    dxi = gram_matvec_bwd_pallas(xi, x, rowv, colv, block_m=p, block_n=block_n, **kw)
    dx = gram_matvec_bwd_pallas(x, xi, colv, rowv, block_m=block_n, block_n=p, **kw)
    return dxi, dx, dlook, db


gram_rows_pair_fused.defvjp(_gram_rows_pair_fused_fwd, _gram_rows_pair_fused_bwd)
