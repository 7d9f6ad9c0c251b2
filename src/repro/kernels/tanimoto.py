"""Fused Pallas TPU kernels of the Tanimoto (MinMax) kernel on count vectors.

``tanimoto_matvec_pallas``: ``O = T(X, Z) @ V`` with
``T(x, z) = Σᵢ min(xᵢ, zᵢ) / Σᵢ max(xᵢ, zᵢ)`` over non-negative integer counts,
without materialising T in HBM. The Σmin of a (bm × bn) tile runs on the MXU
through *count planes*: for integer counts ``0 ≤ a, b ≤ C``,
``min(a, b) = Σ_{t=1..C} [a ≥ t]·[b ≥ t]``, so ``Σᵢ min(xᵢ, zᵢ)`` is the sum of C
products of 0/1 planes. Those products are exact with bfloat16 operands and
float32 accumulation (every partial sum is an integer ≤ C·d < 2²⁴), whatever the
tile precision. ``Σmax = |x|₁ + |z|₁ − Σmin``; the division and the contraction
with the V tile follow, the latter at the tile precision (``fp32`` contracts at
``HIGHEST``, ``bf16`` takes bfloat16 operands). The work of the general MinMax
(2·d min-and-add operations per entry, no matmul form) thus becomes C·d MXU
multiply-adds per entry.

The route is exact only for integer counts 0..``MAX_COUNT``: a tile holding
anything else (a larger count, a negative or non-integer value) gives NaN in every
output it touches, never an approximation — an invalid ``x`` row poisons its own
output row, an invalid ``z`` row every output. Callers check concrete inputs on
the host first (:func:`check_counts`), so a refusal there is a ``ValueError``.

``tanimoto_rf_pallas``: ``Φ(X) @ W`` for Tanimoto random features (Tripp et al.
2023; core/rff.py): feature k of a count vector x is the random sign of its
consistent weighted-sampling hash ``argmin_i a_{k,i}(x_i)`` over the non-zero
dims (Ioffe 2010). For counts 1..C Ioffe's ``a`` takes C values per (k, i),
tabulated once per feature map by the recipe itself (:func:`rf_table`) and
carried with the hash's sign; the kernel selects each row's table entries by
count, takes the least ``a`` over dims and the sign of its first dim (VPU work,
a few selects and three reductions per (k, i)) and contracts those ±1 signs with
the W tile, so the (n × m) feature matrix never exists in HBM. Rows with invalid
counts give NaN, as above.

Neither kernel has a backward pass with respect to the points: differentiating
through ``x`` or ``z`` raises. The cotangent of V is the transposed matvec.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gram_matvec import _mxu_dot

#: a (bm, d) and a (bn, d) float32 tile, double-buffered, plus the planes and
#: the (bm, bn) tile exceed the 16 MiB default scoped VMEM at 512 × 1024
_VMEM_LIMIT = 64 * 1024 * 1024

#: the count-plane depth: counts 0..MAX_COUNT are taken exactly, larger ones
#: refused
MAX_COUNT = 4

#: table value of a zero count: above every finite ``a``
ABSENT = 3.0e38


def check_counts(x) -> None:
    """Refuse ``x`` unless it holds integer counts 0..``MAX_COUNT``: the inputs
    on which the fused kernels are exact. A host check for inputs that enter
    from outside the program."""
    a = np.asarray(x)
    bad = a != np.clip(np.floor(a), 0, MAX_COUNT)
    if bad.any():
        raise ValueError(
            f"tanimoto inputs must be integer counts 0..{MAX_COUNT} (the fused "
            f"kernels' count planes); found {np.unique(a[bad])[:4].tolist()} in "
            f"{int(bad.any(axis=-1).sum())} row(s)"
        )


def _valid(a):
    """Whether each entry is an integer count 0..MAX_COUNT."""
    return a == jnp.clip(jnp.floor(a), 0.0, float(MAX_COUNT))


def _checked_l1(a):
    """Row sums (rows, 1) of a count tile; NaN for a row holding anything but
    integer counts 0..MAX_COUNT."""
    ok = _valid(a)
    return jnp.sum(jnp.where(ok, a, jnp.nan), axis=-1, keepdims=True)


def _tanimoto_tile(x, z):
    """T(x, z) for an (bm, d) and a (bn, d) count tile: (bm, bn) float32."""
    xn = _checked_l1(x)  # (bm, 1)
    zn = _checked_l1(z)[:, 0][None, :]  # (1, bn)
    smin = None
    for t in range(1, MAX_COUNT + 1):
        prod = jax.lax.dot_general(
            (x >= t).astype(jnp.bfloat16), (z >= t).astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        smin = prod if smin is None else smin + prod
    smax = xn + zn - smin
    # two all-zero rows: Σmin = Σmax = 0, T = 0; NaN survives the guard
    return smin / (smax + (smax == 0.0).astype(jnp.float32))


def _tanimoto_matvec_kernel(x_ref, z_ref, v_ref, o_ref, acc_ref, *, ncols,
                            precision):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = _tanimoto_tile(x_ref[...], z_ref[...])
    acc_ref[...] += _mxu_dot(t, v_ref[...], ((1,), (0,)), precision)

    @pl.when(j == ncols - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "interpret", "precision"),
)
def tanimoto_matvec_pallas(
    x: jax.Array,
    z: jax.Array,
    v: jax.Array,
    *,
    block_m: int,
    block_n: int,
    interpret: bool = False,
    precision: str = "fp32",
) -> jax.Array:
    """x:(n,d) z:(m,d) count tiles, v:(m,s) → T(x, z) @ v: (n,s), unit signal.

    The caller pads n and m to multiples of the block sizes (ops.py does)."""
    n, d = x.shape
    m, s = z.shape[0], v.shape[1]
    assert n % block_m == 0 and m % block_n == 0, (n, m, block_m, block_n)
    ncols = m // block_n
    return pl.pallas_call(
        functools.partial(_tanimoto_matvec_kernel, ncols=ncols,
                          precision=precision),
        grid=(n // block_m, ncols),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, s), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, s), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s), v.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, s), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, z, v)


def _refuse_point_grads(x, z):
    if x.perturbed or z.perturbed:
        raise ValueError(
            "the fused Tanimoto matvec has no derivative with respect to the "
            "count vectors; differentiate only the signal, the noise or v"
        )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def tanimoto_matvec_fused(block_m, block_n, interpret, precision, x, z, v):
    """T(x, z) @ v (unit signal), differentiable with respect to v only.

    x:(n,d) z:(m,d) v:(m,s), pre-padded to block multiples."""
    return tanimoto_matvec_pallas(
        x, z, v, block_m=block_m, block_n=block_n, interpret=interpret,
        precision=precision,
    )


def _tanimoto_fwd(block_m, block_n, interpret, precision, x, z, v):
    _refuse_point_grads(x, z)
    out = tanimoto_matvec_fused(block_m, block_n, interpret, precision,
                                x.value, z.value, v.value)
    return out, (x.value, z.value)


def _tanimoto_bwd(block_m, block_n, interpret, precision, res, g):
    x, z = res
    dv = tanimoto_matvec_pallas(
        z, x, g, block_m=block_n, block_n=block_m, interpret=interpret,
        precision=precision,
    )
    return jnp.zeros_like(x), jnp.zeros_like(z), dv


tanimoto_matvec_fused.defvjp(_tanimoto_fwd, _tanimoto_bwd, symbolic_zeros=True)


# ---------------------------------------------------------------------------
# Tanimoto random features: Φ(x) @ w with the hash signs built in VMEM.
# ---------------------------------------------------------------------------


def hash_bit(salt, i, t):
    """The random sign bit of hash ``(i, t)`` under a feature's 32-bit ``salt``:
    the top bit of murmur3's 32-bit finaliser of
    ``salt ^ (i · 0x9E3779B1) ^ (t · 0x85EBCA77)``, all uint32 arithmetic
    (wrapping). Distinct hashes get independent-looking bits, equal hashes
    equal ones — the sign function of Tanimoto random features."""
    u32 = jnp.uint32
    h = salt.astype(u32) ^ (i.astype(u32) * u32(0x9E3779B1)) \
        ^ (t.astype(u32) * u32(0x85EBCA77))
    h = (h ^ (h >> 16)) * u32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * u32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return (h >> 31).astype(jnp.int32)


def _ioffe_host(r, c, beta):
    """Ioffe's ``(t, a)`` for counts 1..C in every (hash, dim), (C, m, d): the
    recipe as it states it, ``t = ⌊ln v / r + β⌋``, ``y = e^{r (t − β)}``,
    ``a = c / (y e^r)``, evaluated in float64 NumPy on the host (no compiler
    rewrites it) and rounded to int32 and float32."""
    r, c, beta = (np.asarray(a, np.float64) for a in (r, c, beta))
    v = np.arange(1, MAX_COUNT + 1, dtype=np.float64)[:, None, None]
    t = np.floor(np.log(v) / r + beta)
    y = np.exp(r * (t - beta))
    a = c / (y * np.exp(r))
    return t.astype(np.int32), a.astype(np.float32)


def rf_table(r, c, beta, salt):
    """(C, m, d) kernel table of Ioffe's consistent weighted sampling for
    counts 1..C (``C = MAX_COUNT``), from the draws r, c ~ Gamma(2, 1),
    β ~ U(0, 1) of shape (m, d) and the (m,) salts: entry ``[v-1, k, i]`` is
    ``±a`` for count v in dim i of hash k, with Ioffe's ``a`` evaluated in
    float64 and rounded to float32 (:func:`_ioffe_host`, a host callback, so
    the table is the same under ``jit``) and the sign
    ``1 − 2 · hash_bit(salt_k, i, t)``. The least ``|entry|`` over a row's
    non-zero dims, the first dim of equal ones, is the row's hash: any float64
    evaluation of the recipe rounded to float32 orders the dims the same way."""
    shape = (MAX_COUNT,) + r.shape
    t, a = jax.pure_callback(
        _ioffe_host,
        (jax.ShapeDtypeStruct(shape, jnp.int32), jax.ShapeDtypeStruct(shape, jnp.float32)),
        r, c, beta,
    )
    i = jnp.arange(r.shape[1], dtype=jnp.int32)[None, None, :]
    bit = hash_bit(salt[None, :, None], i, t)
    return jnp.where(bit == 1, -a, a)


def _first_least(sel):
    """The sign (±1) of the entry of least magnitude along the last axis, the
    first of equal ones, and that magnitude (``ABSENT`` where nothing is
    present); both keep the axis with length 1."""
    mag = jnp.abs(sel)
    low = jnp.min(mag, axis=-1, keepdims=True)
    idx = jax.lax.broadcasted_iota(jnp.int32, sel.shape, sel.ndim - 1)
    idx = idx.astype(jnp.float32)
    first = jnp.min(jnp.where(mag == low, idx, float(sel.shape[-1])), axis=-1,
                    keepdims=True)
    at = jnp.sum(jnp.where(idx == first, sel, 0.0), axis=-1, keepdims=True)
    return jnp.where(at < 0.0, -1.0, 1.0), low


def rf_signs(x, table, chunk_elems: int = 2 ** 24):
    """The (n, m) feature signs the kernel builds, materialised: ±1 per hash,
    0 for an all-zero row, NaN for a row with invalid counts. Rows go in chunks
    of ``chunk_elems / (m d)``, so no (n, m, d) block is held."""
    cnt, m, d = table.shape
    n = x.shape[0]
    rows = max(1, min(n, chunk_elems // (m * d)))

    def block(xc):
        sel = jnp.full((xc.shape[0], m, d), ABSENT, jnp.float32)
        for v in range(1, cnt + 1):
            sel = jnp.where(xc[:, None, :] == v, table[v - 1][None], sel)
        sign, low = _first_least(sel)
        phi = jnp.where(low < ABSENT, sign, 0.0)[..., 0]
        ok = jnp.all(_valid(xc), axis=-1)
        return jnp.where(ok[:, None], phi, jnp.nan)

    pad = (-n) % rows
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, rows, d)
    return jax.lax.map(block, xp).reshape(-1, m)[:n]


def _tanimoto_rf_kernel(x_ref, tab_ref, w_ref, o_ref, *, rows):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...]  # (bk, s)

    def row(r, carry):
        xr = x_ref[pl.ds(r, 1), :]  # (1, d) counts
        sel = jnp.full(tab_ref.shape[1:], ABSENT, jnp.float32)  # (bk, d)
        for v in range(1, MAX_COUNT + 1):
            sel = jnp.where(xr == float(v), tab_ref[v - 1], sel)
        sign, low = _first_least(sel)  # (bk, 1): the winning hash's sign
        phi = jnp.where(low < ABSENT, sign, 0.0)
        poison = jnp.sum(jnp.where(_valid(xr), 0.0, jnp.nan), axis=1, keepdims=True)
        o_ref[pl.ds(r, 1), :] += jnp.sum(phi * w, axis=0, keepdims=True) + poison
        return carry

    jax.lax.fori_loop(0, rows, row, 0)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "interpret"))
def tanimoto_rf_pallas(
    x: jax.Array,
    table: jax.Array,
    w: jax.Array,
    *,
    block_m: int,
    block_k: int,
    interpret: bool = False,
) -> jax.Array:
    """x:(n,d) counts, table:(C,m,d) signed Ioffe values, w:(m,s) →
    Σ_k sign_k(x) w_k: (n,s), unscaled. n and m pre-padded to the blocks."""
    n, d = x.shape
    m, s = w.shape
    assert table.shape == (MAX_COUNT, m, d), (table.shape, MAX_COUNT, m, d)
    assert n % block_m == 0 and m % block_k == 0, (n, m, block_m, block_k)
    return pl.pallas_call(
        functools.partial(_tanimoto_rf_kernel, rows=block_m),
        grid=(n // block_m, m // block_k),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((MAX_COUNT, block_k, d), lambda i, j: (0, j, 0)),
            pl.BlockSpec((block_k, s), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, s), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, table, w)
