"""Plain float32 reference for the Tanimoto (MinMax) GP of the ``dock`` cells.

Independent of the library under test: kernel entries are the definition
``T(x, z) = Σᵢ min(xᵢ, zᵢ) / Σᵢ max(xᵢ, zᵢ)`` computed as ``jnp.minimum`` and
``jnp.maximum`` sums (0 between two all-zero vectors), in blocks of rows and
columns so that no block larger than ``(rows, cols, d)`` is formed; every
contraction runs at ``precision=HIGHEST``, or with bfloat16 operands and
float32 accumulation when ``low=True`` (the control one precision below the
configuration's). Tanimoto random features are evaluated from the program's
draws by Ioffe's consistent weighted sampling as published (Ioffe 2010;
Tripp et al. 2023): per non-zero dim ``t = ⌊ln x / r + β⌋``,
``y = e^{r (t − β)}``, ``a = c / (y e^r)``, in float64 NumPy on the host and
rounded to float32; the hash is ``(i, t)`` at the first dim ``i`` of least
``a`` and the feature its sign under the program's salt, by the sign function
the program documents (murmur3's 32-bit finaliser of
``salt ^ (i · 0x9E3779B1) ^ (t · 0x85EBCA77)``, top bit), times
``sqrt(signal / m)``. The recipe depends on a row only through its count in
each dim, so it is evaluated once per (count, hash, dim) and each row reads
its own counts' values.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import _matmul, ks_scaled


def _pad_to(a, mult):
    pad = (-a.shape[0]) % mult
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))


def minmax(xr, xc):
    """T between the rows of ``xr`` (r, d) and of ``xc`` (c, d): (r, c)."""
    smin = jnp.sum(jnp.minimum(xr[:, None, :], xc[None, :, :]), axis=-1)
    smax = jnp.sum(jnp.maximum(xr[:, None, :], xc[None, :, :]), axis=-1)
    return jnp.where(smax > 0, smin / jnp.where(smax > 0, smax, 1.0), 0.0)


@partial(jax.jit, static_argnames=("rows", "cols", "low"))
def kernel_mv(x_rows, x, v, signal, *, rows=64, cols=1024, low=False):
    """``signal · T(x_rows, x) @ v``: (P, s), ``rows`` × ``cols`` pairs at a
    time."""
    p, s = x_rows.shape[0], v.shape[1]
    xr = _pad_to(x_rows, rows).reshape(-1, rows, x.shape[1])
    xc = _pad_to(x, cols).reshape(-1, cols, x.shape[1])
    vc = _pad_to(v, cols).reshape(-1, cols, s)

    def row_block(r):
        def col(acc, zv):
            z, vb = zv
            return acc + _matmul(minmax(r, z), vb, low), None

        acc, _ = jax.lax.scan(col, jnp.zeros((rows, s), jnp.float32), (xc, vc))
        return acc

    return signal * jax.lax.map(row_block, xr).reshape(-1, s)[:p]


def fit_rows(n, k, seed):
    """``min(n, k)`` seeded distinct training rows, sorted: (k,) int host."""
    rng = np.random.default_rng([seed, 23])
    return np.sort(rng.choice(n, min(n, k), replace=False))


def rel_residual_rows(x_rows, x, rows, rhs_rows, sol, signal, noise):
    """Per-column ``||rhs - (K + noise I) sol||`` over the training rows
    ``rows`` (``x_rows = x[rows]``, ``rhs_rows`` the right-hand sides there),
    over ``||rhs||`` on the same rows: the relative residual of the whole
    system, estimated on a sample of its rows."""
    res = rhs_rows - (kernel_mv(x_rows, x, sol, signal) + noise * sol[rows])
    return jnp.linalg.norm(res, axis=0) / jnp.linalg.norm(rhs_rows, axis=0)


# ------------------------------------------------------ Tanimoto random features


def hash_bit(salt, i, t):
    """The sign bit of hash (i, t) under a feature's salt (uint32 arithmetic)."""
    u32 = jnp.uint32
    h = salt.astype(u32) ^ (i.astype(u32) * u32(0x9E3779B1)) \
        ^ (t.astype(u32) * u32(0x85EBCA77))
    h = (h ^ (h >> 16)) * u32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * u32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return (h >> 31).astype(jnp.int32)


def ioffe_table(draws, max_count):
    """Ioffe's ``t`` (int32) and ``a`` (float32) for counts 1..``max_count``
    in every (hash, dim): two host arrays (max_count, m, d), the recipe in
    float64 rounded once."""
    r, c, beta = (np.asarray(draws[k], np.float64) for k in ("r", "c", "beta"))
    counts = np.arange(1, max_count + 1, dtype=np.float64)[:, None, None]
    t = np.floor(np.log(counts) / r + beta)
    y = np.exp(r * (t - beta))
    a = c / (y * np.exp(r))
    return t.astype(np.int32), a.astype(np.float32)


def hashes(x, t_tab, a_tab):
    """Hashes ``(i, t)`` of counts ``x`` broadcast against the (m, d) hashes of
    the tables ``ioffe_table`` gives (on the device): ``x`` (P, 1, d) gives each
    row under every hash, ``x`` (m, d) row k under hash k alone; and whether
    the row has any non-zero count. Three arrays of the broadcast shape less
    its last axis."""
    shape = jnp.broadcast_shapes(x.shape, a_tab.shape[1:])
    a = jnp.full(shape, jnp.inf, jnp.float32)
    t = jnp.zeros(shape, jnp.int32)
    for v in range(1, a_tab.shape[0] + 1):
        a = jnp.where(x == v, a_tab[v - 1], a)
        t = jnp.where(x == v, t_tab[v - 1], t)
    i = jnp.argmin(a, axis=-1)  # the first of equal values
    ti = jnp.take_along_axis(t, i[..., None], axis=-1)[..., 0]
    nonzero = jnp.broadcast_to(jnp.any(x > 0, axis=-1), i.shape)
    return i.astype(jnp.int32), ti, nonzero


@partial(jax.jit, static_argnames=("rows", "low"))
def rf_mv(x, tables, salt, w, signal, *, rows=8, low=False):
    """``Φ(x) @ w`` for the Tanimoto random features of the ``ioffe_table``
    ``tables`` (device arrays) and the (m,) ``salt``: (P, s)."""
    t_tab, a_tab = tables
    m = a_tab.shape[1]
    p, s = x.shape[0], w.shape[1]
    xb = _pad_to(x, rows).reshape(-1, rows, x.shape[1])

    def block(xr):
        i, t, nonzero = hashes(xr[:, None, :], t_tab, a_tab)
        bit = hash_bit(salt[None, :], i, t).astype(jnp.float32)
        phi = jnp.where(nonzero, 1.0 - 2.0 * bit, 0.0) * jnp.sqrt(signal / m)
        return _matmul(phi, w, low)

    return jax.lax.map(block, xb).reshape(-1, s)[:p]


def moments(xs, x, tables, salt, w, v, alpha, signal, *, low=False):
    """Posterior mean ``K(., x) v`` (P,) and the variance over the sample paths
    ``f_j = Φ w_j + K(., x) (v − alpha_j)`` (P,) at ``xs``, from one pass over
    ``x`` with the columns ``[v | v − alpha]``."""
    cols = jnp.concatenate([v[:, None], v[:, None] - alpha], axis=1)
    k = kernel_mv(xs, x, cols, signal, low=low)
    f = rf_mv(xs, tables, salt, w, signal, low=low)
    return k[:, 0], jnp.var(f + k[:, 1:], axis=1)


# ------------------------------------------------------------ random draws


def gamma2_ks(values) -> float:
    """``ks_scaled`` against Gamma(2, 1): F(v) = 1 − e^{−v} (1 + v)."""
    return ks_scaled(values, lambda v: 1.0 - np.exp(-v) * (1.0 + v))


def uniform_ks(values) -> float:
    """``ks_scaled`` against U(0, 1)."""
    return ks_scaled(values, lambda v: np.clip(v, 0.0, 1.0))


def edited_pairs(x, m, seed, max_count):
    """``m`` seeded pairs of a training molecule and an edit of it: each set
    count moves by ±1 with chance 0.3 (to 0 drops the bit, capped at
    ``max_count``), each unset bit turns on (count 1) with chance 0.01. The
    pairs share bits and differ in counts, the case in which the hash's
    collision law depends on the draws' laws. Host float32 arrays (m, d)."""
    rng = np.random.default_rng([seed, 21])
    a = np.asarray(x[np.sort(rng.choice(x.shape[0], m, replace=False))])
    step = rng.choice([-1.0, 1.0], size=a.shape) * (rng.random(a.shape) < 0.3)
    b = np.where(a > 0, np.clip(a + step, 0, max_count), 0.0)
    b = np.where((a == 0) & (rng.random(a.shape) < 0.01), 1.0, b)
    return a.astype(np.float32), b.astype(np.float32)


def collision_z(a, b, tables) -> float:
    """Hash collisions against their law on independent trials: pair k is
    hashed by feature k alone, so under the right draws the m collision
    indicators are independent Bernoulli(T(a_k, b_k)), and
    ``|Σ (hit − T)| / sqrt(Σ T (1 − T))`` is near |N(0, 1)|: it exceeds 4.42
    with chance 1e-5. ``tables``: ``ioffe_table`` of the draws."""
    ia, ta, _ = hashes(jnp.asarray(a), *tables)
    ib, tb, _ = hashes(jnp.asarray(b), *tables)
    hit = np.asarray((ia == ib) & (ta == tb), dtype=np.float64)
    a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
    t = np.minimum(a64, b64).sum(1) / np.maximum(a64, b64).sum(1)
    return float(abs(np.sum(hit - t)) / np.sqrt(np.sum(t * (1.0 - t))))
