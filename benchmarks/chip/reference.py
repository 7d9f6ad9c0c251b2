"""Plain float32 reference for the GP systems the cells solve and serve.

Independent of the library under test: kernel entries come from explicit
coordinate differences (no distance-as-matmul identity), every contraction
runs at ``precision=HIGHEST``, and rows are processed in chunks so that no
block larger than ``(chunk, n)`` is held. ``low=True`` makes the contractions
take bfloat16 operands (float32 accumulation): the reference one precision
below the configuration's, which is the control that the comparison of
``correct`` has to fail. Random Fourier features are the
paired sin/cos map written out from its definition.

Thompson acquisitions are re-run from their definition (§3.3.2 of the
paper): the multi-start candidates drawn from the request's seed, the top
candidates of each sample path, then Adam ascent on the path with its
gradient written out by hand. The program's random draws (frequencies, prior
weights, noise) are held against their distributions by Kolmogorov–Smirnov
statistics.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: degrees of freedom 2 nu of the Student-t spectral density of each family
SPECTRAL_DOF = {"matern32": 3.0}
#: share of the Thompson candidates drawn near incumbents (the rest uniform)
EXPLOIT_FRAC = 0.9


def _matmul(a, b, low=False):
    if low:
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def matern32(d2, signal):
    s = math.sqrt(3.0) * jnp.sqrt(d2)
    return signal * (1.0 + s) * jnp.exp(-s)


#: covariance map of each kernel family, from squared scaled distances
KERNELS = {"matern32": matern32}


def kernel_mv(kind, x_rows, x, v, lengthscale, signal, *, chunk=64, low=False):
    """``K(x_rows, x) @ v`` through the plain kernel map, ``chunk`` rows at a
    time."""
    cov = KERNELS[kind]
    xs = x / lengthscale
    rows = x_rows / lengthscale
    pad = (-rows.shape[0]) % chunk
    rows = jnp.pad(rows, ((0, pad), (0, 0))).reshape(-1, chunk, x.shape[1])

    def block(r):
        d2 = jnp.sum((r[:, None, :] - xs[None, :, :]) ** 2, axis=-1)
        return _matmul(cov(d2, signal), v, low)

    out = jax.lax.map(block, rows).reshape(-1, v.shape[1])
    return out[: x_rows.shape[0]]


def rff_mv(x, omega, w, signal, *, chunk=8192, low=False):
    """``Phi(x) @ w`` for the paired map
    ``Phi(x) = sqrt(signal / m) [sin(x omega^T), cos(x omega^T)]``."""
    m = omega.shape[0]
    pad = (-x.shape[0]) % chunk
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, chunk, x.shape[1])

    def block(r):
        proj = _matmul(r, omega.T, low)
        feats = jnp.sqrt(signal / m) * jnp.concatenate(
            [jnp.sin(proj), jnp.cos(proj)], axis=-1)
        return _matmul(feats, w, low)

    out = jax.lax.map(block, xb).reshape(-1, w.shape[1])
    return out[: x.shape[0]]


def system_mv(kind, x, v, lengthscale, signal, noise, *, chunk=64):
    """``(K(x, x) + noise I) @ v``."""
    kv = jax.jit(kernel_mv, static_argnums=0, static_argnames="chunk")(
        kind, x, x, v, lengthscale, signal, chunk=chunk)
    return kv + noise * v


def rel_residual(kind, x, rhs, sol, lengthscale, signal, noise):
    """Per-column ``||rhs - (K + noise I) sol|| / ||rhs||``."""
    res = rhs - system_mv(kind, x, sol, lengthscale, signal, noise)
    return jnp.linalg.norm(res, axis=0) / jnp.linalg.norm(rhs, axis=0)


def column_rel_err(got, ref):
    """Per-column ``||got - ref|| / ||ref||``."""
    return jnp.linalg.norm(got - ref, axis=0) / jnp.linalg.norm(ref, axis=0)


# ------------------------------------------------------------ Thompson ascent


def matern32_grad_factor(d2, signal):
    """``g`` with ``grad_p k(p, z) = g (p - z) / lengthscale^2`` for the
    Matérn-3/2 map ``k = signal (1 + s) exp(-s)``, ``s = sqrt(3 d2)``:
    ``dk/dr = -3 signal r exp(-sqrt(3) r)`` and ``dr/dp = (p - z) / (l^2 r)``."""
    return -3.0 * signal * jnp.exp(-math.sqrt(3.0) * jnp.sqrt(d2))


KERNEL_GRADS = {"matern32": matern32_grad_factor}


def path_values(kind, pts, x, omega, w, c, lengthscale, signal, *, low=False):
    """Sample paths ``f_j = Phi w_j + K(., x) c_j`` at ``pts``: (P, s)."""
    return (rff_mv(pts, omega, w, signal, low=low)
            + kernel_mv(kind, pts, x, c, lengthscale, signal, low=low))


def path_grad(kind, pts, cols, x, omega, w, c, lengthscale, signal, *, low=False):
    """Gradient of path ``cols[p]`` at ``pts[p]`` for every point: (P, d).

    The feature part differentiates ``sqrt(signal / m) (sin(omega p) w_sin +
    cos(omega p) w_cos)``; the kernel part sums ``c_i grad_p k(p, x_i)``."""
    m = omega.shape[0]
    proj = _matmul(pts, omega.T, low)
    coef = jnp.cos(proj) * w[:m, cols].T - jnp.sin(proj) * w[m:, cols].T
    g_rff = jnp.sqrt(signal / m) * _matmul(coef, omega, low)
    d2 = jnp.sum((pts[:, None, :] / lengthscale - x[None, :, :] / lengthscale) ** 2,
                 axis=-1)
    gk = KERNEL_GRADS[kind](d2, signal) * c[:, cols].T
    g_k = (jnp.sum(gk, axis=1, keepdims=True) * pts - _matmul(gk, x, low)) \
        / lengthscale ** 2
    return g_rff + g_k


def thompson_candidates(key, x, y, num_candidates, lengthscale):
    """The multi-start candidates of one acquisition: a tenth uniform on
    ``[0, 1]^d``, the rest incumbents drawn in proportion to ``softmax(y)``
    and perturbed by ``lengthscale / 2`` Gaussian noise, all clipped to the
    box."""
    ku, ke, kp = jax.random.split(key, 3)
    n_exploit = int(num_candidates * EXPLOIT_FRAC)
    uniform = jax.random.uniform(ku, (num_candidates - n_exploit, x.shape[1]))
    pick = jax.random.choice(ke, x.shape[0], (n_exploit,), p=jax.nn.softmax(y))
    near = x[pick] + (lengthscale / 2.0) * jax.random.normal(kp, (n_exploit, x.shape[1]))
    return jnp.clip(jnp.concatenate([uniform, near], axis=0), 0.0, 1.0)


def adam_ascent(grad, xs, steps, lr):
    """``steps`` Adam steps up ``grad`` (0.9, 0.999, 1e-8), clipped to the
    unit box after each."""
    m = jnp.zeros_like(xs)
    v = jnp.zeros_like(xs)
    for t in range(steps):
        g = grad(xs)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9 ** (t + 1))
        vh = v / (1 - 0.999 ** (t + 1))
        xs = jnp.clip(xs + lr * mh / (jnp.sqrt(vh) + 1e-8), 0.0, 1.0)
    return xs


def thompson_values(kind, key, x, y, omega, w, c, lengthscale, signal, *,
                    num_candidates, num_top, ascent_steps, lr, low=False, tie=1e-3):
    """The value each sample path reaches in one acquisition: (s,).

    The top ``num_top`` candidates of each path ascend it and the best
    end value counts. Where the ``num_top``-th and the next candidate start
    within ``tie`` of each other (relative to the path's spread over the
    candidates), rounding may pick either, so the smaller of the two
    outcomes counts."""
    s = w.shape[1]
    cands = thompson_candidates(key, x, y, num_candidates, lengthscale)
    vals = path_values(kind, cands, x, omega, w, c, lengthscale, signal, low=low)
    order = jnp.argsort(-vals, axis=0)[: num_top + 1]  # (top + 1, s)
    x0 = cands[order].reshape(-1, x.shape[1])
    cols = jnp.tile(jnp.arange(s), num_top + 1)
    grad = lambda p: path_grad(kind, p, cols, x, omega, w, c, lengthscale,  # noqa: E731
                               signal, low=low)
    xs = adam_ascent(grad, x0, ascent_steps, lr)
    ends = path_values(kind, xs, x, omega, w, c, lengthscale, signal, low=low)
    ends = ends[jnp.arange(xs.shape[0]), cols].reshape(num_top + 1, s)
    best = jnp.max(ends[:num_top], axis=0)
    swap = jnp.max(jnp.concatenate([ends[: num_top - 1], ends[num_top:]]), axis=0)
    start = jnp.take_along_axis(vals, order, axis=0)
    near = (start[num_top - 1] - start[num_top]) <= tie * jnp.std(vals, axis=0)
    return jnp.where(near, jnp.minimum(best, swap), best)


# ------------------------------------------------------------ random draws


def ks_scaled(values, cdf) -> float:
    """``sqrt(N) D``: the Kolmogorov–Smirnov distance of the N ``values`` from
    the distribution function ``cdf``, scaled so that its law under the
    right distribution does not depend on N (it exceeds 1.95 with chance
    1e-3, 2.5 with chance 7.5e-6)."""
    v = np.sort(np.ravel(np.asarray(values, dtype=np.float64)))
    n = v.size
    f = cdf(v)
    d = max(np.max(np.arange(1, n + 1) / n - f), np.max(f - np.arange(n) / n))
    return float(math.sqrt(n) * d)


def spectral_ks(kind, omega, lengthscale) -> float:
    """``ks_scaled`` of the frequencies' squared norms against the spectral
    density: for a Student-t with ``2 nu`` degrees of freedom in d
    dimensions, ``lengthscale^2 |omega|^2 / d`` follows F(d, 2 nu)."""
    from scipy.special import fdtr

    omega = np.asarray(omega, dtype=np.float64)
    d = omega.shape[1]
    norms = np.sum((omega * lengthscale) ** 2, axis=1) / d
    return ks_scaled(norms, lambda v: fdtr(d, SPECTRAL_DOF[kind], v))


def normal_ks(values, scale=1.0) -> float:
    """``ks_scaled`` of ``values / scale`` against the standard normal."""
    from scipy.special import ndtr

    return ks_scaled(np.asarray(values, dtype=np.float64) / scale, ndtr)
