"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; tests sweep shapes/dtypes and assert_allclose the Pallas
kernels (interpret=True on CPU) against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _stationary_map(d2: jax.Array, kind: str) -> jax.Array:
    if kind == "se":
        return jnp.exp(-0.5 * d2)
    r = jnp.sqrt(d2 + 1e-36)
    if kind == "matern12":
        return jnp.exp(-r)
    if kind == "matern32":
        s = jnp.sqrt(3.0) * r
        return (1.0 + s) * jnp.exp(-s)
    if kind == "matern52":
        s = jnp.sqrt(5.0) * r
        return (1.0 + s + s * s / 3.0) * jnp.exp(-s)
    raise ValueError(kind)


def gram_matvec_ref(
    x: jax.Array,
    z: jax.Array,
    v: jax.Array,
    *,
    kind: str = "se",
    signal: float = 1.0,
    jitter: float = 0.0,
) -> jax.Array:
    """(signal·k(x,z) + jitter·I_square) @ v. x:(n,d) z:(m,d) v:(m,s) → (n,s).

    Inputs are assumed already lengthscale-scaled (x/ℓ).
    """
    xn = jnp.sum(x * x, axis=-1)[:, None]
    zn = jnp.sum(z * z, axis=-1)[None, :]
    d2 = jnp.maximum(xn + zn - 2.0 * (x @ z.T), 0.0)
    k = signal * _stationary_map(d2, kind)
    out = k @ v
    if jitter:
        assert x.shape[0] == z.shape[0]
        out = out + jitter * v
    return out


def tanimoto_matvec_ref(
    x: jax.Array, z: jax.Array, v: jax.Array, *, signal: float = 1.0
) -> jax.Array:
    """signal·T(x, z) @ v for the Tanimoto (MinMax) kernel on non-negative
    counts, from its definition: T = Σ min(x, z) / Σ max(x, z), 0 between two
    all-zero vectors. x:(n,d) z:(m,d) v:(m,s) → (n,s)."""
    smin = jnp.sum(jnp.minimum(x[:, None, :], z[None, :, :]), axis=-1)
    smax = jnp.sum(jnp.maximum(x[:, None, :], z[None, :, :]), axis=-1)
    t = jnp.where(smax > 0, smin / jnp.where(smax > 0, smax, 1.0), 0.0)
    return signal * t @ v


def rff_matvec_ref(
    x: jax.Array, omega: jax.Array, w: jax.Array, *, signal: float = 1.0
) -> jax.Array:
    """Φ(x) @ w with paired sin/cos features. x:(n,d) ω:(m,d) w:(2m,s) → (n,s)."""
    m = omega.shape[0]
    proj = x @ omega.T
    phi = jnp.sqrt(signal / m) * jnp.concatenate([jnp.sin(proj), jnp.cos(proj)], -1)
    return phi @ w


def rff_t_matvec_ref(
    x: jax.Array, omega: jax.Array, u: jax.Array, *, signal: float = 1.0
) -> jax.Array:
    """Φ(x)ᵀ @ u with paired sin/cos features. x:(n,d) ω:(m,d) u:(n,s) → (2m,s)."""
    m = omega.shape[0]
    proj = x @ omega.T
    phi = jnp.sqrt(signal / m) * jnp.concatenate([jnp.sin(proj), jnp.cos(proj)], -1)
    return phi.T @ u


def flash_attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True
) -> jax.Array:
    """Reference attention. q,k,v: (b, s, h, dh) → (b, s, h, dh)."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
