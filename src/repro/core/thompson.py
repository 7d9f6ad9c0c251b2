"""Large-scale parallel Thompson sampling (§3.3.2, Fig. 3.6/3.7; §4.3.2 Fig. 4.4).

Each acquisition step draws `acq_batch` posterior *function* samples via pathwise
conditioning (one batched solve), then maximises every sample with the paper's
multi-start strategy: explore (uniform) + exploit (perturbed incumbents) candidates →
top-k by sample value → Adam ascent on the sample function → acquire the argmaxes.
Pathwise conditioning is what makes this possible: each sample is a cheap
deterministic function evaluable at every Adam iterate.

The ascent differentiates through the posterior samples — prior feature matvec
Φ(x)w plus cross-covariance matvec — and both primitives carry custom VJPs
(kernels/rff_matvec.py, kernels/gram_matvec.py), so on TPU every one of the
thousands of Adam gradient evaluations runs through fused Pallas tiles without
materialising features or cross-Gram panels (the FeatureOperator protocol,
docs/features.md).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .kernels_fn import KernelParams
from .pathwise import PosteriorFunctions, posterior_functions
from .solvers.spec import SpecLike, as_spec


@dataclasses.dataclass
class ThompsonState:
    x: jax.Array  # (n, d) observed inputs
    y: jax.Array  # (n,)
    best: float


@functools.partial(
    jax.jit,
    static_argnames=("num_candidates", "num_top", "ascent_steps", "exploit_frac"),
)
def _maximise_samples(
    post: PosteriorFunctions,
    y: jax.Array,
    key: jax.Array,
    *,
    num_candidates: int,
    num_top: int,
    ascent_steps: int,
    lr: float,
    exploit_frac: float = 0.9,
    lengthscale: float = 0.2,
) -> jax.Array:
    """Maximise each posterior sample on [0,1]^d → (s, d) acquisition points.

    One compiled program per shape: candidate draws, candidate evaluation,
    top-k, the Adam scan and the final argmax all run inside this ``jit``.
    ``post`` is a traced argument (its arrays are leaves, its backends static),
    as are ``lr`` and ``lengthscale``; the program is keyed by the input
    shapes (n, d, features, samples) and the static ``num_candidates``,
    ``num_top``, ``ascent_steps`` and ``exploit_frac``.
    """
    d = post.x.shape[1]
    s = post.num_samples
    ku, ke, kp = jax.random.split(key, 3)
    n_exploit = int(num_candidates * exploit_frac)
    uniform = jax.random.uniform(ku, (num_candidates - n_exploit, d))
    # exploitation: resample incumbents ∝ observed value, perturb with ℓ/2 noise (§3.3.2)
    probs = jax.nn.softmax(y)
    pick = jax.random.choice(ke, post.x.shape[0], (n_exploit,), p=probs)
    near = post.x[pick] + (lengthscale / 2.0) * jax.random.normal(kp, (n_exploit, d))
    cands = jnp.clip(jnp.concatenate([uniform, near], axis=0), 0.0, 1.0)

    vals = post(cands)  # (n_cand, s)
    top = jnp.argsort(-vals, axis=0)[:num_top]  # (top, s)
    x0 = cands[top]  # (top, s, d)

    def value(xs_flat):  # xs_flat: (top*s, d) → per-sample values
        v = post(xs_flat)  # (top*s, s)
        v = v.reshape(num_top, s, s)
        return jnp.sum(jnp.einsum("tss->ts", v))

    xs = x0.reshape(num_top * s, d)
    m = jnp.zeros_like(xs)
    vv = jnp.zeros_like(xs)

    def step(carry, t):
        xs, m, vv = carry
        g = jax.grad(value)(xs)
        m = 0.9 * m + 0.1 * g
        vv = 0.999 * vv + 0.001 * g * g
        mh = m / (1 - 0.9 ** (t + 1.0))
        vh = vv / (1 - 0.999 ** (t + 1.0))
        xs = jnp.clip(xs + lr * mh / (jnp.sqrt(vh) + 1e-8), 0.0, 1.0)
        return (xs, m, vv), None

    (xs, _, _), _ = jax.lax.scan(step, (xs, m, vv), jnp.arange(ascent_steps))
    final = post(xs).reshape(num_top, s, s)
    per = jnp.einsum("tss->ts", final)  # value of candidate t for sample s
    best_t = jnp.argmax(per, axis=0)  # (s,)
    xs3 = xs.reshape(num_top, s, d)
    return xs3[best_t, jnp.arange(s)]  # (s, d)


def thompson_step(
    params: KernelParams,
    state: ThompsonState,
    objective: Callable[[jax.Array], jax.Array],
    key: jax.Array,
    *,
    acq_batch: int = 50,
    num_features: int = 1024,
    spec: Optional[SpecLike] = None,
    num_candidates: int = 2000,
    num_top: int = 5,
    ascent_steps: int = 30,
    lr: float = 1e-3,
    **spec_overrides,
) -> ThompsonState:
    """One acquisition round. ``spec`` is any registered SolverSpec (defaults to
    SDD, the paper's Thompson workhorse); extra keyword arguments are spec-field
    overrides."""
    s = as_spec("sdd" if spec is None else spec, **spec_overrides)
    kd, km, ko = jax.random.split(key, 3)
    post = posterior_functions(
        params,
        state.x,
        state.y,
        kd,
        num_samples=acq_batch,
        num_features=num_features,
        spec=s,
    )
    x_new = _maximise_samples(
        post,
        state.y,
        km,
        num_candidates=num_candidates,
        num_top=num_top,
        ascent_steps=ascent_steps,
        lr=lr,
        lengthscale=float(jnp.mean(params.lengthscale)),
    )
    y_new = objective(x_new) + jnp.sqrt(params.noise) * jax.random.normal(
        ko, (x_new.shape[0],)
    )
    x = jnp.concatenate([state.x, x_new], axis=0)
    y = jnp.concatenate([state.y, y_new], axis=0)
    return ThompsonState(x=x, y=y, best=float(jnp.max(y)))
