"""Parallel Thompson sampling (§3.3.2 / §4.3.2) on a small toy problem."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernels_fn import make_params
from repro.core.pathwise import posterior_functions
from repro.core.rff import sample_prior
from repro.core.solvers.spec import CG
from repro.core.thompson import ThompsonState, _maximise_samples, thompson_step
from repro.serve import GPEngine


def _tiny_problem(n=48, d=2):
    x = jax.random.uniform(jax.random.PRNGKey(7), (n, d))
    y = jnp.sin(4.0 * x[:, 0]) + 0.5 * jnp.cos(3.0 * x[:, 1])
    p = make_params("matern32", lengthscale=0.4, signal=1.0, noise=0.05, d=d)
    return p, x, y


def test_thompson_improves_over_random():
    d = 2
    key = jax.random.PRNGKey(0)
    p = make_params("matern32", lengthscale=0.3, signal=1.0, noise=0.01, d=d)
    target_prior = sample_prior(p, jax.random.PRNGKey(42), 1, 2048, d)

    def objective(x):
        return target_prior(x)[:, 0]

    n0 = 100
    x0 = jax.random.uniform(jax.random.fold_in(key, 1), (n0, d))
    y0 = objective(x0)
    state = ThompsonState(x=x0, y=y0, best=float(y0.max()))
    best0 = state.best
    for step in range(3):
        state = thompson_step(
            p, state, objective, jax.random.fold_in(key, 10 + step),
            acq_batch=16, num_candidates=256, num_top=4, ascent_steps=20,
            spec=CG(max_iters=100),
        )
    # random-search baseline with the same total evaluation budget
    xr = jax.random.uniform(jax.random.fold_in(key, 99), (3 * 16, d))
    best_rand = float(jnp.maximum(objective(xr).max(), best0))
    assert state.best >= best0
    assert state.best >= best_rand - 0.15  # at least competitive with random
    assert state.x.shape[0] == n0 + 3 * 16


@pytest.mark.parametrize("ascent_steps", [0, 7])
def test_jitted_ascent_matches_its_eager_body(ascent_steps):
    p, x, y = _tiny_problem()
    post = posterior_functions(p, x, y, jax.random.PRNGKey(3), num_samples=3,
                               num_features=64, spec=CG(max_iters=200))
    kw = dict(num_candidates=32, num_top=2, ascent_steps=ascent_steps, lr=0.05,
              lengthscale=jnp.mean(p.lengthscale))
    key = jax.random.PRNGKey(11)
    jitted = _maximise_samples(post, y, key, **kw)
    eager = _maximise_samples.__wrapped__(post, y, key, **kw)
    assert jitted.shape == (3, 2)
    np.testing.assert_allclose(np.asarray(jitted), np.asarray(eager), atol=1e-5)


def test_engine_ascent_compiles_once_per_static_options():
    p, x, y = _tiny_problem(n=40, d=3)  # a shape no other test here compiles
    eng = GPEngine(p, x, y, spec=CG(max_iters=200, tol=1e-4), num_samples=4,
                   num_features=32)

    def ascend(seed, **opts):
        h = eng.thompson_step(num_samples=2, seed=seed, num_candidates=32,
                              **opts)
        eng.run_until_idle()
        assert h.result().error is None
        return _maximise_samples._cache_size()

    before = _maximise_samples._cache_size()
    first = ascend(1, ascent_steps=4)
    assert first == before + 1
    assert ascend(2, ascent_steps=4) == first  # same options: reused
    assert ascend(3, ascent_steps=6) == first + 1  # new static value: one more
    assert ascend(4, ascent_steps=6, lr=0.05) == first + 1  # lr is traced
