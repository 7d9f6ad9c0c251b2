"""Faults planted in the program under test.

Each is a context manager that patches the program in this process and
restores it on exit. ``calibrate.py --fault <name>`` reads the compared
numbers of a run with one planted (the upper readings of the limits that the
lower-precision control cannot reach), and the tests see ``correct`` fail
with each. The benchmark's own runs never plant one.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def fewer_ascent_steps():
    """Thompson requests ascend half of the Adam steps they ask for."""
    import repro.serve.engine as engine

    inner = engine._maximise_samples

    def half(*args, ascent_steps, **kw):
        return inner(*args, ascent_steps=ascent_steps // 2, **kw)

    with _patched(engine, "_maximise_samples", half):
        yield


@contextlib.contextmanager
def wrong_draws():
    """The prior's frequencies from a Gaussian (the squared exponential's
    spectral density) in place of the kernel's own; each request's prior
    weights at twice their variance, and its noise scaled by the noise
    variance where its standard deviation belongs."""
    import jax
    import jax.numpy as jnp

    import repro.core.rff as rff
    from repro.serve.engine import GPEngine

    draws = GPEngine._request_draws

    def gaussian(params, key, m, d):
        return jax.random.normal(key, (m, d)) / params.lengthscale

    def scaled(self, req):
        w, eps, ka = draws(self, req)
        return w * jnp.sqrt(2.0), eps * jnp.sqrt(self.state.params.noise), ka

    with _patched(rff, "spectral_sample", gaussian), \
            _patched(GPEngine, "_request_draws", scaled):
        yield


FAULTS = {"fewer_ascent_steps": fewer_ascent_steps, "wrong_draws": wrong_draws}
