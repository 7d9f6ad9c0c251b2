"""Regression data from the run's seed, at a UCI dataset's shape.

The same generator as the paper reproduction's synthetic UCI stand-ins: inputs
are standard normal, targets a sum of 16 random sinusoids with a frequency
scale of 1.5/sqrt(d), plus Gaussian noise, then standardised. It is kept here
so that the inputs the benchmark feeds the system cannot change with the
system. Everything is made with NumPy on the host from ``seed`` alone.
"""
from __future__ import annotations

import numpy as np


def regression_data(n: int, d: int, seed: int, noise: float = 0.1):
    """``(x, y)`` float32 host arrays of shapes ``(n, d)`` and ``(n,)``."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(d, 16)) * (1.5 / np.sqrt(d))
    b = rng.uniform(0, 2 * np.pi, size=16)
    amp = rng.normal(size=16) / np.sqrt(16)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = np.cos(x @ w + b) @ amp + noise * rng.normal(size=n)
    y = (y - y.mean()) / (y.std() + 1e-12)
    return x, y.astype(np.float32)


def key_seed(seed: int) -> int:
    """A 31-bit seed for JAX PRNG keys, drawn from the run's seed (which may
    exceed 32 bits)."""
    return int(np.random.default_rng([seed, 7]).integers(0, 2**31 - 1))
