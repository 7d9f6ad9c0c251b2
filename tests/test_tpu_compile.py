"""Compile rehearsals of the main-path Pallas kernels for a TPU v5e.

The chip's compiler is installed even where no chip is attached: each test
lowers one kernel with ``interpret=False`` for a described ``v5e:2x2``
topology, at the width of the paper's ``pol`` problem (n = 15,000 padded to
the ``block="auto"`` tile, d = 26, s = 17 right-hand sides — the engine's
mean column plus 16 pathwise samples), and checks that the compiled program
holds the Mosaic kernel (``tpu_custom_call``). Nothing runs, so these tests
say nothing about values or times — only that the chip's compiler accepts
the kernels at the shapes the serving path feeds them.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under a multi-worker run only
the worker given this file should try.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gram_matvec import (
    gram_matvec_bwd_pallas, gram_matvec_pallas, gram_rows_pair_pallas,
    symmetric_fits,
)
from repro.kernels.ops import stationary_block
from repro.kernels.rff_matvec import (
    rff_matvec_pallas, rff_pair_pallas, rff_t_matvec_pallas,
)

N_POL, D_POL, S = 15_000, 26, 17
M_FEATURES = 512  # RFF prior frequencies: the engine's default 1024 features
P_ROWS = 128  # one row tile of the Gram pair kernel


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Cache off around the compiles: an entry written for a described chip
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def blocks():
    """The ``block="auto"`` tiles the serving path resolves at the pol width,
    and the padded n they give (15,360)."""
    bg = stationary_block("gram", N_POL, D_POL, "fp32")
    br = stationary_block("rff", N_POL, D_POL, "fp32")
    n_pad = -(-N_POL // bg) * bg
    assert n_pad == 15_360 and n_pad % br == 0, (bg, br, n_pad)
    return dict(gram=bg, rff=br, n=n_pad)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("jitter", [0.0, 0.05])
def test_gram_matvec_fwd_compiles(one_chip, blocks, jitter):
    b, n = blocks["gram"], blocks["n"]
    text = _compiled_text(
        lambda x, v: gram_matvec_pallas(
            x, x, v, kind="matern32", jitter=jitter, block_m=b, block_n=b,
            interpret=False,
        ),
        one_chip, (n, D_POL), (n, S),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("s", [1, S, 64])
def test_gram_matvec_symmetric_compiles(one_chip, blocks, s):
    """The symmetric grid at the pol solve's shapes, within its VMEM budget,
    under the name the benchmark's trace reader looks for."""
    b, n = blocks["gram"], blocks["n"]
    assert symmetric_fits(n, s, b)
    text = _compiled_text(
        lambda x, v: gram_matvec_pallas(
            x, x, v, kind="matern32", block_m=b, block_n=b, interpret=False,
            symmetric=True,
        ),
        one_chip, (n, D_POL), (n, s),
    )
    assert "tpu_custom_call" in text
    assert re.search(r"%gram_matvec_pallas[\w.]* = \S+ custom-call\(", text)


def test_gram_matvec_bwd_compiles(one_chip, blocks):
    b, n = blocks["gram"], blocks["n"]
    text = _compiled_text(
        lambda x, r, c: gram_matvec_bwd_pallas(
            x, x, r, c, kind="matern32", block_m=b, block_n=b, interpret=False,
        ),
        one_chip, (n, D_POL), (n, S), (n, S),
    )
    assert "tpu_custom_call" in text


def test_gram_rows_pair_compiles(one_chip, blocks):
    b, n = blocks["gram"], blocks["n"]
    text = _compiled_text(
        lambda xi, x, look, rhs: gram_rows_pair_pallas(
            xi, x, look, rhs, kind="matern32", block_n=b, interpret=False,
        ),
        one_chip, (P_ROWS, D_POL), (n, D_POL), (n, S), (P_ROWS, S),
    )
    assert "tpu_custom_call" in text


def test_rff_matvec_compiles(one_chip, blocks):
    b, n = blocks["rff"], blocks["n"]
    text = _compiled_text(
        lambda x, om, w: rff_matvec_pallas(
            x, om, w, block_m=b, block_f=b, interpret=False,
        ),
        one_chip, (n, D_POL), (M_FEATURES, D_POL), (2 * M_FEATURES, S),
    )
    assert "tpu_custom_call" in text


def test_rff_t_matvec_compiles(one_chip, blocks):
    b, n = blocks["rff"], blocks["n"]
    text = _compiled_text(
        lambda x, om, u: rff_t_matvec_pallas(
            x, om, u, block_m=b, block_f=b, interpret=False,
        ),
        one_chip, (n, D_POL), (M_FEATURES, D_POL), (n, S),
    )
    assert "tpu_custom_call" in text


def test_rff_pair_compiles(one_chip, blocks):
    b, n = blocks["rff"], blocks["n"]
    text = _compiled_text(
        lambda x, om, u: rff_pair_pallas(x, om, u, block_m=b, interpret=False),
        one_chip, (n, D_POL), (M_FEATURES, D_POL), (n, S),
    )
    assert "tpu_custom_call" in text
