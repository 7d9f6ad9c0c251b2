"""Trace-time block autotuning (kernels/autotune.py): static resolution,
no-retrace behaviour, table lookup and the VMEM-budget heuristic fallback."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernels_fn import make_params
from repro.kernels import autotune
from repro.kernels.ops import gram_matvec, gram_mv


# ---------------------------------------------------------------------------
# Key bucketing and the expected-grid contract
# ---------------------------------------------------------------------------


def test_table_key_buckets_nearest_lower():
    assert autotune.table_key("gram", 5000, 3) == "gram|n4096|d2|float32"
    assert autotune.table_key("gram", 1024, 2) == "gram|n1024|d2|float32"
    assert autotune.table_key("rff", 100, 1000, "bfloat16") == "rff|n1024|d128|bfloat16"
    with pytest.raises(ValueError, match="family"):
        autotune.table_key("attention", 1024, 2)
    with pytest.raises(ValueError, match="dtype"):
        autotune.table_key("gram", 1024, 2, "float16")


def test_expected_keys_cover_full_grid():
    keys = autotune.expected_keys()
    assert len(keys) == (
        len(autotune.FAMILIES) * len(autotune.N_GRID)
        * len(autotune.D_GRID) * len(autotune.DTYPES)
    )
    assert "gram|n1024|d2|float32" in keys
    assert "rff|n65536|d128|bfloat16" in keys


# ---------------------------------------------------------------------------
# Heuristic: largest candidate that fits the VMEM budget without out-padding
# ---------------------------------------------------------------------------


def test_heuristic_respects_vmem_budget():
    # narrow RHS: the biggest candidate fits comfortably
    assert autotune.heuristic_block("gram", 65536, 8, s=16) == 512
    # very wide RHS blows the budget for 512 and 256 tiles; 128 fits
    assert autotune.heuristic_block("gram", 65536, 8, s=4096) == 128
    assert (
        autotune.vmem_bytes("gram", 128, 128, 8, s=4096)
        <= autotune.VMEM_BUDGET_BYTES
        < autotune.vmem_bytes("gram", 256, 256, 8, s=4096)
    )


def test_heuristic_never_outpads_small_problems():
    # 300 rows: a 512 tile would pad 40% garbage — refuse it even though it fits
    assert autotune.heuristic_block("gram", 300, 4) <= 256
    assert autotune.heuristic_block("gram", 64, 4) == 128  # floor candidate


def test_bf16_halves_operand_footprint():
    fp32 = autotune.vmem_bytes("gram", 256, 256, 32, s=16, dtype="float32")
    bf16 = autotune.vmem_bytes("gram", 256, 256, 32, s=16, dtype="bfloat16")
    assert bf16 < fp32  # operands shrink; fp32 accumulator/tile stay


# ---------------------------------------------------------------------------
# Table lookup wins over the heuristic; resolve_block is a plain int
# ---------------------------------------------------------------------------


def test_resolve_block_prefers_table_then_heuristic(tmp_path, monkeypatch):
    key = autotune.table_key("gram", 2048, 4)
    path = tmp_path / "table.json"
    path.write_text(json.dumps({"table": {key: 128}}))
    monkeypatch.setenv(autotune.AUTOTUNE_ENV, str(path))
    autotune.load_table.cache_clear()
    try:
        got = autotune.resolve_block("gram", 2048, 4)
        assert got == 128 and type(got) is int
        # a shape outside the table falls back to the heuristic
        fallback = autotune.resolve_block("rff", 2048, 4)
        assert fallback == autotune.heuristic_block("rff", 2048, 4)
        assert type(fallback) is int
    finally:
        autotune.load_table.cache_clear()


def test_missing_table_is_not_an_error(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.AUTOTUNE_ENV, str(tmp_path / "absent.json"))
    autotune.load_table.cache_clear()
    try:
        assert autotune.load_table() == {}
        assert type(autotune.resolve_block("gram", 1024, 2)) is int
    finally:
        autotune.load_table.cache_clear()


@pytest.mark.parametrize("content", ["{not json", "[1, 2"])
def test_unreadable_table_raises(tmp_path, monkeypatch, content):
    path = tmp_path / "broken.json"
    path.write_text(content)
    monkeypatch.setenv(autotune.AUTOTUNE_ENV, str(path))
    autotune.load_table.cache_clear()
    try:
        with pytest.raises(ValueError, match="cannot be read"):
            autotune.load_table()
    finally:
        autotune.load_table.cache_clear()


def test_default_table_resolves_from_checkout(tmp_path, monkeypatch):
    """The committed table is found from any working directory."""
    monkeypatch.delenv(autotune.AUTOTUNE_ENV, raising=False)
    monkeypatch.chdir(tmp_path)
    autotune.load_table.cache_clear()
    try:
        table = autotune.load_table()
        assert set(table) == autotune.expected_keys()
    finally:
        autotune.load_table.cache_clear()


# ---------------------------------------------------------------------------
# block="auto" resolves at trace time: correct values, no retraces
# ---------------------------------------------------------------------------


def test_auto_block_matvec_matches_explicit():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (192, 3))
    v = jax.random.normal(jax.random.fold_in(key, 1), (192, 2))
    p = make_params("se", lengthscale=1.0, signal=1.0, d=3)
    auto = gram_matvec(p, x, v, block="auto", interpret=True)
    explicit = gram_matvec(
        p, x, v, block=autotune.resolve_block("gram", 192, 3), interpret=True
    )
    np.testing.assert_allclose(auto, explicit, rtol=1e-6, atol=1e-6)


def test_auto_block_does_not_retrace():
    """The resolved block is a static Python int derived from static shapes, so
    value-only changes reuse the compiled solve — the autotune lookup must
    never smuggle a traced quantity into the pallas_call config."""
    p = make_params("se", lengthscale=1.0, signal=1.0, d=3)
    traces = []

    @jax.jit
    def mv(x, v):
        traces.append(1)
        return gram_mv(p, x, v, backend="pallas", block="auto", interpret=True)

    key = jax.random.PRNGKey(1)
    x1 = jax.random.normal(key, (160, 3))
    v1 = jax.random.normal(jax.random.fold_in(key, 1), (160, 2))
    mv(x1, v1)
    mv(x1 + 1.0, v1 * 2.0)  # same shapes, new values: no retrace
    assert len(traces) == 1, "block='auto' retraced on value-only changes"
    # a different n is a shape change and legitimately retraces (and may
    # resolve a different block — still statically)
    x2 = jnp.concatenate([x1, x1])
    v2 = jnp.concatenate([v1, v1])
    mv(x2, v2)
    assert len(traces) == 2
