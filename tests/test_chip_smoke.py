"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes.

The script refuses a host without a TPU; its phases are driven here directly
with the ``auto`` backends steered onto the fused Pallas kernels (in interpret
mode), so the same counters and bounds that gate the chip run are exercised.
The four-chip phase runs on four virtual CPU devices in a child process.
"""
import importlib.util
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core.kernels_fn import make_params
from repro.kernels import ops

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture
def pallas_auto(monkeypatch):
    """``backend="auto"`` resolves to the fused kernels, as on a TPU; off
    the chip they run in interpret mode."""
    gram_resolve, feat_resolve = ops.resolve_backend, ops.resolve_feature_backend
    monkeypatch.setattr(ops, "resolve_backend", lambda backend="auto", kind="se":
                        gram_resolve("pallas" if backend == "auto" else backend, kind))
    monkeypatch.setattr(ops, "resolve_feature_backend",
                        lambda backend="auto", paired=True: feat_resolve(
                            "pallas" if backend == "auto" and paired else backend,
                            paired))


def test_smoke_refuses_a_host_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "needs a TPU" in err


def test_reference_matches_library_gram(smoke):
    """The independent reference agrees with the library's dense Gram."""
    from repro.core.kernels_fn import gram

    x = jax.random.normal(jax.random.PRNGKey(0), (100, 5))
    v = jax.random.normal(jax.random.PRNGKey(1), (100, 3))
    p = make_params("matern32", lengthscale=1.3, signal=0.7, noise=0.1, d=5)
    ref = smoke.reference_matern32_mv(x[:37], x, v, p.lengthscale, p.signal, chunk=16)
    lib = jnp.matmul(gram(p, x[:37], x), v, precision=jax.lax.Precision.HIGHEST)
    assert float(jnp.max(smoke.column_rel_err(ref, lib))) < 1e-5


def test_serving_phase_tiny(smoke, pallas_auto):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (192, 3))
    y = jnp.sin(x.sum(-1)) + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (192,))
    params = make_params("matern32", lengthscale=1.0, signal=1.0, noise=0.1, d=3)
    out = smoke.serving_phase(x, y, x[:4] + 0.05, y[:4], params, seed=0,
                              num_samples=4, requests=16, parity_rows=32)
    assert out["counters"]["gram"]["pallas"] > 0
    assert out["stats"]["warm_hits"] > 0
    assert out["parity"] <= smoke.PARITY_BOUND
    assert out["stats"]["phases"]["step"]["calls"] == out["stats"]["steps"]
    assert isinstance(smoke.report_jit(), dict)


def test_sharded_phase_four_virtual_devices():
    code = textwrap.dedent(f"""
        import importlib.util, json
        import jax, jax.numpy as jnp
        from repro.core.kernels_fn import make_params
        from repro.kernels import ops

        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        gram_resolve = ops.resolve_backend
        ops.resolve_backend = lambda backend="auto", kind="se": gram_resolve(
            "pallas" if backend == "auto" else backend, kind)

        x = jax.random.normal(jax.random.PRNGKey(0), (256, 3))
        y = jnp.sin(x.sum(-1))
        params = make_params("matern32", lengthscale=0.5, signal=1.0,
                             noise=0.1, d=3)
        mesh = jax.make_mesh((4,), ("data",))
        out = smoke.sharded_phase(x, y, params, mesh, iters=5)
        print(json.dumps({{k: out[k] for k in ("held", "diffs", "mv_errs")}}))
    """)
    header = (
        "import os\n"
        'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"\n'
        'os.environ["JAX_PLATFORMS"] = "cpu"\n'
    )
    r = subprocess.run(
        [sys.executable, "-c", header + code], capture_output=True, text=True,
        timeout=600, env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=ROOT,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert sorted(int(k) for k in out["held"]) == [0, 1, 2, 3]
    assert all(v == 64 for v in out["held"].values())
    assert max(out["diffs"].values()) <= 1e-3
    assert max(out["mv_errs"].values()) <= 1e-4
