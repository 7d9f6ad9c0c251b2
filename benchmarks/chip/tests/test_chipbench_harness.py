"""The harness off the chip: it refuses a CPU, finds new cells, configurations,
mixes and metrics by name, and drives a whole run of the open-loop cell at a
tiny size, where ``correct`` holds for the program and fails for the control
and for each fault the cell can have.

The chip check is steered off inside the tests, the fused kernels run in
interpret mode, and the persistent compile cache stays off.
"""
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness, work  # noqa: E402

BENCH = ROOT / "benchmarks" / "chip"
SEED = 2**31 + 77


def tiny_bench(tmp: Path) -> harness.Bench:
    """A benchmark tree with one more configuration, mix, metric and cell,
    added as new files and entries only."""
    bd = tmp / "bench"
    shutil.copytree(BENCH, bd, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((bd / "configs" / "pol.json").read_text())
    cfg.update(name="tiny", n=256, d=4, lengthscale=1.0, num_features=64)
    (bd / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((bd / "traffic" / "mixed.json").read_text())
    mix.update(rate_per_s=5.0, predict_rows=[2, 16], sample_rows=4, samples=4,
               identities=6, warmup_requests=6, check_requests=6, drain_s=30)
    (bd / "traffic" / "tinymix.json").write_text(json.dumps(mix))
    (bd / "metrics" / "predict_rows.tiny.py").write_text(
        "def read(run):\n    return run.runner.counters['predict_rows']\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(name="tiny", source="test", file="bench/configs/tiny.json",
                                reduced=["n"], why="tiny"))
    spec["workloads"].append(dict(name="tiny.mixed", config="tiny", traffic="tinymix",
                                  chips=1, why="tiny"))
    for m in spec["end_to_end"]:
        if "pol.mixed" in m.get("workloads", ()):
            m["workloads"].append("tiny.mixed")
    spec["per_layer"].append(dict(name="predict_rows.tiny", unit="rows", better="higher",
                                  source="program_counter", layer="serve.engine",
                                  moves="latency_p95_ms", workloads=["tiny.mixed"]))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Bench(root=tmp, bench_dir=bd)


@pytest.fixture
def steered(monkeypatch):
    """Run off the chip: the host's CPU device stands in, ``auto`` backends
    resolve to the fused kernels (interpret mode), no compile cache."""
    from repro.kernels import ops

    gram, feat = ops.resolve_backend, ops.resolve_feature_backend
    monkeypatch.setattr(ops, "resolve_backend", lambda backend="auto", kind="se":
                        gram("pallas" if backend == "auto" else backend, kind))
    monkeypatch.setattr(ops, "resolve_feature_backend",
                        lambda backend="auto", paired=True: feat(
                            "pallas" if backend == "auto" and paired else backend, paired))
    monkeypatch.setattr(harness, "require_devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(work, "peaks", lambda kind: {"flops_per_s": 1e12,
                                                     "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(harness, "enable_cache", lambda root: "off")


def run_tiny(tmp_path, control=None):
    return harness.run_cell(tiny_bench(tmp_path), "tiny.mixed", SEED, 2.0, False,
                            t_start=time.perf_counter(), control=control,
                            log=lambda m: None)


def test_refuses_a_host_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    rc = harness.main(["--workload", "pol.mixed", "--seed", "1", "--seconds", "1",
                       "--trace", "0"], t_start=time.perf_counter())
    out, err = capsys.readouterr()
    assert rc == 1 and out == ""
    assert "needs a TPU" in err


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                        "pol.mixed", "--seed", "3", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=300,
                       env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                            "HOME": str(tmp_path)})
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_new_files_are_found_by_name(tmp_path):
    bench = tiny_bench(tmp_path)
    assert bench.config("tiny")["n"] == 256
    assert bench.traffic("tinymix")["rate_per_s"] == 5.0
    assert hasattr(bench.kind("open_loop"), "Runner")
    names = [m["name"] for m in bench.per_layer("tiny.mixed")]
    assert names == ["predict_rows.tiny"]
    assert [m["name"] for m in bench.end_to_end("tiny.mixed")] == [
        "latency_p95_ms", "latency_p50_ms", "served_per_s", "setup_s"]

    class Fake:
        counters = {"predict_rows": 41}

    assert bench.reader("predict_rows.tiny")(harness.Run(None, None, Fake, None, None)) == 41
    with pytest.raises(harness.BenchError, match="unknown workload"):
        bench.cell("nope.mixed")


def test_open_loop_rehearsal_is_correct(steered, tmp_path):
    res = run_tiny(tmp_path)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 10
    assert set(res["metrics"]) == {"latency_p95_ms", "latency_p50_ms",
                                   "served_per_s", "setup_s"}
    assert res["device"]["count"] == 1
    assert list(res)[-1] == "checks"


def test_control_fails(steered, tmp_path):
    res = run_tiny(tmp_path, control="bf16")
    assert not res["correct"]
    assert res["checks"]["solve_residual"]["value"] > res["checks"]["solve_residual"]["limit"]


def test_altered_answer_fails(steered, tmp_path, monkeypatch):
    from repro.core.pathwise import PosteriorFunctions

    paths = PosteriorFunctions.sample_paths
    monkeypatch.setattr(PosteriorFunctions, "sample_paths",
                        lambda self, xs, w, a: paths(self, xs, w, a) * 1.001)
    res = run_tiny(tmp_path)
    assert not res["correct"]
    assert res["checks"]["sample_err"]["value"] > res["checks"]["sample_err"]["limit"]


@pytest.mark.parametrize("fault", ["unchanged_state", "half_the_batch"])
def test_solve_faults_fail(steered, tmp_path, monkeypatch, fault):
    import dataclasses

    import repro.serve.engine as engine

    solve = engine.solve

    def broken(op, data, spec, **kw):
        res = solve(op, data, spec, **kw)
        if fault == "unchanged_state":  # the solver returns its start
            sol = kw.get("x0") if kw.get("x0") is not None else jnp.zeros_like(data)
        else:  # half of the batch's columns (every second one) left out
            sol = res.solution.at[:, 1::2].set(0.0)
        return dataclasses.replace(res, solution=sol)

    monkeypatch.setattr(engine, "solve", broken)
    res = run_tiny(tmp_path)
    assert not res["correct"]
    assert res["checks"]["solve_residual"]["value"] > res["checks"]["solve_residual"]["limit"]


@pytest.mark.parametrize("fault,number", [("fewer_ascent_steps", "ascent_gap"),
                                          ("wrong_draws", "eps_ks")])
def test_planted_faults_fail(steered, tmp_path, fault, number):
    from benchmarks.chip.faults import FAULTS

    with FAULTS[fault]():
        res = run_tiny(tmp_path)
    assert not res["correct"]
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]
