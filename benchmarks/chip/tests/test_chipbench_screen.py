"""The screening cell off the chip: its schedule, the work counts and readers of
its per-layer metrics on a recorded trace, and whole runs of a tiny Tanimoto
cell where ``correct`` holds for the program and fails for the control and
for each planted fault.

The chip check is steered off inside the tests (``steered`` from the harness
tests), the fused kernels run in interpret mode, the compile cache stays off.
"""
import json
import shutil
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402
from benchmarks.chip import work_tanimoto as wt  # noqa: E402
from benchmarks.chip.tests.test_chipbench_harness import steered  # noqa: E402,F401

BENCH = ROOT / "benchmarks" / "chip"
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_screen.json"
PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 91


def tiny_bench(tmp: Path) -> harness.Bench:
    """The benchmark with a tiny Tanimoto configuration and cell beside
    ``dock.screen``, as new files and entries."""
    bd = tmp / "bench"
    shutil.copytree(BENCH, bd, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((bd / "configs" / "dock.json").read_text())
    cfg.update(name="dock_tiny", n=256, d=64, density=0.2, num_features=64,
               fit_check_rows=128, num_samples=8,
               predict_split_rows=64)
    (bd / "configs" / "dock_tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((bd / "traffic" / "screen.json").read_text())
    mix.update(rate_per_s=4.0, hit_rows=[1, 4], library_rows=[40],
               warmup_requests=4, check_requests=4, drain_s=30)
    (bd / "traffic" / "tinyscreen.json").write_text(json.dumps(mix))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(name="dock_tiny", source="test", reduced=["n"],
                                file="bench/configs/dock_tiny.json", why="tiny"))
    spec["workloads"].append(dict(name="tiny.screen", config="dock_tiny",
                                  traffic="tinyscreen", chips=1, why="tiny"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "dock.screen" in m.get("workloads", ()):
            m["workloads"].append("tiny.screen")
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Bench(root=tmp, bench_dir=bd)


def run_tiny(tmp_path, control=None):
    return harness.run_cell(tiny_bench(tmp_path), "tiny.screen", SEED, 2.0, False,
                            t_start=time.perf_counter(), control=control,
                            log=lambda m: None)


def test_screen_schedule_is_stratified_and_seeded():
    from benchmarks.chip.traffic.kinds import screen

    cfg = json.loads((BENCH / "configs" / "dock.json").read_text())
    cfg.update(d=64)
    mix = json.loads((BENCH / "traffic" / "screen.json").read_text())
    a = screen.schedule(mix, 5, 16.0, cfg)
    b = screen.schedule(mix, 5, 16.0, cfg)
    c = screen.schedule(mix, 6, 16.0, cfg)
    assert len(a) == round(mix["rate_per_s"] * 16)
    kinds = Counter(it["kind"] for it in a)
    assert abs(kinds["hit_list"] - 3 * kinds["library"]) <= 3
    rows = Counter(it["xs"].shape[0] for it in a)
    assert set(rows) <= set(mix["hit_rows"]) | set(mix["library_rows"])
    assert all(np.array_equal(p["xs"], q["xs"]) for p, q in zip(a, b))
    assert sorted(it["xs"].shape[0] for it in a) == sorted(it["xs"].shape[0] for it in c)
    xs = np.concatenate([it["xs"] for it in a])
    assert set(np.unique(xs)) <= {0.0, 1.0, 2.0, 3.0, 4.0}
    assert abs((xs > 0).mean() - cfg["density"]) < 0.01


def test_tanimoto_work_and_readers_on_a_recorded_trace():
    small = tr.Trace.from_json(FIXTURE)
    calls = wt.tanimoto_mv_calls(small)
    # the padded query block counts at its shape before the pad
    assert [c[2] for c in calls] == [pytest.approx(200e-6), pytest.approx(50e-6)]
    ops, nbytes = wt.tanimoto_mv_work(1000, 1000, 64, 8)
    assert ops == 2 * 10**6 * 64 + 2000 * 64 + 4 * 10**6 + 2 * 10**6 * 8
    assert nbytes == 4 * (1000 * 64 + 1000 * 64 + 1000 * 8 + 1000 * 8)
    assert calls[0][:2] == (ops, nbytes)
    assert calls[1][:2] == wt.tanimoto_mv_work(10, 1000, 64, 17)
    (shapes, secs), = small.kernel_calls(wt.RF_KERNEL)
    assert shapes == [(10, 64), (4, 256, 64), (256, 17)] and secs == pytest.approx(60e-6)

    bench = harness.Bench()
    run = harness.Run({"name": "dock.screen"}, {"kernel": "tanimoto"}, None, small, PEAK)
    from benchmarks.chip.work import least_time_s

    least = sum(least_time_s(o, b, PEAK)[0] for o, b, _ in calls)
    assert bench.reader("tanimoto_mv_roofline.screen")(run) == pytest.approx(
        100 * least / 250e-6)
    assert bench.reader("step_mfu.screen")(run) == pytest.approx(
        100 * (calls[0][0] + calls[1][0]) / (900e-6 * PEAK["flops_per_s"]))
    # busy: [100, 300] + [340, 400] + [420, 520] us = 360 us of a 900 us window
    assert bench.reader("device_idle.screen")(run) == pytest.approx(100 * (1 - 360 / 900))
    assert bench.reader("tanimoto_rf_share.screen")(run) == pytest.approx(100 * 60 / 360)
    # the Gram-matvec log of every traced run finds nothing to count here
    assert tr.gram_mv_bound(small, {"kernel": "tanimoto"}, PEAK)["calls"] == 0
    empty = tr.Trace([[]], [("window", 0, 1000)])
    none = harness.Run({"name": "dock.screen"}, {"kernel": "tanimoto"}, None, empty, PEAK)
    for name in ("tanimoto_mv_roofline.screen", "step_mfu.screen",
                 "tanimoto_rf_share.screen"):
        assert bench.reader(name)(none) is None


def test_screen_rehearsal_is_correct(steered, tmp_path):  # noqa: F811
    res = run_tiny(tmp_path)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 8
    assert set(res["metrics"]) == {"latency_p95_ms", "latency_p50_ms",
                                   "served_per_s", "setup_s"}
    assert res["checks"]["off_pallas_traces"]["value"] == 0
    assert list(res)[-1] == "checks"


def test_screen_control_fails(steered, tmp_path):  # noqa: F811
    res = run_tiny(tmp_path, control="bf16")
    assert not res["correct"]
    assert res["checks"]["predict_err"]["value"] > res["checks"]["predict_err"]["limit"]


@pytest.mark.parametrize("fault,number", [("binary_form", "fit_residual"),
                                          ("r_exponential", "r_ks"),
                                          ("half_columns", "fit_residual")])
def test_screen_faults_fail(steered, tmp_path, fault, number):  # noqa: F811
    from benchmarks.chip.faults_tanimoto import FAULTS

    with FAULTS[fault]():
        res = run_tiny(tmp_path)
    assert not res["correct"]
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


def test_reference_hash_takes_the_first_dim_of_least_a():
    import jax.numpy as jnp

    from benchmarks.chip import reference_tanimoto as rt

    x = jnp.array([[1.0, 2.0, 0.0, 3.0]])
    draws = dict(r=np.ones((2, 4)), beta=np.full((2, 4), 0.5))
    # c = a · y · e^r: feature 0 ties dims 0 and 3 exactly, dim 1 wins feature 1
    t = np.floor(np.log(np.array([1.0, 2.0, 1.0, 3.0])) + 0.5)
    base = np.exp(t - 0.5) * np.e
    draws["c"] = np.stack([base * np.array([1.0, 2.0, 5.0, 1.0]),
                           base * np.array([3.0, 1.0, 5.0, 2.0])])
    t_tab, a_tab = rt.ioffe_table(draws, 3)
    i, ti, nonzero = rt.hashes(x[:, None, :], jnp.asarray(t_tab), jnp.asarray(a_tab))
    assert bool(nonzero.all())
    assert i.tolist() == [[0, 1]] and ti.tolist() == [[int(t[0]), int(t[1])]]
    # paired: row k under hash k alone
    i2, _, _ = rt.hashes(jnp.concatenate([x, x]), jnp.asarray(t_tab), jnp.asarray(a_tab))
    assert i2.tolist() == [0, 1]


def test_reference_features_equal_the_programs():
    """The reference's hashes (the recipe, float64 rounded to float32) and the
    program's fused and materialised features agree exactly."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import reference_tanimoto as rt
    from repro.core.kernels_fn import make_params
    from repro.core.rff import sample_prior
    from repro.kernels.ops import tanimoto_rf_mv

    rng = np.random.default_rng(12)
    cfg = json.loads((BENCH / "configs" / "dock.json").read_text())
    cfg.update(d=128, density=0.1)
    from benchmarks.chip.fingerprints import fingerprints

    x = jnp.asarray(fingerprints(rng, 64, cfg))
    ff = sample_prior(make_params("tanimoto"), jax.random.PRNGKey(13), 1, 256, 128).ff
    draws = {k: np.asarray(getattr(ff, k)) for k in ("r", "c", "beta")}
    tables = tuple(jnp.asarray(a) for a in rt.ioffe_table(draws, cfg["max_count"]))
    eye = jnp.eye(256, dtype=jnp.float32)
    want = rt.rf_mv(x, tables, ff.salt, eye, 1.0)
    got = tanimoto_rf_mv(x, ff.table, eye, backend="pallas", block=64, interpret=True)
    np.testing.assert_array_equal(np.sign(np.asarray(got)), np.sign(np.asarray(want)))
    np.testing.assert_array_equal(np.sign(np.asarray(ff.features(x))),
                                  np.sign(np.asarray(want)))
