"""The trace-to-metric reduction and the work counts, checked by hand."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import trace as tr  # noqa: E402
from benchmarks.chip import work  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_small.json"
PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture
def small():
    return tr.Trace.from_json(FIXTURE)


def test_opcode_and_name():
    text = ("%gram_matvec_pallas.7 = f32[1024,8]{1,0:T(8,128)S(1)} custom-call("
            "f32[1024,4]{1,0} %pad.14)")
    assert tr.opcode(text) == "custom-call"
    assert tr.op_name(text) == "gram_matvec_pallas"
    assert tr.opcode("%while.3 = (f32[8]{0:T(128)}, s32[]{:T(128)}) while((f32[8]) %t)") \
        == "while"


def test_busy_and_idle(small):
    # chip 0: [100,110] + [120,520] (the while covers its body) + [600,700] us;
    # chip 1: [100,300] us; window 900 us
    assert small.window_s() == pytest.approx(900e-6)
    assert small.busy_s() == pytest.approx((510e-6 + 200e-6) / 2)
    assert small.idle_share() == pytest.approx(1 - 355 / 900)


def test_gram_calls_use_the_shapes_before_padding(small):
    calls = small.kernel_calls(r"gram_matvec_pallas")
    assert calls == [([(1000, 4), (1000, 4), (1024, 8)], pytest.approx(200e-6))]
    (ops, nbytes, secs), = tr.gram_mv_calls(small, "matern32")
    # 2 n m d + 2 (n + m) d + 11 n m + 2 n m s at n = m = 1000, d = 4, s = 8
    assert ops == 2 * 10**6 * 4 + 2 * 2000 * 4 + 11 * 10**6 + 2 * 10**6 * 8
    assert nbytes == 4 * (1000 * 4 + 1000 * 4 + 1000 * 8 + 1000 * 8)
    least = ops / PEAK["flops_per_s"]
    assert tr.gram_mv_roofline(small, "matern32", PEAK) == pytest.approx(
        100 * least / 200e-6)
    assert tr.gram_mv_bound(small, {"kernel": "matern32"}, PEAK)["bound"] == \
        {"compute": 1}


def test_step_mfu_reader(small):
    from benchmarks.chip import harness

    read = harness.Bench().reader("step_mfu.mixed")
    run = harness.Run(None, {"kernel": "matern32"}, None, small, PEAK)
    ops = 2 * 10**6 * 4 + 2 * 2000 * 4 + 11 * 10**6 + 2 * 10**6 * 8
    assert read(run) == pytest.approx(100 * ops / (900e-6 * PEAK["flops_per_s"]))
    assert read(harness.Run(None, {"kernel": "matern32"}, None,
                            tr.Trace([small.devices[0][:2]], small.host), PEAK)) is None


def test_host_line_is_found_by_its_window_span(small):
    from types import SimpleNamespace as NS

    def line(name, events):
        return NS(name=name, events=[NS(name=n, start_ns=a, end_ns=b) for n, a, b in events])

    planes = [
        NS(name="/device:TPU:1", lines=[line("XLA Ops", small.devices[1])]),
        NS(name="/device:TPU:0", lines=[line("Steps", []), line("XLA Ops", small.devices[0])]),
        NS(name="/host:CPU", lines=[line("tf_xla-cpu-codegen/7", [("compile", 0, 10)]),
                                    line("python3", small.host)]),
    ]
    got = tr.from_planes(planes, devices=2)
    assert got.devices == small.devices and got.host == small.host
    with pytest.raises(ValueError, match="expected 4"):
        tr.from_planes(planes, devices=4)
    with pytest.raises(ValueError, match="no `window` span"):
        tr.from_planes(planes[:2], devices=2)


def test_breakdown(small):
    bd = small.breakdown()
    ops = dict(bd["device_ops"])
    assert list(ops)[0] == "fusion"  # (60 + 30 + 150) us over 2 chips
    assert ops["fusion"] == pytest.approx(120e-6)
    assert ops["gram_matvec_pallas"] == pytest.approx(100e-6)
    assert ops["all-gather"] == pytest.approx(100e-6)
    assert "while" not in ops
    assert bd["idle_gaps"] == [["wait_arrival", pytest.approx(300e-6)],
                               ["wait_arrival", pytest.approx(80e-6)],
                               ["engine.step", pytest.approx(10e-6)]]


def test_work_hand_counts():
    ops, nbytes = work.gram_mv_work("matern32", 3, 5, 2, 1)
    # 2*15*2 distances + 2*8*2 norms + 11*15 map + 2*15*1 contraction
    assert ops == 60 + 32 + 165 + 30
    assert nbytes == 4 * (3 * 2 + 5 * 2 + 5 * 1 + 3 * 1)
    assert work.least_time_s(2e12, 1e9, PEAK) == (2.0, "compute")
    assert work.least_time_s(1e9, 1e12, PEAK) == (10.0, "memory")


def test_peaks_table():
    assert work.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        work.peaks("TPU v9 imaginary")
