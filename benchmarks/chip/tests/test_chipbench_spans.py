"""The program's spans and counters, and the four metrics that read them.

One file on purpose: a process holds one profiler session at a time, so the
traced engine run stays in one test worker. A tiny ``GPEngine`` is driven
under ``jax.profiler.trace`` and its trace read back through ``spans.py``;
the readers are checked on a hand-made trace with hand-computed values.
"""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness, spans  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_spans.json"
SOLVE_ORDER = ["gp.solve.rhs", "gp.solve.warm", "gp.solve.cg", "gp.solve.flags",
               "gp.solve.paths", "gp.thompson.ascent", "gp.block", "gp.complete"]
PATHS = ("gp.update.lowrank", "gp.update.full")


# --------------------------------------------------------------- the readers


def fixture_run(items=5, with_spans=True):
    trace = tr.Trace.from_json(FIXTURE)
    run = harness.Run({"name": "pol.mixed"}, {"kernel": "matern32"},
                      NS(items=[None] * items), trace, None)
    spans.attach(run, spans.from_json(FIXTURE) if with_spans else [])
    return run


def read(metric, run):
    return harness.Bench().reader(metric)(run)


def test_loader_clips_to_the_window():
    run = fixture_run()
    got = spans.events(run)
    # the counters before and after the window are gone, the last step is cut
    assert [s for s in got if s[0] == "gp.counters"] == [
        s for s in spans.from_json(FIXTURE)
        if s[0] == "gp.counters" and 100000 <= s[1] <= 1100000]
    assert spans.named(run, "gp.step")[-1][1:3] == (1050000, 1100000)
    assert all(100000 <= a <= b <= 1100000 for _, a, b, _ in got)


def test_step_idle_reader():
    # gp.step [150, 400] + [450, 700] + [1050, 1100] us, chip 0 busy
    # [200, 300] + [500, 600] + [900, 950] us: idle inside steps
    # 150 + 150 + 50 = 350 us of a 1000 us window
    assert read("step_idle.mixed", fixture_run()) == pytest.approx(35.0)


def test_queue_wait_reader():
    # in the window: (30 + 10) ms over 3 + 1 requests
    assert read("queue_wait_ms.mixed", fixture_run()) == pytest.approx(10.0)


def test_ascent_reader():
    # two ascents, 80 us and 60 us
    assert read("ascent_ms.mixed", fixture_run()) == pytest.approx(0.07)


def test_jit_per_request_reader():
    # (0.01 + 0.02 + 0.03) + (0 + 0.005 + 0) s over 5 attempted requests
    assert read("jit_ms_per_req.mixed", fixture_run(items=5)) == pytest.approx(13.0)


@pytest.mark.parametrize("metric", ["step_idle.mixed", "queue_wait_ms.mixed",
                                    "ascent_ms.mixed", "jit_ms_per_req.mixed"])
def test_readers_are_silent_without_program_spans(metric):
    """A program that writes no ``gp.*`` span (an engine without this
    instrument) gives no reading, and no error."""
    assert read(metric, fixture_run(with_spans=False)) is None


def test_host_line_with_the_window_holds_the_spans():
    def line(name, events):
        return NS(name=name, events=[NS(name=n, start_ns=a, end_ns=b,
                                        stats=list(st.items()))
                                     for n, a, b, st in events])

    mine = [("window", 0, 100, {}), ("gp.step", 10, 20, {"x": 1}),
            ("engine.step", 9, 21, {})]
    other = [("gp.step", 30, 40, {})]
    planes = [NS(name="/device:TPU:0", lines=[line("XLA Ops", [("gp.step", 1, 2, {})])]),
              NS(name="/host:CPU", lines=[line("worker", other), line("python3", mine)])]
    assert spans.from_planes(planes) == [("gp.step", 10, 20, {"x": 1})]


# ------------------------------------------------------- the traced engine


def tree(events):
    """Direct children of each span (counters left out), by containment."""
    evs = sorted((e for e in events if e[0] != "gp.counters"),
                 key=lambda e: (e[1], -e[2]))
    children = {i: [] for i in range(len(evs))}
    stack = []
    for i, (_, a, b, _) in enumerate(evs):
        while stack and evs[stack[-1]][2] < b:
            stack.pop()
        if stack:
            children[stack[-1]].append(i)
        stack.append(i)
    return evs, children


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny engine (n = 64, CG) serving predict, sample and two Thompson
    requests and one write, under the profiler."""
    from repro.core.kernels_fn import make_params
    from repro.serve import GPEngine

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 3))
    y = jnp.sin(x.sum(-1))
    params = make_params("matern32", lengthscale=1.0, signal=1.0, noise=0.1, d=3)
    eng = GPEngine(params, x, y, spec="cg", num_samples=4, num_features=32)
    rng = np.random.default_rng(0)
    trace_dir = tmp_path_factory.mktemp("trace")
    before = eng.stats()
    with jax.profiler.trace(str(trace_dir)):
        with jax.profiler.TraceAnnotation("window"):
            eng.predict(rng.standard_normal((3, 3), dtype=np.float32))
            eng.sample(rng.standard_normal((5, 3), dtype=np.float32),
                       num_samples=4, seed=1)
            eng.run_until_idle()
            eng.thompson_step(num_samples=4, seed=2, num_candidates=16)
            eng.step()
            first = eng.stats()
            eng.thompson_step(num_samples=4, seed=3, num_candidates=16)
            eng.sample(rng.standard_normal((5, 3), dtype=np.float32),
                       num_samples=4, seed=1)  # a warm repeat
            eng.run_until_idle()
            eng.add_observations(rng.standard_normal((2, 3), dtype=np.float32),
                                 np.zeros(2, np.float32))
    return dict(events=spans.from_dir(trace_dir), before=before, first=first,
                after=eng.stats())


def test_span_tree(traced):
    evs, children = tree(traced["events"])
    kids = {i: [evs[j][0] for j in js] for i, js in children.items()}
    steps = [i for i, e in enumerate(evs) if e[0] == "gp.step"]
    assert len(steps) == traced["after"]["steps"] - traced["before"]["steps"] == 5
    solve_batches = 0
    for i in steps:
        assert kids[i] == ["gp.schedule", "gp.batch"]
        batch = children[i][1]
        meta = evs[batch][3]
        assert {"group", "requests", "bucket"} <= set(meta)
        order = kids[batch]
        if meta["group"] == "predict":
            assert order == ["gp.predict", "gp.block", "gp.complete"]
            assert "rows" in meta
        else:
            solve_batches += 1
            assert "columns" in meta
            assert order == sorted(order, key=SOLVE_ORDER.index)
            assert {"gp.solve.rhs", "gp.solve.cg", "gp.solve.flags",
                    "gp.solve.paths", "gp.block", "gp.complete"} <= set(order)
            assert ("gp.solve.warm" in order) == (meta["group"] == "solve_warm")
    assert solve_batches == 4
    assert sum(e[0] == "gp.thompson.ascent" for e in evs) == 2
    assert sum(e[0] == "gp.submit" for e in evs) == 5
    updates = [i for i, e in enumerate(evs) if e[0] == "gp.update"]
    assert len(updates) == 1
    assert evs[updates[0]][3] == {"policy": "auto", "k": 2}
    assert sum(name in PATHS for name in kids[updates[0]]) == 1


def test_counters_match_the_stats(traced):
    counters = [st for n, _, _, st in traced["events"] if n == "gp.counters"]
    before, after = traced["before"], traced["after"]
    assert len(counters) == 5 + 1  # every step and the write
    assert sum(c["requests"] for c in counters) == \
        after["queued_requests"] - before["queued_requests"] == 5
    assert sum(c["queue_wait_ms"] for c in counters) == pytest.approx(
        1e3 * (after["queue_wait_s"] - before["queue_wait_s"]))
    assert after["queue_wait_mean_s"] == pytest.approx(
        after["queue_wait_s"] / after["queued_requests"])
    for c in counters:
        assert {"trace_s", "lower_s", "compile_s", "compiles"} <= set(c)


def test_first_thompson_request_traces(traced):
    # the eager ascent re-traces its scan on every request
    assert traced["first"]["jit"]["thompson.ascent"]["trace_s"] > 0
    assert traced["after"]["jit"]["thompson.ascent"]["trace_s"] > \
        traced["first"]["jit"]["thompson.ascent"]["trace_s"]


def test_phases_fill_without_a_profiler():
    from repro.core.kernels_fn import make_params
    from repro.serve import GPEngine

    x = jax.random.normal(jax.random.PRNGKey(1), (64, 3))
    params = make_params("matern32", lengthscale=1.0, signal=1.0, noise=0.1, d=3)
    eng = GPEngine(params, x, jnp.cos(x.sum(-1)), spec="cg", num_samples=4,
                   num_features=32)
    eng.predict(np.ones((2, 3), np.float32))
    eng.run_until_idle()
    phases = eng.stats()["phases"]
    for phase in ("submit", "step", "schedule", "batch", "predict", "block",
                  "complete"):
        assert phases[phase]["calls"] == 1
        assert phases[phase]["wall_s"] > 0
    assert phases["step"]["wall_s"] >= phases["batch"]["wall_s"]
