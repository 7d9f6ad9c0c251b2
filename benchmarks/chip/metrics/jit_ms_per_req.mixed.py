"""Tracing, lowering and compile time inside the program's spans per
attempted request of the window, in ms: the sum of ``trace_s + lower_s +
compile_s`` over the window's ``gp.counters`` events (a persistent-cache
load counts as compile time) over the window's requests."""
from benchmarks.chip import spans


def read(run):
    counters = [st for _, _, _, st in spans.named(run, "gp.counters")]
    if not counters or not run.runner.items:
        return None
    jit_s = sum(st.get("trace_s", 0.0) + st.get("lower_s", 0.0)
                + st.get("compile_s", 0.0) for st in counters)
    return 1e3 * jit_s / len(run.runner.items)
