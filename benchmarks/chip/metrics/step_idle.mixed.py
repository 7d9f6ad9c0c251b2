"""Share of the traced window in which chip 0 is idle while the host is
inside the program's ``gp.step`` spans, in %: the union of ``gp.step`` minus
the union of device operations, over the window. The part of
``device_idle`` that the engine's own host path causes; nothing when the
program writes no ``gp.step`` span."""
from benchmarks.chip import spans
from benchmarks.chip.trace import _clip, _length, _subtract, _union


def read(run):
    steps = [(a, b) for _, a, b, _ in spans.named(run, "gp.step")]
    if not steps:
        return None
    tr = run.trace
    busy = _union(_clip([(a, b) for _, a, b in tr.devices[0]], tr.t0, tr.t1))
    return 100.0 * _length(_subtract(_union(steps), busy)) / (tr.t1 - tr.t0)
