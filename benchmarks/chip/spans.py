"""The program's own host spans in a profiler trace, with their metadata.

``GPEngine`` writes ``gp.*`` ``TraceAnnotation`` spans (``gp.step`` and its
phases, ``gp.submit``, ``gp.update``, the zero-length ``gp.counters``) whose
keyword arguments the profiler keeps as each event's stats. ``trace.py``
keeps names and times only, so this module reads the same ``.xplane.pb``
again for them: the ``gp.*`` events of the host line that holds ``window``,
as ``(name, start_ns, end_ns, stats)``. A program without such spans gives
an empty list, so its metrics read nothing.
"""
from __future__ import annotations

import glob
import json
from pathlib import Path

PREFIX = "gp."


def from_planes(planes) -> list:
    """``(name, start, end, stats)`` of every ``gp.*`` event on the host line
    that holds the ``window`` span, in start order."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            found, window = [], False
            for e in line.events:
                name = e.name
                if name.startswith(PREFIX):
                    found.append((name, int(e.start_ns), int(e.end_ns),
                                  dict(e.stats)))
                elif name == "window":
                    window = True
            if window:
                out.extend(found)
    return sorted(out, key=lambda s: s[1])


def from_dir(trace_dir) -> list:
    """The spans of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_planes(ProfileData.from_file(paths[-1]).planes)


def from_json(path) -> list:
    """Spans recorded as ``{"spans": [[name, start, end, stats], ...]}``."""
    data = json.loads(Path(path).read_text())
    return [(n, int(a), int(b), dict(st)) for n, a, b, st in data["spans"]]


def clip(spans, t0: int, t1: int) -> list:
    """The spans that overlap ``[t0, t1]``, cut to it."""
    return [(n, max(a, t0), min(b, t1), st) for n, a, b, st in spans
            if a <= t1 and b >= t0]


def attach(run, spans) -> list:
    """Keep ``spans``, cut to the run's traced window, on ``run``."""
    run.gp_spans = clip(spans, run.trace.t0, run.trace.t1)
    return run.gp_spans


def events(run) -> list:
    """The run's ``gp.*`` spans inside its traced window, read once from the
    harness's trace directory ``.bench_trace/<cell>`` and kept on ``run``."""
    cached = getattr(run, "gp_spans", None)
    if cached is None:
        from .harness import ROOT

        cached = attach(run, from_dir(ROOT / ".bench_trace" / run.cell["name"]))
    return cached


def named(run, name: str) -> list:
    return [s for s in events(run) if s[0] == name]
