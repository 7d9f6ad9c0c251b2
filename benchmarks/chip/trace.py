"""Reduction of a profiler trace to the benchmark's device metrics.

``load()`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote: the ``XLA
Ops`` line of each ``/device:TPU:<i>`` plane (one event per executed HLO
instruction, named by the instruction's text, so operand shapes can be read
from it) and the host line of the benchmark's own thread (its
``TraceAnnotation`` spans and JAX's dispatch and compile events). Host and
device events share one clock. Everything after ``load()`` works on plain
tuples, so a small recorded trace (``Trace.from_json``) checks it.
"""
from __future__ import annotations

import glob
import json
import re
from pathlib import Path

#: instructions whose event spans the instructions they run inside them
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OPERAND = re.compile(r"[a-z]\w*\[([\d,]*)\]\{[^}]*\} %([\w.\-]+)")
_PAD = re.compile(r"^%([\w.\-]+) = \S+ pad\([a-z]\w*\[([\d,]*)\]")


def opcode(text: str) -> str:
    m = _OPCODE.search(" " + text.partition(" = ")[2])
    return m.group(1) if m else ""


def op_name(text: str) -> str:
    """``%gram_matvec_pallas.7 = ...`` -> ``gram_matvec_pallas``."""
    head = text.partition(" = ")[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _length(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def _subtract(base, cut):
    """Parts of the (disjoint, sorted) ``base`` intervals not covered by the
    (disjoint, sorted) ``cut`` intervals."""
    out, j = [], 0
    for a, b in base:
        cur = a
        while j < len(cut) and cut[j][1] <= cur:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


class Trace:
    """Device op events per chip and host spans, in nanoseconds.

    ``devices``: list (one per chip) of ``(text, start, end)``;
    ``host``: list of ``(name, start, end)``.
    """

    def __init__(self, devices, host):
        self.devices = [sorted(evs, key=lambda e: e[1]) for evs in devices]
        self.host = sorted(host, key=lambda e: e[1])
        wins = [(a, b) for n, a, b in self.host if n == "window"]
        if not wins:
            raise ValueError("the host trace holds no `window` span")
        self.t0, self.t1 = wins[-1]

    @classmethod
    def from_json(cls, path):
        data = json.loads(Path(path).read_text())
        return cls([[tuple(e) for e in evs] for evs in data["devices"]],
                   [tuple(e) for e in data["host"]])

    # ----------------------------------------------------------- shares

    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _busy(self, evs):
        return _union(_clip([(a, b) for _, a, b in evs], self.t0, self.t1))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(_length(self._busy(evs)) for evs in self.devices) \
            / len(self.devices) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # ----------------------------------------------------------- kernels

    def kernel_calls(self, pattern: str, exclude: str = "bwd"):
        """``(operand shapes, seconds)`` of every custom call in the window
        whose name matches ``pattern``; a padded operand is given the shape it
        had before the ``pad`` that produced it."""
        rx = re.compile(pattern)
        out = []
        for evs in self.devices:
            padded = {}
            for text, a, b in evs:
                m = _PAD.match(text)
                if m:
                    padded[m.group(1)] = tuple(int(v) for v in m.group(2).split(",") if v)
                    continue
                name = op_name(text)
                if not rx.search(name) or exclude in name:
                    continue
                if opcode(text) != "custom-call" or b <= self.t0 or a >= self.t1:
                    continue
                args = text.partition("custom-call(")[2]
                shapes = []
                for dims, operand in _OPERAND.findall(args):
                    shape = tuple(int(v) for v in dims.split(",") if v)
                    shapes.append(padded.get(operand, shape))
                out.append((shapes, (b - a) / 1e9))
        return out

    # ----------------------------------------------------------- breakdown

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took the most time (seconds per chip,
        containers left out) and the longest idle gaps of chip 0, each named
        by the innermost host event around its middle."""
        per_op = {}
        for evs in self.devices:
            for text, a, b in evs:
                if opcode(text) in CONTAINERS:
                    continue
                lo, hi = max(a, self.t0), min(b, self.t1)
                if hi > lo:
                    name = op_name(text)
                    per_op[name] = per_op.get(name, 0.0) + (hi - lo) / 1e9
        n = len(self.devices)
        ops = sorted(([k, v / n] for k, v in per_op.items()), key=lambda kv: -kv[1])
        gaps = _subtract([(self.t0, self.t1)], self._busy(self.devices[0]))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        named = []
        for a, b in gaps:
            mid = (a + b) / 2
            around = [(s, name) for name, s, e in self.host
                      if s <= mid <= e and name != "window"]
            label = max(around)[1] if around else "(no host event)"
            named.append([label, (b - a) / 1e9])
        return {"device_ops": ops[:top], "idle_gaps": named}


def load(trace_dir, devices: int) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_planes(ProfileData.from_file(paths[-1]).planes, devices)


def from_planes(planes, devices: int) -> Trace:
    """The ``XLA Ops`` line of each of the first ``devices`` TPU planes, and
    the host line of the benchmark's own thread: that line is named after
    the executable, so it is found by the ``window`` span it holds."""
    dev, host = {}, []
    for plane in planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                dev[int(m.group(1))] = [(e.name, int(e.start_ns), int(e.end_ns))
                                        for e in line.events]
            elif plane.name.startswith("/host"):
                evs = [(e.name, int(e.start_ns), int(e.end_ns)) for e in line.events]
                if any(name == "window" for name, _, _ in evs):
                    host.extend(evs)
    ids = sorted(dev)[:devices]
    if len(ids) < devices:
        raise ValueError(f"trace has device planes {sorted(dev)}, expected {devices}")
    return Trace([dev[i] for i in ids], host)


def gram_mv_calls(trace: Trace, kind: str):
    """``(operations, bytes, seconds)`` of each fused Gram matvec in the
    window: operands ``x`` (n, d), ``z`` (m, d), ``v`` (m, s)."""
    from .work import gram_mv_work

    out = []
    for shapes, secs in trace.kernel_calls(r"gram_matvec_pallas"):
        (n, d), (m, _), (_, s) = shapes[:3]
        ops, nbytes = gram_mv_work(kind, n, m, d, s)
        out.append((ops, nbytes, secs))
    return out


def gram_mv_roofline(trace: Trace, kind: str, peak: dict):
    """Least time the chip could take for the window's Gram matvecs over the
    device time of their kernels, in %; ``None`` when none ran."""
    from .work import least_time_s

    calls = gram_mv_calls(trace, kind)
    spent = sum(c[2] for c in calls)
    if not calls or spent <= 0:
        return None
    least = sum(least_time_s(ops, nbytes, peak)[0] for ops, nbytes, _ in calls)
    return 100.0 * least / spent


def gram_mv_bound(trace: Trace, config: dict, peak: dict) -> dict:
    """Which roofline bound binds the window's Gram matvecs, for the log."""
    from .work import least_time_s

    calls = gram_mv_calls(trace, config["kernel"])
    bounds = {}
    for ops, nbytes, _ in calls:
        b = least_time_s(ops, nbytes, peak)[1]
        bounds[b] = bounds.get(b, 0) + 1
    return dict(calls=len(calls), seconds=sum(c[2] for c in calls), bound=bounds)
