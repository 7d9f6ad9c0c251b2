"""One run of one benchmark cell.

``python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` looks the cell up in ``BENCHMARK.json``, loads its
configuration (``configs/<config>.json``), its traffic mix
(``traffic/<mix>.json``) and the generator of the mix's kind
(``traffic/kinds/<kind>.py``), refuses a host without enough TPU chips, and
then:

1. set-up: the generator's ``setup()`` (data from the seed, the system under
   test, warm-up of the cell's own shapes); ``setup_s`` runs from process
   start to the end of it;
2. the window: the generator's ``window(seconds)``, under the profiler when
   ``--trace 1``; compiles inside it are counted and printed;
3. ``memory_peak_bytes`` of the fullest chip, then the generator frees the
   system's state and runs ``check()``: the timed path's answers against the
   plain reference, each number beside its limit;
4. the last line of standard output: ``correct``, ``attempted``, ``failed``,
   ``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
   per-layer metrics, each read by ``metrics/<name>.py``, with ``--trace 1``),
   ``device`` and, traced, ``breakdown``; ``checks`` comes last.

Adding a cell, a configuration, a mix, a kind of traffic or a per-layer
metric takes new files and new entries in ``BENCHMARK.json``, never an edit
here.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


class BenchError(RuntimeError):
    """The run cannot be made as asked (unknown cell, missing file, no chip)."""


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def nearest_rank(values, q: float) -> float:
    """Nearest-rank percentile: the ceil(q/100 N)-th smallest value."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    return float(xs[min(len(xs), max(1, math.ceil(q / 100.0 * len(xs)))) - 1])


class Bench:
    """``BENCHMARK.json`` and the files it names, found by name."""

    def __init__(self, root: Path = ROOT, bench_dir: Path = BENCH_DIR):
        self.root = Path(root)
        self.dir = Path(bench_dir)
        spec_path = self.root / "BENCHMARK.json"
        if not spec_path.is_file():
            raise BenchError(f"no BENCHMARK.json at {self.root}")
        self.spec = json.loads(spec_path.read_text())

    @staticmethod
    def _named(entries, name, what):
        for e in entries:
            if e["name"] == name:
                return e
        raise BenchError(f"unknown {what} {name!r}; known: "
                         f"{[e['name'] for e in entries]}")

    def cell(self, name: str) -> dict:
        return self._named(self.spec["workloads"], name, "workload")

    def config(self, name: str) -> dict:
        entry = self._named(self.spec["configs"], name, "configuration")
        path = self.root / entry["file"]
        if not path.is_file():
            raise BenchError(f"configuration file {entry['file']} is missing")
        return json.loads(path.read_text())

    def traffic(self, mix: str) -> dict:
        path = self.dir / "traffic" / f"{mix}.json"
        if not path.is_file():
            raise BenchError(f"no traffic mix file {path}")
        return json.loads(path.read_text())

    def kind(self, kind: str):
        return _load_module(self.dir / "traffic" / "kinds" / f"{kind}.py",
                            f"bench_kind_{kind}")

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", ())
                or ("workloads" not in m and m["moves"] in e2e)]

    def reader(self, metric: str):
        path = self.dir / "metrics" / f"{metric}.py"
        return _load_module(path, "bench_metric_" + metric.replace(".", "_")).read


def require_devices(chips: int):
    """The first ``chips`` TPU devices; refuses any other platform or fewer
    chips (there is no fallback to the CPU)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devs[0].platform} "
                         f"({devs[0].device_kind}); no fallback")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``: a fixed
    path inside the checkout, whatever the environment names, so that only a
    checkout's first run compiles and two checkouts share nothing. Every
    program is cached, however quickly it compiles."""
    import jax

    path = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class Run:
    """What one run hands the per-layer metric readers: the generator (its
    window counters and records), the reduced trace, the configuration and
    the cell."""

    def __init__(self, cell, config, runner, trace, peak):
        self.cell = cell
        self.config = config
        self.runner = runner
        self.trace = trace
        self.peak = peak


def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, control=None, log=print) -> dict:
    """One run of ``workload``; returns the result line as a dict."""
    import jax

    from . import trace as trace_mod
    from .monitor import CompileMonitor, Spans
    from .work import peaks

    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    devices = require_devices(int(cell["chips"]))
    peak = peaks(devices[0].device_kind)

    log(f"compile cache: {enable_cache(bench.root)}")
    monitor = CompileMonitor()
    spans = Spans()
    runner = bench.kind(traffic["kind"]).Runner(
        config=config, traffic=traffic, seed=seed, devices=devices,
        spans=spans, monitor=monitor, log=log, control=control)
    runner.setup()
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.3f} s, {monitor.snapshot()}")

    trace_dir = bench.root / ".bench_trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    before = monitor.snapshot()
    with spans.span("window"):
        outcome = runner.window(seconds)
    inside = CompileMonitor.delta(before, monitor.snapshot())
    if trace:
        jax.profiler.stop_trace()
    log(f"inside the window: compiles={inside['compiles']} "
        f"compile_s={inside['compile_s']:.3f} cache_hits={inside['cache_hits']} "
        f"cache_misses={inside['cache_misses']}")

    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    runner.release()
    gc.collect()
    checks = runner.check()
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and all(math.isfinite(c["value"]) for c in checks.values())

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": int(outcome["attempted"]),
              "failed": int(outcome["failed"])}
    if trace:
        reduced = trace_mod.load(trace_dir, devices=len(devices))
        run = Run(cell, config, runner, reduced, peak)
        metrics = {}
        for m in bench.per_layer(workload):
            value = bench.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s()
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = reduced.breakdown()
        log(f"gram matvec bound: {trace_mod.gram_mv_bound(reduced, config, peak)}")
    else:
        values = dict(outcome["metrics"], setup_s=setup_s)
        metrics = {}
        for m in bench.end_to_end(workload):
            if m["name"] not in values:
                raise BenchError(f"the {traffic['kind']} generator reports no "
                                 f"{m['name']!r}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None, *, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(message):
        print(f"[bench] {message}", file=sys.stderr, flush=True)

    try:
        result = run_cell(Bench(), args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start, log=log)
    except BenchError as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in result["checks"].items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
