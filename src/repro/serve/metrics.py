"""Cumulative engine counters, host spans and JIT accounting.

Plain host-side Python counters — the engine loop is host code (like any
continuous-batching server); everything device-side stays in the solver's own
``SolveResult``/runtime-matvec accounting. ``EngineStats.snapshot()`` is the
one read path, used by ``GPEngine.stats()``, the serving benchmark, and the
engine tests, so the three can never disagree about what a counter means.

Spans: :func:`span` opens a ``jax.profiler.TraceAnnotation`` named ``gp.<phase>``
(recorded only while a profiler session is open, on the same clock as the
device's events) and pushes the phase on a thread-local stack.
``EngineStats.span`` also adds the phase's wall seconds and call count to
``phase_s``/``phase_calls``. A span never waits on the device: its metadata
is host numbers the caller already has.

JIT accounting: one process-wide ``jax.monitoring`` listener, registered on
first use, adds every trace, lowering and backend compile (which in JAX 0.9
includes a persistent-cache load) and every persistent-cache hit and miss to
the innermost phase open on the recording thread, or to ``(none)``.
:func:`jit_totals` gives the process-wide table; an engine's own phases also
land in its ``EngineStats.jit``.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Dict

import jax

#: phase of a JIT event recorded while no span is open on its thread
UNATTRIBUTED = "(none)"
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_LOCAL = threading.local()
_PROCESS_JIT: Dict[str, "JitCounts"] = {}
_LISTENING = False
_LOCK = threading.Lock()


@dataclasses.dataclass
class JitCounts:
    """Tracing, lowering and compile work of one phase."""

    trace_s: float = 0.0
    lower_s: float = 0.0
    compile_s: float = 0.0  # backend compiles and persistent-cache loads
    compiles: int = 0  # backend_compile_duration events
    cache_hits: int = 0
    cache_misses: int = 0

    def add(self, field: str, amount: float) -> None:
        setattr(self, field, getattr(self, field) + amount)
        if field == "compile_s":
            self.compiles += 1


def _stack() -> list:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


def _record(field: str, amount: float) -> None:
    stack = getattr(_LOCAL, "stack", None)
    phase, owner = stack[-1] if stack else (UNATTRIBUTED, None)
    with _LOCK:  # threads that trace at once share the process table
        _PROCESS_JIT.setdefault(phase, JitCounts()).add(field, amount)
    if owner is not None:
        owner.jit.setdefault(phase, JitCounts()).add(field, amount)
        owner._pending_jit.add(field, amount)


def _on_duration(event, secs, **_):
    field = _DURATIONS.get(event)
    if field is not None:
        _record(field, secs)


def _on_event(event, **_):
    field = _EVENTS.get(event)
    if field is not None:
        _record(field, 1)


def ensure_jit_listener() -> None:
    """Register the process-wide JIT listeners (once)."""
    global _LISTENING
    with _LOCK:
        if not _LISTENING:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _LISTENING = True


def jit_totals() -> Dict[str, dict]:
    """Process-wide JIT work per phase (every span, engine or not)."""
    with _LOCK:
        return {p: dataclasses.asdict(c) for p, c in _PROCESS_JIT.items()}


class _Span:
    """``gp.<phase>`` on the profiler's host line and on the phase stack;
    with an ``owner`` (an :class:`EngineStats`) also its wall time."""

    __slots__ = ("phase", "owner", "annotation", "t0")

    def __init__(self, phase: str, owner, meta: dict):
        self.phase = phase
        self.owner = owner
        self.annotation = jax.profiler.TraceAnnotation("gp." + phase, **meta)

    def __enter__(self):
        _stack().append((self.phase, self.owner))
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        _stack().pop()
        owner = self.owner
        if owner is not None:
            owner.phase_s[self.phase] = owner.phase_s.get(self.phase, 0.0) + wall
            owner.phase_calls[self.phase] = owner.phase_calls.get(self.phase, 0) + 1
        return False


def span(phase: str, **meta) -> _Span:
    """Context manager: the host span ``gp.<phase>`` with ``meta`` as its
    profiler metadata; JIT work inside it is credited to ``phase``."""
    ensure_jit_listener()
    return _Span(phase, None, meta)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]); 0.0 on empty input.

    Textbook nearest-rank: the ``max(⌈q/100 · N⌉, 1)``-th smallest value
    (clamped to N, so q=0 → the minimum and q=100 → the maximum). The previous
    implementation rounded an interpolation index with ``int(round(...))``,
    which goes through Python's round-half-even — biasing small-sample
    quantiles (e.g. p50 of N=4 picked the 3rd element, p50 of N=100 the 51st
    instead of the 50th), exactly where serving latency windows are small.
    """
    if not values:
        return 0.0
    xs = sorted(values)
    rank = min(len(xs), max(1, math.ceil(q / 100.0 * len(xs))))
    return float(xs[rank - 1])


@dataclasses.dataclass
class EngineStats:
    """Monotone counters for one engine's lifetime.

    ``iterations_saved_warm`` is the headline warm-start number: for every
    warm-batch solve, the iteration gap to the most recent *cold* solve of the
    same request kind (clamped at zero).

    Refit accounting (``add_observations``): ``refits`` counts posterior
    updates applied by ANY path; the full-refit path adds its solve iterations
    to ``refit_iterations``, the rank-k path adds its correction-solve
    iterations/matvecs to ``lowrank_iterations``/``lowrank_matvecs`` (k solve
    columns at the OLD n, + one certification matvec). ``compactions`` counts
    ``auto``-policy fallbacks to a full warm refit after the certified drift
    exceeded its budget; ``last_refit_rel_residual`` is the most recent
    update's max true relative residual against the extended operator.

    ``refit_iterations_saved`` credits each WARM full refit against
    ``refit_baseline_iters`` — the most recent COLD solve of the fit system
    (the engine's initial fit, or any ``warm=False`` refit), re-baselined
    whenever one occurs; ``refit_baseline_n`` records the n it was measured
    at. Cold iteration counts are non-decreasing in n at a fixed spec, so a
    baseline measured at a smaller n can only UNDERSTATE savings — the counter
    is a clamped lower bound, never an overstatement (exact lowrank-vs-full
    economics are measured in ``bench_serve``'s write-heavy section instead).

    ``cache_purged`` counts warm-start cache entries dropped because their
    ``hypers_key`` was superseded by a refit re-key (they were unreachable but
    still held LRU slots).

    Every field is a scalar or a dict keyed by a fixed set of names (kinds,
    groups, phases), so nothing grows with the number of requests served.
    ``queue_wait_s`` / ``queued_requests`` give the mean wait from ``submit``
    to batch start; ``phase_s`` / ``phase_calls`` the host time of each
    ``gp.*`` span; ``jit`` the tracing, lowering and compile work inside each
    (``snapshot()["jit"]["(none)"]`` is the process's work outside any span).
    """

    requests_submitted: int = 0
    requests_served: Dict[str, int] = dataclasses.field(default_factory=dict)
    steps: int = 0
    batches: Dict[str, int] = dataclasses.field(default_factory=dict)
    solves: int = 0
    rhs_columns: int = 0  # real RHS columns batched through shared solves
    padded_columns: int = 0  # bucket padding columns on top of them
    solver_iterations: int = 0
    solver_matvecs: int = 0
    warm_hits: int = 0
    warm_misses: int = 0
    iterations_saved_warm: int = 0
    refits: int = 0  # posterior updates applied, any path
    refit_iterations: int = 0  # full-refit solve iterations
    refit_iterations_saved: int = 0  # vs refit_baseline_iters (see docstring)
    refit_baseline_n: int = 0  # n at which the cold baseline was measured
    refit_baseline_iters: int = 0  # iterations of that cold fit-system solve
    lowrank_updates: int = 0  # rank-k bordered updates accepted
    lowrank_rows: int = 0  # observation rows appended via the rank-k path
    lowrank_iterations: int = 0  # correction-solve iterations (k cols, old n)
    lowrank_matvecs: int = 0  # correction-solve matvecs + certification matvecs
    compactions: int = 0  # auto-policy fallbacks to a full warm refit
    cache_purged: int = 0  # stale-key warm-cache entries dropped on re-key
    last_refit_rel_residual: float = 0.0  # latest update's certified drift
    predict_rows: int = 0
    predict_padded_rows: int = 0
    # rows the engine passed to the prior's feature map (fit, solve
    # right-hand sides, predict and sample blocks with their padding)
    feature_rows: int = 0
    # fault-tolerance counters (docs/robustness.md): every failure-handling
    # decision the engine takes is visible here, so chaos tests and the
    # serve_gp --json driver can assert on exactly what happened
    deadline_misses: int = 0  # requests expired before execution
    shed: int = 0  # requests rejected at submit (queue over threshold)
    degraded: int = 0  # sample requests downgraded to predict under overload
    retries: int = 0  # batch execution retries (exec-level exceptions)
    escalations: int = 0  # flagged requests re-run solo via solve_robust
    quarantined: int = 0  # submits refused: (kind, seed) exceeded its strikes
    failed: int = 0  # completions delivered with a structured error
    # scheduler: requests started in a batch, and their summed wait from
    # submit to batch start on the engine clock
    queued_requests: int = 0
    queue_wait_s: float = 0.0
    # host phases (``span``): wall seconds and calls, and JIT work per phase
    phase_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    phase_calls: Dict[str, int] = dataclasses.field(default_factory=dict)
    jit: Dict[str, JitCounts] = dataclasses.field(default_factory=dict)
    # what the next ``gp.counters`` event reports
    _pending_requests: int = dataclasses.field(default=0, init=False,
                                               repr=False)
    _pending_wait_s: float = dataclasses.field(default=0.0, init=False,
                                               repr=False)
    _pending_jit: JitCounts = dataclasses.field(default_factory=JitCounts,
                                                init=False, repr=False)

    def __post_init__(self):
        ensure_jit_listener()

    def span(self, phase: str, **meta) -> _Span:
        """:func:`span`, with the phase's wall time and JIT work credited to
        this engine."""
        return _Span(phase, self, meta)

    def batch_started(self, requests: int, wait_s: float) -> None:
        """A batch of ``requests`` started after ``wait_s`` summed queueing."""
        self.queued_requests += requests
        self.queue_wait_s += wait_s
        self._pending_requests += requests
        self._pending_wait_s += wait_s

    def emit_counters(self) -> None:
        """One zero-length ``gp.counters`` profiler event holding what
        happened since the previous one: requests started in a batch, their
        summed queue wait, and the JIT work inside this engine's spans."""
        j = self._pending_jit
        with jax.profiler.TraceAnnotation(
            "gp.counters",
            requests=self._pending_requests,
            queue_wait_ms=1e3 * self._pending_wait_s,
            trace_s=j.trace_s,
            lower_s=j.lower_s,
            compile_s=j.compile_s,
            compiles=j.compiles,
        ):
            pass
        self._pending_requests = 0
        self._pending_wait_s = 0.0
        self._pending_jit = JitCounts()

    def bump_kind(self, kind: str, n: int = 1) -> None:
        self.requests_served[kind] = self.requests_served.get(kind, 0) + n

    def bump_batch(self, group: str) -> None:
        self.batches[group] = self.batches.get(group, 0) + 1

    def snapshot(self) -> dict:
        """A JSON-ready view — the contract shared by ``GPEngine.stats()``,
        ``benchmarks/bench_serve.py`` and the engine tests."""
        jit = dict(self.jit)
        if UNATTRIBUTED in _PROCESS_JIT:
            jit[UNATTRIBUTED] = _PROCESS_JIT[UNATTRIBUTED]
        return {
            "requests_submitted": self.requests_submitted,
            "requests_served": dict(self.requests_served),
            "steps": self.steps,
            "batches": dict(self.batches),
            "solves": self.solves,
            "rhs_columns": self.rhs_columns,
            "padded_columns": self.padded_columns,
            "solver_iterations": self.solver_iterations,
            "solver_matvecs": self.solver_matvecs,
            "warm_hits": self.warm_hits,
            "warm_misses": self.warm_misses,
            "iterations_saved_warm": self.iterations_saved_warm,
            "refits": self.refits,
            "refit_iterations": self.refit_iterations,
            "refit_iterations_saved": self.refit_iterations_saved,
            "refit_baseline_n": self.refit_baseline_n,
            "refit_baseline_iters": self.refit_baseline_iters,
            "lowrank_updates": self.lowrank_updates,
            "lowrank_rows": self.lowrank_rows,
            "lowrank_iterations": self.lowrank_iterations,
            "lowrank_matvecs": self.lowrank_matvecs,
            "compactions": self.compactions,
            "cache_purged": self.cache_purged,
            "last_refit_rel_residual": self.last_refit_rel_residual,
            "predict_rows": self.predict_rows,
            "predict_padded_rows": self.predict_padded_rows,
            "feature_rows": self.feature_rows,
            "deadline_misses": self.deadline_misses,
            "shed": self.shed,
            "degraded": self.degraded,
            "retries": self.retries,
            "escalations": self.escalations,
            "quarantined": self.quarantined,
            "failed": self.failed,
            "queued_requests": self.queued_requests,
            "queue_wait_s": self.queue_wait_s,
            "queue_wait_mean_s": (
                self.queue_wait_s / self.queued_requests
                if self.queued_requests else 0.0
            ),
            "phases": {
                p: {"wall_s": s, "calls": self.phase_calls[p]}
                for p, s in self.phase_s.items()
            },
            "jit": {p: dataclasses.asdict(c) for p, c in jit.items()},
        }
