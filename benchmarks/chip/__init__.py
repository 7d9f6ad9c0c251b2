"""On-chip benchmark of the GP serving and fitting paths (see ``harness.py``
and ``PERF.md`` at the root of the repository)."""
