"""Share of the shared solves' right-hand-side columns that carry requests
rather than bucket padding, over the window (engine counters), in %."""


def read(run):
    c = run.runner.counters
    total = c["rhs_columns"] + c["padded_columns"]
    return 100.0 * c["rhs_columns"] / total if total else None
