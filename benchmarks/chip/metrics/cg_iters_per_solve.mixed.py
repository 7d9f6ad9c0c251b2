"""CG iterations per shared solve over the window (engine counters)."""


def read(run):
    c = run.runner.counters
    return c["solver_iterations"] / c["solves"] if c["solves"] else None
