"""Share of the window's solve requests (sample and thompson_step) that
re-entered the solver from the warm-start cache (engine counters), in %."""


def read(run):
    c = run.runner.counters
    return 100.0 * c["warm_hits"] / c["solve_requests"] if c["solve_requests"] else None
