"""Mean wall time of the window's ``gp.thompson.ascent`` spans, in ms: one
Thompson request's multi-start ascent and its evaluation, host and device."""
from benchmarks.chip import spans


def read(run):
    ascents = spans.named(run, "gp.thompson.ascent")
    if not ascents:
        return None
    return sum(b - a for _, a, b, _ in ascents) / len(ascents) / 1e6
