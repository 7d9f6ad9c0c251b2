"""Mean wait of a request from ``submit`` to the start of its batch, in ms,
over the window's ``gp.counters`` events (engine clock): the sum of their
``queue_wait_ms`` over the sum of their ``requests``."""
from benchmarks.chip import spans


def read(run):
    counters = [st for _, _, _, st in spans.named(run, "gp.counters")]
    started = sum(st.get("requests", 0) for st in counters)
    if not started:
        return None
    return sum(st.get("queue_wait_ms", 0.0) for st in counters) / started
