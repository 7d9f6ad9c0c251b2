"""Share of the traced window in which no operation ran on the chip, in %."""


def read(run):
    return 100.0 * run.trace.idle_share()
