"""Roofline share of the fused Gram matvecs in the traced window, in %
(see ``trace.gram_mv_roofline`` and ``work.py``)."""
from benchmarks.chip.trace import gram_mv_roofline


def read(run):
    return gram_mv_roofline(run.trace, run.config["kernel"], run.peak)
