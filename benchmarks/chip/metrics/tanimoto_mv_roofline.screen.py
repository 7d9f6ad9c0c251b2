"""Roofline share of the fused Tanimoto matvecs in the traced window, in %
(see ``work_tanimoto.py``)."""
from benchmarks.chip.work_tanimoto import tanimoto_mv_roofline


def read(run):
    return tanimoto_mv_roofline(run.trace, run.peak)
