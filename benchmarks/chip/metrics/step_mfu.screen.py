"""The whole window's share of the chip's peak, in %: the operations of the
window's Tanimoto matvecs (counted from their shapes, ``work_tanimoto.py``)
over the traced window's length times the peak FLOP/s. The feature kernel's
work is left out, so this is a lower bound; nothing when no Tanimoto matvec
ran."""
from benchmarks.chip.work_tanimoto import tanimoto_mv_calls


def read(run):
    calls = tanimoto_mv_calls(run.trace)
    if not calls:
        return None
    ops = sum(c[0] for c in calls)
    return 100.0 * ops / (run.trace.window_s() * run.peak["flops_per_s"])
