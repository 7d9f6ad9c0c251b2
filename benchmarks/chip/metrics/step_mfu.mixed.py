"""The whole window's share of the chip's peak, in %: the operations of the
window's Gram matvecs (counted from their shapes, ``work.py``) over the
traced window's length times the peak FLOP/s. Other kernels' work is left
out (under 2% of the device time on ``pol.mixed``), so this is a lower
bound; nothing when no Gram matvec ran."""
from benchmarks.chip.trace import gram_mv_calls


def read(run):
    calls = gram_mv_calls(run.trace, run.config["kernel"])
    if not calls:
        return None
    ops = sum(c[0] for c in calls)
    return 100.0 * ops / (run.trace.window_s() * run.peak["flops_per_s"])
