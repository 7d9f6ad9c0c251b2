"""Device time of the Tanimoto random-feature kernel (``tanimoto_rf_pallas``,
the prior paths of every predict block) over the window's busy device time,
in %; nothing when the kernel never ran."""
from benchmarks.chip.work_tanimoto import RF_KERNEL


def read(run):
    calls = run.trace.kernel_calls(RF_KERNEL)
    busy = run.trace.busy_s()
    if not calls or busy <= 0:
        return None
    return 100.0 * sum(secs for _, secs in calls) / busy
