"""Host time of one call of the input program (the ``dispatch_prepare`` span
inside ``step``): the median over the ledger windows inside the measured
window of each window's median call. A median, so the calls in which the
harness drains the device do not set it."""
import statistics


def window_median(run, key):
    values = [w[key]["p50"] for w in run.windows if key in w]
    return statistics.median(values) if values else None


def read(run):
    return window_median(run, "dispatch_prepare_ms")
