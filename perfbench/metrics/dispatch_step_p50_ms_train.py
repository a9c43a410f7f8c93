"""Host time of one call of the train step (the ``dispatch_step`` span inside
``step``): the median over the ledger windows inside the measured window of
each window's median call. A median, so the two calls in which the harness
drains the device and starts or stops the profiler do not set it; a call
that blocks at every step does."""
from perfbench.metrics.dispatch_prepare_p50_ms_train import window_median


def read(run):
    return window_median(run, "dispatch_step_ms")
