"""The most loaded held expert's tokens over the mean, worst layer and worst
ledger window inside the measured window (``step_window.moe_load_max_over_mean``,
the program's counter)."""
from perfbench import lm_trace


def read(run):
    seen = lm_trace.counters(run)
    return None if seen is None else seen["load_max_over_mean"]
