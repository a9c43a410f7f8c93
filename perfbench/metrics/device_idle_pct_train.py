"""Share of the traced window in which no op ran on the fullest device."""
from perfbench import xtrace as trace_lib


def read(run):
    if run.trace is None or not run.trace_wall_s:
        return None
    return 100.0 * (1.0 - trace_lib.fullest_busy_s(run.trace) / run.trace_wall_s)
