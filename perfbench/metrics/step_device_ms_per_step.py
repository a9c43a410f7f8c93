"""Device time of the train-step program per step, from the trace's modules
line."""
from perfbench import xtrace as trace_lib


def read(run):
    if run.trace is None:
        return None
    seconds, calls = trace_lib.module_time_s(run.trace, run.cell.traffic["programs"]["step"])
    return 1e3 * seconds / calls if calls else None
