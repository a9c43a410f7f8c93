"""Device time of the trainer's jitted input program (the on-device
augmentation) per train step, from the trace's modules line; the program is
found by the name the cell's traffic file gives."""
from perfbench import xtrace as trace_lib


def read(run):
    if run.trace is None or not run.trace_steps:
        return None
    seconds, calls = trace_lib.module_time_s(run.trace, run.cell.traffic["programs"]["prepare"])
    return 1e3 * seconds / calls if calls else None
