"""Time of the collective ops on device 0 during which no other op ran
there, per step. Cells on one chip have no collectives: nothing to read."""
from perfbench import xtrace as trace_lib


def read(run):
    if run.trace is None or run.cell.chips < 2 or not run.trace_steps:
        return None
    return 1e3 * trace_lib.exposed_collective_s(run.trace) / run.trace_steps
