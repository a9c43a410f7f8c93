"""The whole step's share of the chips' peak: model FLOPs of one train step
(forward and backward, from the configuration's layer table) over the traced
wall time per step times chips times the bf16 peak. Wall, not device time:
idle gaps and the input program count against it."""
from perfbench import flops


def read(run):
    if run.trace is None or not run.trace_steps or not run.trace_wall_s:
        return None
    per_step = run.trace_wall_s / run.trace_steps
    work = flops.step_flops(run.cell.reference_cfg, run.global_batch)
    return 100.0 * work / (per_step * run.cell.chips * run.peaks.bf16_flops)
