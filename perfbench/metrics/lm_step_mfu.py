"""The whole decoder step's share of the chip's peak: model FLOPs of one train
step (projections, the attention pairs the mask leaves, the expert pairs
routed here, router, head; x 3, recomputation not counted:
``flops_lm.step_flops`` from the step's own counters) over the traced wall
time per step times chips times the bf16 peak. Wall, not device time: idle
gaps count against it."""
from perfbench import flops_lm, lm_trace


def read(run):
    if run.trace is None or not run.trace_steps or not run.trace_wall_s:
        return None
    seen = lm_trace.counters(run)
    if seen is None:
        return None
    work = flops_lm.step_flops(run.cell.config, run.cell.traffic,
                               seen["keys_per_query"], seen["moe_pairs"])["total"]
    per_step = run.trace_wall_s / run.trace_steps
    return 100.0 * work / (per_step * run.cell.chips * run.peaks.bf16_flops)
