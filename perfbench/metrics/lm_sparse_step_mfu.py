"""The whole sparse decoder step's share of the chip's peak: model FLOPs of
one train step (``flops_sparse.step_flops`` from the step's own counters:
projections, the indexer's scores over the visible pairs forward and the
selected pairs backward, attention over the selected pairs, the expert pairs
routed here, router, head; recomputation not counted) over the traced wall
time per step times chips times the bf16 peak. Wall, not device time: idle
gaps count against it."""
from perfbench import flops_sparse, lm_sparse_trace


def read(run):
    if run.trace is None or not run.trace_steps or not run.trace_wall_s:
        return None
    seen = lm_sparse_trace.counters(run)
    if seen is None:
        return None
    work = flops_sparse.step_flops(run.cell.config, run.cell.traffic, seen["scored"],
                                   seen["selected"], seen["moe_pairs"])["total"]
    per_step = run.trace_wall_s / run.trace_steps
    return 100.0 * work / (per_step * run.cell.chips * run.peaks.bf16_flops)
