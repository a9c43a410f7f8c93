"""The whole step's share of the chip's peak for a decoder of mixed layers:
model FLOPs of one train step (``flops_mixed.step_flops`` from the step's own
counters: projections with each layer's own head count, the gate, attention
over the keys the queries read, the held pairs' experts, the shared expert,
the dense MLP, router, head; x 3, recomputation not counted) over the traced
wall time per step times chips times the bf16 peak. Wall, not device time:
idle gaps count against it."""
from perfbench import flops_mixed, lm_mixed_trace


def read(run):
    if run.trace is None or not run.trace_steps or not run.trace_wall_s:
        return None
    if not run.cell.config.get("num_attention_heads_per_layer"):
        return None
    seen = lm_mixed_trace.counters(run)
    if seen is None:
        return None
    work = flops_mixed.step_flops(run.cell.config, run.cell.traffic,
                                  seen["keys_per_query"], seen["moe_pairs"])["total"]
    per_step = run.trace_wall_s / run.trace_steps
    return 100.0 * work / (per_step * run.cell.chips * run.peaks.bf16_flops)
