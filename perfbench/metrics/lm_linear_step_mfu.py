"""The whole step's share of the chip's peak for a Kimi-Linear decoder: model
FLOPs of one train step (``flops_linear.step_flops`` from the step's own
counters: the mixers' projections, the KDA recurrence priced in its chunkwise
form, the latent layer's attention over the keys its queries read, the held
pairs' experts, the shared expert, the dense MLP, router, head; x 3,
recomputation not counted) over the traced wall time per step times chips
times the bf16 peak. Wall, not device time: idle gaps count against it. None
where the configuration has no linear layers or the program writes no
decoder counters."""
from perfbench import flops_linear, lm_trace


def read(run):
    if run.trace is None or not run.trace_steps or not run.trace_wall_s:
        return None
    if "linear_attn_config" not in run.cell.config:
        return None
    seen = lm_trace.counters(run)
    if seen is None:
        return None
    work = flops_linear.step_flops(run.cell.config, run.cell.traffic,
                                   seen["keys_per_query"], seen["moe_pairs"])["total"]
    per_step = run.trace_wall_s / run.trace_steps
    return 100.0 * work / (per_step * run.cell.chips * run.peaks.bf16_flops)
