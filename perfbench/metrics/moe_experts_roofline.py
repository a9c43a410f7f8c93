"""The grouped expert products against their roofline: the least time the
chip could take over them (nine products a layer — gate, up, down, each
forward and both backward — over the pairs the step counted;
``flops_lm.experts_floor_s``) over the device time of the custom calls that
move the held experts' matrices, per step. Rows the kernel computes for
nothing, or tiles it leaves half empty, read lower."""
from perfbench import flops_lm, lm_trace


def read(run):
    parts, seen = lm_trace.part_seconds(run), lm_trace.counters(run)
    if parts is None or seen is None or not parts[0].get("moe_experts"):
        return None
    seconds, calls = parts
    floor = flops_lm.experts_floor_s(run.cell.config, seen["moe_pairs"], run.peaks)
    return 100.0 * floor / (seconds["moe_experts"] / calls)
