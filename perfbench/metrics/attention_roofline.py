"""The attention kernels against their roofline: the least time over the
unmasked (query, key) pairs the step counted, forward and backward
(``flops_lm.attention_floor_s``), over the device time of the attention
custom calls, per step. A kernel that computes the blocks the mask empties
reads lower."""
from perfbench import flops_lm, lm_trace


def read(run):
    parts, seen = lm_trace.part_seconds(run), lm_trace.counters(run)
    if parts is None or seen is None or not parts[0].get("attention"):
        return None
    seconds, calls = parts
    floor = flops_lm.attention_floor_s(run.cell.config, run.cell.traffic,
                                       seen["keys_per_query"], run.peaks)
    return 100.0 * floor / (seconds["attention"] / calls)
