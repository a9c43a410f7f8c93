"""The full layers' attention kernels against their roofline: the least time
over the (query, key) pairs the step counted on those layers' own head counts,
forward and backward (``flops_mixed.attention_floor_s``), over the device
time of the attention custom calls that move those layers' head groups, per
step (the forward runs twice where a layer is recomputed; the floor counts it
once). A kernel that computes the blocks the mask empties reads lower."""
from perfbench import flops_mixed, lm_mixed_trace

KIND, PART = "full_attention", "attention_full"


def read(run):
    parts, seen = lm_mixed_trace.part_seconds(run), lm_mixed_trace.counters(run)
    if parts is None or seen is None or not parts[0].get(PART):
        return None
    seconds, calls = parts
    floor = flops_mixed.attention_floor_s(run.cell.config, run.cell.traffic, KIND,
                                          seen["keys_per_query"][KIND], run.peaks)
    return 100.0 * floor / (seconds[PART] / calls)
