"""Attention over the selection against its roofline: the least time over the
*selected* (query, key) pairs, forward and backward
(``flops_sparse.attention_floor_s``), over the device time of the kernels
that attend, per step. A kernel that computes every visible pair and masks
the rest reads low here: that is the finding a kernel that reads only the
selected keys starts from."""
from perfbench import flops_sparse, lm_sparse_trace


def read(run):
    parts, seen = lm_sparse_trace.part_seconds(run), lm_sparse_trace.counters(run)
    if parts is None or seen is None or not parts[0].get("attention"):
        return None
    seconds, calls = parts
    floor = flops_sparse.attention_floor_s(run.cell.config, run.cell.traffic, seen["selected"],
                                           run.peaks)
    return 100.0 * floor / (seconds["attention"] / calls)
