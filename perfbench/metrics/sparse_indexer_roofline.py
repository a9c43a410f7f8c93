"""The indexer's score kernels against their roofline: the least time over the
visible pairs forward and the selected pairs backward
(``flops_sparse.indexer_floor_s``) over the device time of the ops that
compute the scores and their gradients, per step (the forward runs twice
where a layer is recomputed; the floor counts it once)."""
from perfbench import flops_sparse, lm_sparse_trace


def read(run):
    parts, seen = lm_sparse_trace.part_seconds(run), lm_sparse_trace.counters(run)
    if parts is None or seen is None or not parts[0].get("indexer"):
        return None
    seconds, calls = parts
    floor = flops_sparse.indexer_floor_s(run.cell.config, run.cell.traffic, seen["scored"],
                                         seen["selected"], run.peaks)
    return 100.0 * floor / (seconds["indexer"] / calls)
