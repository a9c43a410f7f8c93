"""The grouped expert products against their roofline where a held expert's
group is about one row tile: the least time the chip could take over them
(nine products a sparse layer over the pairs the step counted;
``flops_mixed.experts_floor_s``) over the device time of the custom calls that
move the held experts' matrices (``gmm``, ``tgmm``), per step."""
from perfbench import flops_mixed, lm_mixed_trace


def read(run):
    parts, seen = lm_mixed_trace.part_seconds(run), lm_mixed_trace.counters(run)
    if parts is None or seen is None or not parts[0].get("experts"):
        return None
    seconds, calls = parts
    floor = flops_mixed.experts_floor_s(run.cell.config, seen["moe_pairs"], run.peaks)
    return 100.0 * floor / (seconds["experts"] / calls)
