"""Device time of the expert layers per step: the grouped products and the
routing, sort, gather, activation and combine around them
(``lm_trace.part_of``)."""
from perfbench import lm_trace


def read(run):
    parts = lm_trace.part_seconds(run)
    if parts is None:
        return None
    seconds, calls = parts
    total = seconds.get("moe_experts", 0.0) + seconds.get("moe_other", 0.0)
    return 1e3 * total / calls if total else None
