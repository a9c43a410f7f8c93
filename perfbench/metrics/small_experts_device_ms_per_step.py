"""Device time of the routed experts per step: the grouped products and the
ops around them over the sorted pair buffer's segments — routing, sort,
gathers, activation, the rows' sum into their tokens
(``lm_mixed_trace.part_of``)."""
from perfbench import lm_mixed_trace


def read(run):
    return lm_mixed_trace.part_ms_per_step(run, "experts", "experts_other")
