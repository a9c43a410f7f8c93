"""Device time of the window layers' attention kernels per step
(``lm_mixed_trace.part_of``: the custom calls that move those layers' head
groups)."""
from perfbench import lm_mixed_trace


def read(run):
    return lm_mixed_trace.part_ms_per_step(run, "attention_window")
