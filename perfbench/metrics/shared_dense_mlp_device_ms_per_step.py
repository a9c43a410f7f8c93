"""Device time per step of what every share computes whole: the dense
layer's MLP and the sparse layers' shared experts (``lm_mixed_trace.part_of``:
ops that move their activations, or their matrices beside activations)."""
from perfbench import lm_mixed_trace


def read(run):
    return lm_mixed_trace.part_ms_per_step(run, "shared_dense")
