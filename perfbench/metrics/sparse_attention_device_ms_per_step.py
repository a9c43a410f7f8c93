"""Device time of the kernels that attend over the selection per step:
forward (twice where a layer is recomputed), dq and dkv
(``lm_sparse_trace.part_of``)."""
from perfbench import lm_sparse_trace


def read(run):
    return lm_sparse_trace.part_ms_per_step(run, "attention")
