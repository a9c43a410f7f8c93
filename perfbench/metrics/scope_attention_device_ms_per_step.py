"""Device time of the attention blocks per step, whole: every op under a
``decoder/attn_*`` scope — kernels, projections, rotary, gate, and a sparse
layer's indexer, selection and alignment loss."""
from perfbench import scope_trace


def read(run):
    return scope_trace.ms_per_step(run, lambda scope, which: scope.startswith("decoder/attn_"))
