"""Device time of the KDA layers' mixers per step, whole: every op under
``decoder/attn_linear`` — projections, short convolutions, decay and beta,
the recurrence's kernels and the gated output norm (``scope_trace``)."""
from perfbench import scope_trace


def read(run):
    return scope_trace.ms_per_step(run, lambda scope, which: scope.startswith("decoder/attn_linear"))
