"""Device time of the KDA recurrence's kernels per step: every op whose
innermost scope is ``decoder/attn_linear/kda`` (the forward and backward
kernels, and what their wrappers lay out around them), read by the program's
own scopes (``scope_trace``)."""
from perfbench import scope_trace

SCOPE = "decoder/attn_linear/kda"


def read(run):
    return scope_trace.ms_per_step(run, lambda scope, which: scope == SCOPE)
