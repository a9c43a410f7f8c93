"""Device time of the latent layers' attention kernels per step: every op
whose innermost scope is ``decoder/attn_latent/attend`` (``scope_trace``)."""
from perfbench import scope_trace

SCOPE = "decoder/attn_latent/attend"


def read(run):
    return scope_trace.ms_per_step(run, lambda scope, which: scope == SCOPE)
