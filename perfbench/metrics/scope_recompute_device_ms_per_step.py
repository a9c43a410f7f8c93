"""Device time of the recomputed forward pass per step: every op of the step
program whose pass is ``recompute``, whatever its scope."""
from perfbench import scope_trace


def read(run):
    return scope_trace.ms_per_step(run, lambda scope, which: which == "recompute")
