"""Device time of the vocabulary head and its loss per step: every op under
``decoder/head_loss``."""
from perfbench import scope_trace


def read(run):
    return scope_trace.ms_per_step(run, lambda scope, which: scope == "decoder/head_loss")
