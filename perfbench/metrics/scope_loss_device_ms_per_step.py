"""Device time of the task's loss per step, forward and backward: every op
under ``loss`` (the Lovasz hinge and its two sorts in the segmentation cell)."""
from perfbench import scope_trace


def read(run):
    return scope_trace.ms_per_step(run, lambda scope, which: scope == "loss")
