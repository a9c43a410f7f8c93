"""Device time of the optimizer's update per step: every op of the step program
whose innermost scope is ``optimizer`` (``tx.update`` and ``apply_updates``)."""
from perfbench import scope_trace


def read(run):
    return scope_trace.ms_per_step(run, lambda scope, which: scope == "optimizer")
