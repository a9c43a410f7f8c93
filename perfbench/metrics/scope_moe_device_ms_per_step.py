"""Device time of the expert layers and the dense MLP per step: every op under
``decoder/moe/`` (route, experts, shared) or ``decoder/mlp_dense``."""
from perfbench import scope_trace


def read(run):
    return scope_trace.ms_per_step(
        run, lambda scope, which: scope.startswith("decoder/moe/") or scope == "decoder/mlp_dense")
