"""Device time of the vocabulary head and its loss per step in a cell of
mixed layers: every op that moves logits or the head's matrix beside
activations (``lm_trace.part_of``'s rule, through ``lm_mixed_trace``)."""
from perfbench import lm_mixed_trace


def read(run):
    return lm_mixed_trace.part_ms_per_step(run, "head_loss")
