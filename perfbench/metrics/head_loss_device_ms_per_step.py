"""Device time of the vocabulary head and its loss per step: every op that
moves logits or the head's matrix beside activations."""
from perfbench import lm_trace


def read(run):
    parts = lm_trace.part_seconds(run)
    if parts is None or not parts[0].get("head_loss"):
        return None
    return 1e3 * parts[0]["head_loss"] / parts[1]
