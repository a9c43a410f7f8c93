"""Device time of the indexer's loss per step: the attention's
probabilities summed over the heads against the scores' softmax over the
selection, the loss's gradient to the scores and its scaling by the loss's
cotangent (``lm_sparse_trace.part_of``)."""
from perfbench import lm_sparse_trace


def read(run):
    return lm_sparse_trace.part_ms_per_step(run, "align")
