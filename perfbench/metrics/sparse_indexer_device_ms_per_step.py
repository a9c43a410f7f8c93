"""Device time of the indexer's score kernels per step: the scores forward (twice
where a layer is recomputed) and their gradients to the indexer's queries,
key and weights
(``lm_sparse_trace.part_of``)."""
from perfbench import lm_sparse_trace


def read(run):
    return lm_sparse_trace.part_ms_per_step(run, "indexer")
