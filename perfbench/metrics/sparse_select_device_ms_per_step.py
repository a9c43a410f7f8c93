"""Device time of the selection per step: the search for each query's
threshold and the search of its tie position over the ``[T, T]`` scores
(``lm_sparse_trace.part_of``)."""
from perfbench import lm_sparse_trace


def read(run):
    return lm_sparse_trace.part_ms_per_step(run, "select")
