"""Share of a sparse layer's ``[T, T]`` (query, key column) grid that the
selection's counts ran over, in per cent (``step_window.sparse_select_columns``,
a sequence's columns of all sparse layers together, over sparse layers x T x T;
the mean of the ledger windows inside the measured window): 100 for a search
over whole rows, about half where a block of queries stops at its last
query's own position, less where it also starts at its first document's
start. None where the program writes no such field."""
from perfbench import flops_sparse


def read(run):
    columns = [w["sparse_select_columns"] for w in run.windows if "sparse_select_columns" in w]
    layers = flops_sparse.sparse_layers(run.cell.config)
    if not columns or not layers:
        return None
    t = int(run.cell.traffic["sequence_length"])
    return 100.0 * sum(columns) / len(columns) / (layers * t * t)
