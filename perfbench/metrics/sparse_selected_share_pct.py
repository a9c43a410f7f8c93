"""Share of the visible (query, key) pairs that the queries read, in per cent
(``step_window.sparse_pairs_selected`` over ``sparse_pairs_scored``, the
ledger windows inside the measured window): 23.4 for one whole 16,384-token
document at ``topk`` 2,048, higher where a sequence holds shorter ones."""
from perfbench import lm_sparse_trace


def read(run):
    seen = lm_sparse_trace.counters(run)
    if seen is None or not seen["scored"]:
        return None
    return 100.0 * seen["selected"] / seen["scored"]
