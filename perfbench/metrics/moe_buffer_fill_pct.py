"""Share of the sorted pair buffer's rows that held pairs fill
(``step_window.moe_buffer_fill``: held pairs over the rows of the segments the
expert layer worked over, by layer), in per cent: the least-filled layer of
the ledger windows inside the measured window. The rows past the held pairs
are what the expert layer's gathers, activation and sums still move for
nothing."""


def read(run):
    fills = [min(w["moe_buffer_fill"]) for w in run.windows if w.get("moe_buffer_fill")]
    return 100.0 * min(fills) if fills else None
