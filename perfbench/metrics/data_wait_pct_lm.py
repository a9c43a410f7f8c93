"""Share of the wall time the host spent waiting for the token packer
(``step_window.data_wait_s``), over the ledger windows inside the window:
``data_wait_pct.train``'s reader under the name this cell's metric has (the
harness finds a reader by its metric's name)."""

from perfbench.metrics.data_wait_pct_train import read  # noqa: F401
