"""Share of the wall time the host spent waiting for the loader
(``step_window.data_wait_s``), over the ledger windows inside the window."""


def read(run):
    wall = run.window_wall_s()
    return 100.0 * run.window_sum("data_wait_s") / wall if wall else None
