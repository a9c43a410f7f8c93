"""Share of the wall time the host spent blocked on the device
(``step_window.fetch_wait_s``), over the ledger windows inside the window."""


def read(run):
    wall = run.window_wall_s()
    return 100.0 * run.window_sum("fetch_wait_s") / wall if wall else None
