"""Host time spent dispatching one train step: the program's ``step`` span
(``step_window.compute_s``) over the steps of the ledger windows that lie
wholly inside the measured window."""


def read(run):
    steps = run.window_steps()
    return 1e3 * run.window_sum("compute_s") / steps if steps else None
