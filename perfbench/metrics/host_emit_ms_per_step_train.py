"""Host time per train step spent writing a log window out (TensorBoard
scalars, the window event, health and profiler hooks: ``window_emit_s``) and
on the train-phase image grids (an extra forward and three device_gets:
``image_summary_s``), over the ledger windows inside the measured window."""


def read(run):
    windows = [w for w in run.windows if "window_emit_s" in w]
    steps = sum(w["steps"] for w in windows)
    if not steps:
        return None
    return 1e3 * sum(w["window_emit_s"] + w["image_summary_s"] for w in windows) / steps
