"""The KDA layers' mean decay ``exp(g)`` in per cent (``step_window.kda_decay_mean``:
over positions, layers, heads and channels), weighted by the ledger windows'
steps inside the measured window: 100 keeps the state, 0 forgets it at once.
None where the program writes no such field."""


def read(run):
    windows = [w for w in run.windows if "kda_decay_mean" in w]
    steps = sum(w["steps"] for w in windows)
    if not steps:
        return None
    return 100.0 * sum(w["kda_decay_mean"] * w["steps"] for w in windows) / steps
