"""Seconds of set-up the program spent loading its data set and writing its
fold manifests: the ``startup_phase`` events ``load_dataset`` and ``folds``.

The other ``setup_*`` readers share the two helpers below. A run of a
program that writes no such events reads nothing."""


def startup_phases(run, fold=0):
    """The ``startup_phase`` events of the run's start and of one fold."""
    return [
        e
        for e in run.ledger
        if e.get("event") == "startup_phase" and e.get("fold") in (None, fold)
    ]


def phase_seconds(run, names):
    """Seconds under the named phases, or None where the ledger has none."""
    found = [e["duration_s"] for e in startup_phases(run) if e["name"] in names]
    return sum(found) if found else None


def read(run):
    return phase_seconds(run, ("load_dataset", "folds"))
