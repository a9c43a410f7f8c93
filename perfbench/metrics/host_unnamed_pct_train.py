"""Share of the loop's host wall that lies in no named span: the windows'
``host_other_s`` (``wall_s`` less every span the window drains) over their
``wall_s``, over the ledger windows inside the measured window."""


def read(run):
    windows = [w for w in run.windows if "host_other_s" in w]
    wall = sum(w["wall_s"] for w in windows)
    return 100.0 * sum(w["host_other_s"] for w in windows) / wall if wall else None
