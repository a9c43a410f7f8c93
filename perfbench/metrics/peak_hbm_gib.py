"""``peak_bytes_in_use`` of the fullest device after the window."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 2**30 if peak else None
