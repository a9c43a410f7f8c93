"""Device time of the attention kernels per step (forward, dq and dkv, the
forward again where a layer is recomputed)."""
from perfbench import lm_trace


def read(run):
    parts = lm_trace.part_seconds(run)
    if parts is None or not parts[0].get("attention"):
        return None
    return 1e3 * parts[0]["attention"] / parts[1]
