"""The KDA recurrence against its roofline: the least time a step's KDA
layers could take in the chunkwise form at a chunk of 64
(``flops_linear.kda_floor_s``: forward once and a backward pass of twice the
operations and bytes, each the larger of operations over the bf16 peak and
bytes over the HBM bandwidth) over the device time of the ops under
``decoder/attn_linear/kda`` per step (``kda_device_ms_per_step``)."""
from perfbench import flops_linear
from perfbench.metrics import kda_device_ms_per_step


def read(run):
    ms = kda_device_ms_per_step.read(run)
    if not ms or "linear_attn_config" not in run.cell.config:
        return None
    floor = flops_linear.kda_floor_s(run.cell.config, run.cell.traffic, run.peaks)
    return 100.0 * floor / (ms / 1e3)
