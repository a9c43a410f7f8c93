"""The step's convolutions against their roofline: the least time the chip
could take over them (per convolution the larger of operations over peak
FLOP/s and bytes over peak bytes/s, forward and both backward convolutions,
from the layer table) over the device time of the ops the trace names as
convolutions inside the step program, per step. Only the convolution's own
work is counted, so an epilogue fused into it can only lower the share."""
from perfbench import flops


def read(run):
    if run.trace is None:
        return None
    seconds, calls = run.step_buckets()
    conv_s = seconds.get("conv", 0.0)
    if not calls or not conv_s:
        return None
    floor = flops.step_conv_floor_s(
        run.cell.reference_cfg, run.global_batch // run.cell.chips, run.peaks
    )
    return 100.0 * floor / (conv_s / calls)
