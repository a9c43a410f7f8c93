"""Operations and bytes of the models, from their layer tables — not from
XLA's ``cost_analysis()``, which changes with the implementation.

A convolution of a [B, H, W, Cin] map to [B, Ho, Wo, Cout] with a k x k kernel
in ``groups`` groups does 2·B·Ho·Wo·k·k·(Cin/groups)·Cout operations and has
to move its input, its kernel and its output once (2 bytes an element in
bfloat16). Training runs three such convolutions per layer: forward, the
gradient of the input and the gradient of the kernel, each with the forward's
operation count. BatchNorm, activations, pooling, resizing, the loss and the
optimizer are not counted: model FLOPs are the matrix work.
"""

from __future__ import annotations

from typing import Dict, List

from perfbench.reference.resnet_v2_beta import stage_table

Conv = Dict[str, int]  # h, w (input), cin, cout, k, stride, groups


def conv_out(size: int, stride: int) -> int:
    """SAME padding."""
    return -(-size // stride)


def conv_flops(c: Conv, batch: int) -> float:
    ho, wo = conv_out(c["h"], c["stride"]), conv_out(c["w"], c["stride"])
    return 2.0 * batch * ho * wo * c["k"] * c["k"] * (c["cin"] // c["groups"]) * c["cout"]


def conv_bytes(c: Conv, batch: int, bytes_per: int = 2) -> float:
    ho, wo = conv_out(c["h"], c["stride"]), conv_out(c["w"], c["stride"])
    elements = (
        batch * c["h"] * c["w"] * c["cin"]
        + c["k"] * c["k"] * (c["cin"] // c["groups"]) * c["cout"]
        + batch * ho * wo * c["cout"]
    )
    return float(bytes_per * elements)


def _conv(h, w, cin, cout, k, stride=1, groups=1) -> Conv:
    return {"h": h, "w": w, "cin": cin, "cout": cout, "k": k, "stride": stride, "groups": groups}


def bottleneck_convs(h: int, w: int, cin: int, depth: int, bottleneck: int, stride: int) -> List[Conv]:
    convs = []
    if depth != cin:
        convs.append(_conv(h, w, cin, depth, 1, stride))  # shortcut
    convs.append(_conv(h, w, cin, bottleneck, 1))
    convs.append(_conv(h, w, bottleneck, bottleneck, 3, stride))
    ho, wo = conv_out(h, stride), conv_out(w, stride)
    convs.append(_conv(ho, wo, bottleneck, depth, 1))
    return convs


def aspp_branch_convs(h: int, w: int, cin: int, depth: int) -> List[Conv]:
    """One split-separable atrous branch: depthwise 3x3, pointwise 1x1."""
    return [_conv(h, w, cin, cin, 3, groups=cin), _conv(h, w, cin, depth, 1)]


def model_convs(cfg: dict) -> List[Conv]:
    """Every convolution (and the classifier's matrix product, as a 1x1 conv
    on a 1x1 map) of one forward pass, in order."""
    h, w = cfg["input_shape"]
    convs = [_conv(h, w, cfg["input_channels"], 64, 3, 2)]
    h, w = conv_out(h, 2), conv_out(w, 2)
    convs += [_conv(h, w, 64, 64, 3), _conv(h, w, 64, 128, 3)]
    h, w = conv_out(h, 2), conv_out(w, 2)  # the max-pool
    c = 128
    target = None if cfg["output_stride"] is None else cfg["output_stride"] // 4
    current, skip_hw = 1, None
    for name, depth, bottleneck, stride, _ in stage_table(cfg):
        applied = 1 if (target is not None and current == target) else stride
        if applied == stride:
            current *= stride
        convs += bottleneck_convs(h, w, c, depth, bottleneck, applied)
        h, w, c = conv_out(h, applied), conv_out(w, applied), depth
        if name == "block1_unit1":
            skip_hw = (h, w, depth)
    if cfg["num_classes"] is not None:
        convs.append(_conv(1, 1, c, cfg["num_classes"], 1))
        return convs
    d = cfg["base_depth"]
    convs.append(_conv(h, w, c, d, 1))
    for _ in range(3):
        convs += aspp_branch_convs(h, w, c, d)
    convs.append(_conv(1, 1, c, d, 1))  # pooled branch
    convs.append(_conv(h, w, 5 * d, d, 1))  # project
    sh, sw, sc = skip_hw
    convs.append(_conv(sh, sw, sc, d, 1))  # decoder 1x1 on the skip
    convs.append(_conv(sh, sw, 2 * d, 1, 3))  # decoder 3x3 to one channel
    return convs


def step_flops(cfg: dict, global_batch: int) -> float:
    """Model FLOPs of one train step: forward plus the two backward
    convolutions of every layer."""
    return 3.0 * sum(conv_flops(c, global_batch) for c in model_convs(cfg))


def step_conv_floor_s(cfg: dict, per_chip_batch: int, peaks) -> float:
    """The least time one chip can take over the step's convolutions: for
    each of the three convolutions of each layer, the larger of operations
    over peak FLOP/s and bytes over peak bytes/s."""
    total = 0.0
    for c in model_convs(cfg):
        one = max(
            conv_flops(c, per_chip_batch) / peaks.bf16_flops,
            conv_bytes(c, per_chip_batch) / peaks.hbm_bytes_per_s,
        )
        total += 3.0 * one
    return total
