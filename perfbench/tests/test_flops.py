"""The operation and byte counts against counts made by hand."""

import json
import os

import tiny  # noqa: F401  (puts the repo on the path)
from perfbench import flops

HERE = os.path.dirname(os.path.abspath(__file__))


def test_bottleneck_block_by_hand():
    # block1_unit1 of the segmentation trunk: a 26x26x128 map, bottleneck 128,
    # depth 512, stride 1; batch 1. 26*26 = 676 positions.
    convs = flops.bottleneck_convs(26, 26, 128, 512, 128, 1)
    by_hand = [
        2 * 676 * 128 * 512,  # shortcut 1x1            88,604,672
        2 * 676 * 128 * 128,  # conv1 1x1               22,151,168
        2 * 676 * 9 * 128 * 128,  # conv2 3x3          199,360,512
        2 * 676 * 128 * 512,  # conv3 1x1               88,604,672
    ]
    assert [flops.conv_flops(c, 1) for c in convs] == by_hand
    assert sum(by_hand) == 398_721_024
    # the strided last unit of the stage: the 3x3 and the expand run on 13x13
    strided = flops.bottleneck_convs(26, 26, 512, 512, 128, 2)
    assert len(strided) == 3  # identity shortcut: no conv
    assert flops.conv_flops(strided[1], 1) == 2 * 169 * 9 * 128 * 128
    assert flops.conv_flops(strided[2], 1) == 2 * 169 * 128 * 512


def test_aspp_branch_by_hand():
    # one split-separable branch on the 13x13x1024 features: depthwise 3x3,
    # then pointwise to 256; batch 2
    dw, pw = flops.aspp_branch_convs(13, 13, 1024, 256)
    assert flops.conv_flops(dw, 2) == 2 * 2 * 169 * 9 * 1024  # 6,230,016
    assert flops.conv_flops(pw, 2) == 2 * 2 * 169 * 1024 * 256  # 177,209,344
    # bytes of the depthwise conv in bfloat16: input + kernel + output
    assert flops.conv_bytes(dw, 2) == 2 * (2 * 169 * 1024 + 9 * 1024 + 2 * 169 * 1024)


def _cfg(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json"), encoding="utf-8") as f:
        c = json.load(f)
    cfg = dict(c["model"])
    cfg["multi_grid"] = c["multi_grid"]
    return cfg


def test_whole_models():
    seg = _cfg("tgs_salt_bf16")
    # 3 root convs, 16 units (4 with a shortcut conv: 4*4 + 12*3), 12 head convs
    assert len(flops.model_convs(seg)) == 3 + 4 * 4 + 12 * 3 + 11
    # block3 at 13x13 carries most of it: 15.3 GFLOP forward an image
    assert abs(flops.step_flops(seg, 1) / 3 - 15.311e9) < 0.01e9
    cls = _cfg("resnet50_classic_imagenet")
    assert abs(flops.step_flops(cls, 1) / 3 - 9.675e9) < 0.01e9
    assert flops.step_flops(cls, 256) == 256 * flops.step_flops(cls, 1)


def test_conv_floor_is_the_larger_bound():
    from perfbench.peaks import peaks_of

    peaks = peaks_of("TPU v5 lite")
    big = {"h": 13, "w": 13, "cin": 512, "cout": 512, "k": 3, "stride": 1, "groups": 1}
    dw = {"h": 13, "w": 13, "cin": 1024, "cout": 1024, "k": 3, "stride": 1, "groups": 1024}
    assert flops.conv_flops(big, 256) / peaks.bf16_flops > flops.conv_bytes(big, 256) / peaks.hbm_bytes_per_s
    assert flops.conv_flops(dw, 256) / peaks.bf16_flops < flops.conv_bytes(dw, 256) / peaks.hbm_bytes_per_s
