"""The control — the reference in the program's place, one precision below
the bfloat16 the configurations state (fp8 or int8 operands in every
convolution) —
has to come out as not correct; so has each planted fault of the reference.
Held to the limits of the tiny float32 runs (tiny.TINY_LIMITS); the readings
at the cells' own size are in PERF.md."""

import importlib

import jax
import numpy as np
import pytest

import tiny
from perfbench import compare, harness, weights


def _followed(tmp_path, workload, chips=1):
    root = tiny.tiny_root(tmp_path, workload, chips)
    cell = harness.load_cell(workload, root)
    reference = importlib.import_module("perfbench.reference." + cell.config["reference"])
    cfg = cell.reference_cfg
    params0 = weights.make_weights(reference.param_spec(cfg), 3)
    rng = np.random.default_rng(3)
    h, w = cfg["input_shape"]
    batches = []
    for _ in range(harness.FOLLOWED_STEPS):
        images = rng.normal(size=(8, h, w, cfg["input_channels"])).astype(np.float32)
        if cfg["num_classes"] is None:
            labels = (rng.uniform(size=(8, h, w, 1)) > 0.6).astype(np.float32)
        else:
            labels = rng.integers(0, cfg["num_classes"], 8).astype(np.int32)
        batches.append({"images": images, "labels": labels})
    sound = jax.device_get(reference.train_steps(cfg, params0, batches, shards=chips))
    return cell, reference, cfg, jax.device_get(params0), batches, sound


@pytest.mark.parametrize("workload", ["tgs_kfold_train", "resnet50_fit"])
def test_int8_control_and_half_batch_fail(tmp_path, workload):
    cell, reference, cfg, params0, batches, sound = _followed(tmp_path, workload)
    limits = {k: v for k, v in cell.limits.items() if not k.startswith("prep_")}
    head = reference.head_leaves(cfg)
    for plant in (dict(quant=reference.quant_e4m3), dict(quant=reference.quant_int8),
                  dict(drop_half=True)):
        planted = jax.device_get(
            reference.train_steps(cfg, jax.device_put(params0), batches, **plant)
        )
        numbers = compare.training_numbers(planted, sound, params0, head=head)
        _, correct = compare.verdict(numbers, limits)
        assert not correct, (plant, numbers)
        # what tells precision: the root's batch statistics (before a deep
        # network has scrambled the rounding) and the output layer's gradient
        if "quant" in plant:
            assert numbers["root_norm_var_gap"] > limits["root_norm_var_gap"], (plant, numbers)
        assert numbers["grad1_head_diff"] > limits["grad1_head_diff"], (plant, numbers)
    again = jax.device_get(reference.train_steps(cfg, jax.device_put(params0), batches))
    _, correct = compare.verdict(
        compare.training_numbers(again, sound, params0, head=head), limits
    )
    assert correct


def test_seg_prepare_controls_fail(tmp_path):
    cell, reference, cfg, _, _, _ = _followed(tmp_path, "tgs_kfold_train")
    rng = np.random.default_rng(5)
    images = rng.normal(size=(8, 37, 37, 1)).astype(np.float32)
    yy, xx = np.mgrid[:37, :37]
    disc = ((yy - 15) ** 2 + (xx - 20) ** 2 < 100).astype(np.float32)
    masks = np.repeat(disc[None, :, :, None], 8, axis=0)  # salt bodies, not pixel noise
    key = jax.random.PRNGKey(11)
    aug = cell.config["augment"]
    sound = jax.device_get(reference.augment_seg(aug, key, images, masks))
    skipped = {"images": jax.device_get(reference.laplace_channel(images)), "labels": masks}
    coarse = jax.device_get(
        reference.augment_seg(aug, key, images, masks, matrix_quant=reference.quant_bf16)
    )
    limits = {k: v for k, v in cell.limits.items() if k.startswith("prep_")}
    no_laplace = {"images": np.concatenate([sound["images"][..., :1]] * 2, axis=-1),
                  "labels": sound["labels"]}
    for planted in (skipped, no_laplace):
        numbers = compare.seg_prepare_numbers([planted], [sound], reference.laplace_channel)
        assert not compare.verdict(numbers, limits)[1], numbers
    # a warp a fraction of a pixel off (the matrices in bfloat16, as the chip
    # multiplies them by default) is no fault: tiles and masks barely move
    numbers = compare.seg_prepare_numbers([coarse], [sound], reference.laplace_channel)
    assert numbers["prep_mask_gap"] < 0.05 and numbers["prep_image_gap"] < 0.2, numbers
    same = compare.seg_prepare_numbers([sound], [sound], reference.laplace_channel)
    assert compare.verdict(same, limits)[1]
