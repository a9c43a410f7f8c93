"""Tests for on-device augmentation (reference semantics: preprocessing.py:112-278).
The reference had no tests; its augmentation was only ever eyeballed via matplotlib
(SURVEY §4) — these are the assertions that practice lacked."""

import re
from dataclasses import replace as dataclasses_replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowdistributedlearning_tpu.data import augment
from tensorflowdistributedlearning_tpu.parallel import make_mesh, shard_batch


def _batch(rng, b=2, h=101, w=101):
    images = rng.normal(0, 1, (b, h, w, 1)).astype(np.float32)
    masks = (rng.uniform(size=(b, h, w, 1)) > 0.5).astype(np.float32)
    return jnp.asarray(images), jnp.asarray(masks)


def test_laplacian_of_constant_is_zero():
    x = jnp.ones((1, 8, 8, 1))
    lap = augment.laplacian(x)
    # stencil sums to zero => flat interior response is zero
    assert jnp.allclose(lap[0, 2:-2, 2:-2, 0], 0.0, atol=1e-5)


def test_laplacian_detects_edge():
    x = jnp.zeros((1, 8, 8, 1)).at[:, :, 4:, :].set(1.0)
    lap = augment.laplacian(x)
    assert jnp.abs(lap[0, 4, 4, 0]) > 0.5


def test_add_laplace_channel_shape():
    x = jnp.zeros((3, 101, 101, 1))
    out = augment.add_laplace_channel(x)
    assert out.shape == (3, 101, 101, 2)
    assert jnp.array_equal(out[..., :1], x)


def test_augment_batch_shapes_and_determinism(rng):
    images, masks = _batch(rng)
    key = jax.random.PRNGKey(0)
    out1 = augment.augment_batch(key, images, masks)
    out2 = augment.augment_batch(key, images, masks)
    assert out1["images"].shape == (2, 101, 101, 2)
    assert out1["labels"].shape == (2, 101, 101, 1)
    # fixed key => bitwise identical (the determinism test SURVEY §5.2 calls for)
    assert jnp.array_equal(out1["images"], out2["images"])
    assert jnp.array_equal(out1["labels"], out2["labels"])


def test_augment_batch_per_image_randomness(rng):
    """Different images in one batch get different transforms — the reference's numpy
    shift bug applied ONE shift to all images (SURVEY §2.4.11); verify the fix."""
    img = rng.normal(0, 1, (1, 101, 101, 1)).astype(np.float32)
    images = jnp.asarray(np.repeat(img, 4, axis=0))
    masks = jnp.ones((4, 101, 101, 1), jnp.float32)
    out = augment.augment_batch(jax.random.PRNGKey(1), images, masks)
    a = np.asarray(out["images"])
    assert not np.array_equal(a[0], a[1]) or not np.array_equal(a[1], a[2])


def test_augment_mask_stays_binary(rng):
    """NEAREST interpolation for masks (reference: preprocessing.py:235-238) must not
    create fractional values."""
    images, masks = _batch(rng)
    out = augment.augment_batch(jax.random.PRNGKey(2), images, masks)
    vals = np.unique(np.asarray(out["labels"]))
    assert set(vals.tolist()) <= {0.0, 1.0}


def test_augment_jits(rng):
    images, masks = _batch(rng, b=2)
    f = jax.jit(augment.augment_batch)
    out = f(jax.random.PRNGKey(3), images, masks)
    assert out["images"].shape == (2, 101, 101, 2)


def test_identity_affine_roundtrip(rng):
    """With all randomness disabled the augmentation is pad + identity warp + central
    crop — the image must come back (nearly) unchanged."""
    cfg = augment.AugmentConfig(
        horizontal_flip=False,
        vertical_flip=False,
        rotate_range=0.0,
        crop_probability=0.0,
        height_shift_range=0.0,
        width_shift_range=0.0,
        transpose_probability=0.0,
    )
    images = jnp.asarray(rng.normal(0, 1, (1, 32, 32, 1)).astype(np.float32))
    masks = (jnp.asarray(rng.uniform(size=(1, 32, 32, 1))) > 0.5).astype(jnp.float32)

    out = augment.augment_batch(jax.random.PRNGKey(0), images, masks, cfg)
    got = np.asarray(out["images"][..., :1])
    np.testing.assert_allclose(got, np.asarray(images), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(out["labels"]), np.asarray(masks))


def test_transpose_probability_knob(rng):
    """transpose_probability=0 must disable the transpose; =1 must force it."""
    cfg_off = augment.AugmentConfig(
        horizontal_flip=False, vertical_flip=False, rotate_range=0.0,
        crop_probability=0.0, height_shift_range=0.0, width_shift_range=0.0,
        transpose_probability=0.0,
    )
    cfg_on = dataclasses_replace(cfg_off, transpose_probability=1.0)
    # asymmetric image so a transpose is detectable
    img = np.zeros((1, 16, 16, 1), np.float32)
    img[0, 2, 10, 0] = 1.0
    images = jnp.asarray(img)
    masks = jnp.asarray((img > 0).astype(np.float32))
    for k in range(8):
        out = augment.augment_batch(jax.random.PRNGKey(k), images, masks, cfg_off)
        np.testing.assert_allclose(
            np.asarray(out["images"][..., :1]), img, atol=1e-4
        )
    out = augment.augment_batch(jax.random.PRNGKey(0), images, masks, cfg_on)
    np.testing.assert_allclose(
        np.asarray(out["images"][..., :1]), img.transpose(0, 2, 1, 3), atol=1e-4
    )


def test_tta_transforms_are_involutions(rng):
    x = jnp.asarray(rng.normal(0, 1, (2, 7, 7, 1)).astype(np.float32))
    for name in augment.TTA_TRANSFORMS:
        y = augment.tta_transform(x, name)
        assert jnp.array_equal(augment.tta_inverse(y, name), x)
    with pytest.raises(ValueError):
        augment.tta_transform(x, "bogus")


def test_tta_transforms_differ(rng):
    x = jnp.asarray(rng.normal(0, 1, (1, 5, 5, 1)).astype(np.float32))
    outs = [np.asarray(augment.tta_transform(x, t)) for t in ("vertical", "horizontal", "transpose")]
    for o in outs:
        assert not np.array_equal(o, np.asarray(x))


# ---------------------------------------------------------------------------
# The oracle: the augmentation as it sampled before the dense-weight warp — every
# output pixel of the padded frame fetched by ``map_coordinates`` gathers, then the
# central crop. ``_apply_warp`` and ``central_crop`` are that code verbatim; the
# draws are the module's own (``_sample_affine``, the same three-way split).
# (The gathered warp takes a corner's index and its weight from two readings of one
# coordinate; where XLA evaluates them apart and the coordinate lies within an ulp of
# a whole number, one pixel in millions reads its neighbour's column. The seeds below
# are fixed and hold no such pixel.)
# ---------------------------------------------------------------------------


def _apply_warp(image, matrix, order):
    """Inverse-warp a [H, W, C] image by a 3x3 affine matrix. ``order=1`` bilinear
    (image), ``order=0`` nearest (mask) — reference: preprocessing.py:230-238. Out-of-
    bounds samples fill with 0, matching ``tf.contrib.image.transform``."""
    h, w, c = image.shape
    ys, xs = jnp.meshgrid(
        jnp.arange(h, dtype=jnp.float32), jnp.arange(w, dtype=jnp.float32), indexing="ij"
    )
    in_x = matrix[0, 0] * xs + matrix[0, 1] * ys + matrix[0, 2]
    in_y = matrix[1, 0] * xs + matrix[1, 1] * ys + matrix[1, 2]

    def warp_channel(ch):
        return jax.scipy.ndimage.map_coordinates(
            ch, [in_y, in_x], order=order, mode="constant", cval=0.0
        )

    return jnp.stack([warp_channel(image[..., i]) for i in range(c)], axis=-1)


def central_crop(x, out_hw):
    """Static central crop (the reference's ``tf.image.central_crop(x, 101/181)``,
    preprocessing/preprocessing.py:240-241)."""
    h, w = x.shape[-3], x.shape[-2]
    th, tw = out_hw
    top, left = (h - th) // 2, (w - tw) // 2
    return x[..., top : top + th, left : left + tw, :]


def _oracle_augment_batch(key, images, masks, cfg, out_hw=None):
    if out_hw is None:
        out_hw = images.shape[1:3]

    def one(k, image, mask):
        pad_spec = [(cfg.pad, cfg.pad), (cfg.pad, cfg.pad), (0, 0)]
        image = jnp.pad(image, pad_spec, mode="reflect")
        mask = jnp.pad(mask, pad_spec, mode="reflect")
        k_transpose, k_bright, k_affine = jax.random.split(k, 3)
        if cfg.transpose_probability > 0:
            do_t = jax.random.uniform(k_transpose) < cfg.transpose_probability
            image = jnp.where(do_t, jnp.transpose(image, (1, 0, 2)), image)
            mask = jnp.where(do_t, jnp.transpose(mask, (1, 0, 2)), mask)
        if cfg.brightness_range > 0:
            image = image + jax.random.uniform(
                k_bright, minval=-cfg.brightness_range, maxval=cfg.brightness_range
            )
        h, w = image.shape[:2]
        matrix = augment._sample_affine(k_affine, cfg, float(h), float(w))
        return (
            central_crop(_apply_warp(image, matrix, order=1), out_hw),
            central_crop(_apply_warp(mask, matrix, order=0), out_hw),
        )

    keys = jax.random.split(key, images.shape[0])
    aug_images, aug_masks = jax.vmap(one)(keys, images, masks)
    return {"images": augment.add_laplace_channel(aug_images), "labels": aug_masks}


_DEFAULT = augment.AugmentConfig()
_EQUIVALENCE_CASES = {
    # name: (config, input [H, W], out_hw or None for the input's own)
    "default": (_DEFAULT, (101, 101), None),
    "trainer": (dataclasses_replace(_DEFAULT, crop_probability=0.0), (101, 101), None),
    "zoom_crop": (dataclasses_replace(_DEFAULT, crop_probability=1.0), (101, 101), None),
    "oblong": (dataclasses_replace(_DEFAULT, transpose_probability=0.0), (48, 80), None),
    "pad_7": (dataclasses_replace(_DEFAULT, pad=7), (40, 40), None),
    "no_pad_smaller_out": (dataclasses_replace(_DEFAULT, pad=0), (40, 40), (24, 30)),
    "transpose_never": (dataclasses_replace(_DEFAULT, transpose_probability=0.0), (64, 64), None),
    "transpose_always": (dataclasses_replace(_DEFAULT, transpose_probability=1.0), (64, 64), None),
    "brightness": (dataclasses_replace(_DEFAULT, brightness_range=0.3), (64, 64), None),
    "wide_rotation_and_shift": (
        dataclasses_replace(
            _DEFAULT, rotate_range=180.0, height_shift_range=0.6, width_shift_range=0.6
        ),
        (64, 64),
        None,
    ),
}


@pytest.mark.parametrize("case", sorted(_EQUIVALENCE_CASES))
def test_warp_matches_map_coordinates_oracle(case):
    """The dense-weight warp over the kept pixels IS the gathered warp over the
    whole frame followed by the crop: images to summation order, labels exactly."""
    cfg, (h, w), out_hw = _EQUIVALENCE_CASES[case]
    images, masks = _batch(np.random.default_rng(7), b=16, h=h, w=w)
    key = jax.random.PRNGKey(11)
    got = jax.jit(lambda k, i, m: augment.augment_batch(k, i, m, cfg, out_hw))(
        key, images, masks
    )
    want = jax.jit(lambda k, i, m: _oracle_augment_batch(k, i, m, cfg, out_hw))(
        key, images, masks
    )
    assert got["images"].shape == want["images"].shape
    assert got["labels"].dtype == want["labels"].dtype
    # channel 0 is the warped image; the Laplacian's stencil sums 12 of its pixels
    np.testing.assert_allclose(got["images"][..., 0], want["images"][..., 0], atol=1e-4)
    np.testing.assert_allclose(got["images"][..., 1], want["images"][..., 1], atol=1e-3)
    labels = np.asarray(got["labels"])
    np.testing.assert_array_equal(labels, np.asarray(want["labels"]))
    assert set(np.unique(labels).tolist()) <= {0.0, 1.0}
    if case == "wide_rotation_and_shift":  # the zero fill outside is exercised
        assert np.mean(np.asarray(want["images"][..., 0]) == 0.0) > 0.05


@pytest.mark.parametrize("coordinate", [-1.5, -0.5, -0.49, 0.5, 1.5, 2.5, 3.49, 3.5])
def test_nearest_rounds_half_away_from_zero(coordinate):
    """``map_coordinates(order=0)`` rounds a tie away from zero (2.5 -> 3, -0.5 ->
    -1: outside), not to even; the mask's indicator weights must round the same."""
    row = jnp.asarray([[1.0, 0.0, 1.0, 0.0]])  # [1, 4]; the 1x1 crop is pixel x=1
    shift = jnp.asarray(
        [[1.0, 0.0, coordinate - 1.0], [0.0, 1.0, 0.0], [0.0, 0.0, 1.0]], jnp.float32
    )
    in_x = shift[0, 0] * 1.0 + shift[0, 2]
    assert float(in_x) == pytest.approx(coordinate)
    _, got = augment._warp_crop(row, row, shift, (1, 1), 1)
    want = jax.scipy.ndimage.map_coordinates(
        row, [jnp.zeros((1, 1)), jnp.full((1, 1), in_x)], order=0, cval=0.0
    )
    assert float(got[0, 0]) == float(want[0, 0])
    # to even, -0.5 and 0.5 would read pixel 0 (1.0) and 2.5 pixel 2 (1.0)
    expected = {-1.5: 0.0, -0.5: 0.0, -0.49: 1.0, 0.5: 0.0, 1.5: 1.0, 2.5: 0.0,
                3.49: 0.0, 3.5: 0.0}
    assert float(got[0, 0]) == expected[coordinate]


def _gathers_of_padded_frame(lowered_text, frame):
    return [
        line
        for line in lowered_text.splitlines()
        if "gather" in line and re.search(rf"tensor<\d+x{frame}x{frame}x", line)
    ]


def test_prepare_program_gathers_nothing_from_the_padded_frame(rng):
    """The old path cannot come back unnoticed on a CPU-only check: the trainer's
    ``jit_prepare`` at the flagship's shapes holds no gather over [B, 181, 181]."""
    from tensorflowdistributedlearning_tpu.train import trainer

    images, masks = _batch(rng, b=4)
    cfg = augment.AugmentConfig(crop_probability=0.0)
    prepare = trainer._prepare_train_cached(cfg)
    batch = {"images": images, "masks": masks}
    text = prepare.lower(jax.random.PRNGKey(0), jnp.asarray(0), batch).as_text()
    assert "jit_prepare" in text
    assert _gathers_of_padded_frame(text, 181) == []
    # the check can see one: the oracle's program has five
    oracle = jax.jit(lambda k, i, m: _oracle_augment_batch(k, i, m, cfg))
    oracle_text = oracle.lower(jax.random.PRNGKey(0), images, masks).as_text()
    assert len(_gathers_of_padded_frame(oracle_text, 181)) == 5


def test_sharded_batch_matches_single_device(eight_devices):
    """A batch sharded over the data axis stays sharded through the warp (the batch
    is a plain leading axis; the chunks walk output rows) and reads the same."""
    mesh = make_mesh(8)
    images, masks = _batch(np.random.default_rng(3), b=16, h=32, w=32)
    f = jax.jit(lambda k, b: augment.augment_batch(k, b["images"], b["masks"]))
    key = jax.random.PRNGKey(5)
    single = f(key, {"images": images, "masks": masks})
    sharded_in = shard_batch({"images": images, "masks": masks}, mesh)
    sharded = f(key, sharded_in)
    assert sharded["images"].sharding.is_equivalent_to(
        sharded_in["images"].sharding, sharded["images"].ndim
    )
    np.testing.assert_allclose(sharded["images"], single["images"], atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(sharded["labels"]), np.asarray(single["labels"])
    )
    compiled = f.lower(key, sharded_in).compile().as_text()
    assert "all-gather" not in compiled and "all-to-all" not in compiled


def test_rows_per_chunk_follows_the_shapes():
    """The chunk is derived from the shapes alone: the flagship's batch walks its
    101 rows in even pieces, a small batch takes them at once."""
    assert augment._rows_per_chunk(256, 181, (101, 101)) == 13
    assert augment._rows_per_chunk(2, 181, (101, 101)) == 101
    assert augment._rows_per_chunk(4096, 1024, (513, 513)) == 1
