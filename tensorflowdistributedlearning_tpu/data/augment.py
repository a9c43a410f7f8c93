"""On-device, batched image augmentation (reference: preprocessing/preprocessing.py).

TPU-first redesign of the reference's per-image host-side tf.data augmentation
(reference: preprocessing/preprocessing.py:112-246):

- The whole augmentation is a jittable function of ``(key, images, masks)``; the host
  only decodes PNGs. Geometry runs on TPU as one composed inverse warp per image (the
  reference likewise composed flips/rotation/shift/crop into ONE projective transform,
  reference: preprocessing/preprocessing.py:162-238 — but executed it on the host CPU
  per image), sampled only at the pixels the central crop keeps and as dense
  interpolation weights contracted on the MXU, not gathered (``_warp_crop``).
- Randomness uses per-image PRNG keys from ``jax.random.split``, fixing the reference's
  graph-construction-time numpy RNG for shifts, which sampled ONE shift per pipeline
  and reused it for every image (reference: preprocessing/preprocessing.py:196-203,
  SURVEY §2.4.11).
- Transform semantics preserved: REFLECT pad 40 px (:150-151), random transpose at
  p=0.5 (:165-167), optional brightness jitter (:169-170), horizontal/vertical flips at
  p=0.5 (:172-188), rotation U(-rotate_range°, +rotate_range°) (:190-194), shifts
  U(-range, +range)·height (:196-211), optional zoom-crop (:213-228), BILINEAR for the
  image / NEAREST for the mask (:230-238), central crop 101/181 (:240-241), and the
  Laplacian second channel (:11-30, :243).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# TGS Salt dataset intensity statistics (reference: preprocessing/preprocessing.py:7-8).
MEAN = 0.47194585
STD = 0.16105755

# Reference: preprocessing/preprocessing.py:27-29 — an isotropic 3x3 Laplacian stencil.
_LAPLACE_KERNEL = (
    (0.5, 1.0, 0.5),
    (1.0, -6.0, 1.0),
    (0.5, 1.0, 0.5),
)


@dataclasses.dataclass(frozen=True)
class AugmentConfig:
    """Knob set of ``read_and_preprocess`` (reference:
    preprocessing/preprocessing.py:112-123), same defaults."""

    horizontal_flip: bool = True
    vertical_flip: bool = True
    rotate_range: float = 10.0  # degrees
    crop_probability: float = 0.5  # the trainer passed 0 (reference: model.py:316)
    crop_min_percent: float = 0.9
    crop_max_percent: float = 1.1
    height_shift_range: float = 0.2
    width_shift_range: float = 0.2
    brightness_range: float = 0.0
    pad: int = 40  # REFLECT padding before warping (reference: :150-151)
    transpose_probability: float = 0.5


def normalize(image: jax.Array) -> jax.Array:
    """(x - MEAN) / STD (reference: preprocessing/preprocessing.py:146)."""
    return (image - MEAN) / STD


def laplacian(images: jax.Array) -> jax.Array:
    """Per-channel 3x3 Laplacian of a [B, H, W, C] batch (reference:
    preprocessing/preprocessing.py:11-30 ran a depthwise conv per image)."""
    c = images.shape[-1]
    kernel = jnp.asarray(_LAPLACE_KERNEL, images.dtype)
    kernel = jnp.tile(kernel[:, :, None, None], (1, 1, 1, c))  # HWIO, depthwise
    return lax.conv_general_dilated(
        images,
        kernel,
        window_strides=(1, 1),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c,
    )


def add_laplace_channel(images: jax.Array) -> jax.Array:
    """Concatenate the Laplacian as a second channel (reference:
    preprocessing/preprocessing.py:243)."""
    return jnp.concatenate([images, laplacian(images)], axis=-1)


# ---------------------------------------------------------------------------
# Affine machinery. Matrices are 3x3 INVERSE warps: out-pixel (x, y) samples
# in-pixel (x', y', 1)^T = M @ (x, y, 1)^T — the same output->input convention the
# reference's flat [a0..c1] projective transforms used
# (reference: preprocessing/preprocessing.py:162-238). Applying A then B composes as
# M_A @ M_B.
# ---------------------------------------------------------------------------


def _identity() -> jax.Array:
    return jnp.eye(3, dtype=jnp.float32)


def _hflip(width: float) -> jax.Array:
    return jnp.asarray(
        [[-1.0, 0.0, width - 1.0], [0.0, 1.0, 0.0], [0.0, 0.0, 1.0]], jnp.float32
    )


def _vflip(height: float) -> jax.Array:
    return jnp.asarray(
        [[1.0, 0.0, 0.0], [0.0, -1.0, height - 1.0], [0.0, 0.0, 1.0]], jnp.float32
    )


def _rotation(angle: jax.Array, height: float, width: float) -> jax.Array:
    """Rotation about the image center (the reference used
    ``angles_to_projective_transforms``, preference for same center convention)."""
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    cx, cy = (width - 1.0) / 2.0, (height - 1.0) / 2.0
    # translate center to origin, rotate, translate back (inverse warp)
    return jnp.asarray(
        [
            [cos, -sin, cx - cos * cx + sin * cy],
            [sin, cos, cy - sin * cx - cos * cy],
            [0.0, 0.0, 1.0],
        ],
        jnp.float32,
    )


def _translation(tx: jax.Array, ty: jax.Array) -> jax.Array:
    one = jnp.ones((), jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    return jnp.stack(
        [
            jnp.stack([one, zero, tx]),
            jnp.stack([zero, one, ty]),
            jnp.stack([zero, zero, one]),
        ]
    )


def _zoom_crop(pct: jax.Array, off_x: jax.Array, off_y: jax.Array) -> jax.Array:
    one = jnp.ones((), jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    return jnp.stack(
        [
            jnp.stack([pct, zero, off_x]),
            jnp.stack([zero, pct, off_y]),
            jnp.stack([zero, zero, one]),
        ]
    )


def _hat(d: jax.Array) -> jax.Array:
    """Linear interpolation weight of a sample ``d`` pixels from a grid point."""
    return jnp.maximum(0.0, 1.0 - jnp.abs(d))


def _warp_crop(
    image: jax.Array,
    mask: jax.Array,
    matrix: jax.Array,
    out_hw: Tuple[int, int],
    rows_per_chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """Inverse-warp an [H, W] image/mask pair by a 3x3 affine matrix, at the output
    pixels of the central ``out_hw`` crop only (the reference's
    ``tf.image.central_crop(x, 101/181)`` after the transform,
    preprocessing/preprocessing.py:230-241). Bilinear for the image, nearest (half
    away from zero) for the mask, samples outside the input fill with 0, matching
    ``tf.contrib.image.transform``.

    A TPU gathers scalars one at a time and has a matrix unit, so nothing is
    gathered: each output pixel's interpolation weights over a whole input row and
    column are written out densely (two non-zeros each, the rest 0) and contracted
    with the image, ``out[y, x] = sum_i wy[y, x, i] * sum_j wx[y, x, j] * I[i, j]``.
    Only the in-range ``i, j`` exist, which is the zero fill. Output rows are walked
    ``rows_per_chunk`` at a time so the [rows, out_w, W] weights stay small; the
    batch (this function is vmapped) stays the leading axis of every operand.

    The first contraction costs ``2 * out_h * out_w * H * W`` FLOPs an image — the
    fourth power of the side where a gather is the second; PERF.md §6 (PR 25) has
    the size at which it would have to be banded."""
    h, w = image.shape
    out_h, out_w = out_hw
    top, left = (h - out_h) // 2, (w - out_w) // 2
    xs = left + jnp.arange(out_w, dtype=jnp.float32)
    rows = jnp.arange(h, dtype=jnp.float32)
    cols = jnp.arange(w, dtype=jnp.float32)
    # 0 and 1 are exact in bfloat16 and every sum below has one non-zero term, so
    # the mask takes one bfloat16 pass of the MXU and is exact; the image's pixels
    # and weights are not, and take float32 accuracy (a default-precision dot on
    # a TPU would round both to bfloat16).
    mask_dtype, mask = mask.dtype, mask.astype(jnp.bfloat16)

    def sample_row(y: jax.Array) -> Tuple[jax.Array, jax.Array]:
        in_x = matrix[0, 0] * xs + matrix[0, 1] * y + matrix[0, 2]
        in_y = matrix[1, 0] * xs + matrix[1, 1] * y + matrix[1, 2]
        along_x = jnp.einsum(
            "xj,ij->xi",
            _hat(in_x[:, None] - cols),
            image,
            precision=lax.Precision.HIGHEST,
        )
        image_row = jnp.sum(_hat(in_y[:, None] - rows) * along_x, axis=-1)
        along_x = jnp.einsum(
            "xj,ij->xi",
            (lax.round(in_x)[:, None] == cols).astype(jnp.bfloat16),
            mask,
            preferred_element_type=jnp.float32,
        )
        mask_row = jnp.sum(
            jnp.where(lax.round(in_y)[:, None] == rows, along_x, 0.0), axis=-1
        )
        return image_row, mask_row.astype(mask_dtype)

    ys = top + jnp.arange(out_h, dtype=jnp.float32)
    return lax.map(sample_row, ys, batch_size=rows_per_chunk)


# Bytes one [batch, rows, out_w, side] float32 intermediate of ``_warp_crop`` may
# take; a handful are live at once if the compiler fuses none of them, so the
# augmentation's temporaries stay near 1 GiB. (The v5e's compiler fuses them all,
# and there the size matters another way: one piece of 1.9 GB read wrong on the
# chip, pieces of 1.2 GB and less read right — PERF.md §6, PR 25.)
_WARP_CHUNK_BYTES = 256 << 20


def _rows_per_chunk(batch: int, side: int, out_hw: Tuple[int, int]) -> int:
    """Output rows ``_warp_crop`` takes at a time: as many as the budget above
    holds for the whole batch (``side``: the padded frame's longer side), evened
    out over the chunks."""
    out_h, out_w = out_hw
    most = max(1, _WARP_CHUNK_BYTES // (4 * batch * out_w * side))
    n_chunks = -(-out_h // most)
    return -(-out_h // n_chunks)


def _sample_affine(
    key: jax.Array, cfg: AugmentConfig, height: float, width: float
) -> jax.Array:
    """Sample the composed per-image affine (flips ∘ rotation ∘ shift ∘ crop), the
    reference's transform list (preprocessing/preprocessing.py:162-228)."""
    k_h, k_v, k_rot, k_tx, k_ty, k_crop, k_pct, k_ox, k_oy = jax.random.split(key, 9)
    m = _identity()
    if cfg.horizontal_flip:
        coin = jax.random.uniform(k_h) < 0.5
        m = m @ jnp.where(coin, _hflip(width), _identity())
    if cfg.vertical_flip:
        coin = jax.random.uniform(k_v) < 0.5
        m = m @ jnp.where(coin, _vflip(height), _identity())
    if cfg.rotate_range:
        max_rad = cfg.rotate_range / 180.0 * math.pi
        angle = jax.random.uniform(k_rot, minval=-max_rad, maxval=max_rad)
        m = m @ _rotation(angle, height, width)
    # per-image shifts — the fix for SURVEY §2.4.11; the reference also scaled BOTH
    # shifts by `height` (preprocessing/preprocessing.py:197-201), kept for parity
    # (all its inputs are square).
    tx = (
        jax.random.uniform(
            k_tx, minval=-cfg.width_shift_range, maxval=cfg.width_shift_range
        )
        * height
        if cfg.width_shift_range
        else jnp.zeros(())
    )
    ty = (
        jax.random.uniform(
            k_ty, minval=-cfg.height_shift_range, maxval=cfg.height_shift_range
        )
        * height
        if cfg.height_shift_range
        else jnp.zeros(())
    )
    m = m @ _translation(tx, ty)
    if cfg.crop_probability > 0:
        pct = jax.random.uniform(
            k_pct, minval=cfg.crop_min_percent, maxval=cfg.crop_max_percent
        )
        off_x = jax.random.uniform(k_ox, minval=0.0, maxval=width * jnp.abs(1.0 - pct))
        off_y = jax.random.uniform(k_oy, minval=0.0, maxval=height * jnp.abs(1.0 - pct))
        coin = jax.random.uniform(k_crop) < cfg.crop_probability
        m = m @ jnp.where(coin, _zoom_crop(pct, off_x, off_y), _identity())
    return m


def _augment_one(
    key: jax.Array,
    image: jax.Array,
    mask: jax.Array,
    cfg: AugmentConfig,
    out_hw: Tuple[int, int],
    rows_per_chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """Augment a single [H, W, 1] image/mask pair. vmapped over the batch."""
    pad_spec = [(cfg.pad, cfg.pad), (cfg.pad, cfg.pad)]
    image = jnp.pad(image[..., 0], pad_spec, mode="reflect")
    mask = jnp.pad(mask[..., 0], pad_spec, mode="reflect")

    k_transpose, k_bright, k_affine = jax.random.split(key, 3)

    # random transpose (reference: preprocessing/preprocessing.py:165-167); only a
    # square frame can be transposed in place, so one that never is may be oblong
    if cfg.transpose_probability > 0:
        do_t = jax.random.uniform(k_transpose) < cfg.transpose_probability
        image = jnp.where(do_t, image.T, image)
        mask = jnp.where(do_t, mask.T, mask)

    # brightness jitter (reference: preprocessing/preprocessing.py:169-170)
    if cfg.brightness_range > 0:
        delta = jax.random.uniform(
            k_bright, minval=-cfg.brightness_range, maxval=cfg.brightness_range
        )
        image = image + delta

    h, w = image.shape
    matrix = _sample_affine(k_affine, cfg, float(h), float(w))
    image, mask = _warp_crop(image, mask, matrix, out_hw, rows_per_chunk)
    return image[..., None], mask[..., None]


def augment_batch(
    key: jax.Array,
    images: jax.Array,
    masks: jax.Array,
    cfg: AugmentConfig = AugmentConfig(),
    out_hw: Optional[Tuple[int, int]] = None,
) -> Dict[str, jax.Array]:
    """Jittable batched augmentation + Laplacian channel.

    ``images``/``masks``: [B, H, W, 1] normalized images and binary masks. Returns
    {'images': [B, h, w, 2], 'labels': [B, h, w, 1]} ready for the train step — the
    whole of the reference's augmenting input_fn map (model.py:315-317) as one fused
    XLA computation with per-image keys.
    """
    if out_hw is None:
        out_hw = (images.shape[1], images.shape[2])
    batch = images.shape[0]
    rows_per_chunk = _rows_per_chunk(batch, max(images.shape[1:3]) + 2 * cfg.pad, out_hw)
    keys = jax.random.split(key, batch)
    aug_images, aug_masks = jax.vmap(
        lambda k, i, m: _augment_one(k, i, m, cfg, out_hw, rows_per_chunk)
    )(keys, images, masks)
    return {"images": add_laplace_channel(aug_images), "labels": aug_masks}


def augment_classification_batch(
    key: jax.Array,
    images: jax.Array,
    crop_padding: int = 4,
    flip: bool = True,
) -> jax.Array:
    """Jittable standard classification augmentation: per-image random horizontal
    flip + reflect-padded random crop (the ImageNet/CIFAR recipe), on device.

    The classification twin of ``augment_batch``: geometry runs as one fused XLA
    computation on the accelerator, so the host feed never bottlenecks the MXU
    (the host pipeline only decodes and normalizes). ``flip=False`` drops the
    mirror for chirality-sensitive classes (text, digits, signage)."""
    b, h, w, _ = images.shape
    kf, ky, kx = jax.random.split(key, 3)
    if flip:
        flips = jax.random.bernoulli(kf, 0.5, (b,))
        images = jnp.where(
            flips[:, None, None, None], images[:, :, ::-1, :], images
        )
    if crop_padding > 0:
        p = crop_padding
        padded = jnp.pad(
            images, ((0, 0), (p, p), (p, p), (0, 0)), mode="reflect"
        )
        ys = jax.random.randint(ky, (b,), 0, 2 * p + 1)
        xs = jax.random.randint(kx, (b,), 0, 2 * p + 1)
        images = jax.vmap(
            lambda img, y, x: jax.lax.dynamic_slice(
                img, (y, x, 0), (h, w, img.shape[-1])
            )
        )(padded, ys, xs)
    return images


def mixup_batch(
    key: jax.Array,
    images: jax.Array,
    labels: jax.Array,
    alpha: float = 0.2,
) -> Dict[str, jax.Array]:
    """Mixup (arXiv:1710.09412): convex-combine each image with a permuted
    partner, lambda ~ Beta(alpha, alpha) per example. Returns the training
    batch with pairing info instead of materialized soft labels —
    ``labels``/``labels_b``/``lam`` — so the loss mixes per-example CE terms
    (algebraically identical to CE against the mixed one-hot target, without
    a [B, num_classes] buffer)."""
    kp, kl = jax.random.split(key)
    b = images.shape[0]
    perm = jax.random.permutation(kp, b)
    lam = jax.random.beta(kl, alpha, alpha, (b,)).astype(images.dtype)
    # fold toward the larger half so lam >= 0.5: keeps "labels" the majority
    # target (pure convention; CE mix is symmetric)
    lam = jnp.maximum(lam, 1.0 - lam)
    mixed = lam[:, None, None, None] * images + (
        1.0 - lam[:, None, None, None]
    ) * images[perm]
    return {
        "images": mixed,
        "labels": labels,
        "labels_b": labels[perm],
        "lam": lam.astype(jnp.float32),
    }


def cutmix_batch(
    key: jax.Array,
    images: jax.Array,
    labels: jax.Array,
    alpha: float = 1.0,
) -> Dict[str, jax.Array]:
    """CutMix (arXiv:1905.04899): paste a random rectangle from a permuted
    partner image; the label mixes by surviving area. Boxes are realized as
    iota-comparison masks (no dynamic slicing — XLA-friendly fixed shapes);
    ``lam`` is each example's ACTUAL surviving-area fraction after edge
    clamping, so the loss mix matches the pixels exactly."""
    kp, kl, ky, kx = jax.random.split(key, 4)
    b, h, w, _ = images.shape
    perm = jax.random.permutation(kp, b)
    lam0 = jax.random.beta(kl, alpha, alpha, (b,))
    cut = jnp.sqrt(1.0 - lam0)  # box side fraction
    bh = (cut * h).astype(jnp.int32)
    bw = (cut * w).astype(jnp.int32)
    cy = jax.random.randint(ky, (b,), 0, h)
    cx = jax.random.randint(kx, (b,), 0, w)
    y0 = jnp.clip(cy - bh // 2, 0, h)
    y1 = jnp.clip(cy + (bh + 1) // 2, 0, h)
    x0 = jnp.clip(cx - bw // 2, 0, w)
    x1 = jnp.clip(cx + (bw + 1) // 2, 0, w)
    rows = jnp.arange(h)[None, :, None]  # [1, H, 1]
    cols = jnp.arange(w)[None, None, :]  # [1, 1, W]
    in_box = (
        (rows >= y0[:, None, None])
        & (rows < y1[:, None, None])
        & (cols >= x0[:, None, None])
        & (cols < x1[:, None, None])
    )  # [B, H, W]
    mixed = jnp.where(in_box[..., None], images[perm], images)
    box_frac = jnp.mean(in_box.astype(jnp.float32), axis=(1, 2))
    return {
        "images": mixed,
        "labels": labels,
        "labels_b": labels[perm],
        "lam": 1.0 - box_frac,
    }


def prepare_eval_batch(images: jax.Array, masks: jax.Array) -> Dict[str, jax.Array]:
    """Eval-mode preparation: no geometry, just the Laplacian channel (the reference's
    non-augmenting input_fn path, preprocessing/preprocessing.py:243-246)."""
    return {"images": add_laplace_channel(images), "labels": masks}


# ---------------------------------------------------------------------------
# Test-time augmentation (reference: preprocessing/preprocessing.py:254-278 and the
# PREDICT-branch inversion, model.py:384-387). All four transforms are involutions, so
# each is its own inverse.
# ---------------------------------------------------------------------------

TTA_TRANSFORMS = ("vertical", "horizontal", "transpose", "none")


def tta_transform(x: jax.Array, transformation: str) -> jax.Array:
    """Apply a named TTA transform to a [B, H, W, C] batch."""
    if transformation == "vertical":
        return x[:, ::-1, :, :]
    if transformation == "horizontal":
        return x[:, :, ::-1, :]
    if transformation == "transpose":
        return jnp.transpose(x, (0, 2, 1, 3))
    if transformation == "none":
        return x
    raise ValueError(f"Unknown transformation {transformation}")


def tta_inverse(x: jax.Array, transformation: str) -> jax.Array:
    """Invert a named TTA transform (all are involutions)."""
    return tta_transform(x, transformation)
