"""Lovász hinge loss, TPU-native.

Re-design of the reference's loss stack (reference: core/losses.py:5-92). Differences by
design, not accident:

- The reference looped over images with ``tf.map_fn`` (core/losses.py:27-34) and pinned
  the whole loss to CPU:0 (model.py:391-394), forcing a device->host round trip every
  step. Here the per-image loss is ``vmap``-ed and everything stays on the TPU.
- The descending sort carries what it orders. A sort that returns a permutation
  (``lax.top_k``, ``argsort``) makes the labels go through it as an indexed gather and
  the backward pass come back through it as a scatter: one scalar access a pixel, which
  on a v5e cost 25 + 17 ms of a 147 ms step at batch 256 against 1.7 ms for the sort
  itself (PERF.md §6, PR 27). ``lovasz_hinge_flat`` instead sorts (error, label, valid,
  position) together with one multi-operand ``lax.sort``, un-permutes the Lovász
  weights with a second sort keyed on the positions, and takes the dot product in the
  pixels' own order — so nothing differentiates through a sort and the gradient with
  respect to the logits is elementwise.
- The reference handled void pixels with dynamic-shape ``boolean_mask`` + ``tf.cond``
  (core/losses.py:59-64, 77-80), which cannot be jitted with static shapes. Here void
  pixels are handled with fixed-shape mask arithmetic: invalid errors are pushed to the
  end of the sort and contribute exactly zero to both the hinge terms and the Jaccard
  deltas, so an all-void image yields loss 0 just like the reference's ``tf.cond`` arm.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# Errors of void pixels are set to this so they sort strictly last. relu() of it is 0,
# so they contribute nothing to the hinge dot product.
_VOID_ERROR = -1e9


def lovasz_grad(gt_sorted: jax.Array, valid_sorted: Optional[jax.Array] = None) -> jax.Array:
    """Gradient of the Lovász extension w.r.t. sorted errors (reference:
    core/losses.py:5-15; Alg. 1 of Berman et al. 2018).

    ``gt_sorted``: [P] float 0/1 ground truth, ordered by descending error.
    ``valid_sorted``: optional [P] float 0/1 mask in the same order; void positions are
    weighted out of the cumulative sums so the Jaccard sequence is constant across them
    (delta 0), which is exactly what removing them (as the reference's boolean_mask did)
    produces.
    """
    if valid_sorted is None:
        valid_sorted = jnp.ones_like(gt_sorted)
    gt_sorted = gt_sorted * valid_sorted
    gts = jnp.sum(gt_sorted)
    intersection = gts - jnp.cumsum(gt_sorted)
    union = gts + jnp.cumsum((1.0 - gt_sorted) * valid_sorted)
    jaccard = 1.0 - intersection / jnp.maximum(union, 1e-12)
    return jnp.concatenate([jaccard[:1], jaccard[1:] - jaccard[:-1]])


def lovasz_hinge_flat(
    logits: jax.Array, labels: jax.Array, valid: Optional[jax.Array] = None
) -> jax.Array:
    """Binary Lovász hinge over a flat pixel vector (reference: core/losses.py:40-65).

    ``logits``: [P] float; ``labels``: [P] 0/1; ``valid``: optional [P] 0/1 mask
    (fixed-shape replacement for the reference's ignore-label boolean_mask).
    """
    labels = labels.astype(logits.dtype)
    signs = 2.0 * labels - 1.0
    errors = 1.0 - logits * lax.stop_gradient(signs)
    if valid is not None:
        valid = valid.astype(logits.dtype)
        errors = jnp.where(valid > 0, errors, _VOID_ERROR)
    # Descending by error, equal errors lower index first (stable on the negated key),
    # with the labels, the mask and each pixel's position riding along.
    carried = (labels,) if valid is None else (labels, valid)
    _, order, *carried_sorted = lax.sort(
        (-lax.stop_gradient(errors), lax.iota(jnp.int32, errors.shape[0]), *carried),
        num_keys=1,
        is_stable=True,
    )
    weights_sorted = lovasz_grad(*carried_sorted)
    # Sorting (position, weight) on the position puts each weight back on its pixel;
    # positions are distinct, so this sort needs no tie-break.
    _, weights = lax.sort((order, weights_sorted), num_keys=1, is_stable=False)
    return jnp.dot(jax.nn.relu(errors), lax.stop_gradient(weights))


def lovasz_hinge(
    logits: jax.Array,
    labels: jax.Array,
    per_image: bool = True,
    ignore: Optional[int] = None,
) -> jax.Array:
    """Binary Lovász hinge loss (reference: core/losses.py:18-37).

    ``logits``: [B, H, W] scores; ``labels``: [B, H, W] binary masks.
    ``per_image=True`` computes the loss per image and averages (the reference's
    ``map_fn`` path); ``False`` flattens the whole batch first.
    """
    valid = None if ignore is None else (labels != ignore)

    if per_image:
        return jnp.mean(lovasz_hinge_per_image(logits, labels, ignore))

    return lovasz_hinge_flat(
        logits.reshape(-1),
        labels.reshape(-1),
        None if valid is None else valid.reshape(-1),
    )


def lovasz_hinge_per_image(
    logits: jax.Array, labels: jax.Array, ignore: Optional[int] = None
) -> jax.Array:
    """Per-image Lovász hinge losses, shape [B] — the un-averaged form of the
    reference's ``map_fn`` path (core/losses.py:27-34); used by eval to weight out
    wrap-around-padded examples."""
    valid = None if ignore is None else (labels != ignore)
    flat_logits = logits.reshape(logits.shape[0], -1)
    flat_labels = labels.reshape(labels.shape[0], -1)
    if valid is None:
        return jax.vmap(lovasz_hinge_flat)(flat_logits, flat_labels)
    return jax.vmap(lovasz_hinge_flat)(
        flat_logits, flat_labels, valid.reshape(valid.shape[0], -1)
    )


def lovasz_loss(y_true: jax.Array, y_pred: jax.Array, data_format: str = "NHWC") -> jax.Array:
    """Layout-aware wrapper (reference: core/losses.py:83-92): squeezes the channel axis
    and runs the per-image hinge. ``y_pred`` are raw logits."""
    if data_format == "NHWC":
        labels = jnp.squeeze(y_true, -1)
        logits = jnp.squeeze(y_pred, -1)
    else:
        labels = jnp.squeeze(y_true, 1)
        logits = jnp.squeeze(y_pred, 1)
    return lovasz_hinge(logits.astype(jnp.float32), labels, per_image=True, ignore=None)


def sigmoid_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Numerically-stable BCE-with-logits; auxiliary loss for classification configs
    (no direct reference analogue — the reference only trains the Lovász objective)."""
    labels = labels.astype(logits.dtype)
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def softmax_cross_entropy_per_example(
    logits: jax.Array, labels: jax.Array, label_smoothing: float = 0.0
) -> jax.Array:
    """Per-example softmax cross entropy with integer labels, shape [B].

    ``label_smoothing`` mixes the one-hot target with the uniform distribution
    (Szegedy et al., arXiv:1512.00567) — the standard ImageNet regularizer
    (0.1 in the 76%-top-1 recipe); 0.0 is plain cross entropy."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    true_logp = jnp.take_along_axis(
        logp, labels[:, None].astype(jnp.int32), axis=-1
    )[:, 0]
    if label_smoothing:
        k = logits.shape[-1]
        # target = (1-s)*onehot + s/k: CE = -(1-s)*logp_true - s/k*sum(logp)
        return -(1.0 - label_smoothing) * true_logp - (
            label_smoothing / k
        ) * jnp.sum(logp, axis=-1)
    return -true_logp


def softmax_cross_entropy(
    logits: jax.Array, labels: jax.Array, label_smoothing: float = 0.0
) -> jax.Array:
    """Mean softmax cross entropy with integer labels, for the classification path the
    reference kept alongside segmentation (reference: core/resnet.py:246-256)."""
    return jnp.mean(
        softmax_cross_entropy_per_example(logits, labels, label_smoothing)
    )
