"""Lovász hinge tests against an independent numpy oracle (the reference shipped its
loss untested — reference: core/losses.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tensorflowdistributedlearning_tpu.ops import (
    lovasz_hinge,
    lovasz_hinge_flat,
    lovasz_loss,
)
from tensorflowdistributedlearning_tpu.ops.losses import (
    _VOID_ERROR,
    lovasz_grad,
    sigmoid_cross_entropy,
    softmax_cross_entropy,
)


def np_lovasz_hinge_flat_and_grad(logits, labels):
    """Straight-from-the-paper numpy implementation (Berman et al. 2018, Alg. 1):
    the loss, and its gradient with respect to the logits."""
    if logits.size == 0:
        return 0.0, np.zeros_like(logits)
    signs = 2.0 * labels - 1.0
    errors = 1.0 - logits * signs
    order = np.argsort(-errors, kind="stable")
    errors_sorted = errors[order]
    gt_sorted = labels[order]
    gts = gt_sorted.sum()
    intersection = gts - np.cumsum(gt_sorted)
    union = gts + np.cumsum(1.0 - gt_sorted)
    jaccard = 1.0 - intersection / union
    jaccard[1:] = jaccard[1:] - jaccard[:-1]
    grad = np.zeros_like(logits)
    grad[order] = -signs[order] * (errors_sorted > 0) * jaccard
    return float(np.maximum(errors_sorted, 0.0) @ jaccard), grad


def np_lovasz_hinge_flat(logits, labels):
    return np_lovasz_hinge_flat_and_grad(logits, labels)[0]


def old_lovasz_hinge_flat(logits, labels, valid=None):
    """The formulation ``ops/losses.py`` had up to PR 26, verbatim: ``top_k`` returns a
    permutation, the labels are gathered through it and the backward pass scatters
    through it. Kept here as the oracle the sort-carried form is held to."""
    labels = labels.astype(logits.dtype)
    signs = 2.0 * labels - 1.0
    errors = 1.0 - logits * lax.stop_gradient(signs)
    if valid is not None:
        valid = valid.astype(logits.dtype)
        errors = jnp.where(valid > 0, errors, _VOID_ERROR)
    errors_sorted, perm = lax.top_k(errors, errors.shape[0])
    gt_sorted = jnp.take(labels, perm)
    valid_sorted = None if valid is None else jnp.take(valid, perm)
    grad = lovasz_grad(gt_sorted, valid_sorted)
    return jnp.dot(jax.nn.relu(errors_sorted), lax.stop_gradient(grad))


def old_lovasz_hinge(logits, labels, per_image=True, ignore=None):
    """[B, P] logits and labels through the old flat hinge, as ``lovasz_hinge`` does."""
    valid = None if ignore is None else (labels != ignore)
    if not per_image:
        logits, labels = logits.reshape(1, -1), labels.reshape(1, -1)
        valid = None if valid is None else valid.reshape(1, -1)
    if valid is None:
        return jnp.mean(jax.vmap(old_lovasz_hinge_flat)(logits, labels))
    return jnp.mean(jax.vmap(old_lovasz_hinge_flat)(logits, labels, valid))


def _random_case(rng, batch, pixels, void_share=0.0):
    logits = rng.normal(size=(batch, pixels)).astype(np.float32)
    labels = (rng.random((batch, pixels)) > 0.6).astype(np.float32)
    labels[rng.random((batch, pixels)) < void_share] = 255.0
    return logits, labels


def _all_void_case(rng):
    logits, labels = _random_case(rng, 3, 64, void_share=0.3)
    labels[1] = 255.0
    return logits, labels


def _all_background_case(rng):
    logits, labels = _random_case(rng, 3, 64)
    labels[1] = 0.0
    return logits, labels


def _tied_case(rng, void_share=0.0):
    """Logits from five values, so most errors tie — across the two labels too, and at
    exactly 0 (logit 1 on a foreground pixel), where the order of equal errors decides
    which pixel gets which weight."""
    _, labels = _random_case(rng, 4, 96, void_share)
    return rng.choice(np.float32([-1.0, 0.0, 0.5, 1.0, 2.0]), size=labels.shape), labels


# name -> (builder(rng) -> (logits [B, P], labels [B, P]), per_image, ignore)
HINGE_CASES = {
    "per_image": (lambda rng: _random_case(rng, 4, 64), True, None),
    "flat": (lambda rng: _random_case(rng, 4, 64), False, None),
    "per_image_ignore": (lambda rng: _random_case(rng, 4, 64, 0.3), True, 255),
    "flat_ignore": (lambda rng: _random_case(rng, 4, 64, 0.3), False, 255),
    "all_void_image": (_all_void_case, True, 255),
    "all_background_image": (_all_background_case, True, None),
    "tied_errors": (_tied_case, True, None),
    "tied_errors_ignore": (lambda rng: _tied_case(rng, 0.3), False, 255),
    "flagship_frame_batch4": (lambda rng: _random_case(rng, 4, 101 * 101), True, None),
}


def _loss_and_grad(fn, logits, labels, per_image, ignore):
    loss, grad = jax.jit(
        jax.value_and_grad(lambda x: fn(x, jnp.asarray(labels), per_image, ignore))
    )(jnp.asarray(logits))
    return float(loss), np.asarray(grad)


@pytest.mark.parametrize("case", sorted(HINGE_CASES))
def test_equals_the_old_gather_formulation(case, rng):
    """Loss and gradient of the sort-carried hinge against ``top_k`` + ``take``: the
    same sum with its terms in another order (5e-6 relative; 6.2e-7 read at P = 10,201)
    and the same weight on the same pixel (1e-6 absolute; equal to the bit there)."""
    build, per_image, ignore = HINGE_CASES[case]
    logits, labels = build(rng)
    got_loss, got_grad = _loss_and_grad(lovasz_hinge, logits, labels, per_image, ignore)
    want_loss, want_grad = _loss_and_grad(
        old_lovasz_hinge, logits, labels, per_image, ignore
    )
    assert got_loss == pytest.approx(want_loss, rel=5e-6, abs=1e-7)
    np.testing.assert_allclose(got_grad, want_grad, rtol=0, atol=1e-6)
    assert np.any(got_grad != 0)


@pytest.mark.parametrize("case", sorted(HINGE_CASES))
def test_loss_and_gradient_match_numpy_oracle(case, rng):
    """The same cases against the paper's algorithm in numpy, which drops void pixels
    (the reference's boolean_mask) and orders equal errors lower index first."""
    build, per_image, ignore = HINGE_CASES[case]
    logits, labels = build(rng)
    got_loss, got_grad = _loss_and_grad(lovasz_hinge, logits, labels, per_image, ignore)
    rows = zip(logits, labels) if per_image else [(logits.ravel(), labels.ravel())]
    losses, grads = [], []
    for row_logits, row_labels in rows:
        keep = row_labels != 255.0
        loss, kept_grad = np_lovasz_hinge_flat_and_grad(row_logits[keep], row_labels[keep])
        grad = np.zeros_like(row_logits)
        grad[keep] = kept_grad
        losses.append(loss)
        grads.append(grad)
    assert got_loss == pytest.approx(np.mean(losses), rel=2e-5, abs=1e-7)
    np.testing.assert_allclose(
        got_grad, np.reshape(grads, logits.shape) / len(losses), rtol=0, atol=2e-6
    )


def test_gradient_program_has_no_gather_or_scatter(rng):
    """The CPU-side guard of what the chip's breakdown must show: the lowered gradient
    of the loss at the flagship's frame indexes nothing — no ``gather`` (the labels
    through a permutation) and no ``scatter`` (the cotangent back through it). The old
    formulation, lowered the same way, holds both."""
    y = jnp.asarray((rng.random((4, 101, 101, 1)) > 0.5).astype(np.float32))
    p = jnp.asarray(rng.normal(size=(4, 101, 101, 1)).astype(np.float32))

    def old_loss(logits):
        return old_lovasz_hinge(logits.reshape(4, -1), y.reshape(4, -1))

    new_text = jax.jit(jax.grad(lambda logits: lovasz_loss(y, logits))).lower(p).as_text()
    old_text = jax.jit(jax.grad(old_loss)).lower(p).as_text()
    assert "gather" in old_text and "scatter" in old_text
    assert "gather" not in new_text and "scatter" not in new_text
    assert new_text.count("stablehlo.sort") == 2


def test_matches_numpy_oracle(rng):
    logits = rng.normal(size=128).astype(np.float32)
    labels = (rng.random(128) > 0.6).astype(np.float32)
    got = float(lovasz_hinge_flat(jnp.asarray(logits), jnp.asarray(labels)))
    want = np_lovasz_hinge_flat(logits, labels)
    assert got == pytest.approx(want, rel=1e-5)


def test_perfect_prediction_low_loss(rng):
    labels = (rng.random(64) > 0.5).astype(np.float32)
    logits = (2.0 * labels - 1.0) * 50.0  # confidently correct
    loss = float(lovasz_hinge_flat(jnp.asarray(logits), jnp.asarray(labels)))
    assert loss == pytest.approx(0.0, abs=1e-5)


def test_wrong_prediction_high_loss(rng):
    labels = (rng.random(64) > 0.5).astype(np.float32)
    logits = -(2.0 * labels - 1.0) * 50.0  # confidently wrong
    loss = float(lovasz_hinge_flat(jnp.asarray(logits), jnp.asarray(labels)))
    assert loss > 1.0


def test_all_background_image():
    # empty ground truth: union accumulates, intersection stays 0 — loss is finite and
    # pushes logits negative
    labels = np.zeros(32, np.float32)
    logits = np.full(32, 0.5, np.float32)
    loss = float(lovasz_hinge_flat(jnp.asarray(logits), jnp.asarray(labels)))
    assert np.isfinite(loss) and loss > 0


def test_per_image_averages(rng):
    logits = rng.normal(size=(4, 8, 8)).astype(np.float32)
    labels = (rng.random((4, 8, 8)) > 0.5).astype(np.float32)
    per_image = float(lovasz_hinge(jnp.asarray(logits), jnp.asarray(labels)))
    manual = np.mean(
        [np_lovasz_hinge_flat(l.ravel(), y.ravel()) for l, y in zip(logits, labels)]
    )
    assert per_image == pytest.approx(manual, rel=1e-5)


def test_ignore_mask_matches_dropping_pixels(rng):
    """Fixed-shape void handling must equal the reference's dynamic boolean_mask
    (core/losses.py:68-80): compare against the oracle run on only the valid pixels."""
    logits = rng.normal(size=64).astype(np.float32)
    labels = (rng.random(64) > 0.5).astype(np.float32)
    labels[rng.random(64) < 0.3] = 255.0  # void label
    got = float(
        lovasz_hinge(
            jnp.asarray(logits)[None], jnp.asarray(labels)[None], ignore=255
        )
    )
    keep = labels != 255.0
    want = np_lovasz_hinge_flat(logits[keep], labels[keep])
    assert got == pytest.approx(want, rel=1e-4)


def test_all_void_image_zero_loss():
    """All-void image yields 0 (the reference's tf.cond arm, core/losses.py:59-64)."""
    logits = jnp.ones((1, 16))
    labels = jnp.full((1, 16), 255.0)
    got = float(lovasz_hinge(logits, labels, ignore=255))
    assert got == pytest.approx(0.0, abs=1e-6)


def test_lovasz_loss_layout_wrappers(rng):
    y = (rng.random((2, 8, 8, 1)) > 0.5).astype(np.float32)
    p = rng.normal(size=(2, 8, 8, 1)).astype(np.float32)
    nhwc = float(lovasz_loss(jnp.asarray(y), jnp.asarray(p), "NHWC"))
    nchw = float(
        lovasz_loss(
            jnp.asarray(y.transpose(0, 3, 1, 2)),
            jnp.asarray(p.transpose(0, 3, 1, 2)),
            "NCHW",
        )
    )
    assert nhwc == pytest.approx(nchw, rel=1e-6)


def test_gradients_finite_and_jittable(rng):
    y = (rng.random((2, 8, 8, 1)) > 0.5).astype(np.float32)
    p = rng.normal(size=(2, 8, 8, 1)).astype(np.float32)
    grad = jax.jit(jax.grad(lambda logits: lovasz_loss(jnp.asarray(y), logits)))(
        jnp.asarray(p)
    )
    assert np.all(np.isfinite(np.asarray(grad)))


def test_aux_losses(rng):
    logits = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    labels = jnp.asarray((rng.random(8) > 0.5).astype(np.float32))
    assert np.isfinite(float(sigmoid_cross_entropy(logits, labels)))
    cls_logits = jnp.asarray(rng.normal(size=(4, 10)).astype(np.float32))
    cls_labels = jnp.asarray([1, 2, 3, 4])
    assert np.isfinite(float(softmax_cross_entropy(cls_logits, cls_labels)))


def test_label_smoothing_cross_entropy():
    """Smoothed CE matches the closed form against a one-hot/uniform mixture
    oracle; s=0 reduces to plain CE; perfect predictions keep nonzero loss."""
    import numpy as np

    from tensorflowdistributedlearning_tpu.ops import losses as L

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(0, 2, (6, 5)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 5, 6).astype(np.int32))
    s = 0.1
    got = np.asarray(L.softmax_cross_entropy_per_example(logits, labels, s))

    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    onehot = np.eye(5)[np.asarray(labels)]
    target = (1 - s) * onehot + s / 5
    want = -(target * logp).sum(-1)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    plain = np.asarray(L.softmax_cross_entropy_per_example(logits, labels, 0.0))
    np.testing.assert_allclose(
        plain, -(onehot * logp).sum(-1), rtol=1e-6, atol=1e-6
    )
    # smoothing keeps a loss floor even for confident-correct predictions
    confident = jnp.asarray(onehot * 50.0, jnp.float32)
    assert float(L.softmax_cross_entropy(confident, labels, s)) > 0.01
