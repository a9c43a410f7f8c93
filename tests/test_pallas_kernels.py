"""Pallas depthwise-conv kernel tests (interpreter mode on CPU — same kernel code
the TPU runs): forward exactness vs the XLA grouped-conv oracle across atrous
rates, gradient correctness via the custom VJP, and the VMEM fallback path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowdistributedlearning_tpu.ops.pallas_kernels import (
    depthwise_conv2d,
    depthwise_conv2d_reference,
)


@pytest.mark.parametrize("rate", [1, 2, 4])
@pytest.mark.parametrize("shape", [(2, 13, 13, 128), (1, 10, 7, 128)])
def test_forward_matches_xla(rate, shape):
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, shape).astype(np.float32)
    w = rng.normal(0, 0.5, (3, 3, shape[-1])).astype(np.float32)
    got = depthwise_conv2d(jnp.asarray(x), jnp.asarray(w), rate, interpret=True)
    want = depthwise_conv2d_reference(jnp.asarray(x), jnp.asarray(w), rate)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_5x5_kernel():
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1, (1, 9, 9, 128)).astype(np.float32)
    w = rng.normal(0, 0.5, (5, 5, 128)).astype(np.float32)
    got = depthwise_conv2d(jnp.asarray(x), jnp.asarray(w), 1, interpret=True)
    want = depthwise_conv2d_reference(jnp.asarray(x), jnp.asarray(w), 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_gradients_match_reference():
    rng = np.random.default_rng(2)
    x = rng.normal(0, 1, (2, 8, 8, 128)).astype(np.float32)
    w = rng.normal(0, 0.5, (3, 3, 128)).astype(np.float32)

    def loss_kernel(x, w):
        return jnp.sum(depthwise_conv2d(x, w, 2, interpret=True) ** 2)

    def loss_ref(x, w):
        return jnp.sum(depthwise_conv2d_reference(x, w, 2) ** 2)

    gx, gw = jax.grad(loss_kernel, argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    rx, rw = jax.grad(loss_ref, argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), rtol=1e-4, atol=1e-3)


def test_channel_tiling_matches_oracle():
    # budget that fits one 128-lane tile but not all 256 channels: the kernel must
    # tile C across the grid and still be exact
    rng = np.random.default_rng(6)
    x = rng.normal(0, 1, (2, 12, 12, 256)).astype(np.float32)
    w = rng.normal(0, 0.5, (3, 3, 256)).astype(np.float32)
    budget = (12 + 2) * (12 + 2) * 128 * 4 + 1
    got = depthwise_conv2d(
        jnp.asarray(x), jnp.asarray(w), 1, interpret=True, vmem_limit_bytes=budget
    )
    want = depthwise_conv2d_reference(jnp.asarray(x), jnp.asarray(w), 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_vmem_fallback_used_for_large_blocks():
    rng = np.random.default_rng(3)
    x = rng.normal(0, 1, (1, 64, 64, 128)).astype(np.float32)
    w = rng.normal(0, 0.5, (3, 3, 128)).astype(np.float32)
    # tiny budget forces the XLA path; result must still be exact
    got = depthwise_conv2d(
        jnp.asarray(x), jnp.asarray(w), 1, interpret=True, vmem_limit_bytes=1024
    )
    want = depthwise_conv2d_reference(jnp.asarray(x), jnp.asarray(w), 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_bfloat16_inputs():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (1, 8, 8, 128)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(0, 0.5, (3, 3, 128)), jnp.bfloat16)
    got = depthwise_conv2d(x, w, 1, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = depthwise_conv2d_reference(x.astype(jnp.float32), w.astype(jnp.float32), 1)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=0.05, atol=0.05
    )


def test_model_paths_agree(monkeypatch):
    # the ASPP with use_pallas_depthwise on/off must produce identical outputs from
    # the same parameters (pure execution-path switch); the platform gate is
    # patched open so the Pallas (interpreter) path actually runs on the CPU
    # mesh — without the patch both models would take XLA and the check would
    # be vacuous
    import tensorflowdistributedlearning_tpu.models.layers as layers_mod
    from tensorflowdistributedlearning_tpu.config import ModelConfig
    from tensorflowdistributedlearning_tpu.models import build_model

    monkeypatch.setattr(layers_mod, "_pallas_platform_ok", lambda: True)
    base = dict(input_shape=(33, 33), n_blocks=(1, 1, 1), base_depth=32)
    m_xla = build_model(ModelConfig(use_pallas_depthwise=False, **base))
    m_pl = build_model(ModelConfig(use_pallas_depthwise=True, **base))
    x = jnp.asarray(np.random.default_rng(5).normal(0, 1, (1, 33, 33, 2)), jnp.float32)
    variables = m_xla.init(jax.random.PRNGKey(0), x, train=False)
    out_xla = m_xla.apply(variables, x, train=False)
    out_pl = m_pl.apply(variables, x, train=False)  # same params, pallas path
    np.testing.assert_allclose(
        np.asarray(out_pl), np.asarray(out_xla), rtol=1e-4, atol=1e-4
    )


@pytest.mark.skipif(
    jax.default_backend() == "tpu",
    reason="asserts the gate's off-TPU behavior; on TPU the kernel SHOULD engage",
)
def test_platform_gate_blocks_pallas_off_tpu():
    """With the real (unpatched) gate on the CPU backend, use_pallas=True at
    a winning rate still dispatches to XLA — the default-ON config can never
    route CI or CPU-mesh users through the Pallas interpreter."""
    import tensorflowdistributedlearning_tpu.ops.pallas_kernels as pk
    from tensorflowdistributedlearning_tpu.models.layers import DepthwiseConv2D

    calls = []
    orig = pk.depthwise_conv2d
    try:
        pk.depthwise_conv2d = lambda *a, **k: calls.append(1) or orig(*a, **k)
        layer = DepthwiseConv2D(rate=8, use_pallas=True)
        x = jnp.zeros((1, 8, 8, 4), jnp.float32)
        variables = layer.init(jax.random.PRNGKey(0), x)
        layer.apply(variables, x)
    finally:
        pk.depthwise_conv2d = orig
    assert not calls  # CPU backend: the gate kept everything on XLA


def test_validation():
    x = jnp.zeros((1, 4, 4, 8))
    with pytest.raises(ValueError, match="odd kernel"):
        depthwise_conv2d(x, jnp.zeros((2, 2, 8)), interpret=True)
    with pytest.raises(ValueError, match="channel mismatch"):
        depthwise_conv2d(x, jnp.zeros((3, 3, 4)), interpret=True)


def test_rate_gate_dispatch(monkeypatch):
    """The layer engages the Pallas kernel only at rates
    >= PALLAS_DEPTHWISE_MIN_RATE even when use_pallas=True. The threshold is
    1 as of the 2026-08-01 device-dominated microbench (Pallas wins every
    rate), so the gate is exercised here by PATCHING it back to 4 — the
    machinery must keep restricting correctly if a future re-measure
    re-raises it. The platform gate is patched open so the dispatch logic
    runs on the CPU test mesh."""
    import tensorflowdistributedlearning_tpu.models.layers as layers_mod
    import tensorflowdistributedlearning_tpu.ops.pallas_kernels as pk
    from tensorflowdistributedlearning_tpu.models.layers import DepthwiseConv2D

    monkeypatch.setattr(layers_mod, "_pallas_platform_ok", lambda: True)
    monkeypatch.setattr(pk, "PALLAS_DEPTHWISE_MIN_RATE", 4)
    taken = []
    real = pk.depthwise_conv2d
    monkeypatch.setattr(
        pk,
        "depthwise_conv2d",
        lambda *a, **k: taken.append("pallas") or real(*a, **k),
    )
    x = jnp.asarray(
        np.random.default_rng(0).normal(0, 1, (1, 16, 16, 8)), jnp.float32
    )
    # init() traces the layer too, so each engaged rate records two calls
    for rate, expect in ((1, 0), (2, 0), (4, 2), (8, 4)):
        layer = DepthwiseConv2D(rate=rate, use_pallas=True)
        variables = layer.init(jax.random.PRNGKey(0), x)
        layer.apply(variables, x)
        assert len(taken) == expect, (rate, taken)


# -- fused inference BN + activation (+ residual) ----------------------------


def _bn_vectors(c, seed=7):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.normal(1, 0.1, c), jnp.float32),
        jnp.asarray(rng.normal(0, 0.1, c), jnp.float32),
        jnp.asarray(rng.normal(0, 0.1, c), jnp.float32),
        jnp.asarray(rng.uniform(0.5, 1.5, c), jnp.float32),
    )


@pytest.mark.parametrize("act", ["none", "relu", "relu6", "sigmoid", "gelu"])
@pytest.mark.parametrize("with_residual", [False, True])
def test_fused_bn_act_matches_xla(act, with_residual):
    from tensorflowdistributedlearning_tpu.ops.pallas_kernels import (
        fused_bn_act,
        fused_bn_act_reference,
    )

    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(0, 1, (2, 9, 11, 128)), jnp.float32)
    r = jnp.asarray(rng.normal(0, 1, x.shape), jnp.float32) if with_residual else None
    got = fused_bn_act(x, *_bn_vectors(128), act=act, residual=r, interpret=True)
    want = fused_bn_act_reference(x, *_bn_vectors(128), act=act, residual=r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_fused_bn_act_bfloat16_io():
    """bf16 activations (the quantized serving regime) compute in f32 inside
    and return bf16 — parity against the reference at bf16 resolution."""
    from tensorflowdistributedlearning_tpu.ops.pallas_kernels import (
        fused_bn_act,
        fused_bn_act_reference,
    )

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(0, 1, (1, 8, 8, 128)), jnp.bfloat16)
    r = jnp.asarray(rng.normal(0, 1, x.shape), jnp.bfloat16)
    got = fused_bn_act(x, *_bn_vectors(128), residual=r, interpret=True)
    want = fused_bn_act_reference(x, *_bn_vectors(128), residual=r)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=1e-2, atol=1e-2,
    )


def test_fused_bn_act_channel_tiling_and_fallback():
    from tensorflowdistributedlearning_tpu.ops.pallas_kernels import (
        fused_bn_act,
        fused_bn_act_reference,
    )

    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.normal(0, 1, (2, 12, 12, 256)), jnp.float32)
    vecs = _bn_vectors(256)
    want = fused_bn_act_reference(x, *vecs)
    # budget fits one 128-lane tile but not all 256 channels: grid tiles C
    budget = 12 * 12 * 128 * 4 + 1
    got = fused_bn_act(x, *vecs, interpret=True, vmem_limit_bytes=budget)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    # tiny budget: the XLA fallback must be exact too
    got = fused_bn_act(x, *vecs, interpret=True, vmem_limit_bytes=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_fused_bn_act_validation():
    from tensorflowdistributedlearning_tpu.ops.pallas_kernels import fused_bn_act

    x = jnp.zeros((1, 4, 4, 8))
    s = b = m = v = jnp.ones((8,))
    with pytest.raises(ValueError, match="act"):
        fused_bn_act(x, s, b, m, v, act="swiglu", interpret=True)
    with pytest.raises(ValueError, match="channels"):
        fused_bn_act(x, jnp.ones((4,)), b, m, v, interpret=True)
    with pytest.raises(ValueError, match="residual"):
        fused_bn_act(x, s, b, m, v, residual=jnp.zeros((1, 4, 4, 4)), interpret=True)
    with pytest.raises(ValueError, match="B, H, W, C"):
        fused_bn_act(jnp.zeros((4, 8)), s, b, m, v, interpret=True)


# -- fused bias + activation (the shared epilogue's standalone face) -----------


@pytest.mark.parametrize("act", ["none", "relu", "gelu"])
@pytest.mark.parametrize("with_bias", [False, True])
def test_fused_bias_act_matches_reference(act, with_bias):
    from tensorflowdistributedlearning_tpu.ops.pallas_kernels import (
        fused_bias_act,
        fused_bias_act_reference,
    )

    rng = np.random.default_rng(20)
    x = jnp.asarray(rng.normal(0, 1, (3, 7, 128)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.2, (128,)), jnp.float32) if with_bias else None
    got = fused_bias_act(x, b, act=act, interpret=True)
    want = fused_bias_act_reference(x, b, act=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_fused_bias_act_row_tiling_and_fallback():
    from tensorflowdistributedlearning_tpu.ops.pallas_kernels import (
        fused_bias_act,
        fused_bias_act_reference,
    )

    rng = np.random.default_rng(21)
    x = jnp.asarray(rng.normal(0, 1, (16, 64)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.2, (64,)), jnp.float32)
    want = fused_bias_act_reference(x, b, act="relu")
    # budget admits a quarter of the rows: the grid must tile and stay exact
    tiled = fused_bias_act(
        x, b, act="relu", interpret=True, vmem_limit_bytes=4 * 64 * 8 + 1
    )
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(want))
    # tiny budget: XLA fallback, still exact
    fb = fused_bias_act(x, b, act="relu", interpret=True, vmem_limit_bytes=16)
    np.testing.assert_array_equal(np.asarray(fb), np.asarray(want))


def test_fused_bias_act_validation():
    from tensorflowdistributedlearning_tpu.ops.pallas_kernels import fused_bias_act

    with pytest.raises(ValueError, match="act"):
        fused_bias_act(jnp.zeros((2, 4)), act="swish", interpret=True)
    with pytest.raises(ValueError, match="bias"):
        fused_bias_act(jnp.zeros((2, 4)), jnp.zeros((3,)), interpret=True)


# -- the segmentation serve head: plain XLA, on purpose ------------------------
#
# PR 20 routed the serving closure's head through a Pallas sigmoid+threshold
# kernel to save HBM passes. Its block shape was one Mosaic refuses for every
# batch but 1 (and for the exported symbolic batch), so `train --export-serving`
# died on a TPU — and XLA:TPU already emits the plain head as ONE multi-output
# fusion that reads the logits once (compiled for a v5e, PR 21). The kernel is
# gone; these pin what serving relies on from the plain head.


def _mask_logits(shape=(2, 9, 9, 1), seed=22):
    # spread logits across the threshold so some pixels land on each side,
    # including values AT zero (sigmoid(0) == 0.5 exactly — the boundary the
    # strict > must not flip)
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 2, shape).astype(np.float32)
    x.flat[:3] = 0.0
    return jnp.asarray(x)


@pytest.mark.parametrize("threshold", [0.5, 0.3])
def test_segmentation_head_mask_is_a_strict_threshold_of_its_probabilities(
    threshold,
):
    from tensorflowdistributedlearning_tpu.train.step import SegmentationTask

    logits = _mask_logits()
    out = SegmentationTask(threshold=threshold).predictions(logits)
    probs, mask = np.asarray(out["probabilities"]), np.asarray(out["mask"])
    assert out["probabilities"].dtype == logits.dtype
    assert out["mask"].dtype == jnp.float32
    assert set(np.unique(mask)) <= {0.0, 1.0}
    np.testing.assert_array_equal(mask, (probs > threshold).astype(np.float32))
    # sigmoid(0) == 0.5 exactly: strictly-greater keeps it out at 0.5
    assert mask.flat[0] == (1.0 if threshold < 0.5 else 0.0)


def test_serving_has_one_head_for_every_task():
    """The serving closures call `predictions` — there is no second,
    serving-only head to keep bit-identical with it."""
    from tensorflowdistributedlearning_tpu.ops import pallas_kernels
    from tensorflowdistributedlearning_tpu.train.step import (
        ClassificationTask,
        SegmentationTask,
    )

    for task in (SegmentationTask(), ClassificationTask()):
        assert not hasattr(task, "serve_predictions")
    assert not hasattr(pallas_kernels, "fused_sigmoid_mask")


def test_segmentation_head_lowers_for_tpu_without_a_custom_call():
    """One head, any batch: the plain ops lower for a TPU at the exported
    symbolic batch with no Mosaic call for the compiler to refuse."""
    from jax import export as jax_export

    from tensorflowdistributedlearning_tpu.train.step import SegmentationTask

    (b,) = jax_export.symbolic_shape("b")
    exported = jax_export.export(
        jax.jit(SegmentationTask().predictions), platforms=["tpu"]
    )(jax.ShapeDtypeStruct((b, 101, 101, 1), jnp.float32))
    assert "tpu_custom_call" not in exported.mlir_module()
    assert exported.platforms == ("tpu",)
