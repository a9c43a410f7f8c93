"""Quantized-compute kernel parity (interpreter mode on CPU — the same
integer kernel body the TPU compiles): int8 matmul/conv vs the
dequantize-f32 oracle across odd channels, zero-scale channels, and the
bucket-ladder batch sizes; bitwise accumulator equivalence against XLA's
genuine int8 arithmetic (fallback-path proof); the dynamic activation
quantizer's padding invariant the serving engine relies on; and the
interceptor's routing envelope (quantized dense/conv in, everything else
falls through untouched)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowdistributedlearning_tpu.ops.quant_kernels import (
    _QMM_ROW_TILE,
    int8_conv2d,
    int8_conv2d_reference,
    int8_intercept,
    int8_matmul,
    int8_matmul_reference,
    int8_matmul_xla,
    quantize_activations,
)
from tensorflowdistributedlearning_tpu.train.quantize import quantize_pytree


def quantize_weight(w):
    """Per-channel symmetric int8 via the real export recipe — the same
    records the interceptor sees, not a test-local reimplementation."""
    qtree, _ = quantize_pytree({"m": {"kernel": w}}, "int8")
    rec = qtree["m"]["kernel"]
    return jnp.asarray(rec["q"]), jnp.asarray(rec["scale"])


# -- dynamic activation quantization ------------------------------------------


def test_quantize_activations_roundtrip_and_zero_guard():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 2, (4, 33)), jnp.float32)
    q, s = quantize_activations(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(q, np.float32) * np.asarray(s), np.asarray(x),
        atol=float(s) * 0.5 + 1e-7,
    )
    # all-zero tensor: scale pins to 1.0, nothing divides by zero
    qz, sz = quantize_activations(jnp.zeros((3, 5)))
    assert float(sz) == 1.0 and not np.any(np.asarray(qz))


def test_quantize_activations_padding_invariant():
    """Zero-point 0 is the property the bucket ladder leans on: appending
    zero rows (engine pad) changes neither the scale nor the quantized
    values of the live rows."""
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1, (3, 16)).astype(np.float32)
    padded = np.zeros((8, 16), np.float32)
    padded[:3] = x
    q, s = quantize_activations(jnp.asarray(x))
    qp, sp = quantize_activations(jnp.asarray(padded))
    assert float(s) == float(sp)
    np.testing.assert_array_equal(np.asarray(qp[:3]), np.asarray(q))
    assert not np.any(np.asarray(qp[3:]))


# -- int8 matmul: kernel vs dequantize-f32 oracle ------------------------------


@pytest.mark.parametrize("m", [1, 4, 16, 64])  # the serve bucket ladder
@pytest.mark.parametrize("k,n", [(32, 48), (33, 129)])  # even and odd channels
def test_matmul_parity_vs_reference(m, k, n):
    rng = np.random.default_rng(m * 1000 + k)
    x = jnp.asarray(rng.normal(0, 1, (m, k)), jnp.float32)
    wq, ws = quantize_weight(
        jnp.asarray(rng.normal(0, 0.5, (k, n)), jnp.float32)
    )
    bias = jnp.asarray(rng.normal(0, 0.1, (n,)), jnp.float32)
    got = int8_matmul(x, wq, ws, bias=bias, act="relu", interpret=True)
    want = int8_matmul_reference(x, wq, ws, bias=bias, act="relu")
    # integer accumulation is exact; only f32 rounding differs between paths
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3
    )


def test_matmul_zero_scale_channels():
    """All-zero weight columns quantize with the scale-1.0 guard; the kernel
    must emit exact zeros there (bias-only after the epilogue)."""
    rng = np.random.default_rng(2)
    w = rng.normal(0, 0.5, (16, 8)).astype(np.float32)
    w[:, 3] = 0.0
    w[:, 6] = 0.0
    wq, ws = quantize_weight(jnp.asarray(w))
    assert float(ws[3]) == 1.0 and float(ws[6]) == 1.0
    x = jnp.asarray(rng.normal(0, 1, (4, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 1, (8,)), jnp.float32)
    got = np.asarray(int8_matmul(x, wq, ws, bias=bias, interpret=True))
    np.testing.assert_allclose(got[:, 3], float(bias[3]), rtol=1e-6)
    np.testing.assert_allclose(got[:, 6], float(bias[6]), rtol=1e-6)
    want = np.asarray(int8_matmul_reference(x, wq, ws, bias=bias))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_matmul_leading_dims_and_out_dtype():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (2, 3, 16)), jnp.float32)
    wq, ws = quantize_weight(jnp.asarray(rng.normal(0, 0.5, (16, 8))))
    got = int8_matmul(x, wq, ws, out_dtype=jnp.bfloat16, interpret=True)
    assert got.shape == (2, 3, 8) and got.dtype == jnp.bfloat16
    want = int8_matmul_reference(x, wq, ws, out_dtype=jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_matmul_n_tiling_matches_untiled():
    """A VMEM budget that forces output-feature tiling across the grid must
    not change results."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (8, 32)), jnp.float32)
    wq, ws = quantize_weight(jnp.asarray(rng.normal(0, 0.5, (32, 64))))
    full = int8_matmul(x, wq, ws, interpret=True)
    # budget fits ~a quarter of N: fixed tm*32 + nt*(32+tm*4+8), with tm the
    # kernel's row block (independent of the 8 rows served)
    tm = _QMM_ROW_TILE
    tiled = int8_matmul(
        x, wq, ws, interpret=True,
        vmem_limit_bytes=tm * 32 + 16 * (32 + tm * 4 + 8) + 1,
    )
    np.testing.assert_array_equal(np.asarray(full), np.asarray(tiled))


def test_matmul_vmem_overflow_falls_back_to_reference():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(0, 1, (4, 16)), jnp.float32)
    wq, ws = quantize_weight(jnp.asarray(rng.normal(0, 0.5, (16, 6))))
    got = int8_matmul(x, wq, ws, interpret=True, vmem_limit_bytes=64)
    want = int8_matmul_reference(x, wq, ws)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_matmul_integer_accumulator_bitwise_vs_xla():
    """Fallback-path equivalence at the arithmetic level: the interpreted
    Pallas kernel and XLA's int8 dot produce BITWISE-equal int32
    accumulators (both integer paths are exact; only the separately-compiled
    f32 epilogues may differ in the last ulp from FMA fusion)."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(0, 1, (16, 64)), jnp.float32)
    wq, ws = quantize_weight(jnp.asarray(rng.normal(0, 0.5, (64, 40))))
    ones = jnp.ones((40,), jnp.float32)
    # scale=1, no bias, no act: the raw accumulator in f32 carry-out
    acc_kernel = int8_matmul(x, wq, ones, interpret=True)
    acc_xla = int8_matmul_xla(x, wq, ones)
    # int32 accumulators cast to f32 are exact for |acc| < 2^24
    np.testing.assert_array_equal(np.asarray(acc_kernel), np.asarray(acc_xla))


def test_matmul_validation():
    x = jnp.zeros((2, 8))
    wq = jnp.zeros((8, 4), jnp.int8)
    with pytest.raises(ValueError, match="int8"):
        int8_matmul(x, jnp.zeros((8, 4)), jnp.ones((4,)), interpret=True)
    with pytest.raises(ValueError, match="last dim"):
        int8_matmul(jnp.zeros((2, 7)), wq, jnp.ones((4,)), interpret=True)
    with pytest.raises(ValueError, match="w_scale"):
        int8_matmul(x, wq, jnp.ones((3,)), interpret=True)
    with pytest.raises(ValueError, match="bias"):
        int8_matmul(x, wq, jnp.ones((4,)), bias=jnp.ones((5,)), interpret=True)


# -- int8 conv2d ---------------------------------------------------------------


@pytest.mark.parametrize("padding", ["SAME", "VALID"])
@pytest.mark.parametrize("khw,cin,cout", [(3, 8, 16), (1, 8, 16), (3, 5, 7)])
def test_conv_parity_vs_reference(padding, khw, cin, cout):
    rng = np.random.default_rng(khw * 100 + cin)
    x = jnp.asarray(rng.normal(0, 1, (2, 9, 11, cin)), jnp.float32)
    wq, ws = quantize_weight(
        jnp.asarray(rng.normal(0, 0.5, (khw, khw, cin, cout)), jnp.float32)
    )
    bias = jnp.asarray(rng.normal(0, 0.1, (cout,)), jnp.float32)
    got = int8_conv2d(
        x, wq, ws, padding=padding, bias=bias, act="relu", interpret=True
    )
    want = int8_conv2d_reference(x, wq, ws, padding=padding, bias=bias, act="relu")
    assert got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3
    )


def test_conv_explicit_padding_and_zero_scale():
    rng = np.random.default_rng(9)
    w = rng.normal(0, 0.5, (3, 3, 4, 6)).astype(np.float32)
    w[..., 2] = 0.0  # zero output channel -> scale-1.0 guard
    wq, ws = quantize_weight(jnp.asarray(w))
    assert float(ws[2]) == 1.0
    x = jnp.asarray(rng.normal(0, 1, (1, 7, 7, 4)), jnp.float32)
    pads = ((2, 0), (0, 2))
    got = int8_conv2d(x, wq, ws, padding=pads, interpret=True)
    want = int8_conv2d_reference(x, wq, ws, padding=pads)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3
    )
    assert not np.any(np.asarray(got)[..., 2])


def test_conv_vmem_overflow_falls_back():
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.normal(0, 1, (1, 8, 8, 4)), jnp.float32)
    wq, ws = quantize_weight(jnp.asarray(rng.normal(0, 0.5, (3, 3, 4, 6))))
    got = int8_conv2d(x, wq, ws, interpret=True, vmem_limit_bytes=256)
    want = int8_conv2d_reference(x, wq, ws)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_conv_validation():
    x = jnp.zeros((1, 8, 8, 4))
    wq = jnp.zeros((3, 3, 4, 6), jnp.int8)
    ws = jnp.ones((6,))
    with pytest.raises(ValueError, match="int8"):
        int8_conv2d(x, jnp.zeros((3, 3, 4, 6)), ws, interpret=True)
    with pytest.raises(ValueError, match="channels"):
        int8_conv2d(jnp.zeros((1, 8, 8, 3)), wq, ws, interpret=True)
    with pytest.raises(ValueError, match="padding"):
        int8_conv2d(x, wq, ws, padding="CIRCULAR", interpret=True)
    with pytest.raises(ValueError, match="expects"):
        int8_conv2d(jnp.zeros((8, 4)), wq, ws, interpret=True)


# -- the interceptor -----------------------------------------------------------


class _MixedNet:
    """A net straddling the interceptor envelope: a supported conv + dense,
    and a STRIDED conv that must fall through to the float path."""

    def __new__(cls):
        from flax import linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                x = nn.Conv(8, (3, 3), padding="SAME", name="conv_ok")(x)
                x = nn.relu(x)
                x = nn.Conv(8, (3, 3), strides=(2, 2), name="conv_strided")(x)
                x = x.reshape((x.shape[0], -1))
                return nn.Dense(4, name="head")(x)

        return Net()


def _init_mixed(net):
    x = jnp.zeros((2, 8, 8, 3), jnp.float32)
    params = net.init(jax.random.PRNGKey(0), x)["params"]
    return params, x


def test_interceptor_routes_supported_layers_only(monkeypatch):
    import tensorflowdistributedlearning_tpu.ops.quant_kernels as qk

    net = _MixedNet()
    params, x = _init_mixed(net)
    qparams, _ = quantize_pytree(params, "int8-compute")
    calls = []
    real_mm, real_conv = qk.int8_matmul, qk.int8_conv2d
    monkeypatch.setattr(
        qk, "int8_matmul", lambda *a, **k: calls.append("mm") or real_mm(*a, **k)
    )
    monkeypatch.setattr(
        qk, "int8_conv2d",
        lambda *a, **k: calls.append("conv") or real_conv(*a, **k),
    )
    from tensorflowdistributedlearning_tpu.train.quantize import (
        dequantize_pytree,
    )

    deq = dequantize_pytree(qparams, jnp.float32)
    with int8_intercept(qparams, jnp.float32):
        out = net.apply({"params": deq}, x)
    # dense + the stride-1 conv routed; the strided conv did NOT
    assert sorted(calls) == ["conv", "mm"]
    assert out.shape == (2, 4)


def test_interceptor_output_tracks_dequantized_path():
    """int8-compute differs from the dequantized float path only by
    activation-quantization noise — same weights, bounded drift. (Exact
    equality would mean the interceptor silently fell through.)"""
    from tensorflowdistributedlearning_tpu.train.quantize import (
        dequantize_pytree,
    )

    net = _MixedNet()
    params, _ = _init_mixed(net)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(0, 1, (2, 8, 8, 3)), jnp.float32)
    qparams, _ = quantize_pytree(params, "int8-compute")
    deq = dequantize_pytree(qparams, jnp.float32)
    float_path = net.apply({"params": deq}, x)
    with int8_intercept(qparams, jnp.float32):
        quant_path = net.apply({"params": deq}, x)
    delta = np.abs(np.asarray(quant_path) - np.asarray(float_path))
    assert delta.max() > 0  # genuinely different arithmetic
    assert delta.max() < 0.25  # within the int8-compute drift budget


def test_interceptor_noop_on_unquantized_tree():
    """A float32 params tree holds no records: the interceptor must leave
    every layer on the float path, bit-identically."""
    net = _MixedNet()
    params, x = _init_mixed(net)
    plain = net.apply({"params": params}, x)
    with int8_intercept(params, jnp.float32):
        intercepted = net.apply({"params": params}, x)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(intercepted))
