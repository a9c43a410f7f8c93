"""Pallas TPU kernels for the framework's hot custom ops.

Second kernel: **fused inference BatchNorm + activation (+ residual add)** —
the serving-side answer to the step profile's dominant bucket
(PROFILE_SEG_r05.json: 53.2% of serialized device time in bandwidth-bound
elementwise/BN fusions). At inference BN is an affine per-channel transform
(running statistics are constants), so the whole
``BN -> (+residual) -> activation`` chain is one read and one write of the
activation tensor at the HBM roofline. :func:`fused_bn_act` folds the four BN
vectors into a per-channel multiplier/offset in XLA (a [C]-sized epsilon of
work) and runs the memory-bound part as a single VMEM-resident Pallas pass;
:func:`fused_bn_act_reference` is the XLA oracle and the off-TPU/VMEM-overflow
fallback. Inference-only by design — training BN needs batch statistics and a
VJP, which the flax path already owns.

First kernel: **depthwise (per-channel) 2-D convolution**, the core of the
split-separable convolutions the ASPP head runs at atrous rates 2/4/8 and the
decoder runs at rate 1 (reference: core/layers.py:7-49 built these from
``slim.separable_conv2d``; SURVEY §3.3). On TPU the depthwise stage is VPU-bound —
XLA lowers it as a grouped convolution, while this kernel computes it directly as
``kh*kw`` shifted multiply-accumulates over a VMEM-resident block with channels on
the 128-wide lane dimension, the natural TPU layout.

The kernel is stride-1 SAME with dilation (atrous) support — exactly the shapes the
models use. Gradients are provided by a ``jax.custom_vjp``: dx is the same kernel
applied with a spatially-flipped filter; dw is nine cheap XLA reductions. A pure-XLA
reference (`depthwise_conv2d_reference`) doubles as the numerical oracle in tests
and the fallback when the image block exceeds the VMEM budget.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowdistributedlearning_tpu.parallel.collectives import vma_of

logger = logging.getLogger(__name__)

# One image block (padded H x W x C fp32) must fit comfortably in the ~16 MB VMEM
# alongside double-buffering; beyond this the public wrapper falls back to XLA.
_VMEM_BLOCK_LIMIT_BYTES = 4 * 1024 * 1024

# Measured on a v5e chip, 2026-08-01 (bench_kernels.py `_chained` + interleaved
# median-of-ratios, ASPP shape [32, 13, 13, 1024]): Pallas vs XLA grouped conv
# — rate 1: 1.51x, rate 2: 1.46x, rate 4: 1.56x, rate 8: 1.61x; the
# shift-accumulate VMEM kernel is rate-independent (~4.6 ms/chained-kernel)
# while XLA's grouped-conv lowering sits at ~7.3 ms at every rate. Models gate
# their Pallas dispatch on this threshold (models/layers.py:DepthwiseConv2D);
# 1 = every rate takes the kernel.
PALLAS_DEPTHWISE_MIN_RATE = 1


def pallas_platform_ok() -> bool:
    """True where the Pallas kernels run COMPILED (TPU); elsewhere they only
    have the slow interpreter. The ONE copy of this decision — the layer
    dispatch gate (models/layers.py:DepthwiseConv2D) and the interpret
    auto-selects of BOTH kernels (this module and ops/flash_attention.py)
    consult it, so they can never disagree."""
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def _warn_reference_once(kernel: str, why: str) -> None:
    logger.warning(
        "%s: running the XLA reference on a TPU, not the Pallas kernel (%s)",
        kernel, why,
    )


def note_reference_fallback(kernel: str, why: str) -> None:
    """Called where a wrapper takes its XLA reference for a reason other than
    the platform. On a TPU that is a kernel the caller asked for and did not
    get, so it is logged — once per (kernel, reason). Off-TPU the reference is
    the expected path and stays silent."""
    if pallas_platform_ok():
        _warn_reference_once(kernel, why)


def depthwise_conv2d_reference(
    x: jax.Array, w: jax.Array, rate: int = 1
) -> jax.Array:
    """XLA oracle/fallback: stride-1 SAME depthwise conv via grouped convolution.

    ``x``: [B, H, W, C]; ``w``: [kh, kw, C] per-channel filters.
    """
    kh, kw, c = w.shape
    kernel = w.reshape(kh, kw, 1, c)  # HWIO with I=1, feature_group_count=C
    pad_h = rate * (kh - 1) // 2
    pad_w = rate * (kw - 1) // 2
    return lax.conv_general_dilated(
        x,
        kernel.astype(x.dtype),
        window_strides=(1, 1),
        padding=[(pad_h, pad_h), (pad_w, pad_w)],
        rhs_dilation=(rate, rate),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c,
    )


def _dw_kernel(x_ref, w_ref, o_ref, *, kh: int, kw: int, rate: int):
    """One image per grid step: out = sum_ij w[i,j] * shift(x, (i,j))."""
    x = x_ref[0]  # [H, W, C]
    h, wdt, _ = x.shape
    ph = rate * (kh - 1) // 2
    pw = rate * (kw - 1) // 2
    xp = jnp.pad(x, ((ph, ph), (pw, pw), (0, 0)))
    acc = jnp.zeros(x.shape, jnp.float32)
    for i in range(kh):
        for j in range(kw):
            tap = lax.slice(
                xp, (i * rate, j * rate, 0), (i * rate + h, j * rate + wdt, xp.shape[2])
            )
            acc = acc + tap.astype(jnp.float32) * w_ref[i, j].astype(jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype)


def _channel_tile(c: int, block_elems: int, limit_bytes: int, itemsize: int) -> int:
    """Largest lane-aligned channel tile whose padded image block fits the VMEM
    budget. Channels are independent in a depthwise conv, so tiling C is free."""
    if c % 128 != 0:
        return c  # Mosaic pads the lane dim; only whole-C blocks possible
    ct = c
    while ct > 128 and block_elems * ct * itemsize > limit_bytes:
        ct //= 2
        while c % ct != 0 and ct > 128:
            ct -= 128
    return max(ct, 128)


def _dw_pallas(
    x: jax.Array, w: jax.Array, rate: int, interpret: bool, channel_tile: int
) -> jax.Array:
    b, h, wdt, c = x.shape
    kh, kw, _ = w.shape
    ct = channel_tile
    kernel = functools.partial(_dw_kernel, kh=kh, kw=kw, rate=rate)
    # Inside shard_map with check_vma, the out aval must declare how it varies
    # across mesh axes — the output varies exactly like the input block.
    vma = vma_of(x)
    out_shape = (
        jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)
        if vma
        else jax.ShapeDtypeStruct(x.shape, x.dtype)
    )
    return pl.pallas_call(
        kernel,
        grid=(b, c // ct),
        in_specs=[
            pl.BlockSpec(
                (1, h, wdt, ct), lambda i, j: (i, 0, 0, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((kh, kw, ct), lambda i, j: (0, 0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, h, wdt, ct), lambda i, j: (i, 0, 0, j), memory_space=pltpu.VMEM
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _dw_with_grad(
    x: jax.Array, w: jax.Array, rate: int, interpret: bool, channel_tile: int
) -> jax.Array:
    return _dw_pallas(x, w, rate, interpret, channel_tile)


def _dw_fwd(x, w, rate, interpret, channel_tile):
    return _dw_pallas(x, w, rate, interpret, channel_tile), (x, w)


def _dw_bwd(rate, interpret, channel_tile, res, g):
    x, w = res
    # dx: correlate the cotangent with the spatially flipped filter — for stride-1
    # SAME with symmetric padding this is again a depthwise conv (same kernel).
    dx = _dw_pallas(g, w[::-1, ::-1, :], rate, interpret, channel_tile).astype(x.dtype)
    # dw[i, j, c] = sum_{b,y,x} g * shift(x): nine reductions, left to XLA.
    kh, kw, _ = w.shape
    h, wdt = x.shape[1], x.shape[2]
    ph = rate * (kh - 1) // 2
    pw = rate * (kw - 1) // 2
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    g32 = g.astype(jnp.float32)
    taps = []
    for i in range(kh):
        row = []
        for j in range(kw):
            tap = lax.slice(
                xp,
                (0, i * rate, j * rate, 0),
                (x.shape[0], i * rate + h, j * rate + wdt, x.shape[3]),
            )
            row.append(jnp.sum(tap.astype(jnp.float32) * g32, axis=(0, 1, 2)))
        taps.append(jnp.stack(row))
    dw = jnp.stack(taps).astype(w.dtype)
    # Inside shard_map, custom_vjp must hand back cotangents whose varying manual
    # axes match the primal inputs. dw is built from varying activations, so when
    # the weight itself is replicated it needs the cross-shard psum the automatic
    # transposition would have inserted for a standard primitive.
    extra = tuple(sorted(vma_of(g) - vma_of(w)))
    if extra:
        dw = lax.psum(dw, extra)
    return dx, dw


_dw_with_grad.defvjp(_dw_fwd, _dw_bwd)


def depthwise_conv2d(
    x: jax.Array,
    w: jax.Array,
    rate: int = 1,
    *,
    interpret: Optional[bool] = None,
    vmem_limit_bytes: int = _VMEM_BLOCK_LIMIT_BYTES,
) -> jax.Array:
    """Stride-1 SAME depthwise conv, Pallas-accelerated where it fits.

    ``x``: [B, H, W, C]; ``w``: [kh, kw, C]; ``rate``: atrous dilation. Odd kernel
    dims required. Differentiable (custom VJP). ``interpret=None`` auto-selects:
    the Pallas path on TPU, the interpreter off-TPU (so tests exercise the same
    kernel code on the CPU mesh). Falls back to the XLA grouped-conv reference when
    one padded image block would not fit the VMEM budget.
    """
    kh, kw, c = w.shape
    if kh % 2 != 1 or kw % 2 != 1:
        raise ValueError(f"depthwise_conv2d requires odd kernel dims, got {kh}x{kw}")
    if x.shape[-1] != c:
        raise ValueError(f"channel mismatch: x has {x.shape[-1]}, w has {c}")
    ph = rate * (kh - 1)
    pw = rate * (kw - 1)
    block_elems = (x.shape[1] + ph) * (x.shape[2] + pw)
    itemsize = jnp.dtype(x.dtype).itemsize
    ct = _channel_tile(c, block_elems, vmem_limit_bytes, itemsize)
    if block_elems * ct * itemsize > vmem_limit_bytes:
        # even a single 128-lane tile (or an unsplittable C) is too large spatially
        note_reference_fallback(
            "depthwise_conv2d", f"image block {x.shape[1:]} over the VMEM budget"
        )
        return depthwise_conv2d_reference(x, w, rate)
    if interpret is None:
        interpret = not pallas_platform_ok()
    if interpret and vma_of(x):
        # Pallas's HLO interpreter cannot run under shard_map's varying-manual-axes
        # tracking (its internal dynamic_slice mixes varying/unvarying operands and
        # jax rejects it). Only the off-TPU debug path is affected — on TPU the
        # kernel lowers through Mosaic, not the interpreter.
        return depthwise_conv2d_reference(x, w, rate)
    return _dw_with_grad(x, w, rate, interpret, ct)


# -- fused inference BN + activation (+ residual) ----------------------------

# the activations the models' BN chains end in; "none" covers the pre-residual
# projection case where the add itself is the last op
_BN_ACTIVATIONS = {
    "none": lambda y: y,
    "relu": lambda y: jnp.maximum(y, 0.0),
    "relu6": lambda y: jnp.clip(y, 0.0, 6.0),
    "sigmoid": jax.nn.sigmoid,
    "gelu": jax.nn.gelu,
}


def bias_act_epilogue(y, bias=None, act: str = "none"):
    """The shared f32 epilogue: ``act(y + bias)``. ONE copy of the
    bias-then-activate tail used by :func:`fused_bias_act`'s kernel body,
    both quant-kernel epilogues (ops/quant_kernels.py fuses it after the
    int32->f32 scale application), and their XLA references — so a kernel
    and its oracle can never disagree about the tail math. ``y`` is f32;
    ``bias`` broadcasts over the leading dims (``None`` skips the add)."""
    if act not in _BN_ACTIVATIONS:
        raise ValueError(f"act {act!r} not in {sorted(_BN_ACTIVATIONS)}")
    if bias is not None:
        y = y + bias
    return _BN_ACTIVATIONS[act](y)


def _fold_bn(scale, bias, mean, var, eps):
    """Inference BN as per-channel affine: ``y = x*m + b`` with
    ``m = scale*rsqrt(var+eps)``, ``b = bias - mean*m``. Folded in float32 —
    a [C]-sized computation, numerically the safest place to spend f32."""
    inv = lax.rsqrt(var.astype(jnp.float32) + jnp.float32(eps))
    m = scale.astype(jnp.float32) * inv
    b = bias.astype(jnp.float32) - mean.astype(jnp.float32) * m
    return m, b


def fused_bn_act_reference(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    mean: jax.Array,
    var: jax.Array,
    *,
    eps: float = 1e-3,
    act: str = "relu",
    residual: Optional[jax.Array] = None,
) -> jax.Array:
    """XLA oracle/fallback: ``act((x - mean)/sqrt(var+eps)*scale + bias
    [+ residual])`` with f32 internal math, output in ``x``'s dtype."""
    if act not in _BN_ACTIVATIONS:
        raise ValueError(
            f"act {act!r} not in {sorted(_BN_ACTIVATIONS)}"
        )
    m, b = _fold_bn(scale, bias, mean, var, eps)
    y = x.astype(jnp.float32) * m + b
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return _BN_ACTIVATIONS[act](y).astype(x.dtype)


def _bn_act_kernel(x_ref, m_ref, b_ref, o_ref, *, act: str):
    y = x_ref[0].astype(jnp.float32) * m_ref[0] + b_ref[0]
    o_ref[0] = _BN_ACTIVATIONS[act](y).astype(o_ref.dtype)


def _bn_act_res_kernel(x_ref, m_ref, b_ref, r_ref, o_ref, *, act: str):
    y = x_ref[0].astype(jnp.float32) * m_ref[0] + b_ref[0]
    y = y + r_ref[0].astype(jnp.float32)
    o_ref[0] = _BN_ACTIVATIONS[act](y).astype(o_ref.dtype)


def fused_bn_act(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    mean: jax.Array,
    var: jax.Array,
    *,
    eps: float = 1e-3,
    act: str = "relu",
    residual: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
    vmem_limit_bytes: int = _VMEM_BLOCK_LIMIT_BYTES,
) -> jax.Array:
    """Fused inference BN + activation (+ residual add), Pallas where it fits.

    ``x``: [B, H, W, C] activations (channels on the 128-lane dim, the
    natural TPU layout); ``scale``/``bias``/``mean``/``var``: [C] running BN
    parameters; ``residual``: optional [B, H, W, C] skip input added before
    the activation. One grid step handles one image (channel-tiled like the
    depthwise kernel when an image block overflows the VMEM budget); the BN
    fold happens once in XLA outside the kernel, so the kernel body is
    exactly the HBM-roofline pass: read x (+residual), multiply-add,
    activate, write.

    INFERENCE-ONLY: no custom VJP — serving graphs never differentiate it.
    ``interpret=None`` auto-selects compiled Pallas on TPU and the
    interpreter off-TPU (tests); falls back to the XLA reference when the
    image block exceeds the VMEM budget or under shard_map's interpreter
    restriction (same policy as ``depthwise_conv2d``).
    """
    if act not in _BN_ACTIVATIONS:
        raise ValueError(f"act {act!r} not in {sorted(_BN_ACTIVATIONS)}")
    if x.ndim != 4:
        raise ValueError(f"fused_bn_act expects [B, H, W, C], got {x.shape}")
    c = x.shape[-1]
    for name, v in (("scale", scale), ("bias", bias), ("mean", mean), ("var", var)):
        if v.shape != (c,):
            raise ValueError(
                f"{name} must be [{c}] to match x's channels, got {v.shape}"
            )
    if residual is not None and residual.shape != x.shape:
        raise ValueError(
            f"residual shape {residual.shape} != x shape {x.shape}"
        )
    b_, h, wdt, _ = x.shape
    itemsize = jnp.dtype(x.dtype).itemsize
    # the block must hold x (and the residual, when present) simultaneously
    block_elems = h * wdt * (2 if residual is not None else 1)
    ct = _channel_tile(c, block_elems, vmem_limit_bytes, itemsize)
    if block_elems * ct * itemsize > vmem_limit_bytes:
        note_reference_fallback(
            "fused_bn_act", f"image block {x.shape[1:]} over the VMEM budget"
        )
        return fused_bn_act_reference(
            x, scale, bias, mean, var, eps=eps, act=act, residual=residual
        )
    if interpret is None:
        interpret = not pallas_platform_ok()
    if interpret and vma_of(x):
        # same interpreter-under-shard_map restriction as the depthwise kernel
        return fused_bn_act_reference(
            x, scale, bias, mean, var, eps=eps, act=act, residual=residual
        )
    m, b = _fold_bn(scale, bias, mean, var, eps)
    # 2-D [1, C] so the per-channel vectors land on the lane dimension
    m2, b2 = m.reshape(1, c), b.reshape(1, c)
    vma = vma_of(x)
    out_shape = (
        jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)
        if vma
        else jax.ShapeDtypeStruct(x.shape, x.dtype)
    )
    x_spec = pl.BlockSpec(
        (1, h, wdt, ct), lambda i, j: (i, 0, 0, j), memory_space=pltpu.VMEM
    )
    chan_spec = pl.BlockSpec(
        (1, ct), lambda i, j: (0, j), memory_space=pltpu.VMEM
    )
    if residual is None:
        kernel = functools.partial(_bn_act_kernel, act=act)
        in_specs = [x_spec, chan_spec, chan_spec]
        operands = (x, m2, b2)
    else:
        kernel = functools.partial(_bn_act_res_kernel, act=act)
        in_specs = [x_spec, chan_spec, chan_spec, x_spec]
        operands = (x, m2, b2, residual)
    return pl.pallas_call(
        kernel,
        grid=(b_, c // ct),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, h, wdt, ct), lambda i, j: (i, 0, 0, j), memory_space=pltpu.VMEM
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)


# -- fused bias + activation (the reusable epilogue) --------------------------


def fused_bias_act_reference(
    x: jax.Array, bias: Optional[jax.Array] = None, *, act: str = "none"
) -> jax.Array:
    """XLA oracle/fallback: ``act(x + bias)`` with f32 internal math, output
    in ``x``'s dtype. ``bias``: [C] over the last axis (or ``None``)."""
    y = bias_act_epilogue(
        x.astype(jnp.float32),
        None if bias is None else bias.astype(jnp.float32),
        act,
    )
    return y.astype(x.dtype)


def _fused_bias_act_kernel(x_ref, b_ref, o_ref, *, act: str):
    y = bias_act_epilogue(x_ref[...].astype(jnp.float32), b_ref[...], act)
    o_ref[...] = y.astype(o_ref.dtype)


def fused_bias_act(
    x: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    act: str = "none",
    interpret: Optional[bool] = None,
    vmem_limit_bytes: int = _VMEM_BLOCK_LIMIT_BYTES,
) -> jax.Array:
    """Fused per-channel bias + activation over the last axis, Pallas where
    it fits: one read and one write of ``x`` instead of XLA's
    add-then-activate pair when the fusion boundary splits them. This is the
    standalone face of :func:`bias_act_epilogue` — the quantized matmul/conv
    kernels (ops/quant_kernels.py) fuse the identical tail after their
    int32->f32 scale application, so the epilogue math has exactly one home.

    ``x``: [..., C]; ``bias``: [C] or ``None``. INFERENCE-ONLY (no VJP).
    ``interpret=None`` auto-selects compiled Pallas on TPU and the XLA
    reference off-TPU (the interpreter is for tests, not the hot path);
    falls back to the reference when a row block exceeds the VMEM budget or
    under shard_map's interpreter restriction.
    """
    if act not in _BN_ACTIVATIONS:
        raise ValueError(f"act {act!r} not in {sorted(_BN_ACTIVATIONS)}")
    c = x.shape[-1]
    if bias is not None and bias.shape != (c,):
        raise ValueError(f"bias must be [{c}] to match x's last axis, got {bias.shape}")
    if x.ndim < 2:
        return fused_bias_act_reference(x, bias, act=act)
    if interpret is None:
        interpret = not pallas_platform_ok()
        if interpret:
            return fused_bias_act_reference(x, bias, act=act)
    if interpret and vma_of(x):
        return fused_bias_act_reference(x, bias, act=act)
    x2 = x.reshape(-1, c)
    r = x2.shape[0]
    itemsize = jnp.dtype(x.dtype).itemsize
    rt = r
    while rt > 1 and rt % 2 == 0 and rt * c * (itemsize + 4) > vmem_limit_bytes:
        rt //= 2
    if rt * c * (itemsize + 4) > vmem_limit_bytes:
        note_reference_fallback(
            "fused_bias_act", f"row block of {x.shape} over the VMEM budget"
        )
        return fused_bias_act_reference(x, bias, act=act)
    b32 = (
        jnp.zeros((1, c), jnp.float32)
        if bias is None
        else bias.astype(jnp.float32).reshape(1, c)
    )
    vma = vma_of(x)
    out_shape = (
        jax.ShapeDtypeStruct(x2.shape, x.dtype, vma=vma)
        if vma
        else jax.ShapeDtypeStruct(x2.shape, x.dtype)
    )
    out = pl.pallas_call(
        functools.partial(_fused_bias_act_kernel, act=act),
        grid=(r // rt,),
        in_specs=[
            pl.BlockSpec((rt, c), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rt, c), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=out_shape,
        interpret=interpret,
    )(x2, b32)
    return out.reshape(x.shape)
