"""Quantized-compute kernels: real int8 arithmetic on the serve hot path.

PR 6 shipped int8 *storage* — kernels quantize to int8 at export, the traced
graph dequantizes them back to bf16, and every matmul still runs in floating
point. This module closes the gap ROADMAP item 2 names: the arithmetic
itself. An ``int8-compute`` artifact (train/quantize.py spec) routes its
dense/conv layers through these kernels, which

1. **dynamically quantize activations** per-tensor symmetric (scale =
   max|x|/127, zero-point 0 — so the engine's zero-padded bucket rows stay
   exact: padding can never change the max or the quantized zeros),
2. run the matmul/conv as **int8 x int8 -> int32** on the MXU
   (``jnp.dot(..., preferred_element_type=jnp.int32)`` inside a Pallas
   kernel), and
3. fuse the epilogue — ``acc.f32 * (x_scale * w_scale[channel]) + bias``
   then the activation — into the same VMEM-resident pass, reusing
   :func:`ops.pallas_kernels.bias_act_epilogue` so the tail math has one
   home shared with the fused elementwise kernels.

Dispatch policy (same shape as the other Pallas ops): compiled kernels on
TPU behind :func:`pallas_kernels.pallas_platform_ok` (looked up on the
module at call time: the one switch for every wrapper); off-TPU the public wrappers take the
**exact dequantize-f32 XLA fallback** — the same dynamic activation
quantization followed by f32 dequantize-and-matmul. That fallback is also
the parity oracle (`*_reference`): integer accumulation is exact, so the
kernel and the oracle differ only by f32 accumulation rounding, which the
parity tests pin (tests/test_quant_kernels.py). XLA's own int8 dot is
measured ~12x slower than f32 on this CPU backend, so the honest CPU path
is the f32-arithmetic twin, not interpreted integer math; ``interpret=True``
still runs the real integer kernel body for tests, and
:func:`int8_matmul_xla` exposes XLA's genuine int8->int32 arithmetic for
bitwise accumulator-equivalence checks.

The serving integration is :func:`int8_intercept`: a
``flax.linen.intercept_methods`` context that, at serving-closure trace
time, replaces ``nn.Dense`` / stride-1 undilated ``nn.Conv`` calls whose
kernel is an ``{__int8__, q, scale}`` record (train/quantize.py) with the
quantized-compute path. Layers outside that envelope (strided/dilated
convs, grouped convs, custom modules) fall through to the dequantized
float path untouched — partial coverage is explicit, not silent: the
quantize-check gate compares the *composed* artifact against the f32
reference, whatever mix of paths it traced.
"""

from __future__ import annotations

import functools
from collections.abc import Mapping
from contextlib import contextmanager
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowdistributedlearning_tpu.ops import pallas_kernels
from tensorflowdistributedlearning_tpu.ops.pallas_kernels import (
    _VMEM_BLOCK_LIMIT_BYTES,
    bias_act_epilogue,
    note_reference_fallback,
)
from tensorflowdistributedlearning_tpu.parallel.collectives import vma_of

__all__ = [
    "quantize_activations",
    "int8_matmul",
    "int8_matmul_reference",
    "int8_matmul_xla",
    "int8_conv2d",
    "int8_conv2d_reference",
    "int8_intercept",
    "make_int8_interceptor",
]


def quantize_activations(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-tensor symmetric dynamic quantization: ``(q_int8, scale_f32)``
    with ``scale = max|x|/127`` (1.0 when the tensor is all-zero so nothing
    ever divides by zero) and ``q = clip(round(x/scale), -127, 127)``.

    Zero-point is 0 by construction, which is the property the serving
    engine's bucket padding relies on: appended zero rows quantize to zero,
    contribute exactly zero to every dot product, and cannot move the
    per-tensor max, so a padded batch computes bit-identical results for
    the real rows."""
    xf = x.astype(jnp.float32)
    m = jnp.max(jnp.abs(xf))
    scale = jnp.where(m > 0, m / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _epilogue(acc_f32, scale_vec, bias, act, out_dtype):
    """int32 accumulator -> output: per-channel scale, then the shared
    bias+act tail. ``scale_vec`` broadcasts over leading dims."""
    return bias_act_epilogue(acc_f32 * scale_vec, bias, act).astype(out_dtype)


# -- int8 matmul --------------------------------------------------------------


def int8_matmul_reference(
    x: jax.Array,
    wq: jax.Array,
    w_scale: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    act: str = "none",
    out_dtype=None,
) -> jax.Array:
    """Exact dequantize-f32 oracle AND the off-TPU serving fallback: the
    same dynamic activation quantization as the kernel, then f32
    dequantize-and-matmul. Mathematically ``(xq*xs) @ (wq*ws)`` — identical
    to the kernel's ``(xq @ wq) * (xs*ws)`` up to f32 accumulation rounding
    (the integer path is the exact one)."""
    out_dtype = x.dtype if out_dtype is None else out_dtype
    # jnp.asarray FIRST (same contract as dequantize_pytree): a numpy wq
    # would upcast EAGERLY and the exported graph would embed f32 weight
    # constants — 4x the bytes at rest the int8 manifest promises
    wq = jnp.asarray(wq)
    xq, xs = quantize_activations(x)
    xf = xq.astype(jnp.float32) * xs
    wf = wq.astype(jnp.float32) * jnp.asarray(w_scale, jnp.float32)
    acc = xf @ wf
    b32 = None if bias is None else jnp.asarray(bias, jnp.float32)
    return bias_act_epilogue(acc, b32, act).astype(out_dtype)


def int8_matmul_xla(
    x: jax.Array,
    wq: jax.Array,
    w_scale: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    act: str = "none",
    out_dtype=None,
) -> jax.Array:
    """XLA's genuine int8 x int8 -> int32 arithmetic with the identical
    epilogue — the integer accumulator is bitwise-equal to the Pallas
    kernel's (both are exact), and the f32 tail matches up to FMA fusion
    (last-ulp), used by tests to prove fallback-path equivalence. NOT
    the serving fallback: XLA CPU has no vectorized int8 GEMM (~12x slower
    than f32 here), so the hot path's off-TPU twin is the f32 reference."""
    out_dtype = x.dtype if out_dtype is None else out_dtype
    wq = jnp.asarray(wq)
    xq, xs = quantize_activations(x)
    acc = lax.dot_general(
        xq, wq, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    scale_vec = xs * jnp.asarray(w_scale, jnp.float32)
    b32 = None if bias is None else jnp.asarray(bias, jnp.float32)
    return _epilogue(acc.astype(jnp.float32), scale_vec, b32, act, out_dtype)


def _qmm_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, *, act: str):
    acc = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.int32)
    y = bias_act_epilogue(acc.astype(jnp.float32) * s_ref[...], b_ref[...], act)
    o_ref[...] = y.astype(o_ref.dtype)


# Rows of the activation block one grid step holds: a multiple of the int8
# sublane tile (32). The row count of a served batch is the symbolic batch of
# the exported artifact, so the block — and with it the VMEM budget — must
# not depend on it; the grid covers the rows with a last partial block.
_QMM_ROW_TILE = 256


def _n_tile(n: int, fixed_bytes: int, per_n_bytes: int, limit: int) -> int:
    """Largest divisor-of-n output-feature tile whose block set fits VMEM.
    Features are independent columns, so tiling N is free."""
    nt = n
    while nt > 1 and nt % 2 == 0 and fixed_bytes + nt * per_n_bytes > limit:
        nt //= 2
    return nt


def int8_matmul(
    x: jax.Array,
    wq: jax.Array,
    w_scale: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    act: str = "none",
    out_dtype=None,
    interpret: Optional[bool] = None,
    vmem_limit_bytes: int = _VMEM_BLOCK_LIMIT_BYTES,
) -> jax.Array:
    """Quantized-compute dense layer: dynamic-quantize ``x``, int8 matmul
    against the pre-quantized ``wq`` with per-output-channel ``w_scale``,
    fused scale+bias+act epilogue.

    ``x``: [..., K] float (leading dims flattened for the kernel and
    restored; they may be symbolic — the exported batch); ``wq``: [K, N]
    int8; ``w_scale``: [N] f32; ``bias``: [N] or ``None``; output [..., N] in
    ``out_dtype`` (default ``x.dtype``).

    Dispatch: compiled Pallas on TPU (rows in blocks of ``_QMM_ROW_TILE``,
    N-tiled when the block set overflows the VMEM budget, whole-K always
    resident); the exact dequantize-f32 XLA reference off-TPU, on VMEM
    overflow, and under shard_map's interpreter restriction.
    ``interpret=True`` runs the real integer kernel body interpreted (tests
    only — slow)."""
    if wq.dtype != jnp.int8:
        raise ValueError(f"wq must be int8, got {wq.dtype}")
    k, n = wq.shape
    if x.shape[-1] != k:
        raise ValueError(f"x last dim {x.shape[-1]} != wq rows {k}")
    if w_scale.shape != (n,):
        raise ValueError(f"w_scale must be [{n}], got {w_scale.shape}")
    if bias is not None and bias.shape != (n,):
        raise ValueError(f"bias must be [{n}], got {bias.shape}")
    out_dtype = x.dtype if out_dtype is None else out_dtype
    if interpret is None:
        interpret = not pallas_kernels.pallas_platform_ok()
        if interpret:
            return int8_matmul_reference(
                x, wq, w_scale, bias=bias, act=act, out_dtype=out_dtype
            )
    if interpret and vma_of(x):
        return int8_matmul_reference(
            x, wq, w_scale, bias=bias, act=act, out_dtype=out_dtype
        )
    tm = _QMM_ROW_TILE
    # block budget: xq [tm,k]i8 + wq [k,nt]i8 + acc/out [tm,nt]f32 + vectors
    fixed = tm * k
    per_n = k + tm * 4 + 8
    nt = _n_tile(n, fixed, per_n, vmem_limit_bytes)
    if fixed + nt * per_n > vmem_limit_bytes:
        note_reference_fallback(
            "int8_matmul", f"[{tm}, {k}] x [{k}, {nt}] over the VMEM budget"
        )
        return int8_matmul_reference(
            x, wq, w_scale, bias=bias, act=act, out_dtype=out_dtype
        )
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m = m * d
    wq = jnp.asarray(wq)
    xq, xs = quantize_activations(x)
    xq2 = xq.reshape(m, k)
    scale_vec = (xs * jnp.asarray(w_scale, jnp.float32)).reshape(1, n)
    b32 = (
        jnp.zeros((1, n), jnp.float32)
        if bias is None
        else jnp.asarray(bias, jnp.float32).reshape(1, n)
    )
    vma = vma_of(x)
    out_shape = (
        jax.ShapeDtypeStruct((m, n), out_dtype, vma=vma)
        if vma
        else jax.ShapeDtypeStruct((m, n), out_dtype)
    )
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, act=act),
        grid=(pl.cdiv(m, tm), n // nt),
        in_specs=[
            pl.BlockSpec((tm, k), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, nt), lambda i, j: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nt), lambda i, j: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nt), lambda i, j: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tm, nt), lambda i, j: (i, j), memory_space=pltpu.VMEM
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(xq2, wq, scale_vec, b32)
    return out.reshape(*lead, n)


# -- int8 conv2d (stride-1, undilated) ----------------------------------------


def _conv_pads(padding, kh: int, kw: int) -> Optional[Tuple]:
    """Normalize a SAME/VALID/explicit padding spec to ((lo,hi),(lo,hi)) for
    a stride-1 undilated conv; None = unsupported (caller falls back)."""
    if isinstance(padding, str):
        p = padding.upper()
        if p == "VALID":
            return ((0, 0), (0, 0))
        if p == "SAME":
            # stride-1 SAME: total pad k-1, split low-first like XLA
            return (
                ((kh - 1) // 2, kh // 2),
                ((kw - 1) // 2, kw // 2),
            )
        return None
    try:
        (a, b), (c, d) = ((p[0], p[1]) for p in padding)
    except (TypeError, ValueError, IndexError):
        return None
    if min(a, b, c, d) < 0:
        return None
    return ((int(a), int(b)), (int(c), int(d)))


def int8_conv2d_reference(
    x: jax.Array,
    wq: jax.Array,
    w_scale: jax.Array,
    *,
    padding="SAME",
    bias: Optional[jax.Array] = None,
    act: str = "none",
    out_dtype=None,
) -> jax.Array:
    """Exact dequantize-f32 oracle/fallback for the stride-1 undilated conv:
    same dynamic activation quantization, f32 dequantize, XLA conv.
    ``x``: [B, H, W, Cin]; ``wq``: [kh, kw, Cin, Cout] int8; ``w_scale``:
    [Cout]."""
    kh, kw, _, _ = wq.shape
    pads = _conv_pads(padding, kh, kw)
    if pads is None:
        raise ValueError(f"unsupported padding spec {padding!r}")
    out_dtype = x.dtype if out_dtype is None else out_dtype
    # jnp.asarray FIRST — see int8_matmul_reference: numpy weights would
    # constant-fold the dequantize and serialize f32 bytes
    wq = jnp.asarray(wq)
    xq, xs = quantize_activations(x)
    xf = xq.astype(jnp.float32) * xs
    wf = wq.astype(jnp.float32) * jnp.asarray(w_scale, jnp.float32)
    acc = lax.conv_general_dilated(
        xf,
        wf,
        window_strides=(1, 1),
        padding=list(pads),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    b32 = None if bias is None else jnp.asarray(bias, jnp.float32)
    return bias_act_epilogue(acc, b32, act).astype(out_dtype)


def _qconv_kernel(
    x_ref, w_ref, s_ref, b_ref, o_ref, *, kh: int, kw: int, act: str
):
    """One image per grid step: shift-and-matmul over the kh*kw taps, int32
    accumulation on the MXU, fused epilogue. ``x_ref``: pre-padded
    [1, H+ph, W+pw, Cin] int8; ``o_ref``: [1, H, W, Cout]."""
    xp = x_ref[0]
    cin = xp.shape[-1]
    _, h, wd, cout = o_ref.shape
    acc = jnp.zeros((h * wd, cout), jnp.int32)
    for i in range(kh):
        for j in range(kw):
            tap = lax.slice(xp, (i, j, 0), (i + h, j + wd, cin))
            acc = acc + jnp.dot(
                tap.reshape(h * wd, cin),
                w_ref[i, j],
                preferred_element_type=jnp.int32,
            )
    y = bias_act_epilogue(acc.astype(jnp.float32) * s_ref[...], b_ref[...], act)
    o_ref[0] = y.reshape(h, wd, cout).astype(o_ref.dtype)


def int8_conv2d(
    x: jax.Array,
    wq: jax.Array,
    w_scale: jax.Array,
    *,
    padding="SAME",
    bias: Optional[jax.Array] = None,
    act: str = "none",
    out_dtype=None,
    interpret: Optional[bool] = None,
    vmem_limit_bytes: int = _VMEM_BLOCK_LIMIT_BYTES,
) -> jax.Array:
    """Quantized-compute stride-1 undilated conv: dynamic-quantize ``x``,
    int8 direct convolution (shift-and-matmul over the kh*kw taps, the same
    decomposition the depthwise kernel uses, but with an MXU contraction
    over Cin), fused scale+bias+act epilogue.

    ``x``: [B, H, W, Cin] float; ``wq``: [kh, kw, Cin, Cout] int8;
    ``w_scale``: [Cout] f32; ``padding``: SAME/VALID/explicit pairs.
    Strided or dilated convs are out of envelope by design — the
    interceptor routes those layers through the dequantized float path.

    Dispatch: compiled Pallas on TPU; the exact dequantize-f32 XLA
    reference off-TPU, on VMEM overflow, and under shard_map's interpreter
    restriction. The zero-padding the conv itself applies is exact under
    symmetric quantization (zero-point 0), so padding before or after
    quantizing is the same arithmetic."""
    if wq.dtype != jnp.int8:
        raise ValueError(f"wq must be int8, got {wq.dtype}")
    if x.ndim != 4 or wq.ndim != 4:
        raise ValueError(
            f"int8_conv2d expects x [B,H,W,Cin] and wq [kh,kw,Cin,Cout], "
            f"got {x.shape} and {wq.shape}"
        )
    kh, kw, cin, cout = wq.shape
    if x.shape[-1] != cin:
        raise ValueError(f"x channels {x.shape[-1]} != wq Cin {cin}")
    if w_scale.shape != (cout,):
        raise ValueError(f"w_scale must be [{cout}], got {w_scale.shape}")
    if bias is not None and bias.shape != (cout,):
        raise ValueError(f"bias must be [{cout}], got {bias.shape}")
    pads = _conv_pads(padding, kh, kw)
    if pads is None:
        raise ValueError(f"unsupported padding spec {padding!r}")
    out_dtype = x.dtype if out_dtype is None else out_dtype
    if interpret is None:
        interpret = not pallas_kernels.pallas_platform_ok()
        if interpret:
            return int8_conv2d_reference(
                x, wq, w_scale, padding=pads, bias=bias, act=act,
                out_dtype=out_dtype,
            )
    if interpret and vma_of(x):
        return int8_conv2d_reference(
            x, wq, w_scale, padding=pads, bias=bias, act=act,
            out_dtype=out_dtype,
        )
    b, h, wd, _ = x.shape
    (pt, pb), (pl_, pr) = pads
    ho = h + pt + pb - (kh - 1)
    wo = wd + pl_ + pr - (kw - 1)
    if ho <= 0 or wo <= 0:
        note_reference_fallback("int8_conv2d", f"empty output for {x.shape}")
        return int8_conv2d_reference(
            x, wq, w_scale, padding=pads, bias=bias, act=act,
            out_dtype=out_dtype,
        )
    if not interpret and cin % 128 and wo % 8:
        # the kernel flattens each [ho, wo, cin] int8 tap to [ho*wo, cin] for
        # the MXU; Mosaic (libtpu 0.0.34, compiled for v5e) refuses that
        # shape cast unless the lanes are full or the rows tile evenly
        note_reference_fallback(
            "int8_conv2d",
            f"Mosaic cannot flatten a [{ho}, {wo}, {cin}] int8 tile "
            "(needs cin % 128 == 0 or width % 8 == 0)",
        )
        return int8_conv2d_reference(
            x, wq, w_scale, padding=pads, bias=bias, act=act,
            out_dtype=out_dtype,
        )
    hp, wp = h + pt + pb, wd + pl_ + pr
    # block budget: padded image i8 + filter i8 + int32 acc + f32 out
    block_bytes = (
        hp * wp * cin + kh * kw * cin * cout + ho * wo * cout * 8
    )
    if block_bytes > vmem_limit_bytes:
        note_reference_fallback(
            "int8_conv2d",
            f"image block {(hp, wp, cin)} -> {(ho, wo, cout)} over the VMEM "
            "budget",
        )
        return int8_conv2d_reference(
            x, wq, w_scale, padding=pads, bias=bias, act=act,
            out_dtype=out_dtype,
        )
    wq = jnp.asarray(wq)
    xq, xs = quantize_activations(x)
    xqp = jnp.pad(xq, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    scale_vec = (xs * jnp.asarray(w_scale, jnp.float32)).reshape(1, cout)
    b32 = (
        jnp.zeros((1, cout), jnp.float32)
        if bias is None
        else jnp.asarray(bias, jnp.float32).reshape(1, cout)
    )
    vma = vma_of(x)
    out_shape = (
        jax.ShapeDtypeStruct((b, ho, wo, cout), out_dtype, vma=vma)
        if vma
        else jax.ShapeDtypeStruct((b, ho, wo, cout), out_dtype)
    )
    return pl.pallas_call(
        functools.partial(_qconv_kernel, kh=kh, kw=kw, act=act),
        grid=(b,),
        in_specs=[
            pl.BlockSpec(
                (1, hp, wp, cin), lambda i: (i, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (kh, kw, cin, cout), lambda i: (0, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec((1, cout), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, ho, wo, cout), lambda i: (i, 0, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(xqp, wq, scale_vec, b32)


# -- the serving-closure interceptor ------------------------------------------

# train/quantize.py's record marker, duplicated here (not imported) so this
# module never imports train/ — ops stays a leaf package
_QKEY = "__int8__"


def _is_quant_record(node) -> bool:
    return isinstance(node, Mapping) and _QKEY in node


def _lookup(tree, path) -> Optional[Any]:
    node = tree
    for key in path:
        if not isinstance(node, Mapping) or key not in node:
            return None
        node = node[key]
    return node


def _norm_pair(v, default=1) -> Optional[Tuple[int, int]]:
    if v is None:
        v = default
    if isinstance(v, int):
        return (v, v)
    try:
        t = tuple(int(e) for e in v)
    except (TypeError, ValueError):
        return None
    return t if len(t) == 2 else None


def make_int8_interceptor(qparams, act_dtype=jnp.bfloat16):
    """Build the ``nn.intercept_methods`` interceptor that routes quantized
    layers through the int8-compute kernels.

    ``qparams`` is the quantize_pytree output (records still in place, leaves
    already jnp arrays so the int8 constants are SHARED with any
    dequantize_pytree call on the same tree — one constant in the exported
    graph, not two). For each ``nn.Dense`` / supported ``nn.Conv`` whose
    params-tree path holds an ``{__int8__, q, scale}`` kernel record, the
    module's ``__call__`` is replaced by the quantized-compute path (bias
    fused into the kernel epilogue). Everything else — including convs
    outside the stride-1 undilated feature_group_count=1 envelope — falls
    through to ``next_fun`` untouched, i.e. the PR-6 dequantized float path.
    """
    from flax import linen as nn

    def intercept(next_fun, args, kwargs, context):
        mod = context.module
        if context.method_name != "__call__" or not args:
            return next_fun(*args, **kwargs)
        if not isinstance(mod, (nn.Dense, nn.Conv)):
            return next_fun(*args, **kwargs)
        node = _lookup(qparams, tuple(mod.path))
        if not isinstance(node, Mapping):
            return next_fun(*args, **kwargs)
        rec = node.get("kernel")
        if not _is_quant_record(rec):
            return next_fun(*args, **kwargs)
        x = args[0]
        wq, w_scale = rec["q"], rec["scale"]
        bias = node.get("bias") if mod.use_bias else None
        if isinstance(mod, nn.Dense):
            return int8_matmul(
                x, wq, w_scale, bias=bias, act="none", out_dtype=act_dtype
            )
        # nn.Conv: only the 2-D stride-1 undilated ungrouped case
        if wq.ndim != 4 or x.ndim != 4 or mod.feature_group_count != 1:
            return next_fun(*args, **kwargs)
        if _norm_pair(mod.strides) != (1, 1):
            return next_fun(*args, **kwargs)
        if _norm_pair(mod.kernel_dilation) != (1, 1):
            return next_fun(*args, **kwargs)
        if _norm_pair(getattr(mod, "input_dilation", None)) != (1, 1):
            return next_fun(*args, **kwargs)
        kh, kw = wq.shape[0], wq.shape[1]
        if _conv_pads(mod.padding, kh, kw) is None:
            return next_fun(*args, **kwargs)
        return int8_conv2d(
            x,
            wq,
            w_scale,
            padding=mod.padding,
            bias=bias,
            act="none",
            out_dtype=act_dtype,
        )

    return intercept


@contextmanager
def int8_intercept(qparams, act_dtype=jnp.bfloat16):
    """Context manager the serving closures trace under: inside it, flax
    module applications route quantized dense/conv layers through the
    int8-compute kernels. Tracing under jit is exactly the intended use —
    the kernels (or their fallback) are baked into the exported graph."""
    from flax import linen as nn

    with nn.intercept_methods(make_int8_interceptor(qparams, act_dtype)):
        yield
