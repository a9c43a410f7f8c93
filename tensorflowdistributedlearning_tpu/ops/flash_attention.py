"""Pallas TPU kernel: fused (flash-style) block attention.

The framework's attention hot path is the per-device block attention inside
``parallel/ring_attention.py`` and ``models/vit.py`` — sequence LENGTH scaling is
handled by the ring (each chip holds S/n tokens), so the kernel's job is making
one device's block attention fast: QK^T -> online softmax -> PV fused in VMEM,
never materializing the [T, T] score matrix in HBM (XLA's unfused lowering
writes scores + softmax out to HBM twice at fp32 — pure bandwidth waste).

Shape strategy: grid over (batch*heads, query blocks); each step holds one
``block_q x D`` query tile plus the full K/V block ``[T, D]`` in VMEM, computes
the ``[block_q, T]`` score tile in one shot (softmax over the full row — no
inner K scan; the VMEM budget check below bounds the score tile, and longer
blocks fall back to the XLA oracle), accumulating in float32 on the MXU
(``preferred_element_type``). Causal masking compares global row/column indices
via ``broadcasted_iota`` (TPU requires >=2-D iota).

Gradients come from a ``jax.custom_vjp`` whose backward REBUILDS the scores
with plain XLA einsums from the saved residuals (q, k, v only — nothing
O(T^2) is saved across the forward). Note the backward itself still
materializes [B*H, T, T] score/weight tensors transiently in HBM; the flash
memory win applies to the forward pass and to saved activations, which is the
regime that matters here because ``parallel/ring_attention.py`` bounds T to one
device's block. The XLA oracle (`attention_reference`) is the numerical
fallback for shapes that exceed the VMEM budget and the test oracle; off-TPU
the kernel runs in interpreter mode so CPU CI exercises the identical code
path.

Which attention path serves which shapes (after PR 26): this kernel serves the
ViT family — bidirectional or plainly causal attention over one device's
block, all of K and V in VMEM, up to ``models/vit.py:_FUSED_MAX_SEQ`` patch
tokens, behind ``use_fused_attention``; longer ViT sequences and the ring take
``attention_reference``. The decoder family (``models/decoder.py``: grouped
queries, sliding window, packed documents, 8,192 tokens) takes
``ops/blocked_attention.py``, which blocks over keys with an online softmax
in both passes. The ViT path is left as it was: the blocked kernel wants head
sizes that are multiples of 128, and ViT-S heads are 64 wide.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowdistributedlearning_tpu.parallel.collectives import vma_of
from tensorflowdistributedlearning_tpu.parallel.ring_attention import (
    _MASK_VALUE,
    attention_reference,
)

# Per-grid-step VMEM estimate must fit well under the ~16 MB/core budget
# (double-buffering included); above it the public wrapper falls back to the
# XLA oracle instead of failing Mosaic compilation.
_VMEM_KV_LIMIT_BYTES = 8 * 1024 * 1024
_BLOCK_Q = 256


def _vmem_estimate_bytes(t: int, d: int, block_q: int) -> int:
    """float32 working set of one grid step: K + V blocks, the q tile and the
    output tile, and the [block_q, T] scores twice (raw + exp)."""
    return 4 * (2 * t * d + 2 * block_q * d + 2 * block_q * t)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool, block_q: int):
    """One (batch*head, q-block) grid step: one-shot softmax over the full K row."""
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # [block_q, D]
    k = k_ref[0].astype(jnp.float32)  # [T, D]
    v = v_ref[0].astype(jnp.float32)  # [T, D]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [block_q, T]
    if causal:
        q_pos = j * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, _MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool, interpret: bool
) -> jax.Array:
    """[BH, T, D] fused attention via pallas_call."""
    bh, t, d = q.shape
    block_q = min(_BLOCK_Q, t)
    n_q = pl.cdiv(t, block_q)
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, block_q=block_q
    )
    # inside shard_map the output inherits the inputs' varying-manual-axes type
    # (the batch axis of the SPMD train step); outside, vma is empty
    out_vma = vma_of(q) | vma_of(k) | vma_of(v)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype, vma=out_vma),
        grid=(bh, n_q),
        in_specs=[
            pl.BlockSpec(
                (1, block_q, d), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, d), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_with_grad(q, k, v, causal: bool, interpret: bool):
    return _flash_forward(q, k, v, causal, interpret)


def _flash_fwd(q, k, v, causal, interpret):
    o = _flash_forward(q, k, v, causal, interpret)
    return o, (q, k, v)


def _flash_bwd(causal, interpret, res, g):
    """Flash-style recompute backward in plain XLA (scores rebuilt, never saved).

    With p the post-softmax weights and o = p @ v:
      dv = p^T @ g
      dp = g @ v^T
      ds = p * (dp - rowsum(dp * p))       (softmax JVP transpose)
      dq = ds @ k * scale ; dk = ds^T @ q * scale
    """
    q, k, v = res
    orig_dtype = q.dtype
    q32, k32, v32, g32 = (x.astype(jnp.float32) for x in (q, k, v, g))
    d = q.shape[-1]
    scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("btd,bsd->bts", q32, k32) * scale
    if causal:
        t, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((t, t_k), bool))
        s = jnp.where(mask, s, _MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    dv = jnp.einsum("bts,btd->bsd", p, g32)
    dp = jnp.einsum("btd,bsd->bts", g32, v32)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jnp.einsum("bts,bsd->btd", ds, k32) * scale
    dk = jnp.einsum("bts,btd->bsd", ds, q32) * scale
    return (
        dq.astype(orig_dtype),
        dk.astype(orig_dtype),
        dv.astype(orig_dtype),
    )


_flash_with_grad.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused block attention, [B, T, H, D] -> [B, T, H, D] (same contract as
    ``attention_reference``). Differentiable (custom VJP with flash-style
    recompute). ``interpret=None`` auto-selects: the Mosaic kernel on TPU, the
    Pallas interpreter off-TPU (so CPU CI runs the identical kernel code).
    Falls back to the XLA oracle when the per-step working set (K/V blocks plus
    the [block_q, T] score tile) would not fit the VMEM budget."""
    b, t, h, d = q.shape
    block_q = min(_BLOCK_Q, t)
    from tensorflowdistributedlearning_tpu.ops.pallas_kernels import (
        note_reference_fallback,
        pallas_platform_ok,
    )

    if _vmem_estimate_bytes(t, d, block_q) > _VMEM_KV_LIMIT_BYTES:
        note_reference_fallback(
            "flash_attention", f"K/V block [{t}, {d}] over the VMEM budget"
        )
        return attention_reference(q, k, v, causal=causal)
    if interpret is None:
        interpret = not pallas_platform_ok()
    if interpret and (vma_of(q) | vma_of(k) | vma_of(v)):
        # the Pallas interpreter's block slicing trips shard_map's varying-axes
        # checks (same limitation as ops/pallas_kernels.py): inside shard_map
        # off-TPU, take the XLA oracle; the Mosaic path owns this case on TPU
        return attention_reference(q, k, v, causal=causal)
    # [B, T, H, D] -> [B*H, T, D]: heads become independent grid rows
    qh, kh, vh = (
        x.transpose(0, 2, 1, 3).reshape(b * h, t, d) for x in (q, k, v)
    )
    out = _flash_with_grad(qh, kh, vh, causal, interpret)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
