"""Causal attention blocked over keys with an online softmax, forward and
backward, for the decoder family (models/decoder.py): grouped queries (several
query heads on one key-value head), an optional sliding window and packed
documents (positions see each other only inside one segment).

On a TPU it is the Pallas kernel ``jax`` ships
(``jax.experimental.pallas.ops.tpu.splash_attention``): forward, dq and dkv
kernels that never write a ``[T, T]`` score matrix and that skip the key
blocks the causal and window masks empty. It is taken when the shapes fit its
tiles (sequence a multiple of 128, head size a multiple of 128 — or, where the
values are narrower than the queries and keys as in a latent-attention layer,
queries and keys a multiple of 64 and values of 128); any other shape, and
every other backend, takes ``masked_attention_reference``: the same
mathematics in XLA, blocked over queries so that the scores standing at once
are ``[heads, block, T]``. The choice is by shape and backend, no flag.

The kernel's forward rule names its output and log-sum-exp, the residuals its
dq and dkv kernels read (``sparse_attention.ATTENTION_NAME``): a recomputed
decoder layer keeps them (models/decoder.py:REMAT_POLICY), so its backward
pass does not run the forward kernel again. The XLA path names nothing.

The library kernel's ``out_shape`` carries no ``vma``, so ``pallas_call``
refuses it inside a ``shard_map`` that checks varying manual axes: a step that
runs it is built with ``check_vma=False`` (train/step.py:SequenceTask).

``ops/flash_attention.py`` stays the ViT family's kernel: bidirectional or
plainly causal, all of K and V in VMEM, sequences up to
``models/vit.py:_FUSED_MAX_SEQ``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from tensorflowdistributedlearning_tpu.ops.sparse_attention import ATTENTION_NAME

# queries per block of the XLA path: [heads, 512, T] float32 scores at once
_REFERENCE_BLOCK_Q = 512
# the kernel's tiles on a v5e: 512 x 512 score tiles keep the MXU fed and
# three double-buffered [512, 128] operands well inside VMEM
_KERNEL_BLOCK = 512


def masked_attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """q/k [B, T, Hq|Hkv, hd], v [B, T, Hkv, vd], segment_ids [B, T] -> [B, T,
    Hq, vd]. Position i sees j where j <= i, both in one segment, and i - j <
    window. Scores and softmax in float32, operands in the inputs' dtype."""
    b, t, hq, hd = q.shape
    group = hq // k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    block = math.gcd(t, _REFERENCE_BLOCK_Q)
    idx = jnp.arange(t)

    def one_sequence(args):
        qs, ks, vs, seg = args  # [T, Hq, hd], [T, Hkv, hd], [T]
        qs = qs.reshape(t, ks.shape[1], group, hd)

        @jax.checkpoint
        def rows(start):
            qi = lax.dynamic_slice_in_dim(qs, start, block, 0)
            i = start + jnp.arange(block)
            scores = jnp.einsum(
                "qngd,knd->ngqk", qi, ks, preferred_element_type=jnp.float32
            ) * scale
            seen = (idx[None, :] <= i[:, None]) & (
                lax.dynamic_slice_in_dim(seg, start, block, 0)[:, None] == seg[None, :]
            )
            if window is not None:
                seen &= i[:, None] - idx[None, :] < window
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum(
                "ngqk,knd->qngd", probs.astype(vs.dtype), vs,
                preferred_element_type=jnp.float32,
            ).astype(q.dtype)

        return lax.map(rows, jnp.arange(0, t, block)).reshape(t, hq, v.shape[-1])

    return lax.map(one_sequence, (q, k, v, segment_ids))


@functools.lru_cache(maxsize=None)
def _splash_kernel(t: int, group: int, window: Optional[int], interpret: bool):
    """One key-value head's kernel: ``group`` query heads on it."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as masks,
    )

    mask = masks.CausalMask((t, t)) if window is None else masks.LocalMask(
        (t, t), window_size=(window - 1, 0), offset=0
    )
    block = math.gcd(t, _KERNEL_BLOCK)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block,
    )
    # the kernel object holds the block-sparse mask tables as arrays: built
    # concretely, so one object serves every trace
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            masks.MultiHeadMask([mask] * group), block_sizes=sizes, interpret=interpret,
            residual_checkpoint_name=ATTENTION_NAME,
        )


def kernel_serves(t: int, head_dim: int, value_dim: Optional[int] = None) -> bool:
    """The shapes the Pallas kernel takes (on a TPU)."""
    if value_dim is None or value_dim == head_dim:
        return t % 128 == 0 and head_dim % 128 == 0
    return t % 128 == 0 and head_dim % 64 == 0 and value_dim % 128 == 0


def splash_attention(q, k, v, segment_ids, *, window=None, interpret=False):
    """The kernel path, whatever the backend (``interpret`` runs it on the
    CPU, for tests): same contract as ``masked_attention_reference``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
    )

    b, t, hq, hd = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    kernel = _splash_kernel(t, group, window, interpret)
    # [B, Hkv, group, T, hd] queries, scaled here: the kernel does not
    qh = (q * (1.0 / math.sqrt(hd))).astype(q.dtype)
    qh = qh.reshape(b, t, hkv, group, hd).transpose(0, 2, 3, 1, 4)
    kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    def one_head(qg, kk, vv, seg):
        return kernel(qg, kk, vv, segment_ids=splash.SegmentIds(q=seg, kv=seg))

    per_head = jax.vmap(one_head, in_axes=(0, 0, 0, None))
    out = jax.vmap(per_head)(qh, kh, vh, segment_ids.astype(jnp.int32))
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, hq, v.shape[-1])


def blocked_attention(q, k, v, segment_ids, *, window: Optional[int] = None):
    """Causal grouped-query attention over packed documents; see the module
    docstring for which path serves which shapes."""
    from tensorflowdistributedlearning_tpu.ops import pallas_kernels

    if pallas_kernels.pallas_platform_ok() and kernel_serves(q.shape[1], q.shape[3], v.shape[3]):
        return splash_attention(q, k, v, segment_ids, window=window)
    return masked_attention_reference(q, k, v, segment_ids, window=window)
