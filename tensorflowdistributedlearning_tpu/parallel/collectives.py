"""Pytree collectives used inside `shard_map`-ped steps.

The reference never wrote a collective — gradient all-reduce lived inside
MirroredStrategy's cross-device ops (NCCL on GPU; reference: model.py:115-116). Here the
same reduction is an explicit `lax.psum`/`lax.pmean` over the named mesh axis, which XLA
lowers to ICI all-reduces within a slice and DCN collectives across slices.
"""

from __future__ import annotations

from typing import Any

import jax
from jax import lax

from tensorflowdistributedlearning_tpu.parallel import mesh as mesh_lib


def vma_of(x: Any) -> frozenset:
    """The varying-manual-axes set of a traced value inside ``shard_map`` —
    empty when the value is replicated or outside shard_map."""
    return getattr(jax.typeof(x), "vma", None) or frozenset()


def psum_tree(tree: Any, axis_name: str = mesh_lib.BATCH_AXIS) -> Any:
    """Sum every leaf across the given mesh axis (gradient/metric reduction)."""
    return jax.tree.map(lambda x: lax.psum(x, axis_name), tree)


def pmean_tree(tree: Any, axis_name: str = mesh_lib.BATCH_AXIS) -> Any:
    """Mean every leaf across the given mesh axis (the MirroredStrategy gradient
    aggregation semantics: per-tower grads averaged into one update)."""
    return jax.tree.map(lambda x: lax.pmean(x, axis_name), tree)
