"""Device mesh construction and sharding helpers.

This module is the TPU-native equivalent of the reference's entire "distribution" layer:
GPU discovery (reference: utils.py:6-8), MirroredStrategy construction over the first
``n_gpus`` devices (reference: model.py:115-116), and the per-tower batch-splitting math
(reference: model.py:156-159). Here:

- devices come from ``jax.devices()`` (all hosts' devices under multi-host SPMD, so
  cross-host data parallelism — absent from the reference, which was single-host only —
  falls out for free);
- replication + gradient all-reduce are expressed as a named ``Mesh`` axis over which
  ``shard_map``/``pjit`` emit XLA collectives on ICI/DCN, instead of NCCL calls;
- the mesh reserves named axes for model (tensor), and sequence (context) parallelism so
  future parallelism strategies compose without API changes.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical mesh-axis names. The reference only implemented data parallelism
# (reference: model.py:115-116); `model` and `sequence` are reserved for tensor and
# sequence/context parallelism so the mesh API is forward-compatible.
BATCH_AXIS = "batch"
MODEL_AXIS = "model"
SEQUENCE_AXIS = "sequence"


def available_devices(platform: Optional[str] = None) -> list:
    """Enumerate accelerator devices (reference: utils.py:6-8 enumerated GPUs via
    ``device_lib.list_local_devices``)."""
    if platform is None:
        return list(jax.devices())
    return list(jax.devices(platform))


def make_mesh(
    n_devices: Optional[int] = None,
    *,
    model_parallel: int = 1,
    sequence_parallel: int = 1,
    devices: Optional[Sequence[Any]] = None,
) -> Mesh:
    """Build a (batch, model, sequence) mesh.

    ``n_devices=None`` uses every visible device (the reference defaulted to the first
    ``n_gpus`` local GPUs, reference: model.py:114-116). The data-parallel degree is
    inferred as ``n_devices // (model_parallel * sequence_parallel)``.
    """
    if devices is None:
        devices = available_devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"Requested {n_devices} devices but only {len(devices)} are visible"
            )
        devices = devices[:n_devices]
    n = len(devices)
    denom = model_parallel * sequence_parallel
    if n % denom != 0:
        raise ValueError(
            f"{n} devices not divisible by model_parallel*sequence_parallel={denom}"
        )
    # Multi-host: every data-parallel (batch-axis) shard must live within ONE
    # process — per-process data feeding (host_shard + global_shard_batch)
    # assumes each process's examples land on its own devices. A batch shard
    # spanning processes would silently assemble inconsistent data.
    if jax.process_count() > 1 and jax.local_device_count() % denom != 0:
        raise ValueError(
            f"model_parallel*sequence_parallel={denom} does not divide the "
            f"{jax.local_device_count()} devices local to each process; a "
            "data-parallel shard would span processes and per-process batch "
            "feeding would assemble inconsistent data. Lower the degree or "
            "use more chips per host."
        )
    dp = n // denom
    dev_array = np.asarray(devices).reshape(dp, model_parallel, sequence_parallel)
    return Mesh(dev_array, (BATCH_AXIS, MODEL_AXIS, SEQUENCE_AXIS))


def batch_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Sharding that splits axis 0 over the batch mesh axis, replicating the rest."""
    spec = P(BATCH_AXIS, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding that fully replicates a value (how the reference's MirroredStrategy kept
    per-tower copies of variables in sync)."""
    return NamedSharding(mesh, P())


def shard_batch(tree: Any, mesh: Mesh) -> Any:
    """Place a pytree of host arrays on the mesh, sharding axis 0 over ``batch``.

    TPU-native replacement for the reference's per-tower ``input_fn`` contract where each
    tower independently pulled ``batch/n_gpus`` examples (reference: model.py:156-159,
    298-299).
    """

    def _put(x):
        x = np.asarray(x)
        return jax.device_put(x, batch_sharding(mesh, x.ndim))

    return jax.tree.map(_put, tree)


def shard_batch_spatial(tree: Any, mesh: Mesh) -> Any:
    """Place a batch for sequence-parallel training: ``images`` sharded (batch,
    sequence) — axis 0 over data-parallel shards, axis 1 (H) over the sequence
    axis — and every other leaf (labels, valid) sharded on batch only. The H
    extent must divide the sequence-axis size."""

    def _put(key, x):
        x = np.asarray(x)
        if key == "images":
            if x.shape[1] % mesh.shape[SEQUENCE_AXIS] != 0:
                raise ValueError(
                    f"Spatial extent {x.shape[1]} must be divisible by the "
                    f"sequence-parallel degree {mesh.shape[SEQUENCE_AXIS]}"
                )
            spec = P(BATCH_AXIS, SEQUENCE_AXIS, *([None] * (x.ndim - 2)))
        else:
            spec = P(BATCH_AXIS, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return {k: _put(k, v) for k, v in tree.items()}


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Place a pytree on the mesh fully replicated (params/optimizer state)."""
    sharding = replicated_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def local_batch_size(global_batch: int, mesh: Mesh) -> int:
    """Per-shard batch size; validates divisibility exactly as the reference did for its
    per-tower split (reference: model.py:156-159)."""
    n = mesh.shape[BATCH_AXIS]
    if global_batch % n != 0:
        raise ValueError(
            f"Batch size {global_batch} must be divisible by the data-parallel degree {n}"
        )
    return global_batch // n


def data_parallel_degree(mesh: Mesh) -> int:
    return mesh.shape[BATCH_AXIS]


def largest_divisible_dim(
    shape: Sequence[int], degree: int, *, taken: Optional[set] = None
) -> Optional[int]:
    """Index of the largest dimension of ``shape`` divisible by ``degree``,
    skipping indices in ``taken`` (dimensions another mesh axis already
    shards); None when nothing divides — the shared eligibility rule of the
    ZeRO-1 weight-update specs (parallel/zero.py). Picking the LARGEST
    divisible dimension (not a fixed one) keeps the replicated tail small:
    a conv kernel [3, 3, C_in, C_out] shards its widest channel dim, a bias
    [C] shards outright, and only scalars/tiny vectors stay whole."""
    taken = taken or set()
    best: Optional[int] = None
    for i, d in enumerate(shape):
        if i in taken or d % degree != 0:
            continue
        if best is None or d > shape[best]:
            best = i
    return best


def check_accum_divisibility(
    global_batch: int, mesh: Mesh, grad_accum_steps: int
) -> int:
    """Fail fast (before any compile) when the per-shard batch cannot split
    into ``grad_accum_steps`` equal microbatches; returns the per-shard batch.
    Shared by both trainers so the contract and message cannot drift."""
    local_bs = local_batch_size(global_batch, mesh)
    if local_bs % grad_accum_steps:
        raise ValueError(
            f"per-shard batch {local_bs} (global {global_batch} over "
            f"{data_parallel_degree(mesh)} data-parallel shards) is not "
            f"divisible by grad_accum_steps={grad_accum_steps}; raise the "
            "batch size or lower the accumulation factor"
        )
    return local_bs
