"""Tensor (model) parallelism via GSPMD sharding annotations.

The third mesh axis. The reference had no model parallelism at all (SURVEY §2.3:
its only strategy was MirroredStrategy data parallelism), so this is a
beyond-parity capability — and it is built the idiomatic TPU way: rather than
rewriting layers with explicit collectives (the shard_map/halo route the
sequence axis uses, where exactness demands hand phase control), tensor
parallelism annotates PARAMETER shardings over the ``model`` axis and lets
XLA's SPMD partitioner place the matching all-reduces/all-gathers on ICI — the
"pick a mesh, annotate shardings, let XLA insert collectives" recipe.

What gets sharded (the channel dimension is the TP-natural axis of a CNN):

- conv kernels  [kh, kw, C_in, C_out]  → sharded on C_out;
- conv biases / BN scale/offset/stats [C_out] → sharded likewise (they are
  per-output-channel vectors);
- dense kernels [D_in, D_out] → sharded on D_out (the classifier head);
- everything smaller (scalars, the 1-channel segmentation head) → replicated.

Optimizer state (Adam moments) shards identically to its parameter — pytree
structure mirrors params, so the same spec tree applies. Per-chip parameter and
optimizer memory drops by ~the model-axis degree, the reason TP exists.

Gradient semantics need no hand-written psum: the train step is plain jit
(not shard_map), so the loss-mean over the global batch IS the global mean and
GSPMD derives every reduction. BatchNorm statistics are computed over the full
global batch under GSPMD (jit sees the global tensor) — a deliberate semantic
difference from the shard_map data-parallel step's per-tower BN, noted in
``make_train_step_gspmd``'s docstring.
"""

from __future__ import annotations

import functools

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorflowdistributedlearning_tpu.obs import scopes
from tensorflowdistributedlearning_tpu.parallel.mesh import (
    BATCH_AXIS,
    MODEL_AXIS,
)


def _spec_for_leaf(leaf, axes: Tuple[Tuple[str, int], ...]) -> P:
    """Shard a leaf's trailing (output-channel/feature) dimension over the given
    (axis_name, degree) mesh axes — the single eligibility rule every sharding
    path here uses. Axes with degree 1 are dropped; if the trailing dim does not
    divide by the combined degree, axes are dropped from the right until it
    does (so TP+ZeRO degrades to TP-only, then to replicated)."""
    shape = jnp.shape(leaf)
    usable = [(a, d) for a, d in axes if d > 1]
    while usable:
        total = 1
        for _, d in usable:
            total *= d
        if shape and shape[-1] % total == 0:
            spec: list = [None] * len(shape)
            names = tuple(a for a, _ in usable)
            spec[-1] = names if len(names) > 1 else names[0]
            return P(*spec)
        usable = usable[:-1]
    return P()


def tensor_parallel_specs(tree: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree sharding every eligible leaf's trailing (channel)
    dimension over the ``model`` mesh axis."""
    axes = ((MODEL_AXIS, mesh.shape[MODEL_AXIS]),)
    return jax.tree.map(lambda leaf: _spec_for_leaf(leaf, axes), tree)


def tensor_parallel_spec_for_shape(shape, tp: int):
    """The tensor-parallel eligibility rule queryable by plain degree — no
    mesh needed. The parallelism planner predicts candidate layouts' exact
    per-chip param bytes through this, so the prediction and the placement
    (``tensor_parallel_specs`` above, which shares ``_spec_for_leaf``) can
    never disagree."""
    return _spec_for_leaf(
        jax.ShapeDtypeStruct(tuple(shape), jnp.float32), ((MODEL_AXIS, tp),)
    )


def _place_full_value(x, sharding: NamedSharding):
    """Place a host value (identical on every process — e.g. a seeded init)
    under ``sharding``. Single-process this is a plain device_put; multi-process
    it assembles the global array from each process's addressable slices via
    ``make_array_from_callback`` (device_put cannot target non-addressable
    devices)."""
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])


def shard_state_tensor_parallel(state, mesh: Mesh):
    """Place a TrainState with params/batch_stats/opt_state sharded over the
    model axis (and replicated over batch/sequence); ``step`` stays replicated.

    The optimizer state mirrors the param tree structure (Adam's mu/nu), so the
    param specs apply leaf-for-leaf wherever shapes match. Works multi-host:
    every process holds the same seeded init, and each contributes its
    addressable shards."""

    def place_tree(tree):
        specs = tensor_parallel_specs(tree, mesh)
        return jax.tree.map(
            lambda x, s: _place_full_value(x, NamedSharding(mesh, s)),
            tree,
            specs,
        )

    # one sharding rule for everything: optimizer leaves either mirror a param
    # (Adam mu/nu — shard like it) or are scalars/counters (replicated by the
    # per-leaf rule)
    return state.replace(
        step=_place_full_value(state.step, NamedSharding(mesh, P())),
        params=place_tree(state.params),
        batch_stats=place_tree(state.batch_stats),
        opt_state=place_tree(state.opt_state),
    )


def shard_state_weight_update(state, mesh: Mesh):
    """Cross-replica weight-update (ZeRO-1 optimizer-state) sharding for the
    GSPMD path: the optimizer state additionally shards over the ``batch``
    axis — each data-parallel replica stores and updates only 1/dp of it —
    the technique of "Automatic Cross-Replica Sharding of Weight Update in
    Data-Parallel Training" (arXiv:2004.13336), which XLA implements natively
    on TPU. Delegates to ``parallel/zero.py`` (the canonical spec machinery
    shared with the shard_map trainers): params and batch stats keep their
    model-axis sharding, optimizer leaves shard along the batch axis on their
    largest divisible free dimension; numerics are identical to the
    replicated update. Pair with
    ``make_train_step_gspmd(weight_update_sharding=True)`` so the update
    itself runs under the matching constraints."""
    from tensorflowdistributedlearning_tpu.parallel import zero as zero_lib

    return zero_lib.shard_state_weight_update(state, mesh, tensor_parallel=True)


def make_train_step_gspmd(
    mesh: Mesh,
    task,
    *,
    donate: bool = True,
    weight_update_sharding: bool = False,
) -> Callable:
    """jit (auto-SPMD) train step for meshes with a ``model`` axis degree > 1.

    Memoized on its arguments (like train/step.py's builders): repeated calls —
    across evals, trainer instances, tests — return the same jitted callable so
    each (mesh, task, model, shapes) combination compiles once per process.

    Differences from the shard_map step (train/step.py:make_train_step):

    - parallelism is derived by XLA's SPMD partitioner from the input shardings
      (batch sharded over ``batch``, params over ``model``) instead of being
      written as explicit collectives;
    - BatchNorm statistics are computed over the GLOBAL batch (jit sees global
      tensors), not per data-parallel shard — mathematically the synced-BN
      variant; use the shard_map step when exact per-tower BN parity with the
      reference is required.

    ``weight_update_sharding=True`` runs the optimizer update under ZeRO-1
    sharding constraints (``parallel/zero.py``): pass state placed with
    ``shard_state_weight_update`` so the optimizer leaves arrive (and leave,
    and are checkpointed) sharded over the data axis.
    """
    return _make_train_step_gspmd_cached(mesh, task, donate, weight_update_sharding)


@functools.lru_cache(maxsize=None)
def _make_train_step_gspmd_cached(
    mesh: Mesh, task, donate: bool, weight_update_sharding: bool = False
) -> Callable:
    def step(state, batch: Dict[str, jax.Array]):
        def loss_fn(params):
            outputs, mutated = state.apply_fn(
                {"params": params, "batch_stats": state.batch_stats},
                batch["images"],
                train=True,
                mutable=["batch_stats", "aux_loss"],
            )
            with scopes.scope("loss"):
                loss = task.loss(outputs, batch)
            # model-sown auxiliary losses (MoE load balancing) — empty
            # collection for every non-MoE model
            for aux in jax.tree_util.tree_leaves(mutated.get("aux_loss", {})):
                loss = loss + aux
            return loss, (outputs, mutated.get("batch_stats", state.batch_stats))

        (loss, (outputs, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        if weight_update_sharding:
            from tensorflowdistributedlearning_tpu.parallel import zero as zero_lib

            new_state = zero_lib.apply_gradients_sharded(
                state, grads, new_stats, mesh, tensor_parallel=True
            )
        else:
            new_state = state.apply_gradients(grads, new_stats)

        from tensorflowdistributedlearning_tpu.ops import metrics as metrics_lib

        scores = task.metric_scores(outputs, batch)
        metrics = {
            name: metrics_lib.Mean.empty().update(s) for name, s in scores.items()
        }
        metrics["loss"] = metrics_lib.Mean.empty().update(loss[None])
        return new_state, metrics

    jitted = scopes.Program(jax.jit(step, donate_argnums=(0,) if donate else ()))

    def run(state, batch: Dict[str, jax.Array]):
        # bind the step to its mesh: fail fast on batch/axis mismatches instead
        # of letting GSPMD quietly replicate an indivisible batch
        from tensorflowdistributedlearning_tpu.parallel import mesh as mesh_lib

        mesh_lib.local_batch_size(int(batch["images"].shape[0]), mesh)
        return jitted(state, batch)

    return run


def make_eval_step_gspmd(mesh: Mesh, task) -> Callable:
    """jit (auto-SPMD) eval step for tensor-parallel state: inference forward,
    per-example loss so an optional ``valid`` mask weights correctly, Mean
    metric pytrees — the GSPMD twin of train/step.py:make_eval_step. Memoized —
    see ``make_train_step_gspmd``."""
    return _make_eval_step_gspmd_cached(mesh, task)


@functools.lru_cache(maxsize=None)
def _make_eval_step_gspmd_cached(mesh: Mesh, task) -> Callable:
    def step(state, batch: Dict[str, jax.Array]):
        outputs = state.apply_fn(
            {"params": state.params, "batch_stats": state.batch_stats},
            batch["images"],
            train=False,
        )
        loss = task.loss_per_example(outputs, batch)
        weights = batch.get("valid")

        from tensorflowdistributedlearning_tpu.ops import metrics as metrics_lib

        scores = task.metric_scores(outputs, batch)
        metrics = {
            name: metrics_lib.Mean.empty().update(s, weights)
            for name, s in scores.items()
        }
        metrics["loss"] = metrics_lib.Mean.empty().update(loss, weights)
        return metrics

    jitted = jax.jit(step)

    def run(state, batch: Dict[str, jax.Array]):
        from tensorflowdistributedlearning_tpu.parallel import mesh as mesh_lib

        mesh_lib.local_batch_size(int(batch["images"].shape[0]), mesh)
        return jitted(state, batch)

    return run


def place_batch_gspmd(batch: Dict[str, np.ndarray], mesh: Mesh) -> Dict:
    """Shard a host batch over the batch axis for the gspmd step (model axis
    replicated for activations — GSPMD re-shards internally where profitable)."""

    def put(x):
        x = np.asarray(x)
        return jax.device_put(
            x, NamedSharding(mesh, P(BATCH_AXIS, *([None] * (x.ndim - 1))))
        )

    return {k: put(v) for k, v in batch.items()}
