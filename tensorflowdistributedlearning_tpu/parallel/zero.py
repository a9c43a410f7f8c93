"""Cross-replica (ZeRO-1) sharding of the weight update and optimizer state.

In plain data parallelism the optimizer state — Adam's two moments, LARS/SGD
momentum, the EMA tracker — is fully replicated: every chip stores ~2-3x the
parameter bytes in slots and runs the identical weight update N times. The fix
is the one "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arXiv:2004.13336) built into XLA and the pjit/TPUv4 scaling report
(arXiv:2204.06514) runs in production: shard the optimizer state (and the
update computing it) across the DATA axis, so each replica stores and updates
1/dp of the slots, then gather the freshly-updated parameters.

This module is the spec/placement/update machinery behind
``TrainConfig.weight_update_sharding``:

- ``weight_update_specs`` — PartitionSpec pytree partitioning every leaf along
  the ``batch`` mesh axis on its LARGEST dp-divisible dimension (replicated
  fallback for scalars and indivisible leaves). With ``tensor_parallel=True``
  the batch-axis shard composes on top of the model-axis channel sharding
  (``parallel/tensor.py``): the batch shard lands on a dimension the model
  axis does not already occupy, or stacks onto the channel dimension when
  that is the only one that divides.
- ``shard_state_weight_update`` — TrainState placement: params/batch_stats in
  their canonical layout (replicated, or channel-sharded under TP),
  ``opt_state`` under the weight-update specs. Multi-host capable via
  ``tensor.place_full_value``.
- ``apply_gradients_sharded`` — the update itself, run inside jit under GSPMD
  sharding constraints: replicated gradients are constrained to the opt-state
  sharding (a local slice — the cross-replica reduce already happened inside
  the step), ``tx.update`` then computes each slot shard at 1/dp cost, and
  the parameter gather falls out of constraining the updated params back to
  their canonical spec. Numerics are those of the replicated update (the same
  elementwise math over the same global gradient), which the equivalence
  tests pin step-for-step.

The shard_map train step (train/step.py) composes with this by returning
(grads, batch_stats, metrics) from the manual region and applying the update
OUTSIDE it, where GSPMD owns placement; the GSPMD tensor-parallel step
(parallel/tensor.py:make_train_step_gspmd) applies it inline.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorflowdistributedlearning_tpu.obs import scopes
from tensorflowdistributedlearning_tpu.parallel.mesh import (
    BATCH_AXIS,
    MODEL_AXIS,
    largest_divisible_dim,
)


def weight_update_spec(
    shape: Tuple[int, ...], mesh: Mesh, *, tensor_parallel: bool = False
) -> P:
    """The ZeRO-1 PartitionSpec for one optimizer-state (or gradient) leaf.

    The ``batch`` axis partitions the largest dimension divisible by the
    data-parallel degree; scalars and leaves with no divisible dimension stay
    replicated (they are the cheap tail — BN scale/offset vectors, schedule
    counters). Under ``tensor_parallel`` the leaf keeps the channel sharding
    its mirrored parameter has (``tensor._spec_for_leaf`` is shape-driven, so
    applying it to an Adam moment reproduces the param's spec exactly), and
    the batch axis takes the largest dimension the model axis left unsharded —
    or stacks onto the channel dimension when nothing else divides."""
    return weight_update_spec_for_degrees(
        shape,
        dp=mesh.shape[BATCH_AXIS],
        tp=mesh.shape[MODEL_AXIS] if tensor_parallel else 1,
    )


def weight_update_spec_for_degrees(
    shape: Tuple[int, ...], *, dp: int, tp: int = 1
) -> P:
    """:func:`weight_update_spec` queryable by plain degrees — no mesh (and
    no devices) needed, so the parallelism planner can predict a candidate
    layout's exact per-chip optimizer bytes with the SAME rule placement
    uses (the rules cannot drift apart: the mesh form delegates here)."""
    from tensorflowdistributedlearning_tpu.parallel.tensor import _spec_for_leaf

    base = (
        _spec_for_leaf(jax.ShapeDtypeStruct(shape, jnp.float32), ((MODEL_AXIS, tp),))
        if tp > 1
        else P()
    )
    if dp <= 1:
        return base
    taken = {i for i, names in enumerate(base) if names is not None}
    dim = largest_divisible_dim(shape, dp, taken=taken)
    if dim is None:
        # every free dimension resists dp: try stacking batch onto the
        # model-sharded channel dimension (per-shard extent must still divide)
        if taken and shape[-1] % (tp * dp) == 0:
            spec = list(base)
            spec[-1] = (MODEL_AXIS, BATCH_AXIS)
            return P(*spec)
        return base
    spec = [base[i] if i < len(base) else None for i in range(len(shape))]
    spec[dim] = BATCH_AXIS
    return P(*spec)


def weight_update_specs(
    tree: Any, mesh: Mesh, *, tensor_parallel: bool = False
) -> Any:
    """``weight_update_spec`` mapped over a pytree (opt_state, params, grads).

    Purely shape-driven, so the one function serves the optimizer state, the
    gradients, and the updates — leaves of equal shape land on equal specs,
    which is what lets the sharded ``tx.update`` run without any resharding
    between its operands."""
    return jax.tree.map(
        lambda leaf: weight_update_spec(
            tuple(jnp.shape(leaf)), mesh, tensor_parallel=tensor_parallel
        ),
        tree,
    )


def param_placement_specs(
    params: Any, mesh: Mesh, *, tensor_parallel: bool = False
) -> Any:
    """The canonical (non-ZeRO) placement of the parameters themselves:
    replicated in plain data parallelism, channel-sharded over the model axis
    under tensor parallelism. ZeRO-1 deliberately keeps params here — only
    the OPTIMIZER state shards over data (ZeRO-2/3 territory starts where
    gradients and params shard too)."""
    if tensor_parallel:
        from tensorflowdistributedlearning_tpu.parallel.tensor import (
            tensor_parallel_specs,
        )

        return tensor_parallel_specs(params, mesh)
    return jax.tree.map(lambda _: P(), params)


def _constrain(tree: Any, mesh: Mesh, specs: Any) -> Any:
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
        tree,
        specs,
    )


def shard_state_weight_update(state, mesh: Mesh, *, tensor_parallel: bool = False):
    """Place a TrainState for ZeRO-1 training: params/batch_stats in their
    canonical layout, ``opt_state`` sharded over the data axis under
    ``weight_update_specs``. Works multi-host (every process holds the same
    seeded init and contributes its addressable shards)."""
    from tensorflowdistributedlearning_tpu.parallel.tensor import _place_full_value

    def place(tree, specs):
        return jax.tree.map(
            lambda x, s: _place_full_value(x, NamedSharding(mesh, s)), tree, specs
        )

    return state.replace(
        step=_place_full_value(state.step, NamedSharding(mesh, P())),
        params=place(
            state.params,
            param_placement_specs(state.params, mesh, tensor_parallel=tensor_parallel),
        ),
        batch_stats=place(
            state.batch_stats,
            param_placement_specs(
                state.batch_stats, mesh, tensor_parallel=tensor_parallel
            ),
        ),
        opt_state=place(
            state.opt_state,
            weight_update_specs(
                state.opt_state, mesh, tensor_parallel=tensor_parallel
            ),
        ),
    )


def apply_gradients_sharded(
    state, grads: Any, new_batch_stats: Any, mesh: Mesh, *,
    tensor_parallel: bool = False,
):
    """One ZeRO-1 optimizer update under GSPMD sharding constraints (call
    inside jit, on gradients that are already the cross-replica global mean).

    Constraining the replicated gradients to the opt-state sharding is a free
    local slice; ``tx.update`` then runs every slot update at 1/dp per-chip
    cost (Adam moment math, LARS trust ratios, the EMA tracker all ride
    along, since their state leaves mirror param shapes and therefore specs);
    constraining the updated params back to their canonical placement is the
    all-gather that completes the round trip. The input opt_state is also
    constrained so a caller whose placement drifted (e.g. a checkpoint
    restored without shardings) converges back to the declared layout instead
    of letting GSPMD propagate an accidental one."""
    grad_specs = weight_update_specs(grads, mesh, tensor_parallel=tensor_parallel)
    opt_specs = weight_update_specs(
        state.opt_state, mesh, tensor_parallel=tensor_parallel
    )
    with scopes.scope("optimizer"):
        grads = _constrain(grads, mesh, grad_specs)
        opt_state = _constrain(state.opt_state, mesh, opt_specs)
        updates, new_opt_state = state.tx.update(grads, opt_state, state.params)
        updates = _constrain(updates, mesh, grad_specs)
        new_opt_state = _constrain(new_opt_state, mesh, opt_specs)
        new_params = optax.apply_updates(state.params, updates)
        new_params = _constrain(
            new_params,
            mesh,
            param_placement_specs(state.params, mesh, tensor_parallel=tensor_parallel),
        )
    return state.replace(
        step=state.step + 1,
        params=new_params,
        batch_stats=new_batch_stats,
        opt_state=new_opt_state,
    )
