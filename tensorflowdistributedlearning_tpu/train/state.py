"""Functional training state.

The reference's training state was implicit TF1 graph collections — GLOBAL_VARIABLES,
UPDATE_OPS for the BN moving stats, the optimizer's slots, and the global step
(reference: model.py:457-467). Here it is one explicit pytree, which is what makes
donation, sharding, and Orbax checkpointing trivial.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import core, struct

from tensorflowdistributedlearning_tpu.obs import scopes


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: core.FrozenDict
    # BN moving statistics — the explicit form of the reference's UPDATE_OPS dance
    # (reference: model.py:465-467)
    batch_stats: core.FrozenDict
    opt_state: optax.OptState
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    def apply_gradients(self, grads: Any, new_batch_stats: Any) -> "TrainState":
        with scopes.scope("optimizer"):
            updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
            new_params = optax.apply_updates(self.params, updates)
        return self.replace(
            step=self.step + 1,
            params=new_params,
            batch_stats=new_batch_stats,
            opt_state=new_opt_state,
        )


def create_train_state(
    model, tx: optax.GradientTransformation, rng: jax.Array, sample_input: jax.Array
) -> TrainState:
    """Initialize parameters/BN stats from a sample input and wrap them with the
    optimizer state.

    Init runs EAGERLY on purpose: op-by-op dispatch hits jax's process-wide
    primitive cache (shared across all architectures), whereas a jitted init
    compiles a fresh ~10s executable per architecture — the wrong trade for
    K-fold loops and test suites that build many small model variants."""
    variables = model.init(rng, sample_input, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", core.FrozenDict())
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
        apply_fn=model.apply,
        tx=tx,
    )


def tree_bytes_per_device(tree: Any) -> int:
    """Bytes ONE device holds for a placed pytree: each leaf counts its shard
    (``sharding.shard_shape``), so a replicated leaf counts full size and a
    ZeRO-sharded optimizer moment counts 1/dp — the number the weight-update
    sharding mode exists to shrink, reported by the trainers' memory events
    and bench.py so the saving is measured, not asserted. Host numpy leaves
    (and ShapeDtypeStructs without a sharding) count full size."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            continue
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and hasattr(sharding, "shard_shape"):
            shape = sharding.shard_shape(tuple(shape))
        total += int(np.prod(shape)) * np.dtype(leaf.dtype).itemsize
    return total
