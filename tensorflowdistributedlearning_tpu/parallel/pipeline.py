"""Pipeline parallelism: a GPipe-style microbatched runner over mesh shards.

The reference had no pipeline parallelism (SURVEY §2.3: data parallelism was its
only strategy), so — like the tensor and sequence axes — this is a beyond-parity
capability, built compiler-first: the schedule is a ``lax.scan`` whose per-tick
body applies THIS shard's stage and hands activations to the next shard over one
``ppermute`` ICI hop. Because the whole schedule is expressed as traced JAX ops,
reverse-mode autodiff differentiates straight through it — the backward pass
(reversed pipeline with transposed ppermutes) is derived by the compiler, not
hand-written.

Scope: homogeneous stages — every pipeline stage must share one computation
graph (same ``stage_fn``, same param shapes), the classic transformer-layer
regime; in this framework's model family it maps exactly onto Xception's middle
flow (8 identical 728-wide sum-skip units, models/xception.py) and onto stacks
of equal-width residual units. Heterogeneous stage support (different shapes per
stage) would need per-stage padding and is out of scope.

Schedule: plain GPipe fill/drain — ``M`` microbatches over ``K`` stages take
``M + K - 1`` ticks, bubble fraction ``(K-1)/(M+K-1)``; choose ``M >> K``.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tensorflowdistributedlearning_tpu.parallel.mesh import MODEL_AXIS


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    my_stage_params: Any,
    x_microbatches: jax.Array,
    *,
    axis_name: str = MODEL_AXIS,
) -> jax.Array:
    """Run ``K`` pipeline stages over ``M`` microbatches inside ``shard_map``.

    ``my_stage_params``: THIS shard's stage parameters (shard the stacked
    [K, ...] param tree over ``axis_name`` in the enclosing shard_map's
    in_specs and squeeze the leading 1). ``x_microbatches``: [M, mb, ...],
    replicated across the axis (only stage 0 consumes it). Returns the
    pipeline output [M, mb, ...], replicated across the axis.

    Stage ``k`` processes microbatch ``m`` at tick ``t = m + k``; activations
    move to stage ``k+1`` via a neighbor ``ppermute`` each tick.

    Delegates to ``pipeline_apply_aux`` (the one copy of the fill/drain
    schedule) with an empty aux stream.
    """
    out, _ = pipeline_apply_aux(
        lambda p, x: (stage_fn(p, x), ()),
        my_stage_params,
        x_microbatches,
        axis_name=axis_name,
    )
    return out


def pipeline_apply_aux(
    stage_fn: Callable[[Any, jax.Array], tuple],
    my_stage_params: Any,
    x_microbatches: jax.Array,
    *,
    axis_name: str = MODEL_AXIS,
) -> tuple:
    """``pipeline_apply`` for stages that also EMIT per-tick auxiliary state:
    ``stage_fn(params, x) -> (y, aux)``. Returns ``(out, aux_mean)`` where
    ``aux_mean`` averages this stage's aux over its M REAL microbatch ticks —
    stage ``k`` processes real work at ticks ``k .. k+M-1``; fill/drain ticks
    (whose input is the zero padding or a neighbor's garbage) are excluded.

    Built for BatchNorm-bearing pipeline stages (Xception's middle flow): the
    aux is the per-microbatch updated running stats, and because flax's update
    is affine in the batch statistic (``new = m*old + (1-m)*mu_i``), the MEAN
    of per-microbatch updates equals ONE update with the microbatch-averaged
    statistic — the same single-update-per-step bookkeeping as the plain step.
    """
    k_stages = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m_micro = x_microbatches.shape[0]
    ticks = m_micro + k_stages - 1

    pad = jnp.zeros((k_stages - 1,) + x_microbatches.shape[1:], x_microbatches.dtype)
    inject = jnp.concatenate([x_microbatches, pad], axis=0)
    perm = [(i, i + 1) for i in range(k_stages - 1)]

    def tick(buf, x_t):
        inp = jnp.where(idx == 0, x_t, buf)
        y, aux = stage_fn(my_stage_params, inp)
        buf_next = lax.ppermute(y, axis_name, perm)
        return buf_next, (y, aux)

    zero = jnp.zeros_like(x_microbatches[0])
    buf0 = lax.pcast(zero, axis_name, to="varying")
    _, (ys, auxs) = lax.scan(tick, buf0, inject[:ticks])

    tail = lax.dynamic_slice_in_dim(ys, k_stages - 1, m_micro, axis=0)
    out = lax.psum(
        jnp.where(idx == k_stages - 1, tail, jnp.zeros_like(tail)), axis_name
    )
    # this stage's real ticks: a device-varying dynamic slice (each shard
    # starts at its own stage index), then the microbatch mean
    aux_mean = jax.tree.map(
        lambda a: jnp.mean(
            lax.dynamic_slice_in_dim(a, idx, m_micro, axis=0), axis=0
        ),
        auxs,
    )
    return out, aux_mean


def stack_stage_params(param_trees) -> Any:
    """Stack K per-stage param pytrees on a new leading axis (shard it over the
    model axis with ``P(MODEL_AXIS, ...)`` in_specs)."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *param_trees)


def stage_in_spec() -> P:
    """in_spec for stacked stage params: leading (stage) axis over the model
    mesh axis."""
    return P(MODEL_AXIS)


def make_pipeline_fn(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    *,
    donate: bool = False,
) -> Callable:
    """Jitted end-to-end pipeline forward: ``f(stacked_params, x_microbatches)``.

    ``stacked_params``: [K, ...] per-stage params (K = the mesh's model-axis
    size); ``x_microbatches``: [M, mb, ...]. Output: [M, mb, ...]. Used
    standalone or as a building block inside a larger shard_mapped step.
    """

    def run(stacked_params, x_microbatches):
        k = mesh.shape[MODEL_AXIS]
        n_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        if n_stages != k:
            # a proper multiple would SILENTLY run only every (n/k)-th stage
            # after the per-shard squeeze below — reject anything but exact
            raise ValueError(
                f"{n_stages} pipeline stages on a model axis of size {k}; "
                "the stage count must equal the mesh's model-axis size"
            )

        def body(params_shard, x):
            my_params = jax.tree.map(lambda p: p[0], params_shard)
            return pipeline_apply(stage_fn, my_params, x)

        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(stage_in_spec(), P()),
            out_specs=P(),
        )(stacked_params, x_microbatches)

    return jax.jit(run, donate_argnums=(0,) if donate else ())
