"""Jitted SPMD train/eval steps for PIPELINE parallelism over ViT blocks.

The reference had no pipeline parallelism (SURVEY §2.3 — data parallel was its
only strategy); this is the trainable form of the beyond-parity GPipe runner
(parallel/pipeline.py). The mesh is (batch=dp, model=K): each data-parallel
replica is a K-stage pipeline whose stages each hold ``vit_layers/K``
consecutive transformer blocks. One train step:

- patch-embed + position-embed run replicated on every stage (token-local,
  cheap — the heavy per-layer compute is what pipelines);
- the local batch splits into M microbatches and flows through the
  ``lax.scan``-scheduled GPipe fill/drain with one ``ppermute`` hop per tick;
  autodiff derives the reversed-pipeline backward automatically;
- the head (final LN + pool + logits) runs on the gathered output, loss and
  metrics exactly as the plain classification step.

Parameters stay in the canonical ``ViTClassifier`` tree, REPLICATED across the
mesh — checkpoints, serving export, and eval are interchangeable with every
other execution strategy; inside the step each stage dynamically slices its own
block group. Gradient assembly rides shard_map's varying-manual-axes-aware
transposition (verified empirically: raw cotangents arrive at exactly
``dp x`` the single-device global-mean gradient for EVERY leaf):

- block params: stage k's cotangent is nonzero only in slot k; the model-axis
  reduction assembles the slots without over-counting;
- shared params (embed/head): the forward is unvarying on the model axis, so
  the cotangent is taken once, not K times — vma tracking knows an unvarying
  primal has an unvarying cotangent.

What remains is the per-tower mean over data-parallel shards — the same
``_mean_grads`` normalization as the plain step.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tensorflowdistributedlearning_tpu.config import ModelConfig
from tensorflowdistributedlearning_tpu.models import vit as vit_lib
from tensorflowdistributedlearning_tpu.obs import scopes
from tensorflowdistributedlearning_tpu.ops import metrics as metrics_lib
from tensorflowdistributedlearning_tpu.parallel.mesh import BATCH_AXIS, MODEL_AXIS
from tensorflowdistributedlearning_tpu.parallel.pipeline import (
    pipeline_apply,
    pipeline_apply_aux,
)
from tensorflowdistributedlearning_tpu.train.state import TrainState
from tensorflowdistributedlearning_tpu.train import step as step_lib
from tensorflowdistributedlearning_tpu.train.step import Metrics, _metric_deltas


def validate_pipeline_config(
    config: ModelConfig, pipeline_parallel: int, microbatches: int
) -> None:
    """Config-time checks so misconfiguration fails before any compile."""
    if config.backbone not in ("vit", "xception"):
        # whitelist, not a resnet blacklist: a backbone added later must opt
        # in explicitly rather than silently falling through to the ViT
        # divisibility branch below and being built as a ViT pipeline
        raise ValueError(
            f"pipeline_parallel does not support backbone={config.backbone!r}: "
            "it requires homogeneous stages (the GPipe runner's regime) — "
            "backbone='vit' (transformer blocks) or backbone='xception' (the "
            "8 identical 728-wide middle-flow units). ResNet's bottleneck "
            "stages change width/stride and cannot pipeline"
        )
    if config.moe_experts:
        raise ValueError(
            "pipeline_parallel and moe_experts cannot combine: MoE blocks "
            "break the homogeneous-stage regime the GPipe runner requires "
            "(dense and MoE blocks have different param shapes)"
        )
    if config.backbone == "xception":
        from tensorflowdistributedlearning_tpu.models.xception import (
            MIDDLE_FLOW_UNITS,
        )

        if config.num_classes is None:
            raise ValueError(
                "pipeline_parallel with backbone='xception' supports the "
                "classifier layout only (the segmentation head needs the "
                "atrous end-point dict, which the stage split does not "
                "thread through)"
            )
        if MIDDLE_FLOW_UNITS % pipeline_parallel:
            raise ValueError(
                f"{MIDDLE_FLOW_UNITS} Xception middle-flow units not "
                f"divisible by pipeline_parallel={pipeline_parallel}: stages "
                "must hold equal unit groups (use 2, 4, or 8)"
            )
    elif config.vit_layers % pipeline_parallel:
        raise ValueError(
            f"vit_layers={config.vit_layers} not divisible by "
            f"pipeline_parallel={pipeline_parallel}: stages must hold equal "
            "block groups"
        )
    if microbatches < pipeline_parallel:
        raise ValueError(
            f"pipeline_microbatches={microbatches} < pipeline stages "
            f"{pipeline_parallel}: the fill/drain schedule needs at least one "
            "microbatch per stage (and wants many more — bubble fraction is "
            "(K-1)/(M+K-1))"
        )


def _pipelined_forward(
    config: ModelConfig, stage_fn, microbatches: int, params, images: jax.Array
) -> jax.Array:
    """Full ViT forward with the block stack routed through the GPipe runner.
    Runs inside shard_map; ``images`` is the local batch shard."""
    k = lax.axis_size(MODEL_AXIS)
    # registered scopes (obs/scopes.py): the step's ``program_scopes`` record
    # attributes a capture's device time to embed / fill-drain / head
    with scopes.scope("pipeline/embed"):
        tokens = vit_lib.embed_tokens(config, params, images)
    b, t, d = tokens.shape
    if b % microbatches:
        raise ValueError(
            f"local batch {b} not divisible into {microbatches} microbatches"
        )
    x = tokens.reshape(microbatches, b // microbatches, t, d)
    stacked = vit_lib.stack_vit_block_params(params, config.vit_layers, n_stages=k)
    my_stage = jax.tree.map(
        lambda p: lax.dynamic_index_in_dim(
            p, lax.axis_index(MODEL_AXIS), 0, keepdims=False
        ),
        stacked,
    )
    with scopes.scope("pipeline/fill_drain"):
        out = pipeline_apply(stage_fn, my_stage, x)
    with scopes.scope("pipeline/head"):
        return vit_lib.head_logits(config, params, out.reshape(b, t, d))


def _reduce_metrics(metrics: Metrics) -> Metrics:
    """Sum metric contributions over batch shards; the model-axis pmean is
    numerically an identity (every stage computes identical metrics from the
    replicated pipeline output) but clears the varying type."""

    def reduce(x):
        x = lax.psum(x, BATCH_AXIS)
        return lax.pmean(x, MODEL_AXIS)

    return jax.tree.map(reduce, metrics)


def make_train_step_pipeline(
    mesh: Mesh,
    task,
    config: ModelConfig,
    microbatches: int,
    *,
    donate: bool = True,
    seed: int = 0,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Metrics]]:
    """Build the jitted pipeline-parallel train step. Memoized like the
    builders in train/step.py so K-fold loops / evals / tests share one
    executable per configuration. Dispatches on the backbone family: ViT
    pipelines its transformer blocks; Xception pipelines the middle flow
    (8 identical 728-wide sum-skip units) with the entry/exit flows
    replicated, BN normalizing per microbatch (the standard GPipe regime).
    ``seed`` roots the xception head's dropout PRNG stream exactly as in
    train/step.py:make_train_step — the same value must be passed to both
    builders for the cross-strategy mask parity the tests pin. The ViT
    branch deliberately ignores it (no stochastic layer anywhere in its
    pipelined forward, so keying its cache on seed would only force
    pointless recompiles per seed); a future dropout-bearing ViT pipeline
    must thread it into _make_train_step_pipeline_cached too."""
    if config.backbone == "xception":
        return _make_train_step_pipeline_xception_cached(
            mesh, task, config, microbatches, donate, seed
        )
    return _make_train_step_pipeline_cached(mesh, task, config, microbatches, donate)


@functools.lru_cache(maxsize=None)
def _make_train_step_pipeline_cached(
    mesh: Mesh, task, config: ModelConfig, microbatches: int, donate: bool
):
    k = mesh.shape[MODEL_AXIS]
    stage_fn = vit_lib.grouped_pipeline_stage_fn(config, config.vit_layers // k)

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        def loss_fn(params):
            logits = _pipelined_forward(
                config, stage_fn, microbatches, params, batch["images"]
            )
            return task.loss(logits, batch), logits

        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params
        )
        # raw cotangents are dp x the global-mean gradient (module docstring);
        # the vma-aware division in _mean_grads restores the tower mean
        grads = step_lib._mean_grads(grads)
        # ViT has no BatchNorm: batch_stats is an empty pytree, passed through
        new_state = state.apply_gradients(grads, state.batch_stats)
        metrics = _reduce_metrics(
            _metric_deltas(task.metric_scores(logits, batch), loss)
        )
        return new_state, metrics

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), P(BATCH_AXIS)),
        out_specs=(P(), P()),
    )
    return scopes.Program(jax.jit(sharded, donate_argnums=(0,) if donate else ()))


def _xception_stage_bundle(params, batch_stats, k):
    """This stage's (param, stat) groups: stack the 8 middle-unit subtrees
    into [K, G, ...] and dynamic-index the model-axis slot. Differentiable —
    the transpose of stack+index routes each stage's cotangent back to its own
    units' slots."""
    from tensorflowdistributedlearning_tpu.models import xception as xc

    idx = lax.axis_index(MODEL_AXIS)
    take = lambda tree: jax.tree.map(  # noqa: E731
        lambda l: lax.dynamic_index_in_dim(l, idx, 0, keepdims=False),
        xc.stack_middle_unit_tree(tree, k),
    )
    return take(params["backbone"]), take(batch_stats["backbone"])


# canonical-tree key split for the replicated (non-pipelined) flows
_XC_ENTRY_KEYS = (
    "conv1_1",
    "conv1_2",
    "entry_block1_unit1",
    "entry_block2_unit1",
    "entry_block3_unit1",
)
_XC_EXIT_KEYS = ("exit_block1_unit1", "exit_block2_unit1")


@functools.lru_cache(maxsize=None)
def _make_train_step_pipeline_xception_cached(
    mesh: Mesh, task, config: ModelConfig, microbatches: int, donate: bool,
    seed: int = 0,
):
    from tensorflowdistributedlearning_tpu.models import xception as xc

    k = mesh.shape[MODEL_AXIS]
    entry = xc.XceptionEntryFlow(config)
    exit_head = xc.XceptionExitHead(config)
    stage_fn = xc.grouped_middle_stage_fn(
        config, xc.MIDDLE_FLOW_UNITS // k, train=True
    )

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        # per-(step, batch-shard) dropout stream for the pre-logits dropout;
        # the model axis is NOT folded in — every stage computes the same
        # replicated head and must agree on one mask. The trailing fold_in(0)
        # mirrors the plain step's accum-chunk fold (train/step.py) so the
        # two strategies draw the IDENTICAL mask for a given (step, shard) —
        # the parity tests rely on it.
        dropout_rng = jax.random.fold_in(
            jax.random.fold_in(
                jax.random.fold_in(jax.random.key(seed), state.step),
                lax.axis_index(BATCH_AXIS),
            ),
            0,
        )

        def loss_fn(params):
            backbone_p = params["backbone"]
            stats = state.batch_stats
            backbone_s = stats["backbone"]
            with scopes.scope("pipeline/entry"):
                feats, entry_mut = entry.apply(
                    {
                        "params": {
                            key: backbone_p[key] for key in _XC_ENTRY_KEYS
                        },
                        "batch_stats": {
                            key: backbone_s[key] for key in _XC_ENTRY_KEYS
                        },
                    },
                    batch["images"],
                    True,
                    mutable=["batch_stats"],
                )
            b = feats.shape[0]
            if b % microbatches:
                raise ValueError(
                    f"local batch {b} not divisible into {microbatches} "
                    "microbatches"
                )
            x = feats.reshape(
                (microbatches, b // microbatches) + feats.shape[1:]
            )
            my_p, my_s = _xception_stage_bundle(params, stats, k)
            with scopes.scope("pipeline/fill_drain"):
                out, my_new_stats = pipeline_apply_aux(
                    stage_fn, (my_p, my_s), x
                )
            logits, exit_mut = exit_head.apply(
                {
                    "params": {
                        **{key: backbone_p[key] for key in _XC_EXIT_KEYS},
                        "logits": params["logits"],
                    },
                    "batch_stats": {
                        key: backbone_s[key] for key in _XC_EXIT_KEYS
                    },
                },
                out.reshape((b,) + out.shape[2:]),
                True,
                mutable=["batch_stats"],
                rngs={"dropout": dropout_rng},
            )
            loss = task.loss(logits, batch)
            # assemble the full new batch_stats tree: each stage scatters its
            # group's microbatch-averaged stats into its [K, G, ...] slot; the
            # model-axis psum fills the other slots (zeros elsewhere — a copy,
            # not a reduction)
            idx = lax.axis_index(MODEL_AXIS)
            scattered = jax.tree.map(
                lambda s: jnp.zeros((k,) + s.shape, s.dtype).at[idx].set(s),
                my_new_stats,
            )
            middle_new = xc.unstack_middle_unit_tree(
                lax.psum(scattered, MODEL_AXIS)
            )
            new_backbone = dict(entry_mut["batch_stats"])
            new_backbone.update(middle_new)
            new_backbone.update(exit_mut["batch_stats"])
            return loss, (logits, {"backbone": new_backbone})

        (loss, (logits, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        grads = step_lib._mean_grads(grads)
        # per-tower BN stats -> replicated (same normalization as the plain
        # step); the stats are already model-axis unvarying: entry/exit ran
        # replicated, the middle slots were psum-assembled above
        new_stats = lax.pmean(new_stats, BATCH_AXIS)
        new_state = state.apply_gradients(grads, new_stats)
        metrics = _reduce_metrics(
            _metric_deltas(task.metric_scores(logits, batch), loss)
        )
        return new_state, metrics

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), P(BATCH_AXIS)),
        out_specs=(P(), P()),
    )
    return scopes.Program(jax.jit(sharded, donate_argnums=(0,) if donate else ()))


@functools.lru_cache(maxsize=None)
def _make_eval_step_pipeline_xception_cached(
    mesh: Mesh, task, config: ModelConfig, microbatches: int
):
    from tensorflowdistributedlearning_tpu.models import xception as xc

    k = mesh.shape[MODEL_AXIS]
    entry = xc.XceptionEntryFlow(config)
    exit_head = xc.XceptionExitHead(config)
    stage_fn = xc.grouped_middle_stage_fn(
        config, xc.MIDDLE_FLOW_UNITS // k, train=False
    )

    def step(state: TrainState, batch: Dict[str, jax.Array]) -> Metrics:
        backbone_p = state.params["backbone"]
        backbone_s = state.batch_stats["backbone"]
        feats = entry.apply(
            {
                "params": {key: backbone_p[key] for key in _XC_ENTRY_KEYS},
                "batch_stats": {key: backbone_s[key] for key in _XC_ENTRY_KEYS},
            },
            batch["images"],
            False,
        )
        b = feats.shape[0]
        if b % microbatches:
            raise ValueError(
                f"local batch {b} not divisible into {microbatches} "
                "microbatches"
            )
        x = feats.reshape((microbatches, b // microbatches) + feats.shape[1:])
        bundle = _xception_stage_bundle(state.params, state.batch_stats, k)
        out = pipeline_apply(stage_fn, bundle, x)
        logits = exit_head.apply(
            {
                "params": {
                    **{key: backbone_p[key] for key in _XC_EXIT_KEYS},
                    "logits": state.params["logits"],
                },
                "batch_stats": {key: backbone_s[key] for key in _XC_EXIT_KEYS},
            },
            out.reshape((b,) + out.shape[2:]),
            False,
        )
        loss = task.loss_per_example(logits, batch)
        weights = batch.get("valid")
        return _reduce_metrics(
            _metric_deltas(task.metric_scores(logits, batch), loss, weights)
        )

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), P(BATCH_AXIS)),
        out_specs=P(),
    )
    return jax.jit(sharded)


def make_eval_step_pipeline(
    mesh: Mesh, task, config: ModelConfig, microbatches: int
) -> Callable[[TrainState, Dict[str, jax.Array]], Metrics]:
    """Jitted pipeline-parallel eval step: the pipelined forward in inference
    mode, per-example loss so the ``valid`` wrap-around mask weights correctly
    (same contract as train/step.py:make_eval_step). Dispatches on backbone
    like ``make_train_step_pipeline``."""
    if config.backbone == "xception":
        return _make_eval_step_pipeline_xception_cached(
            mesh, task, config, microbatches
        )
    return _make_eval_step_pipeline_cached(mesh, task, config, microbatches)


@functools.lru_cache(maxsize=None)
def _make_eval_step_pipeline_cached(
    mesh: Mesh, task, config: ModelConfig, microbatches: int
):
    k = mesh.shape[MODEL_AXIS]
    stage_fn = vit_lib.grouped_pipeline_stage_fn(config, config.vit_layers // k)

    def step(state: TrainState, batch: Dict[str, jax.Array]) -> Metrics:
        logits = _pipelined_forward(
            config, stage_fn, microbatches, state.params, batch["images"]
        )
        loss = task.loss_per_example(logits, batch)
        weights = batch.get("valid")
        return _reduce_metrics(
            _metric_deltas(task.metric_scores(logits, batch), loss, weights)
        )

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), P(BATCH_AXIS)),
        out_specs=P(),
    )
    return jax.jit(sharded)
