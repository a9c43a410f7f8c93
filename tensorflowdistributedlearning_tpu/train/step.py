"""Jitted SPMD train/eval/predict steps.

This module is where the reference's whole distribution machinery collapses: the
per-GPU towers, per-tower input_fns, NCCL gradient all-reduce, and UPDATE_OPS control
dependencies (reference: model.py:115-121, 326-505) become ONE function, shard_map-ped
over the device mesh:

- the batch arrives sharded on the `batch` mesh axis (each shard sees batch/n, the
  reference's per-tower split, model.py:156-159);
- BN statistics are computed per shard — matching the reference's per-tower slim BN
  under MirroredStrategy — then averaged across shards so the replicated-state
  invariant holds;
- gradients and metrics are reduced with `lax.pmean`/`lax.psum`, which XLA lowers to
  ICI all-reduces (the NCCL equivalent, emitted by the compiler);
- the optimizer update runs identically on every shard, keeping params replicated.

Everything is a pure function of (state, batch), so `jax.jit` with donated state gives
in-place buffer reuse on TPU.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

from tensorflowdistributedlearning_tpu.config import (
    DecoderConfig,
    ModelConfig,
    TokenStreamConfig,
    TrainConfig,
)
from tensorflowdistributedlearning_tpu.obs import scopes
from tensorflowdistributedlearning_tpu.ops import losses as losses_lib
from tensorflowdistributedlearning_tpu.ops import metrics as metrics_lib
from tensorflowdistributedlearning_tpu.parallel.mesh import BATCH_AXIS, SEQUENCE_AXIS
from tensorflowdistributedlearning_tpu.train.state import TrainState

Metrics = Dict[str, metrics_lib.Mean]


def make_lr_schedule(cfg: TrainConfig) -> optax.Schedule:
    """The configured learning-rate schedule.

    ``exponential`` (default) reproduces the reference: continuous decay, lr
    halves every ``lr_decay_steps`` (reference: model.py:457-462,
    staircase=False). ``cosine`` is the standard ImageNet recipe — linear
    warmup over ``lr_warmup_steps`` then cosine decay to ~0 at
    ``lr_decay_steps``; with ``lr_warmup_steps=0`` it starts straight at the
    peak lr (a zero-lr first step would silently waste it)."""
    if cfg.lr_schedule == "cosine":
        if cfg.lr_warmup_steps == 0:
            return optax.cosine_decay_schedule(
                init_value=cfg.lr, decay_steps=max(cfg.lr_decay_steps, 1)
            )
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=cfg.lr,
            warmup_steps=cfg.lr_warmup_steps,
            decay_steps=max(cfg.lr_decay_steps, cfg.lr_warmup_steps + 1),
        )
    return optax.exponential_decay(
        init_value=cfg.lr,
        transition_steps=cfg.lr_decay_steps,
        decay_rate=cfg.lr_decay_rate,
        staircase=False,
    )


def make_host_lr_schedule(cfg: TrainConfig) -> Callable[[int], float]:
    """Pure-host (math-library) mirror of ``make_lr_schedule``.

    The trainers log the next update's lr every window; evaluating the optax
    schedule for that dispatches a tiny device computation per log line — the
    logging path should add ZERO device work, especially under the async host
    loop where the device queue must stay full. Parity with the optax
    schedules is pinned by
    tests/test_async_loop.py::test_host_lr_schedule_matches_optax."""
    import math

    lr = float(cfg.lr)
    if cfg.lr_schedule == "cosine":
        warmup = cfg.lr_warmup_steps
        if warmup == 0:
            decay_steps = max(cfg.lr_decay_steps, 1)

            def sched(step: int) -> float:
                frac = min(max(step, 0), decay_steps) / decay_steps
                return lr * 0.5 * (1.0 + math.cos(math.pi * frac))

            return sched
        decay_steps = max(cfg.lr_decay_steps, warmup + 1)

        def sched(step: int) -> float:
            if step < warmup:
                return lr * max(step, 0) / warmup
            frac = min(step - warmup, decay_steps - warmup) / (
                decay_steps - warmup
            )
            return lr * 0.5 * (1.0 + math.cos(math.pi * frac))

        return sched
    transition, rate = cfg.lr_decay_steps, cfg.lr_decay_rate

    def sched(step: int) -> float:
        return lr * rate ** (step / transition)

    return sched


# weight-matrix leaf names: flax conv/dense "kernel", plus the MoE FFN's
# explicitly-declared expert matrices and router (models/vit.py:MoEMlp) —
# the direct replacements for the dense mlp kernels they stand in for
_DECAYED_LEAF_NAMES = frozenset(
    {"kernel", "w_in", "w_out", "router", "w_gate", "w_up", "w_down"}
)


def kernel_decay_mask(params: Any) -> Any:
    """Weight-decay mask: True only for weight-matrix leaves (conv/dense
    kernels, MoE expert matrices + router). BN scale/bias, plain biases,
    LayerNorm params, ViT cls/position embeddings stay undecayed — the
    standard ImageNet recipe (arXiv:1706.02677 §5.3) and the same
    kernels-only scoping the reference's declared l2 used
    (reference: core/resnet.py:357-376, weights_regularizer on conv weights)."""
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    mask_leaves = [
        any(getattr(k, "key", None) in _DECAYED_LEAF_NAMES for k in path)
        for path, _ in paths_leaves
    ]
    return jax.tree_util.tree_unflatten(treedef, mask_leaves)


class EmaTrackerState(NamedTuple):
    """State of ``ema_tracker``: the parameter EMA (same pytree as params)."""

    ema: Any


def ema_tracker(decay: float) -> optax.GradientTransformation:
    """Pass-through transformation that maintains an exponential moving average
    of the PARAMETERS (not the gradients) in its own state.

    Appended after the real optimizer in the chain, its ``update`` sees the
    final updates and the current params, so ``params + updates`` is exactly
    the post-step parameter value: ``ema <- decay * ema + (1 - decay) * new``.
    The EMA initializes AT the initial params (no zero-init debias needed) and
    rides ``opt_state`` — so checkpointing, donation, replication, and every
    execution strategy (shard_map, GSPMD tensor-parallel, pipeline) carry it
    with zero extra plumbing. Updates pass through UNCHANGED; evaluation opts
    in via ``with_ema_params``. Beyond-parity: the reference had no weight
    averaging (its slim arg_scope declared none); this is the standard modern
    ImageNet/ViT recipe component (e.g. arXiv:1706.02677-era baselines ship
    without it, RandAug/EffNet-era recipes with it)."""

    def init_fn(params):
        # a REAL copy, not jnp.asarray: the EMA must not alias the param
        # buffers, or donating TrainState would donate each buffer twice
        return EmaTrackerState(ema=jax.tree.map(jnp.copy, params))

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("ema_tracker needs params in tx.update()")
        new_ema = jax.tree.map(
            lambda e, p, u: e * decay + (p + u) * (1.0 - decay),
            state.ema,
            params,
            updates,
        )
        return updates, EmaTrackerState(ema=new_ema)

    return optax.GradientTransformation(init_fn, update_fn)


def find_ema_params(opt_state: Any) -> Optional[Any]:
    """The tracked parameter EMA inside ``opt_state``, or None when the
    optimizer chain has no ``ema_tracker``."""
    if isinstance(opt_state, EmaTrackerState):
        return opt_state.ema
    if isinstance(opt_state, (tuple, list)):
        for sub in opt_state:
            found = find_ema_params(sub)
            if found is not None:
                return found
    return None


def with_ema_params(state: TrainState) -> TrainState:
    """``state`` with params swapped for their EMA when one is tracked (the
    eval/export view); identity otherwise. The EMA tree matches the params
    tree exactly, so jitted eval/predict executables cache-hit either way."""
    ema = find_ema_params(state.opt_state)
    return state if ema is None else state.replace(params=ema)


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    """The configured optimizer under the configured lr schedule: ``adam``
    (the reference's choice, model.py:462), ``sgd`` (Nesterov momentum —
    the standard ImageNet recipe behind the 76%-top-1 north star), or
    ``lars`` (large-batch layer-wise scaling, arXiv:1708.03888).

    ``cfg.weight_decay > 0`` adds kernels-only decoupled decay to the chain:
    before momentum+lr scaling for sgd (classic l2-SGD, the Goyal recipe),
    as AdamW for adam, and through optax.lars' own decay/trust-ratio masks
    for lars. Living in the optimizer chain means every execution strategy —
    the shard_map step, the GSPMD tensor-parallel step, the pipeline runner —
    applies it identically through ``TrainState.tx``.

    Memoized on the optimizer-relevant fields only: optax transformations are
    pure function pairs, and ``TrainState.tx`` is a static pytree field compared
    by ``==`` inside jax.jit — returning the SAME object for equivalent
    configurations is what lets the jitted train step's cache hit across K-fold
    iterations, Trainer instances, and configs that differ only in
    orchestration knobs (checkpoint cadence, fold count, ...), instead of
    recompiling per fold."""
    return _make_optimizer_cached(
        cfg.optimizer,
        # momentum only shapes the sgd/lars transformations: normalize it for
        # adam so configs differing in an UNUSED knob still share one tx object
        cfg.sgd_momentum if cfg.optimizer in ("sgd", "lars") else 0.0,
        cfg.lr,
        cfg.lr_schedule,
        cfg.lr_decay_steps,
        cfg.lr_decay_rate,
        cfg.lr_warmup_steps,
        cfg.weight_decay,
        cfg.ema_decay,
        cfg.grad_clip_norm,
    )


@functools.lru_cache(maxsize=None)
def _make_optimizer_cached(
    optimizer: str,
    momentum: float,
    lr: float,
    schedule: str,
    decay_steps: int,
    decay_rate: float,
    warmup_steps: int,
    weight_decay: float,
    ema_decay: float = 0.0,
    grad_clip_norm: float = 0.0,
) -> optax.GradientTransformation:
    cfg = TrainConfig(
        lr=lr,
        lr_schedule=schedule,
        lr_decay_steps=decay_steps,
        lr_decay_rate=decay_rate,
        lr_warmup_steps=warmup_steps,
    )
    sched = make_lr_schedule(cfg)
    if optimizer == "lars":
        tx = optax.lars(
            sched,
            weight_decay=weight_decay,
            weight_decay_mask=kernel_decay_mask,
            trust_ratio_mask=kernel_decay_mask,
            momentum=momentum,
            nesterov=True,
        )
    elif optimizer == "sgd":
        if weight_decay:
            # decay BEFORE momentum+lr scaling == the classic coupled l2-SGD
            # update the 76%-top-1 recipe trains with (arXiv:1706.02677)
            tx = optax.chain(
                optax.add_decayed_weights(weight_decay, mask=kernel_decay_mask),
                optax.sgd(sched, momentum=momentum, nesterov=True),
            )
        else:
            tx = optax.sgd(sched, momentum=momentum, nesterov=True)
    elif weight_decay:
        tx = optax.adamw(sched, weight_decay=weight_decay, mask=kernel_decay_mask)
    else:
        tx = optax.adam(sched)
    if grad_clip_norm:
        # clip FIRST so decay/momentum/trust-ratio all see the clipped gradient
        # (the standard ViT/large-LR stabilizer placement)
        tx = optax.chain(optax.clip_by_global_norm(grad_clip_norm), tx)
    if ema_decay:
        tx = optax.chain(tx, ema_tracker(ema_decay))
    return tx


@dataclasses.dataclass(frozen=True)
class SegmentationTask:
    """Binary segmentation objective: per-image Lovász hinge on the logits, Kaggle
    thresholded mIOU + pixel accuracy on the thresholded sigmoid (reference:
    model.py:371-372, 391-398)."""

    threshold: float = 0.5

    name = "segmentation"

    def run_header(self) -> Dict[str, Any]:
        return {}

    def window_fields(self, examples: int, scalars, vectors, images_per_sec) -> Dict[str, Any]:
        return {}

    def loss(self, logits: jax.Array, batch: Dict[str, jax.Array]) -> jax.Array:
        return losses_lib.lovasz_loss(batch["labels"], logits, "NHWC")

    def loss_per_example(
        self, logits: jax.Array, batch: Dict[str, jax.Array]
    ) -> jax.Array:
        return losses_lib.lovasz_hinge_per_image(
            jnp.squeeze(logits, -1).astype(jnp.float32),
            jnp.squeeze(batch["labels"], -1),
        )

    def metric_scores(
        self, logits: jax.Array, batch: Dict[str, jax.Array]
    ) -> Dict[str, jax.Array]:
        probs = jax.nn.sigmoid(logits)
        predicted = (probs > self.threshold).astype(jnp.float32)
        labels = batch["labels"]
        return {
            "metrics/mean_iou": metrics_lib.iou_scores(labels, predicted),
            "metrics/mean_acc": metrics_lib.mean_accuracy_scores(labels, predicted),
        }

    def predictions(self, logits: jax.Array) -> Dict[str, jax.Array]:
        probs = jax.nn.sigmoid(logits)
        return {
            "probabilities": probs,
            "mask": (probs > self.threshold).astype(jnp.float32),
        }


@dataclasses.dataclass(frozen=True)
class ClassificationTask:
    """Softmax classification objective for the ImageNet/CIFAR configs (the
    classification path the reference kept in its backbone, core/resnet.py:246-256).
    ``label_smoothing`` (train loss only — eval stays plain CE so metrics remain
    comparable across smoothing settings) is the standard ImageNet regularizer."""

    label_smoothing: float = 0.0

    name = "classification"
    # the trainer's loaders feed it; a task with a stream of its own
    # (SequenceTask) defines batches()
    batches = None

    def run_header(self) -> Dict[str, Any]:
        return {}

    def window_fields(self, examples: int, scalars, vectors, images_per_sec) -> Dict[str, Any]:
        return {}

    def loss(self, logits: jax.Array, batch: Dict[str, jax.Array]) -> jax.Array:
        if "lam" in batch:
            # mixup/cutmix pairing (data/augment.py:mixup_batch/cutmix_batch):
            # lam-weighted sum of the two per-example CE terms == CE against
            # the mixed target, without materializing soft labels. Label
            # smoothing applies to both terms (each target one-hot smooths
            # independently; the mix is linear).
            ce_a = losses_lib.softmax_cross_entropy_per_example(
                logits, batch["labels"], self.label_smoothing
            )
            ce_b = losses_lib.softmax_cross_entropy_per_example(
                logits, batch["labels_b"], self.label_smoothing
            )
            lam = batch["lam"]
            return jnp.mean(lam * ce_a + (1.0 - lam) * ce_b)
        return losses_lib.softmax_cross_entropy(
            logits, batch["labels"], self.label_smoothing
        )

    def loss_per_example(
        self, logits: jax.Array, batch: Dict[str, jax.Array]
    ) -> jax.Array:
        return losses_lib.softmax_cross_entropy_per_example(logits, batch["labels"])

    def metric_scores(
        self, logits: jax.Array, batch: Dict[str, jax.Array]
    ) -> Dict[str, jax.Array]:
        scores = {
            "metrics/top1": metrics_lib.top1_accuracy_scores(logits, batch["labels"])
        }
        # only meaningful with more than 5 classes (otherwise it would just
        # repeat top-1 under a misleading name — class count is trace-static)
        if logits.shape[-1] > 5:
            scores["metrics/top5"] = metrics_lib.topk_accuracy_scores(
                logits, batch["labels"], k=5
            )
        return scores

    def predictions(self, logits: jax.Array) -> Dict[str, jax.Array]:
        probs = jax.nn.softmax(logits, axis=-1)
        return {"probabilities": probs, "class": jnp.argmax(logits, axis=-1)}


@dataclasses.dataclass(frozen=True)
class SequenceTask:
    """Next-token prediction over packed documents (``backbone="decoder"``,
    models/decoder.py). The model takes the whole token batch (tokens,
    segment ids, positions, targets: data/tokens.py) and computes the
    cross-entropy itself, in token chunks, so the task reads sums: the loss
    is their quotient, and the step's metrics are counted in tokens.

    What differs from the image tasks beyond the objective is the task's too
    (train/fit.py asks it): its data (``batches``), what it adds to the run
    header and to each window event, and the layouts it can train under."""

    decoder: DecoderConfig
    stream: TokenStreamConfig

    name = "next_token"
    # the model runs library Pallas kernels (jax's splash attention and
    # megablox) whose out_shapes carry no vma: pallas_call refuses them inside
    # a shard_map that checks varying manual axes, so this task's steps are
    # built with check_vma=False and reduce their gradients explicitly
    check_vma = False

    def check_train_config(self, tcfg: TrainConfig) -> None:
        if max(tcfg.model_parallel, tcfg.pipeline_parallel, tcfg.expert_parallel,
               tcfg.sequence_parallel) > 1:
            raise ValueError(
                "the decoder trains data-parallel only: its share of a layer is "
                "in its configuration (DecoderConfig.share_count), and the "
                "exchange between shares is not built yet"
            )
        if tcfg.augmentation != "none":
            raise ValueError(
                "token batches are fed as packed: TrainConfig.augmentation must "
                f"be 'none' for the decoder, not {tcfg.augmentation!r}"
            )

    def run_header(self) -> Dict[str, Any]:
        """The run header's ``decoder`` block: the share of a layer this chip
        holds and the pattern of the layers kept."""
        cfg = self.decoder
        layers = range(cfg.num_hidden_layers)
        block = {
            "share": [cfg.share_index, cfg.share_count],
            "layer_types": list(cfg.layer_types[: cfg.num_hidden_layers]),
            "sequence_length": cfg.sequence_length,
        }
        # what goes by layer, where the layers differ in it
        if "dense" in cfg.mlp_layer_types[: cfg.num_hidden_layers]:
            block["mlp_layer_types"] = [cfg.mlp_type(i) for i in layers]
        if cfg.num_attention_heads_per_layer:
            block["attention_heads"] = [cfg.heads(i) for i in layers]
        return {"decoder": block}

    def batches(self, batch_size: int, seed: int, steps=None, start_index: int = 0):
        """The packed synthetic token stream (index-keyed, so a resumed run
        replays it)."""
        from tensorflowdistributedlearning_tpu.data import tokens as tokens_lib

        cfg = self.decoder
        return tokens_lib.packed_token_batches(
            batch_size, cfg.sequence_length, cfg.vocab_size, self.stream,
            seed=seed, steps=steps, start_index=start_index,
        )

    def window_fields(self, sequences: int, scalars, vectors, images_per_sec) -> Dict[str, Any]:
        """The decoder's counters of one log window of ``sequences`` sequences,
        from the step's own metrics (fetched with the loss, no extra sync):
        docs/LEDGER_SCHEMA.md "The decoder's window fields"."""
        per_expert = np.asarray(vectors["moe/expert_tokens"]) * sequences  # [layers, held]
        fields = {
            "tokens": int(round(scalars["tokens"] * sequences)),
            "moe_pairs": int(round(float(per_expert.sum()))),
            "moe_pairs_dropped": int(round(scalars["moe/pairs_dropped"] * sequences)),
            # over the held experts, the worst layer of the window
            "moe_load_max_over_mean": round(float(
                (per_expert.max(axis=1) / np.maximum(per_expert.mean(axis=1), 1e-9)).max()
            ), 4),
            "moe_expert_tokens": [[int(round(x)) for x in row] for row in per_expert],
            # the sorted pair buffer, layer by layer: its rows (a step's mean)
            # and the share of them that held pairs filled
            "moe_buffer_rows": [round(x, 1) for x in vectors["moe/buffer_rows"]],
            "moe_buffer_fill": [round(x, 4) for x in vectors["moe/buffer_fill"]],
            # row tiles the grouped products visit for the held experts'
            # groups, every sparse layer together (a step's mean)
            "moe_tile_visits": round(scalars["moe/tile_visits"], 2),
            "attn_keys_per_query": {
                kind: round(scalars[f"attn/keys_per_query_{kind.split('_')[0]}"], 2)
                for kind in sorted(set(self.decoder.layer_types[: self.decoder.num_hidden_layers]))
            },
        }
        # the head gates' mean by layer type (a gated model's alone)
        gated = {kind: round(scalars[key], 6) for kind in fields["attn_keys_per_query"]
                 if (key := f"attn/gate_mean_{kind.split('_')[0]}") in scalars}
        if gated:
            fields["attn_gate_mean"] = gated
        if "align_loss" in scalars:
            # the indexer's own loss, and a window's (query, key) pairs its
            # layers scored (the visible ones) and selected
            fields["align_loss"] = round(scalars["align_loss"], 6)
            fields["sparse_pairs_scored"] = int(round(scalars["sparse/pairs_scored"] * sequences))
            fields["sparse_pairs_selected"] = int(round(
                float(np.sum(vectors["sparse/key_reads"])) * sequences))
            # a sequence's, all sparse layers together: the key columns the
            # selections' counts ran over, summed over the queries, and the
            # blocks of rows whose tie positions were searched
            fields["sparse_select_columns"] = int(round(scalars["sparse/select_columns"]))
            fields["sparse_tie_blocks"] = round(scalars["sparse/tie_blocks"], 2)
        if images_per_sec is not None:
            fields["tokens_per_sec"] = round(images_per_sec * self.decoder.sequence_length, 2)
        return fields

    def model_input(self, batch: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return batch

    def loss(self, outputs: Dict[str, jax.Array], batch) -> jax.Array:
        # the mean over the GLOBAL batch's target positions: the shards'
        # gradients are averaged afterwards, so each divides by the mean count
        n = jax.lax.pmean(outputs["n_targets"], BATCH_AXIS)
        loss = outputs["loss_sum"] / jnp.maximum(n, 1.0)
        if "align_sum" in outputs:
            # the indexer's loss, a mean over the positions, weight 1: inside
            # the model its graph and the cross-entropy's are cut from each
            # other, so each leaf's gradient is one loss's alone
            loss = loss + outputs["align_sum"] / jax.lax.pmean(outputs["n_positions"], BATCH_AXIS)
        return loss

    def metric_deltas(self, outputs: Dict[str, jax.Array], batch) -> Metrics:
        """Mean states whose totals are the step's counters. Per target
        position: ``loss``, ``metrics/top1``. Per sequence: ``tokens`` (target
        positions), ``moe/expert_tokens`` ([layers, experts held]: tokens
        routed to each), ``moe/pairs_dropped``. Per step, by layer:
        ``moe/buffer_rows`` (rows of the sorted pair buffer); per buffer row,
        by layer: ``moe/buffer_fill`` (held pairs). Per position:
        ``attn/keys_per_query_*`` by layer type; per step ``moe/tile_visits``
        (row tiles the held groups overlap); per gate ``attn/gate_mean_*`` by
        layer type (a gated model's); and, where layers are sparse,
        ``align_loss`` (the indexer's loss; ``loss`` stays the cross-entropy);
        per sequence ``sparse/pairs_scored``, ``sparse/key_reads`` ([sparse
        layers, T]: queries that read each key position),
        ``sparse/select_columns`` and ``sparse/tie_blocks`` (what the
        selections searched, ops/sparse_attention.py)."""
        mean = metrics_lib.Mean
        targets, rows = outputs["n_targets"], outputs["n_sequences"]
        buffer_rows = outputs["buffer_rows"]
        sparse = {}
        if "align_sum" in outputs:
            sparse = {
                "align_loss": mean(outputs["align_sum"], outputs["n_positions"]),
                "sparse/pairs_scored": mean(outputs["sparse_pairs_scored"], rows),
                "sparse/key_reads": mean(outputs["sparse_key_reads"], rows),
                "sparse/select_columns": mean(outputs["sparse_select_columns"], rows),
                "sparse/tie_blocks": mean(outputs["sparse_tie_blocks"], rows),
            }
        return {
            "loss": mean(outputs["loss_sum"], targets),
            "metrics/top1": mean(outputs["n_correct"], targets),
            "tokens": mean(targets, rows),
            "moe/expert_tokens": mean(outputs["expert_tokens"], rows),
            "moe/pairs_dropped": mean(outputs["pairs_dropped"], rows),
            "moe/buffer_rows": mean(buffer_rows, jnp.ones((), jnp.float32)),
            "moe/buffer_fill": mean(jnp.sum(outputs["expert_tokens"], axis=1), buffer_rows),
            "moe/tile_visits": mean(outputs["tile_visits"], jnp.ones((), jnp.float32)),
            **{
                "attn/gate_mean_" + name[len("attn_gate_sum_"):]: mean(
                    total, outputs["attn_gate_n_" + name[len("attn_gate_sum_"):]])
                for name, total in outputs.items() if name.startswith("attn_gate_sum_")
            },
            **{
                "attn/keys_per_query_" + name[len("attn_keys_"):]: mean(keys, outputs["n_positions"])
                for name, keys in outputs.items() if name.startswith("attn_keys_")
            },
            **sparse,
        }


def fit_task(model_config, train_config: TrainConfig):
    """The task ``fit`` trains a configuration under: what the configuration
    carries decides (a decoder's own block, or a class count)."""
    if model_config.decoder is not None:
        task = SequenceTask(
            model_config.decoder, train_config.token_stream or TokenStreamConfig()
        )
        task.check_train_config(train_config)
        return task
    if model_config.num_classes is None:
        raise ValueError(
            "fit() trains classification models; model_config.num_classes is None "
            "(use train.trainer.Trainer for the segmentation task)"
        )
    return ClassificationTask(label_smoothing=train_config.label_smoothing)


def _model_input(task, batch: Dict[str, jax.Array]):
    """What the model's forward takes of a batch: the images, or what a task
    that feeds its model otherwise (SequenceTask) names."""
    pick = getattr(task, "model_input", None)
    return batch["images"] if pick is None else pick(batch)


def _task_deltas(task, outputs, batch, loss, weights=None) -> Metrics:
    """The step's metric contributions: a task's own (``metric_deltas``), or
    the per-example scores and the loss as Mean states."""
    own = getattr(task, "metric_deltas", None)
    if own is not None:
        return own(outputs, batch)
    return _metric_deltas(task.metric_scores(outputs, batch), loss, weights)


def _l2_penalty(params: Any) -> jax.Array:
    """slim-style l2: scale * sum(w^2)/2 over conv/dense kernels only (reference:
    core/resnet.py:376 attached l2_regularizer to conv weights — though the reference
    never added the collected penalty to its minimized loss; see make_train_step)."""
    leaves = jax.tree_util.tree_leaves_with_path(params)
    total = jnp.zeros((), jnp.float32)
    for path, leaf in leaves:
        if any(getattr(k, "key", None) == "kernel" for k in path):
            total = total + 0.5 * jnp.sum(jnp.square(leaf.astype(jnp.float32)))
    return total


def _metric_deltas(
    scores: Dict[str, jax.Array],
    loss: jax.Array,
    weights: Optional[jax.Array] = None,
) -> Metrics:
    """Per-step metric contributions as psum-able Mean states. The loss is tracked the
    same way the reference tracked it in eval — as a streaming mean
    (reference: model.py:401-403). ``weights`` ([B] 0/1) excludes wrap-around-padded
    eval examples; ``loss`` must then be per-example [B]."""
    out: Metrics = {
        name: metrics_lib.Mean.empty().update(s, weights) for name, s in scores.items()
    }
    out["loss"] = metrics_lib.Mean.empty().update(
        loss if loss.ndim else loss[None], weights if loss.ndim else None
    )
    return out


def _checks_vma(task) -> bool:
    """Whether a task's steps run under shard_map's varying-manual-axes
    check (every task does that does not say otherwise: SequenceTask)."""
    return getattr(task, "check_vma", True)


def _mean_grads(grads: Any, checked: bool = True) -> Any:
    """Average gradients across the batch (and sequence) mesh axes, leaf-by-leaf
    vma-aware. With the check off (``checked=False``) nothing was summed for
    us and every leaf is per-shard: a plain mean over both axes.

    Inside ``shard_map`` with varying-manual-axes checking, the gradient of a
    REPLICATED (unvarying) parameter is already psum'd by the automatic
    transposition, so the mean is ``leaf / axis_size``; a leaf that is still
    per-shard (varying on an axis) needs a real ``pmean``. The sequence axis
    matters under spatial parallelism: every sequence shard computes the same
    (gathered) loss, so the automatic psum over-counts by the axis size — the
    division below is what restores the true gradient. Axis size 1 (the
    non-spatial meshes) makes it a no-op.
    """
    from tensorflowdistributedlearning_tpu.parallel.collectives import vma_of

    if not checked:
        return jax.lax.pmean(grads, (BATCH_AXIS, SEQUENCE_AXIS))

    def mean_leaf(g):
        vma = vma_of(g)
        for axis in (BATCH_AXIS, SEQUENCE_AXIS):
            if axis in vma:
                g = jax.lax.pmean(g, axis)
            else:
                g = g / jax.lax.axis_size(axis)
        return g

    return jax.tree.map(mean_leaf, grads)


def _psum_metrics(metrics: Metrics) -> Metrics:
    """Total metric contributions across batch shards. The trailing pmean over the
    sequence axis is numerically an identity (every sequence shard computes
    identical metrics from the gathered outputs) but makes the result unvarying on
    that axis so it can leave the shard_map replicated."""

    def reduce(x):
        x = jax.lax.psum(x, BATCH_AXIS)
        return jax.lax.pmean(x, SEQUENCE_AXIS)

    return jax.tree.map(reduce, metrics)


def merge_metrics(acc: Optional[Metrics], new: Metrics) -> Metrics:
    """Host-side accumulation across steps (functional tf.metrics update_op)."""
    if acc is None:
        return new
    return {k: acc[k].merge(v) for k, v in new.items()}


def _merge_stacked_metrics(stacked: Metrics) -> Metrics:
    """Merge metric pytrees stacked on a leading axis (a scan's per-iteration
    outputs — the accumulation microbatch loop produces one) into a single
    stream by summing over that axis.

    Summation IS the K-way merge only because every leaf is a ``Mean`` state
    (``Mean.merge`` is addition of total/count). A non-additive metric leaf
    slipping into a scanned step would be silently mis-merged by a blind
    ``jnp.sum`` — fail loudly instead, naming the offender, so whoever adds
    such a metric also adds its merge path here."""
    for name, leaf in stacked.items():
        if not isinstance(leaf, metrics_lib.Mean):
            raise TypeError(
                f"stacked per-step metric {name!r} is a "
                f"{type(leaf).__name__}, not a Mean state — summing over the "
                "step axis is only a valid merge for Mean's (total, count); "
                "teach _merge_stacked_metrics this type before scanning it"
            )
    return jax.tree.map(lambda x: jnp.sum(x, axis=0), stacked)


def compute_metrics(acc: Metrics) -> Dict[str, Any]:
    """Each stream's mean: a float, or a nested list where the stream is of
    vectors (``moe/expert_tokens``) — ``split_scalars`` parts the two. Worked
    out in numpy on the host (device arrays are fetched first): a window's
    emission dispatches nothing and compiles nothing, so it neither queues
    behind the steps in flight nor puts a compile into a run's first window."""
    out = {}
    for k, v in acc.items():
        total, count = np.asarray(v.total, np.float32), np.asarray(v.count, np.float32)
        value = total / np.maximum(count, np.float32(1.0))
        out[k] = float(value) if value.ndim == 0 else value.tolist()
    return out


def split_scalars(computed: Dict[str, Any]) -> Tuple[Dict[str, float], Dict[str, list]]:
    """(the float-valued metrics, the vector-valued ones): scalar sinks
    (TensorBoard, the ledger's ``scalars``) take the first only."""
    scalars = {k: v for k, v in computed.items() if not isinstance(v, list)}
    return scalars, {k: v for k, v in computed.items() if isinstance(v, list)}


def _batch_in_specs(spatial: bool, keys: Tuple[str, ...]):
    """shard_map in_specs for a batch dict: everything sharded on the batch axis;
    under spatial (sequence) parallelism the images are additionally H-sharded
    over the sequence axis, while labels/valid stay whole per batch shard (they
    are 1-channel/scalar-sized, and the loss needs full images)."""
    if not spatial:
        return P(BATCH_AXIS)
    return {
        k: P(BATCH_AXIS, SEQUENCE_AXIS) if k == "images" else P(BATCH_AXIS)
        for k in keys
    }


def make_train_step(
    mesh: Mesh,
    task,
    *,
    weight_decay: float = 0.0,
    apply_weight_decay: bool = False,
    donate: bool = True,
    spatial: bool = False,
    accum: int = 1,
    seed: int = 0,
    auto_model: bool = False,
    weight_update_sharding: bool = False,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Metrics]]:
    """Build the jitted SPMD train step.

    Memoized on its (hashable) arguments: the reference rebuilt its graph per fold
    and per Estimator (model.py:164-172); here repeated calls — across K-fold
    iterations, Trainer instances, and tests — return the SAME jitted callable, so
    XLA compiles each (mesh, task, model, shapes) combination exactly once per
    process. jax.jit's own cache handles different models/shapes arriving through
    the returned callable (the model rides in as ``state.apply_fn``, a static
    pytree field; ``build_model`` is memoized so equal configs share one module
    instance and therefore one ``apply`` bound method).

    ``apply_weight_decay`` exists because the reference *declared* an l2 regularizer on
    every conv but minimized only the Lovász loss (reference: model.py:462-467 — the
    REGULARIZATION_LOSSES collection was never added). Default False reproduces the
    effective reference objective; True applies the declared one.

    ``spatial=True`` expects a model built with ``spatial_axis_name=SEQUENCE_AXIS``
    and a batch whose images are sharded (batch, sequence) — see
    ``mesh.shard_batch_spatial``. The model's forward runs H-sharded over the
    sequence mesh axis with halo exchanges; outputs are gathered inside the model,
    so loss/metrics math below is unchanged.

    ``accum > 1`` splits each shard's batch into that many equal microbatches,
    runs them sequentially under ``lax.scan`` (one microbatch's activation
    memory), and applies ONE optimizer update on the mean gradient — the
    effective global batch is ``accum`` times what the loop feeds, with the lr
    schedule advancing per update. BN statistics flow microbatch-to-microbatch
    sequentially, then average across shards as usual.

    ``seed`` roots the dropout PRNG stream (TrainConfig.seed in the drivers):
    runs configured with different seeds draw different dropout masks while the
    (step, shard, chunk) fold-in structure — which the cross-strategy parity
    tests rely on — is unchanged.

    ``auto_model=True`` runs the shard_map MANUAL over (batch, sequence) only,
    leaving the ``model`` mesh axis to XLA's SPMD partitioner (shard_map's
    hybrid ``axis_names`` mode). This composes the two execution strategies
    that otherwise exclude each other: the halo-exchange spatial convs need
    manual sequence-axis collectives, while GSPMD tensor parallelism
    (parallel/tensor.py — params channel-sharded over ``model``) needs the
    partitioner to derive its all-reduces. Pass state through
    ``shard_state_tensor_parallel`` and GSPMD partitions the channel math
    inside each manual shard — the dp x tp x sp layout real pods run.

    ``weight_update_sharding=True`` is the ZeRO-1 mode (arXiv:2004.13336,
    parallel/zero.py): the forward/backward still runs under the manual
    shard_map (per-tower BN, explicit collectives — semantics unchanged), but
    the shard_map returns (grads, batch_stats, metrics) and the OPTIMIZER
    UPDATE moves outside it, under GSPMD sharding constraints that keep every
    optimizer-state leaf sharded along the ``batch`` mesh axis on its largest
    divisible dimension. Each chip then stores and updates 1/dp of the
    Adam/LARS/EMA slots; the parameter all-gather falls out of constraining
    the updated params back to replicated. Pass state placed with
    ``parallel.zero.shard_state_weight_update``. Composes with ``accum``,
    ``spatial`` and ``auto_model`` tensor parallelism
    (slots shard over (model, batch) jointly).
    """
    return _make_train_step_cached(
        mesh, task, weight_decay, apply_weight_decay, donate, spatial, accum,
        seed, auto_model, weight_update_sharding,
    )


@functools.lru_cache(maxsize=None)
def _make_train_step_cached(
    mesh: Mesh,
    task,
    weight_decay: float,
    apply_weight_decay: bool,
    donate: bool,
    spatial: bool,
    accum: int = 1,
    seed: int = 0,
    auto_model: bool = False,
    weight_update_sharding: bool = False,
):
    def forward_backward(state: TrainState, batch: Dict[str, jax.Array]):
        """Per-shard forward/backward inside the manual region: returns the
        globally-meaned grads, the replicated new BN stats, and the psum'd
        metric deltas — everything the optimizer update needs, with the
        update itself left to the caller (inside the shard_map for the
        replicated update, outside under GSPMD for ZeRO-1)."""
        # Deterministic per-(step, batch-shard) dropout stream for the models
        # that have a stochastic layer (Xception41's pre-logits dropout — the
        # reference declared keep_prob but never used it; here it is live, so
        # train-mode apply needs a PRNG). Folding in the batch index gives each
        # tower its own masks; the sequence/spatial axis is deliberately NOT
        # folded in — spatially-sharded towers compute the same replicated
        # post-pool activations and must agree on one mask. Models without
        # dropout simply never draw from the stream.
        dropout_rng = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(seed), state.step),
            jax.lax.axis_index(BATCH_AXIS),
        )

        def grads_of(batch_stats, chunk, chunk_idx):
            """value_and_grad of one microbatch against the CURRENT params,
            threading BN state in (not closed over) so scan can carry it."""

            def loss_fn(params):
                outputs, mutated = state.apply_fn(
                    {"params": params, "batch_stats": batch_stats},
                    _model_input(task, chunk),
                    train=True,
                    mutable=["batch_stats", "aux_loss"],
                    rngs={"dropout": jax.random.fold_in(dropout_rng, chunk_idx)},
                )
                with scopes.scope("loss"):
                    loss = task.loss(outputs, chunk)
                # auxiliary losses sown by the model (MoE load balancing,
                # models/vit.py:MoEMlp) join the training objective; the
                # collection is empty for every non-MoE model
                for aux in jax.tree.leaves(mutated.get("aux_loss", {})):
                    loss = loss + aux
                if apply_weight_decay and weight_decay:
                    loss = loss + weight_decay * _l2_penalty(params)
                # BN-free models mutate nothing; keep the (empty) pytree structure
                new_stats = mutated.get("batch_stats", batch_stats)
                return loss, (outputs, new_stats)

            return jax.value_and_grad(loss_fn, has_aux=True)(state.params)

        if accum == 1:
            (loss, (outputs, new_batch_stats)), grads = grads_of(
                state.batch_stats, batch, 0
            )
            metrics = _task_deltas(task, outputs, batch, loss)
        else:
            local = jax.tree.leaves(batch)[0].shape[0]
            if local % accum:
                raise ValueError(
                    f"grad accumulation needs the per-shard batch ({local}) "
                    f"divisible by grad_accum_steps ({accum})"
                )
            chunks = jax.tree.map(
                lambda x: x.reshape((accum, local // accum) + x.shape[1:]), batch
            )
            # scan carries must keep a stable varying-axes type: BN stats start
            # unvarying (replicated) but each microbatch's updated stats are
            # batch-shard varying — pre-varying the initial carry keeps the
            # types fixed across iterations.
            def pvary_leaf(x):
                return jax.lax.pcast(
                    x, (BATCH_AXIS, SEQUENCE_AXIS), to="varying"
                )

            def body(carry, chunk_with_idx):
                chunk, chunk_idx = chunk_with_idx
                stats, grads_acc = carry
                (loss, (outputs, new_stats)), grads = grads_of(
                    stats, chunk, chunk_idx
                )
                grads_acc = jax.tree.map(
                    lambda a, g: a + g / accum, grads_acc, grads
                )
                deltas = _task_deltas(task, outputs, chunk, loss)
                return (new_stats, grads_acc), deltas

            # unfreeze so the carry's pytree TYPE matches what flax's mutable
            # apply returns (plain dict), keeping scan's carry structure stable
            from flax.core import unfreeze

            init = (
                jax.tree.map(pvary_leaf, unfreeze(state.batch_stats)),
                # grads of replicated params arrive cross-shard psum'd, i.e.
                # unvarying — the accumulator stays unvarying to match
                jax.tree.map(jnp.zeros_like, state.params),
            )
            (new_batch_stats, grads), stacked = jax.lax.scan(
                body, init, (chunks, jnp.arange(accum))
            )
            # stacked Mean states carry a leading [accum] dim on total/count
            metrics = _merge_stacked_metrics(stacked)

        # MirroredStrategy's gradient MEAN across towers. Under shard_map's
        # varying-manual-axes tracking, autodiff of replicated params already
        # inserts the cross-shard psum (the cotangent of an unvarying input must
        # be unvarying), so grads arrive as the SUM of per-shard local-mean
        # grads; _mean_grads turns that into the global mean — and still works
        # if a grad leaf arrives per-shard (varying), where an explicit pmean is
        # the right reduction.
        grads = _mean_grads(grads, _checks_vma(task))
        # per-shard (per-tower) BN stats, averaged to keep state replicated (the
        # sequence pmean is an identity when BN already syncs over that axis, and
        # required either way so the stored stats leave the shard_map unvarying)
        new_batch_stats = jax.lax.pmean(new_batch_stats, BATCH_AXIS)
        new_batch_stats = jax.lax.pmean(new_batch_stats, SEQUENCE_AXIS)
        return grads, new_batch_stats, _psum_metrics(metrics)

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        grads, new_batch_stats, metrics = forward_backward(state, batch)
        return state.apply_gradients(grads, new_batch_stats), metrics

    # hybrid mode: only (batch, sequence) are manual axes; the model axis is
    # left to the SPMD partitioner, so channel-sharded params (GSPMD tensor
    # parallelism) keep their sharding through the specs below, which describe
    # manual axes only
    batch_specs = _batch_in_specs(spatial, ("images", "labels"))
    if not weight_update_sharding:
        sharded = jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(P(), batch_specs),
            out_specs=(P(), P()),
            **_hybrid_kwargs(auto_model, task),
        )
        return scopes.Program(jax.jit(sharded, donate_argnums=(0,) if donate else ()))

    # ZeRO-1: the manual region ends at (grads, stats, metrics) — all
    # unvarying, so they leave replicated — and the optimizer update runs in
    # the enclosing jit under GSPMD constraints that shard every slot (and
    # its 1/dp of the update math) along the batch axis. opt_state never
    # enters the shard_map (the gradient computation does not read it), so
    # its data-axis sharding is invisible to the manual region.
    from tensorflowdistributedlearning_tpu.parallel import zero as zero_lib

    sharded_grads = jax.shard_map(
        forward_backward,
        mesh=mesh,
        in_specs=(P(), batch_specs),
        out_specs=(P(), P(), P()),
        **_hybrid_kwargs(auto_model, task),
    )

    def zero_step(state: TrainState, batch: Dict[str, jax.Array]):
        grads, new_batch_stats, metrics = sharded_grads(
            state.replace(opt_state=None), batch
        )
        new_state = zero_lib.apply_gradients_sharded(
            state, grads, new_batch_stats, mesh, tensor_parallel=auto_model
        )
        return new_state, metrics

    return scopes.Program(jax.jit(zero_step, donate_argnums=(0,) if donate else ()))


def make_eval_step(
    mesh: Mesh,
    task,
    *,
    spatial: bool = False,
    with_valid: bool = True,
    auto_model: bool = False,
) -> Callable[[TrainState, Dict[str, jax.Array]], Metrics]:
    """Jitted SPMD eval step: forward in inference mode (BN running stats), streaming
    metric deltas (the reference's EVAL branch, model.py:391-403). Memoized — see
    ``make_train_step``; ``auto_model`` is the same hybrid mode (model axis left
    to GSPMD for channel-sharded params)."""
    return _make_eval_step_cached(mesh, task, spatial, with_valid, auto_model)


def _hybrid_kwargs(auto_model: bool, task=None) -> dict:
    """shard_map kwargs for hybrid mode: (batch, sequence) manual, model auto
    (see make_train_step's ``auto_model``); and the vma check off for a task
    that asks (``_checks_vma``)."""
    kwargs = {} if task is None or _checks_vma(task) else {"check_vma": False}
    if auto_model:
        kwargs["axis_names"] = frozenset({BATCH_AXIS, SEQUENCE_AXIS})
    return kwargs


@functools.lru_cache(maxsize=None)
def _make_eval_step_cached(
    mesh: Mesh, task, spatial: bool, with_valid: bool, auto_model: bool = False
):
    def step(state: TrainState, batch: Dict[str, jax.Array]) -> Metrics:
        outputs = state.apply_fn(
            {"params": state.params, "batch_stats": state.batch_stats},
            _model_input(task, batch),
            train=False,
        )
        if hasattr(task, "metric_deltas"):
            return _psum_metrics(task.metric_deltas(outputs, batch))
        # per-example losses so the optional batch["valid"] mask (wrap-around padding
        # of the final eval batch — data/pipeline.py eval_batches) weights correctly
        loss = task.loss_per_example(outputs, batch)
        weights = batch.get("valid")
        return _psum_metrics(
            _metric_deltas(task.metric_scores(outputs, batch), loss, weights)
        )

    keys = ("images", "labels", "valid") if with_valid else ("images", "labels")
    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), _batch_in_specs(spatial, keys)),
        out_specs=P(),
        **_hybrid_kwargs(auto_model, task),
    )
    return jax.jit(sharded)


def make_predict_step(
    mesh: Mesh, task, *, spatial: bool = False, auto_model: bool = False
) -> Callable[[TrainState, Dict[str, jax.Array]], Dict[str, jax.Array]]:
    """Jitted SPMD predict step (the reference's PREDICT branch, model.py:371-387);
    outputs stay sharded on the batch axis. Memoized — see ``make_train_step``;
    ``auto_model`` is the same hybrid mode."""
    return _make_predict_step_cached(mesh, task, spatial, auto_model)


@functools.lru_cache(maxsize=None)
def _make_predict_step_cached(
    mesh: Mesh, task, spatial: bool, auto_model: bool = False
):
    def step(state: TrainState, batch: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        outputs = state.apply_fn(
            {"params": state.params, "batch_stats": state.batch_stats},
            batch["images"],
            train=False,
        )
        preds = task.predictions(outputs)
        if spatial:
            # every sequence shard holds the full gathered prediction; reduce to
            # clear the sequence-varying type (numerically an identity)
            preds = jax.tree.map(
                lambda v: jax.lax.pmax(v, SEQUENCE_AXIS)
                if jnp.issubdtype(v.dtype, jnp.integer)
                else jax.lax.pmean(v, SEQUENCE_AXIS),
                preds,
            )
        return preds

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), _batch_in_specs(spatial, ("images",))),
        out_specs=P(BATCH_AXIS),
        **_hybrid_kwargs(auto_model),
    )
    return jax.jit(sharded)
