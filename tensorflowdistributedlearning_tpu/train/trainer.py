"""K-fold trainer orchestration — the reference's ``Model`` class, TPU-native.

API parity with ``Model(model_dir, data_directory, ...)`` / ``.train(X, y, batch_size,
steps)`` / ``.predict(test_dir, batch_size, tta)`` / ``.params`` (reference:
model.py:27-512), redesigned around one jitted SPMD step per phase instead of
per-fold Estimators:

- folds are JSON index manifests, not symlink trees (data/folds.py; reference:
  preprocessing/preprocessing.py:33-88);
- the train/eval alternation of ``tf.estimator.train_and_evaluate`` (reference:
  model.py:219-223) becomes an explicit loop: train N steps → periodic checkpoint
  (every ``checkpoint_every_steps``, reference: model.py:118) → throttled eval
  (>= ``eval_throttle_secs`` apart, reference: model.py:214) → best-k export keyed on
  ``metrics/mean_iou`` with the comparison the right way around (reference:
  model.py:196-204, utils.py:23-28 — SURVEY §2.4.4);
- auto-resume per fold directory reproduces the Estimator restart contract
  (reference: model.py:164-167);
- TTA predict averages the fold x transform ensemble — finishing what the reference
  left TODO (reference: model.py:229, 255) — and fixes the inverted ``tti`` flag
  (reference: model.py:240-243, SURVEY §2.4.3);
- summaries go to ``fold{i}/train`` and ``fold{i}/eval`` event files with the
  reference's tag layout (reference: model.py:400, 447-448, 470-481).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tensorflowdistributedlearning_tpu import config as config_lib
from tensorflowdistributedlearning_tpu import obs as obs_lib
from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.data import augment as augment_lib
from tensorflowdistributedlearning_tpu.data import folds as folds_lib
from tensorflowdistributedlearning_tpu.data import pipeline as pipeline_lib
from tensorflowdistributedlearning_tpu.models import build_model
from tensorflowdistributedlearning_tpu.parallel import mesh as mesh_lib
from tensorflowdistributedlearning_tpu.parallel import multihost
from tensorflowdistributedlearning_tpu.train import async_loop
from tensorflowdistributedlearning_tpu.train import loop as loop_lib
from tensorflowdistributedlearning_tpu.train import step as step_lib
from tensorflowdistributedlearning_tpu.train.checkpoint import CheckpointManager
from tensorflowdistributedlearning_tpu.train.state import TrainState, create_train_state
from tensorflowdistributedlearning_tpu.utils.params import count_params
from tensorflowdistributedlearning_tpu.utils.summary import SummaryWriter

logger = logging.getLogger(__name__)

_MODEL_FIELDS = {f.name for f in dataclasses.fields(ModelConfig)}


@functools.lru_cache(maxsize=None)
def _prepare_train_cached(cfg: augment_lib.AugmentConfig):
    """One compiled augmentation executable per AugmentConfig (shared across folds
    and Trainer instances — the per-fold randomness rides in through the key)."""

    @jax.jit
    def prepare(base_key, step, batch):
        key = jax.random.fold_in(base_key, step)
        return augment_lib.augment_batch(key, batch["images"], batch["masks"], cfg)

    return prepare


@functools.lru_cache(maxsize=None)
def _prepare_eval_cached():
    @jax.jit
    def prepare(batch):
        out = augment_lib.prepare_eval_batch(batch["images"], batch["masks"])
        if "valid" in batch:
            out["valid"] = batch["valid"]
        return out

    return prepare


@functools.lru_cache(maxsize=None)
def _forward_cached(model):
    """Single-device inference forward, one executable per model architecture
    (build_model returns a shared instance per config, so this caches across
    Trainer instances)."""

    @jax.jit
    def forward(state, images):
        return model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images,
            train=False,
        )

    return forward


class Trainer:
    """K-fold cross-validated SPMD trainer for the segmentation task.

    ``**kwargs`` accepts every ``ModelConfig`` field, reproducing the reference's
    kwargs plumbing (reference: model.py:63-106) with typo-safety: unknown keys raise
    instead of being silently dropped.
    """

    def __init__(
        self,
        model_dir: str,
        data_directory: str,
        data_format: str = "NHWC",
        lr: float = 0.001,
        n_devices: Optional[int] = None,
        n_fold: int = 5,
        seed: int = 42,
        save_best: int = 5,
        train_config: Optional[TrainConfig] = None,
        augment_config: Optional[augment_lib.AugmentConfig] = None,
        plan: Optional[Dict] = None,
        **kwargs,
    ):
        unknown = set(kwargs) - _MODEL_FIELDS
        if unknown:
            raise ValueError(f"Unknown model config keys: {sorted(unknown)}")
        self.model_dir = model_dir
        self.data_directory = data_directory
        self.model_config = ModelConfig(**kwargs)
        self.train_config = train_config or TrainConfig(
            data_format=data_format,
            lr=lr,
            n_devices=n_devices,
            n_folds=n_fold,
            seed=seed,
            save_best=save_best,
        )
        # reference default: the trainer passed crop_probability=0 (model.py:316)
        self.augment_config = augment_config or augment_lib.AugmentConfig(
            crop_probability=0.0
        )
        # before the first compile (fold state init): a restarted run loads
        # its executables from the cache instead of rebuilding. The CLI has
        # resolved the same directory already; this is the library caller's
        # entry point (utils/compile_cache.py decides where the cache goes)
        from tensorflowdistributedlearning_tpu.utils import compile_cache

        compile_cache.configure(self.train_config.compile_cache_dir)
        if self.train_config.parallelism == "auto" and plan is None:
            # same contract as ClassifierTrainer: the mesh is built below
            # from the explicit degrees, so 'auto' must be resolved (and its
            # plan handed in) before the trainer exists — the `train` CLI
            # does this; programmatic callers use parallel.planner.plan()
            raise ValueError(
                "parallelism='auto' must be resolved before constructing "
                "Trainer: plan the layout first (the train CLI does this "
                "automatically; programmatically, call parallel.planner."
                "plan(model_config, train_config, global_batch), apply "
                "plan.overrides() onto the config, and pass "
                "plan=plan.header())"
            )
        self.task = step_lib.SegmentationTask()
        tcfg = self.train_config
        # model_parallel > 1: tensor parallelism via shard_map's hybrid
        # ``axis_names`` mode — params/optimizer channel-sharded over the
        # model axis (parallel/tensor.py) while the step stays manual over
        # (batch, sequence), so GSPMD derives the tensor-parallel reductions
        # inside the K-fold segmentation loop's own step
        # (make_train_step(auto_model=True)). TrainConfig keeps tp and sp
        # mutually exclusive at the config level (fit()'s whole-step GSPMD tp
        # cannot compose with sp); the library-level 3-axis composition is
        # proven in tests/test_tensor_parallel.py + tests/test_multiprocess.py.
        self._tp = tcfg.model_parallel > 1
        self.mesh = mesh_lib.make_mesh(
            tcfg.n_devices,
            model_parallel=tcfg.model_parallel,
            sequence_parallel=tcfg.sequence_parallel,
        )
        # sequence_parallel > 1: H-sharded backbone with halo-exchange convs and
        # sequence-synced BN (parallel/spatial.py; a TPU-first capability — the
        # reference was data-parallel only, model.py:115-116)
        from tensorflowdistributedlearning_tpu.parallel.spatial import (
            validate_spatial_config,
        )

        validate_spatial_config(self.model_config, tcfg.sequence_parallel)
        self._spatial = tcfg.sequence_parallel > 1
        axis = mesh_lib.SEQUENCE_AXIS if self._spatial else None
        # sync_batch_norm: BN statistics span the batch mesh axis too —
        # cross-replica BN (semantics and evidence: config.py's field
        # comment)
        bn_axis = axis
        if tcfg.sync_batch_norm:
            bn_axis = (
                (mesh_lib.BATCH_AXIS, axis) if axis else mesh_lib.BATCH_AXIS
            )
        self._sync_bn = tcfg.sync_batch_norm
        self.model = build_model(
            self.model_config, bn_axis_name=bn_axis, spatial_axis_name=axis
        )
        self._n_params: Optional[int] = None
        # the parallelism plan this run trains under (planner header dict):
        # handed in by the CLI's --parallelism auto path, else derived
        # best-effort at train() time for the run-header ledger event
        self._plan = plan
        # train() swaps in a live Telemetry; the null instance keeps predict/
        # serving (which reuse _evaluate-adjacent paths) span-safe
        self._telemetry = obs_lib.NULL_TELEMETRY
        os.makedirs(model_dir, exist_ok=True)

    # -- state ------------------------------------------------------------

    @property
    def params(self) -> int:
        """Total trainable parameter count; available once a state has been built
        (the reference computed it inside model_fn and raised before first train,
        reference: model.py:444-445, 507-512)."""
        if self._n_params is None:
            raise AttributeError(
                "Parameter count unknown — train() or predict() must build the model "
                "first"
            )
        return self._n_params

    def _fold_dir(self, fold: int) -> str:
        return os.path.join(self.model_dir, f"fold{fold}")

    @property
    def _plain_model(self):
        """Unsharded twin of ``self.model`` (identical param tree — SpatialConv is
        nn.Conv-compatible): used for init and host-side single-device forwards,
        which cannot run the spatial collectives outside shard_map."""
        if not hasattr(self, "_plain_model_cache"):
            self._plain_model_cache = (
                build_model(self.model_config)
                if (self._spatial or self._sync_bn)
                else self.model
            )
        return self._plain_model_cache

    def _init_state(self) -> TrainState:
        cfg, tcfg = self.model_config, self.train_config
        tx = step_lib.make_optimizer(tcfg)
        h, w = cfg.input_shape
        sample = np.zeros((1, h, w, cfg.input_channels), np.float32)
        state = create_train_state(
            self._plain_model, tx, jax.random.PRNGKey(tcfg.seed), sample
        )
        if self._spatial or self._sync_bn:
            # state.apply_fn must be the axis-named model (halo-exchange
            # convs / sync-BN pmean), not the plain init twin
            state = state.replace(apply_fn=self.model.apply)
        self._n_params = count_params(state.params)
        if tcfg.weight_update_sharding:
            from tensorflowdistributedlearning_tpu.parallel import zero as zero_lib

            # opt_state 1/dp over the data axis; params/batch_stats keep
            # their canonical layout (channel-sharded under TP, where the
            # optimizer leaves shard over (model, batch) jointly and the
            # hybrid auto-model step constrains params back each step)
            return zero_lib.shard_state_weight_update(
                state, self.mesh, tensor_parallel=self._tp
            )
        if self._tp:
            from tensorflowdistributedlearning_tpu.parallel import tensor as tp_lib

            return tp_lib.shard_state_tensor_parallel(state, self.mesh)
        return mesh_lib.replicate(state, self.mesh)

    def _checkpointer(self, fold: int) -> CheckpointManager:
        tcfg = self.train_config
        return CheckpointManager(
            self._fold_dir(fold),
            save_every_steps=tcfg.checkpoint_every_steps,
            save_best=tcfg.save_best,
            async_checkpointing=tcfg.async_checkpointing,
            # live during train(), the null instance on predict/serving —
            # checkpoint_retry/checkpoint_corrupt events reach the run ledger
            telemetry=self._telemetry,
        )

    # -- training ---------------------------------------------------------

    def train(
        self,
        X: Sequence[str],
        y: Optional[Sequence[int]] = None,
        batch_size: int = 64,
        steps: int = 10_000,
    ) -> List[Dict[str, float]]:
        """Train every fold; returns each fold's final eval metrics.

        ``X``: example ids under ``{data_directory}/images``; ``y``: stratification
        classes (computed from mask coverage when omitted — the notebooks'
        ``cov_to_class``, Untitled.ipynb cell 4). ``batch_size`` is global and must
        divide the data-parallel degree (reference: model.py:156-159).
        """
        tcfg = self.train_config
        config_lib.validate_training_data_format(tcfg)
        mesh_lib.check_accum_divisibility(
            batch_size, self.mesh, tcfg.grad_accum_steps
        )
        # one ledger for the whole K-fold run; events carry their fold
        with loop_lib.telemetry_run(
            self, steps, batch_size, run_info={"n_folds": tcfg.n_folds}
        ) as tel:
            with tel.span("startup/load_dataset"):
                dataset = pipeline_lib.InMemoryDataset.from_directory(
                    self.data_directory, ids=list(X)
                )
                if y is None:
                    y = folds_lib.coverage_to_class(
                        pipeline_lib.mask_coverage(dataset.masks)
                    )
            with tel.span("startup/folds"):
                manifests = folds_lib.write_fold_manifests(
                    self.model_dir, list(X), list(np.asarray(y)), tcfg.n_folds,
                    tcfg.seed,
                )
            # the CLI's --parallelism auto resolves its plan BEFORE this
            # trainer exists; otherwise the header describes the explicit one
            loop_lib.finish_header(self, batch_size)
            results = []
            for fold, manifest in enumerate(manifests):
                logger.info("Processing fold %d", fold)  # reference: model.py:162
                results.append(
                    self._train_fold(fold, dataset, manifest, batch_size, steps)
                )
                logger.info("Finished training fold %d", fold)  # reference: model.py:225
            tel.close(
                folds=len(results),
                final_metrics={
                    k: float(v) for k, v in (results[-1] if results else {}).items()
                },
            )
            return results

    def _train_fold(
        self,
        fold: int,
        dataset: pipeline_lib.InMemoryDataset,
        manifest: Dict[str, List[str]],
        batch_size: int,
        steps: int,
    ) -> Dict[str, float]:
        """One fold's start-up phases and input stream, then the loop
        (train/loop.py)."""
        tcfg = self.train_config
        tel = self._telemetry
        tel.fold = fold
        # one telemetry (and one HealthMonitor) spans all K folds, but loss
        # history and step-time baselines are per-FOLD facts: a converged
        # fold's low-loss median would flag the next fold's fresh untrained
        # loss as a spike
        if tel.health is not None:
            tel.health.reset()
        # the fold's start-up phases lie back to back, so that no second
        # before the first step is unnamed: `init_state` holds the fold's data
        # selection and its checkpointer too, `restore` the wait for the
        # restored step number (the first value the host needs off the device)
        with tel.span("startup/init_state"):
            # per-process data: each host loads only its round-robin shard of
            # the fold and draws batch/P examples per step; global_shard_batch
            # assembles them into one globally-sharded batch (the per-host
            # generalization of the reference's per-tower batch/n_gpus
            # contract, model.py:156-159)
            local_bs = multihost.per_process_batch_size(batch_size)
            train_ds = dataset.select(pipeline_lib.host_shard(manifest["train"]))
            eval_ds = dataset.select(pipeline_lib.host_shard(manifest["eval"]))
            eval_global_n = len(manifest["eval"])
            ckpt = self._checkpointer(fold)
            state = self._init_state()
        with tel.span("startup/restore"):
            state = ckpt.restore_latest(state)
            loop_lib.record_footprint(self, state, batch_size)
            start_step = int(jax.device_get(state.step))
        if start_step >= steps:
            logger.info("fold %d already trained to step %d", fold, start_step)
            ckpt.close()
            return self._evaluate(
                state, eval_ds, batch_size, fold, writer=None,
                global_n=eval_global_n, step_no=start_step,
            )
        if start_step > 0:
            # resume verification: training actually CONTINUES from a prior
            # checkpoint (an already-trained fold rerun above is not a resume);
            # telemetry-report lines restarts up with the recovered progress
            tel.event("resumed", step=start_step, fold=fold)

        with tel.span("startup/build_step"):
            train_step = step_lib.make_train_step(
                self.mesh,
                self.task,
                weight_decay=self.model_config.weight_decay,
                spatial=self._spatial,
                accum=self.train_config.grad_accum_steps,
                seed=self.train_config.seed,
                auto_model=self._tp,
                weight_update_sharding=tcfg.weight_update_sharding,
            )
            prepare = self._make_prepare_train(fold)

            is_main = jax.process_index() == 0
            tb_train = SummaryWriter(os.path.join(self._fold_dir(fold), "train")) if is_main else None
            tb_eval = SummaryWriter(os.path.join(self._fold_dir(fold), "eval")) if is_main else None
            # the gauges are drained per log window; a run that never writes
            # windows (telemetry off, or a non-main host with no TB writer)
            # must not record into them — the samples would accumulate for the
            # life of the run with nothing reading them
            registry = tel.registry if tel.enabled and is_main else None

            data_service = None
            if tcfg.data_service_workers > 0:
                # streaming data service over the in-memory fold (data/service.py
                # ArrayBatchSource): batch assembly moves off the host loop onto
                # N workers, and the stream is INDEX-KEYED — batch i is a pure
                # function of (seed+fold, i), so a resumed fold replays the exact
                # remaining stream instead of approximating it by folding the
                # resume step into the seed
                from tensorflowdistributedlearning_tpu.data import (
                    service as service_lib,
                )

                data_service = service_lib.StreamingDataService(
                    service_lib.ArrayBatchSource(
                        {"images": train_ds.images, "masks": train_ds.masks},
                        # the fold arrays were host-sharded for THIS world size:
                        # stamping it into the resume sidecar makes a resume that
                        # crossed a world resize an explicit, ledgered re-deal
                        # (the per-host rows change meaning) instead of a silent
                        # re-index — the same resize-aware contract as fit()'s
                        # record path
                        process_count=jax.process_count(),
                    ),
                    batch_size=local_bs,
                    seed=tcfg.seed + fold,
                    workers=tcfg.data_service_workers,
                    start_batch=start_step,
                    registry=registry,
                    resume_state=(
                        ckpt.restore_data_state(start_step)
                        if start_step > 0 else None
                    ),
                )
                if data_service.redeal is not None:
                    tel.event(
                        "data_redeal", step=start_step, fold=fold,
                        **data_service.redeal,
                    )
                batches = data_service.batches(steps=steps - start_step)
            else:
                batches = pipeline_lib.train_batches(
                    train_ds,
                    local_bs,
                    # fold the resume point into the shuffle seed so a resumed
                    # run does not replay the same shuffled order from the
                    # beginning (see ClassifierTrainer._train_stream)
                    seed=tcfg.seed + fold + 7919 * start_step,
                    steps=steps - start_step,
                )
            batches = pipeline_lib.device_prefetch(
                batches,
                lambda b: multihost.global_shard_batch(
                    b, self.mesh, spatial=self._spatial
                ),
                depth=tcfg.prefetch_depth,
                registry=registry,
            )
        last_eval_time = 0.0

        def evaluate(state: TrainState, step_no: int) -> Dict[str, float]:
            nonlocal last_eval_time
            last_eval_time = time.time()
            return self._evaluate(
                state, eval_ds, batch_size, fold, writer=tb_eval,
                global_n=eval_global_n, step_no=step_no,
            )

        def eval_due(step_no: int, saved: bool) -> bool:
            # an explicit eval_every_steps knob decouples eval from
            # checkpointing AND bypasses the time throttle (explicit user
            # intent, same semantics as fit()); the default preserves the
            # reference's train_and_evaluate shape — eval when a checkpoint
            # lands and the >=eval_throttle_secs window passed (reference:
            # model.py:214)
            if tcfg.eval_every_steps:
                return step_no % tcfg.eval_every_steps == 0
            return saved and time.time() - last_eval_time >= tcfg.eval_throttle_secs

        _, _, final_metrics = loop_lib.train_loop(
            tel,
            tcfg,
            self.task,
            state=state,
            start_step=start_step,
            batch_size=batch_size,
            batches=batches,
            prepare=prepare,
            train_step=train_step,
            ckpt=ckpt,
            evaluate=evaluate,
            eval_due=eval_due,
            tb_train=tb_train,
            data_service=data_service,
            # train-phase image grids every train_log_every_steps — the
            # reference's SummarySaverHook wrote input/label/probability/
            # prediction to fold{i}/train every 20 steps (model.py:470-481);
            # one extra inference-mode forward per log interval, and only
            # where the batches are fully addressable
            after_window=(
                functools.partial(self._write_image_summaries, tb_train)
                if jax.process_count() == 1 else None
            ),
            event_fields={"fold": fold},
        )
        if tb_train is not None:
            tb_train.close()
        if tb_eval is not None:
            tb_eval.close()
        ckpt.close()
        return final_metrics

    def _make_prepare_train(self, fold: int):
        """Jitted on-device augmentation: {'images','masks'} -> {'images','labels'}
        with the Laplacian channel (the reference's augmenting input_fn map,
        model.py:315-317, run on TPU instead of the host: ``augment_batch``, whose
        warp is matrix products over the kept pixels). The fold's base PRNG key
        is a traced argument, so every fold (and every Trainer with the same
        augment config) shares ONE compiled executable."""
        base_key = jax.random.PRNGKey(self.train_config.seed + fold)
        prepare = _prepare_train_cached(self.augment_config)

        def bound(step: jax.Array, batch: Dict[str, jax.Array]):
            return prepare(base_key, step, batch)

        return bound

    def _evaluate(
        self,
        state: TrainState,
        eval_ds: pipeline_lib.InMemoryDataset,
        batch_size: int,
        fold: int,
        writer: Optional[SummaryWriter],
        global_n: Optional[int] = None,
        step_no: Optional[int] = None,
    ) -> Dict[str, float]:
        """One full eval pass with streaming metrics (the EVAL branch + SummarySaverHook,
        reference: model.py:391-403, 475-481). Runs at the caller's ``batch_size``
        (the reference used 2x the train batch, model.py:207-211 — here the wrap-around
        padding makes eval batch size a pure throughput knob, so it is not doubled).

        ``eval_ds`` is this process's host shard; ``global_n`` (the fold's total eval
        size) pins the step count so every process runs the same number of
        collective-bearing steps. The metric accumulator stays DEVICE-RESIDENT
        (train/async_loop.py): one host transfer per pass regardless of batch
        count. ``step_no`` is the host-known step (None = fetch ``state.step``
        — direct callers only)."""
        mesh_lib.local_batch_size(batch_size, self.mesh)  # fail fast, clear message
        # evaluate the EMA view when one is tracked (TrainConfig.ema_decay>0),
        # then drop the optimizer state: eval reads params/batch_stats only,
        # and under weight_update_sharding the data-axis-sharded moments would
        # otherwise be all-gathered into the eval executable for nothing
        state = step_lib.with_ema_params(state).replace(opt_state=None)
        local_bs = multihost.per_process_batch_size(batch_size)
        num = multihost.eval_num_batches(
            global_n if global_n is not None else len(eval_ds), local_bs
        )
        tel = self._telemetry
        t0 = time.perf_counter()
        with tel.span(obs_lib.SPAN_EVAL):
            eval_step = self._eval_step
            prepare = self._prepare_eval
            # in-flight bound: without it, device-resident accumulation would
            # let the host enqueue EVERY eval batch's copy+step at once
            budget = async_loop.eval_budget(
                tel, self.train_config.dispatch_ahead_steps
            )
            acc = None
            first_batch = None
            for raw in pipeline_lib.eval_batches(eval_ds, local_bs, num_batches=num):
                sharded = multihost.global_shard_batch(
                    raw, self.mesh, spatial=self._spatial
                )
                batch = prepare(sharded)
                metrics = eval_step(state, batch)
                acc = async_loop.merge_metrics_device(acc, metrics)
                budget.track(acc)
                if first_batch is None:
                    first_batch = batch
            result = async_loop.fetch_metrics(acc, telemetry=tel)
        if step_no is None:
            step_no = int(jax.device_get(state.step))
        tel.eval_event(step_no, result, time.perf_counter() - t0, fold=fold)
        # this pass compiled whatever eval needed; later eval compiles are
        # recompiles
        tel.mark_warm(obs_lib.SPAN_EVAL)
        logger.info("fold %d eval @ %d: %s", fold, step_no, result)
        if writer is not None:
            writer.scalars(result, step_no)
            if jax.process_count() == 1:
                # image grids need fully-addressable batches; multi-host scalar
                # summaries still flow from process 0
                self._write_image_summaries(writer, state, first_batch, step_no)
            writer.flush()
        return result

    def _write_image_summaries(
        self, writer: SummaryWriter, state: TrainState, batch, step_no: int
    ) -> None:
        """input/label/probability/prediction image grids (reference:
        model.py:405-426 summarized the same four tensors)."""
        if self._tp:
            # the single-device forward cannot consume model-axis-sharded
            # params; pull one addressable copy of ONLY what it reads (the
            # Adam moments are ~2x the param bytes and _forward never
            # touches them)
            state = state.replace(
                params=jax.device_get(state.params),
                batch_stats=jax.device_get(state.batch_stats),
            )
        # an extra forward and three device_gets behind whatever is in flight:
        # host time of the window it falls into (`image_summary_s`)
        with self._telemetry.span(obs_lib.SPAN_IMAGE_SUMMARY):
            outputs = self._forward(state, batch["images"])
            probs = np.asarray(jax.device_get(jax.nn.sigmoid(outputs)))[..., 0]
            images = np.asarray(jax.device_get(batch["images"]))[..., 0]
            labels = np.asarray(jax.device_get(batch["labels"]))[..., 0]
            n = min(3, images.shape[0])
            for i in range(n):
                lo, hi = images[i].min(), images[i].max()
                writer.image(f"image/{i}", (images[i] - lo) / max(hi - lo, 1e-6), step_no)
                writer.image(f"label/{i}", labels[i], step_no)
                writer.image(f"probability/{i}", probs[i], step_no)
                writer.image(f"prediction/{i}", (probs[i] > 0.5).astype(np.float32), step_no)

    # -- cached jitted helpers --------------------------------------------

    @property
    def _eval_step(self):
        return step_lib.make_eval_step(
            self.mesh, self.task, spatial=self._spatial, auto_model=self._tp
        )

    @property
    def _predict_step(self):
        return step_lib.make_predict_step(
            self.mesh, self.task, spatial=self._spatial, auto_model=self._tp
        )

    @property
    def _prepare_eval(self):
        return _prepare_eval_cached()

    @property
    def _forward(self):
        return _forward_cached(self._plain_model)

    # -- prediction -------------------------------------------------------

    def predict(
        self,
        test_dir: str,
        batch_size: int = 64,
        tta: bool = True,
        folds: Optional[Sequence[int]] = None,
    ) -> Dict[str, np.ndarray]:
        """Fold x TTA ensemble prediction.

        For every fold's best exported state and every TTA transform, forward the
        transformed images and inverse-transform the probabilities (reference:
        model.py:230-255, 384-387), then average the ensemble — the step the reference
        left unfinished (``# TODO: finish writing this method``, model.py:229).
        ``tta=True`` really enables all four transforms (the reference's ``tti`` flag
        was inverted, SURVEY §2.4.3).

        Returns ``{"ids", "probabilities" [N,H,W,1], "masks" [N,H,W,1]}`` —
        ``[N,1,H,W]`` under ``data_format="NCHW"`` (prediction is a user-facing
        array boundary, honored like ``serving_fn``; the reference's NCHW mode
        produced NCHW predictions, model.py:344-351, 384-387).
        """
        transforms = augment_lib.TTA_TRANSFORMS if tta else ("none",)
        mesh_lib.local_batch_size(batch_size, self.mesh)  # fail fast, clear message
        folds = list(folds) if folds is not None else list(
            range(self.train_config.n_folds)
        )
        test_ds = pipeline_lib.InMemoryDataset.from_directory(
            test_dir, with_masks=False
        )
        template = self._init_state()
        total = None
        n_members = 0
        for fold in folds:
            # EMA-trained folds predict with the averaged weights even when the
            # restore fell back to a periodic checkpoint; identity otherwise
            state = step_lib.with_ema_params(
                self._restore_fold_or_raise(fold, template)
            )
            for transformation in transforms:
                probs = self._predict_one(state, test_ds, batch_size, transformation)
                total = probs if total is None else total + probs
                n_members += 1
        mean_probs = total / n_members
        if self.train_config.data_format == "NCHW":
            mean_probs = np.transpose(mean_probs, (0, 3, 1, 2))
        return {
            "ids": list(test_ds.ids),
            "probabilities": mean_probs,
            "masks": (mean_probs > self.task.threshold).astype(np.float32),
        }

    def _restore_fold_or_raise(self, fold: int, template: TrainState) -> TrainState:
        """Best exported state for ``fold`` (falling back to the latest periodic
        checkpoint); raises if the fold was never trained."""
        if jax.process_count() > 1:
            # multi-process checkpoints restore into sharded/global layouts;
            # serving and TTA prediction want one addressable copy (same
            # contract as ClassifierTrainer._restore_best_host)
            raise RuntimeError(
                "serving/predict restore runs single-process; load this "
                "model_dir from a single-process session"
            )
        ckpt = self._checkpointer(fold)
        try:
            return ckpt.restore_best_or_raise(
                template,
                hint=f"train fold {fold} first or pass folds=[...] with only "
                "the trained folds",
            )
        finally:
            ckpt.close()

    def serving_fn(self, fold: int, serving_dtype: str = "float32"):
        """Jitted single-model inference function for deployment — the JAX analogue
        of the reference's exported SavedModel with serving signature
        ``image: [None, H, W, input_channels] float32`` (reference: model.py:190-194).

        Loads the fold's best state and returns ``serve(images) ->
        {'probabilities', 'mask'}`` where ``images`` is the preprocessed input batch
        (normalized + Laplacian channel, exactly what the reference's serving
        placeholder received).

        ``serving_dtype`` selects the post-training precision spec
        (train/quantize.py SERVING_SPECS): ``float32`` is the training graph
        unchanged, ``bfloat16`` casts params/batch_stats and runs bf16
        activations, ``int8`` stores conv/dense kernels as int8 with
        per-channel scales (dequantized to bf16 inside the graph), and
        ``int8-compute`` stores the same bytes but traces dense/stride-1
        conv layers through the int8-arithmetic kernels
        (ops/quant_kernels.py). Wire contract is constant across specs:
        float32 in, float32 out. The returned closure carries its manifest
        ``quantization`` section as ``serve.quantization``.

        ``data_format="NCHW"`` is honored at this boundary: inputs arrive
        ``[B, C, H, W]`` and outputs return ``[B, 1, H, W]`` (the reference's NCHW
        mode transposed at the top of model_fn, model.py:344-351; on TPU, XLA owns
        the internal layout, so the transpose happens exactly once, here).
        """
        from tensorflowdistributedlearning_tpu.ops import quant_kernels
        from tensorflowdistributedlearning_tpu.train import quantize

        state = self._restore_fold_or_raise(fold, self._init_state())
        # EMA-trained models serve the averaged weights even when restore fell
        # back to a periodic (live-trajectory) checkpoint; identity otherwise
        state = step_lib.with_ema_params(state)
        # serving reads params/batch_stats only; dropping the Adam moments
        # frees ~2x parameter memory for the closure's lifetime
        state = state.replace(opt_state=None)
        qparams, qstats, quant_section = quantize.quantize_state(
            state.params, state.batch_stats, serving_dtype
        )
        act_dtype = quantize.compute_dtype(serving_dtype)
        int8_compute = quant_section.get("compute_dtype") == "int8"
        task = self.task
        forward = self._forward
        nchw = self.train_config.data_format == "NCHW"

        def serve(images):
            if nchw:
                images = jnp.transpose(images, (0, 2, 3, 1))
            st = state.replace(
                params=quantize.dequantize_pytree(qparams, act_dtype),
                batch_stats=quantize.dequantize_pytree(qstats, act_dtype),
            )
            x = images.astype(act_dtype)
            if int8_compute:
                # quantized layers take the int8-compute kernels; layers
                # outside the kernels' envelope keep the dequantized path
                with quant_kernels.int8_intercept(qparams, act_dtype):
                    logits = forward(st, x)
            else:
                logits = forward(st, x)
            out = task.predictions(logits)
            out = quantize.cast_outputs_float32(out)
            if nchw:
                out = {k: jnp.transpose(v, (0, 3, 1, 2)) for k, v in out.items()}
            return out

        serve.quantization = quant_section
        return serve

    def export_serving(
        self,
        fold: int,
        directory: Optional[str] = None,
        serving_dtype: str = "float32",
    ) -> str:
        """Write a standalone serialized-StableHLO serving artifact for the fold's
        best state (the reference's SavedModel export, model.py:190-204, done the
        JAX-native way — see train/serving.py). Returns the artifact path; default
        location ``{fold_dir}/export/serving`` (``serving-{dtype}`` for quantized
        exports, so the f32 reference and its candidates coexist for
        quantize-check)."""
        from tensorflowdistributedlearning_tpu.train import serving as serving_lib

        suffix = "serving" if serving_dtype == "float32" else f"serving-{serving_dtype}"
        directory = directory or os.path.join(
            self._fold_dir(fold), "export", suffix
        )
        h, w = self.model_config.input_shape
        c = self.model_config.input_channels
        shape = (
            (1, c, h, w)
            if self.train_config.data_format == "NCHW"
            else (1, h, w, c)
        )
        serve = self.serving_fn(fold, serving_dtype=serving_dtype)
        return serving_lib.export_serving_artifact(
            serve,
            shape,
            directory,
            metadata={
                "fold": fold,
                "data_format": self.train_config.data_format,
                "backbone": self.model_config.backbone,
            },
            quantization=serve.quantization,
        )

    def _predict_one(
        self,
        state: TrainState,
        test_ds: pipeline_lib.InMemoryDataset,
        batch_size: int,
        transformation: str,
    ) -> np.ndarray:
        """Probabilities [N, H, W, 1] for one (state, transform) ensemble member.

        Every process holds the full test set, so batches are placed with
        ``shard_replicated_batch`` and outputs pulled with ``fetch`` (a cross-process
        allgather under multi-host; plain device_get single-process)."""
        predict_step = self._predict_step
        chunks = []
        n = len(test_ds)
        for raw in pipeline_lib.eval_batches(test_ds, batch_size):
            images = augment_lib.tta_transform(jnp.asarray(raw["images"]), transformation)
            batch = {"images": augment_lib.add_laplace_channel(images)}
            batch = multihost.shard_replicated_batch(
                batch, self.mesh, spatial=self._spatial
            )
            out = predict_step(state, batch)
            probs = augment_lib.tta_inverse(out["probabilities"], transformation)
            valid = raw["valid"].astype(bool)
            chunks.append(multihost.fetch(probs)[valid])
        return np.concatenate(chunks)[:n]


# The reference exposed this as ``class Model`` (reference: model.py:27).
Model = Trainer
