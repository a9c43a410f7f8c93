"""The one training loop, and the run scaffold around it.

``Trainer.train`` (per fold) and ``ClassifierTrainer.fit`` run the same host
loop: wait on data, dispatch ``prepare`` and the train step, track the
dispatch budget, fire the fault site, honor a preemption, emit the deferred
log window, checkpoint on cadence, evaluate, and at the end flush, force-save
and run the final eval. The trainers keep what differs before it — their
start-up phases, their input stream, their choice of step builder — and hand
the rest to ``train_loop``; what differs inside it (the window's fields, when
an eval is due, how one is run) the Task and a few callables say.

Every span of the loop (``data_wait``, ``step`` and its two children,
``checkpoint``) is opened here and nowhere else, so the per-layer host
metrics read one place (PERF.md §3).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from tensorflowdistributedlearning_tpu import obs as obs_lib
from tensorflowdistributedlearning_tpu.config import TrainConfig
from tensorflowdistributedlearning_tpu.parallel import multihost
from tensorflowdistributedlearning_tpu.resilience import faults as faults_lib
from tensorflowdistributedlearning_tpu.resilience import preempt as preempt_lib
from tensorflowdistributedlearning_tpu.train import async_loop
from tensorflowdistributedlearning_tpu.train import state as state_lib
from tensorflowdistributedlearning_tpu.train import step as step_lib
from tensorflowdistributedlearning_tpu.train.state import TrainState

logger = logging.getLogger(__name__)


@contextlib.contextmanager
def telemetry_run(
    trainer,
    steps: int,
    batch_size: int,
    run_info: Optional[Dict[str, Any]] = None,
    cleanup: Optional[Callable[[], None]] = None,
):
    """One run's live ``Telemetry`` on ``trainer._telemetry``, torn down on
    the way out. Built before anything else of the run, so that the start-up
    phases are spans and the compile listener hears the whole start; the
    header waits for the plan (``finish_header``). ``run_info`` is what the
    trainer adds to the run header beyond its task's block; ``cleanup`` runs
    first on the way out, before the telemetry closes."""
    tcfg = trainer.train_config
    tel = trainer._telemetry = obs_lib.Telemetry(
        trainer.model_dir,
        enabled=tcfg.telemetry,
        memory_every_windows=tcfg.telemetry_memory_every_windows,
        # sampled per-step/eval/checkpoint traces (obs/trace.py) and the
        # online health monitors (obs/health.py) ride the window stream
        trace_sample_rate=tcfg.trace_sample_rate,
        health=obs_lib.HealthMonitor.from_train_config(tcfg),
        hold_header=True,
        run_info={
            "task": trainer.task.name,
            **trainer.task.run_header(),
            "steps": steps,
            "global_batch": batch_size,
            **(run_info or {}),
            "mesh": {
                name: int(size)
                for name, size in zip(
                    trainer.mesh.axis_names, trainer.mesh.devices.shape
                )
            },
            "model_config": dataclasses.asdict(trainer.model_config),
            "train_config": dataclasses.asdict(tcfg),
        },
    )
    # time cross-process sync points as this run's barrier_wait span —
    # per-host barrier asymmetry is the fleet report's straggler signal
    multihost.instrument(tel)
    try:
        yield tel
    finally:
        # idempotent: a run that succeeded has closed with its final fields;
        # an exceptional exit reaches this close first and is recorded as
        # interrupted (and the compile listener never leaks either way)
        if cleanup is not None:
            cleanup()
        multihost.uninstrument(tel)
        tel.close(interrupted=True)
        trainer._telemetry = obs_lib.NULL_TELEMETRY


def finish_header(trainer, batch_size: int) -> None:
    """The ``startup/plan`` phase, then the held run header written out with
    the plan: the chosen layout + predicted bytes/chip (parallel/planner.py),
    which telemetry-report renders, obs/compare hashes, and the watermark
    events' measured-vs-predicted deltas are judged against. A trainer built
    without a plan describes its explicit layout through the planner here —
    best-effort: the mesh already validated divisibility in ``__init__``, so
    a planner hiccup is telemetry loss, not a training error."""
    tel, tcfg = trainer._telemetry, trainer.train_config
    run_plan = trainer._plan
    with tel.span("startup/plan"):
        if run_plan is None and tcfg.telemetry:
            # the plan's only consumer here is the run header
            try:
                from tensorflowdistributedlearning_tpu.parallel import (
                    planner as planner_lib,
                )

                run_plan = planner_lib.validate_config(
                    trainer.model_config, tcfg, batch_size
                ).header()
            except Exception as e:  # noqa: BLE001 — plan is telemetry here
                logger.warning("parallelism plan unavailable: %s", e)
    tel.finish_header(**({"plan": run_plan} if run_plan else {}))


def record_footprint(trainer, state: TrainState, batch_size: int) -> None:
    """Post-init, inside ``startup/restore``: the params/optimizer footprint
    with exact per-device opt-state accounting (1/dp of it under
    weight_update_sharding), the step's FLOPs for MFU pricing, and the
    run's profiler."""
    tel, tcfg = trainer._telemetry, trainer.train_config
    params_bytes = state_lib.tree_bytes_per_device(state.params)
    tel.memory_event(
        params_bytes_per_device=params_bytes,
        opt_state_bytes_per_device=state_lib.tree_bytes_per_device(
            state.opt_state
        ),
        weight_update_sharding=tcfg.weight_update_sharding,
    )
    if not tel.enabled:
        return
    # MFU pricing: the planner's dense-proxy FLOPs against the window's wall
    # per step turn every step_window into an MFU point — where the proxy
    # holds (planner.dense_proxy_flops); elsewhere (the convolutional
    # backbones) the windows omit `mfu`
    from tensorflowdistributedlearning_tpu.parallel import planner as planner_lib

    step_flops = planner_lib.dense_proxy_flops(
        trainer.model_config, trainer.params, batch_size
    )
    if step_flops is not None:
        n_dev = trainer.mesh.devices.size
        tel.set_step_flops(
            step_flops,
            n_devices=n_dev,
            # dominant steady-state collective: the gradient all-reduce, ~2x
            # params bytes on-wire per step (ring); only priced when there is
            # a wire to cross
            collective_bytes_per_step=(
                2.0 * float(params_bytes) if n_dev > 1 else None
            ),
        )
    # continuous profiling: windowed/triggered jax.profiler captures, the
    # per-op roofline ledgered (obs/profiler.py); one for the whole run, so a
    # later fold keeps the first's
    if tel.profiler is None:
        tel.set_profiler(obs_lib.ContinuousProfiler(
            tel, every_windows=tcfg.profile_every_windows,
        ))


def train_loop(
    tel,
    tcfg: TrainConfig,
    task,
    *,
    state: TrainState,
    start_step: int,
    batch_size: int,
    batches: Iterable[Any],
    prepare: Callable[[jax.Array, Any], Any],
    train_step: Callable[[TrainState, Any], Tuple[TrainState, Any]],
    ckpt,
    evaluate: Callable[[TrainState, int], Dict[str, float]],
    eval_due: Callable[[int, bool], bool],
    tb_train=None,
    data_service=None,
    after_window: Optional[Callable[[TrainState, Any, int], None]] = None,
    event_fields: Optional[Dict[str, Any]] = None,
) -> Tuple[TrainState, int, Dict[str, float]]:
    """Train until ``batches`` ends; returns (state, step, last eval metrics).

    ``evaluate(state, step_no)`` runs one eval pass and writes its summaries;
    ``eval_due(step_no, saved)`` says whether one follows this step (``saved``:
    a cadence checkpoint just landed). ``tb_train`` is this process's
    TensorBoard writer, None where it writes no windows. ``data_service`` is
    the input stream whose resume state rides every checkpoint, if it has
    one. ``after_window(state, batch, step_no)`` runs after a log window's
    boundary. ``event_fields`` go on every ledger event of the loop (the
    K-fold trainer's ``fold``).

    Raises ``PreemptedError`` after the preemption checkpoint, and passes on
    whatever the step, the loader or a health abort raises."""
    fields = event_fields or {}
    is_main = jax.process_index() == 0
    step_no = start_step
    last_eval_step = -1
    final_metrics: Dict[str, float] = {}
    window_t0 = time.perf_counter()
    window_start = step_no
    # the first window contains the train-step compile; windows containing
    # an eval pass or a synchronous checkpoint save are likewise not
    # training time — mark them dirty and skip their throughput point
    window_dirty = True
    # host-side schedule mirror: the lr log line must not dispatch device
    # work (the whole point of the deferred-fetch loop is a full queue)
    lr_sched = step_lib.make_host_lr_schedule(tcfg)
    # cost accounting (obs/capacity.py): examples THIS PROCESS's chips handle
    # — the meter counts local devices, so a multi-host run must price the
    # per-process batch share, not the global batch (which would inflate
    # per-chip throughput by the process count)
    local_bs = multihost.per_process_batch_size(batch_size)

    def emit_window(rec: async_loop.PendingWindow, computed) -> None:
        scalars, vectors = step_lib.split_scalars(computed)
        if tb_train is not None:
            tb_train.scalars(scalars, rec.step)
        tel.window_event(
            rec.step,
            steps=rec.steps,
            images_per_sec=rec.images_per_sec,
            scalars=scalars,
            dirty=rec.dirty,
            samples=rec.samples,
            **task.window_fields(
                rec.steps * batch_size, scalars, vectors, rec.images_per_sec
            ),
            examples=rec.steps * local_bs,
            **fields,
        )

    # dispatch-ahead + deferred window fetch (train/async_loop.py);
    # dispatch_ahead_steps=0 is the synchronous legacy loop
    overlap = async_loop.HostOverlap(
        tel, dispatch_ahead=tcfg.dispatch_ahead_steps, emit=emit_window
    )

    def save_data_sidecar(step: int) -> None:
        # the input stream's resume state rides every checkpoint (process 0
        # writes; the validated fields — seed, batch_index — are identical
        # on every host by construction): the durable half of the service
        # resume contract
        if data_service is not None and is_main:
            ckpt.save_data_state(step, data_service.state(step).to_json())

    batches_it = iter(batches)
    _end = object()
    # the last start-up phase: until the tracker retires the first step
    tel.begin_first_step()
    while True:
        # host blocked on the loader (prefetch underrun) vs dispatching
        # compute: the split the ledger's step windows record
        with tel.span(obs_lib.SPAN_DATA_WAIT):
            raw = next(batches_it, _end)
        if raw is _end:
            break
        with tel.span(obs_lib.SPAN_STEP):
            with tel.span(obs_lib.SPAN_DISPATCH_PREPARE):
                batch = prepare(jnp.asarray(step_no), raw)
            with tel.span(obs_lib.SPAN_DISPATCH_STEP):
                state, metrics = train_step(state, batch)
        step_no += 1
        # bounded dispatch-ahead: block (as fetch_wait) once more than
        # dispatch_ahead_steps steps are in flight; the step that wait
        # retires gets its completion time
        overlap.track(metrics, step_no)
        # resilience boundary: injected faults fire here (a SIGTERM lands
        # in the preemption handler below within the same boundary), and a
        # pending preemption turns into a final checkpoint + distinct exit
        faults_lib.fire(faults_lib.SITE_STEP, step_no)
        if preempt_lib.requested():
            # the deferred window reaches the ledger BEFORE the preemption
            # checkpoint/events — resilience reporting stays complete.
            # Preemption outranks a health abort surfacing from this
            # flush: the alert is already ledgered, and the supervisor
            # contract (final checkpoint + EXIT_PREEMPTED) must hold.
            try:
                overlap.flush()
            except obs_lib.HealthAbortError:
                pass
            with tel.span(obs_lib.SPAN_CHECKPOINT):
                ckpt.save(state, force=True)
            save_data_sidecar(step_no)
            tel.checkpoint_event(step_no, **fields, preempted=True)
            tel.event(
                "preempted", step=step_no, **fields, reason=preempt_lib.reason()
            )
            raise preempt_lib.PreemptedError(step_no)
        if tb_train is not None and step_no % tcfg.train_log_every_steps == 0:
            now = time.perf_counter()
            images_per_sec = None
            if not window_dirty and step_no > window_start:
                images_per_sec = (
                    (step_no - window_start) * batch_size / (now - window_t0)
                )
            # sync mode fetches+emits here; async mode emits the PREVIOUS
            # window and defers this one while the device keeps running.
            # rec.lr is the lr the NEXT update will use — exact, the
            # schedule is step-driven (observability the reference's TB
            # summaries never had)
            overlap.window(
                async_loop.PendingWindow(
                    step=step_no,
                    metrics=metrics,
                    steps=step_no - window_start,
                    lr=lr_sched(step_no),
                    images_per_sec=images_per_sec,
                    dirty=window_dirty,
                )
            )
            window_t0, window_start, window_dirty = now, step_no, False
            # train-side executables exist now: further train compiles
            # are recompiles (the first eval marks its own phase warm)
            tel.mark_warm(obs_lib.SPAN_STEP, obs_lib.SPAN_DATA_WAIT)
            if after_window is not None:
                after_window(state, batch, step_no)
        # the checkpoint span is a trace boundary (sampled runs show
        # checkpoint spans in --export-trace timelines), not a window
        # span; opened only on the manager's own save cadence so
        # off-cadence steps stay span-free
        saved = False
        if ckpt.is_save_step(step_no):
            with tel.span(obs_lib.SPAN_CHECKPOINT):
                saved = ckpt.maybe_save(state, step=step_no)
        if saved:
            overlap.flush()
            window_dirty = True
            save_data_sidecar(step_no)
            tel.checkpoint_event(step_no, **fields)
        if eval_due(step_no, saved):
            overlap.flush()
            last_eval_step = step_no
            final_metrics = evaluate(state, step_no)
            # best-export stores the eval view: EMA params when tracked
            ckpt.export_best(step_lib.with_ema_params(state), final_metrics)
            window_dirty = True
    # end of training: final checkpoint + eval + export (train_and_evaluate's
    # final-eval contract) — the eval skipped when the last loop iteration
    # already evaluated at this exact step. An abort surfacing from the
    # end-of-run flush must not skip the final checkpoint — write it, then
    # re-raise (abort means "stop at a recorded boundary", not "discard the
    # run's last steps")
    abort_err: Optional[BaseException] = None
    try:
        overlap.flush()
    except obs_lib.HealthAbortError as e:
        abort_err = e
    with tel.span(obs_lib.SPAN_CHECKPOINT):
        ckpt.save(state, force=True)
    save_data_sidecar(step_no)
    tel.checkpoint_event(step_no, **fields, final=True)
    if abort_err is not None:
        raise abort_err
    if last_eval_step != step_no:
        final_metrics = evaluate(state, step_no)
        ckpt.export_best(step_lib.with_ema_params(state), final_metrics)
    return state, step_no, final_metrics
