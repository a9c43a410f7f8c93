"""What the two training entries share: observe the program's own loop, then
judge and measure it.

The loop is the program's (``Trainer.train`` / ``ClassifierTrainer.fit``),
called once. The harness reaches into it at three seams and changes none of
its work: the initial state takes the harness's weights (``_init_state``),
the input program and the train step are wrapped by a :class:`Probe`
(``_make_prepare_train`` and ``train/step.make_train_step``), and the run is
ended through the program's own preemption request.
"""

from __future__ import annotations

import importlib
import os
import sys
import shutil
import tempfile
from typing import Any, Callable, Dict, Optional

import numpy as np

from perfbench import compare, harness, peaks, weights, xtrace


def model_config(cell):
    from tensorflowdistributedlearning_tpu.config import ModelConfig

    return ModelConfig(
        **{k: tuple(v) if isinstance(v, list) else v for k, v in cell.config["model"].items()}
    )


def train_config(cell, seed: int):
    from tensorflowdistributedlearning_tpu.config import TrainConfig

    # any whole number up to a little over 2**31 has to do as a seed
    return TrainConfig(seed=int(seed) % (2**31 - 4096), n_devices=cell.chips, **cell.config["train"])


def observed_class(base, probe: harness.Probe, flat_weights: Dict[str, Any]):
    """``base`` with the harness's weights in its initial state and the probe
    around its input program."""
    import jax

    class Observed(base):
        def _init_state(self):
            state = super()._init_state()
            poured = weights.unflatten_like(state.params, flat_weights)
            placed = jax.tree.map(
                lambda new, old: jax.device_put(new, old.sharding), poured, state.params
            )
            return state.replace(params=placed)

        def _make_prepare_train(self, *args):
            return probe.wrap_prepare(super()._make_prepare_train(*args))

    Observed.__name__ = base.__name__
    return Observed


def run_training(
    cell,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    process_t0: float,
    trainer_base,
    make_trainer: Callable[[Any, str], Any],
    start: Callable[[Any], None],
    prepare_numbers: Callable[[Any, harness.Probe, Dict[str, Any]], Dict[str, float]],
    warmup_steps: Optional[int] = None,
    keep_trace: Optional[str] = None,
    keep_xplane: Optional[str] = None,
    collect: Optional[Dict[str, Any]] = None,
):
    """One run of a training cell: returns (result, checks)."""
    import jax

    from tensorflowdistributedlearning_tpu.resilience import preempt as preempt_lib
    from tensorflowdistributedlearning_tpu.train import step as step_lib

    devices = harness.require_chips(cell.chips)
    chip_peaks = peaks.peaks_of(devices[0].device_kind)
    reference = importlib.import_module("perfbench.reference." + cell.config["reference"])
    ref_cfg = cell.reference_cfg
    flat_weights = weights.make_weights(reference.param_spec(ref_cfg), seed)
    # the step donates its state, and with it the buffers handed to it
    params0 = jax.device_get(flat_weights)

    phases = harness.Phases()
    workdir = tempfile.mkdtemp(prefix="perfbench_run_")
    trace_dir = os.path.join(workdir, "trace") if trace else None
    handler = preempt_lib.install(signals=None)
    probe = harness.Probe(
        warmup_steps=cell.traffic["warmup_steps"] if warmup_steps is None else warmup_steps,
        seconds=seconds,
        trace_dir=trace_dir,
        trace_seconds=min(cell.traffic["trace_seconds"], seconds),
        on_close=lambda: handler.request("perfbench: the measured window has closed"),
    )
    real_factory = step_lib.make_train_step
    step_lib.make_train_step = lambda *a, **k: probe.wrap_step(real_factory(*a, **k))
    try:
        trainer = make_trainer(observed_class(trainer_base, probe, flat_weights), workdir)
        try:
            start(trainer)
            raise RuntimeError("the program's loop ended before the window closed")
        except preempt_lib.PreemptedError:
            pass
    finally:
        step_lib.make_train_step = real_factory
        preempt_lib.uninstall()
    if not probe.closed:
        raise RuntimeError("the program stopped before the window closed")
    phases.mark("trainer_window_stop")

    device = harness.device_report(devices)
    ledger = harness.read_ledger(workdir)
    trace_data = xtrace.load_xplane(trace_dir) if trace else None
    if keep_trace and trace_data is not None:
        trace_data.to_json(keep_trace)
    if keep_xplane and trace_dir is not None:
        shutil.copytree(trace_dir, keep_xplane, dirs_exist_ok=True)
    observed = probe.observed(cell.config["model"]["batch_norm_decay"])
    phases.mark("read_ledger_trace")
    del trainer, flat_weights
    harness.free_device_memory()
    shutil.rmtree(workdir, ignore_errors=True)

    # the reference follows the first steps from the same weights and feeds
    fed = [{k: np.asarray(v) for k, v in b.items()} for b in probe.fed]
    ref_out = reference.train_steps(
        ref_cfg, jax.device_put(params0), fed, shards=cell.chips
    )
    ref_out = jax.device_get(ref_out)
    phases.mark("reference")
    observed["grad1"] = reference.first_gradient(ref_cfg, observed["moments"], params0)
    worst_leaves: Dict[str, str] = {}
    numbers = compare.training_numbers(
        observed, ref_out, params0, worst_leaves, head=reference.head_leaves(ref_cfg)
    )
    for name, leaf in worst_leaves.items():
        print(f"perfbench: {name} is widest at {leaf}", file=sys.stderr)
    if collect is not None:
        collect.update(fed=fed, params0=params0, reference=ref_out, raw=probe.raw)
    numbers.update(prepare_numbers(reference, probe, cell.config))
    checks, correct = compare.verdict(numbers, cell.limits)
    phases.mark("compare")
    if collect is not None:
        collect["numbers"] = numbers

    batch = int(cell.traffic["global_batch"])
    wall = probe.t_close - probe.t_open
    first = probe.warmup_steps
    run = harness.Run(
        cell=cell,
        ledger=ledger,
        windows=harness.windows_inside(ledger, first, first + probe.steps_in_window),
        global_batch=batch,
        steps=probe.steps_in_window,
        wall_s=wall,
        setup_s=probe.t_open - process_t0,
        trace=trace_data,
        trace_steps=probe.trace_steps,
        trace_wall_s=(probe.trace_span[1] - probe.trace_span[0]) if probe.trace_span else 0.0,
        device=device,
        peaks=chip_peaks,
    )
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": probe.steps_in_window,
        "failed": 0,
    }
    if trace:
        result["metrics"] = harness.read_metrics(run, cell.per_layer)
        device["busy_s"] = xtrace.busy_s(trace_data) if trace_data else 0.0
        device["window_s"] = run.trace_wall_s
        result["breakdown"] = {
            "device_ops": xtrace.top_ops(trace_data) if trace_data else [],
            "idle_gaps": xtrace.idle_gaps(trace_data) if trace_data else [],
        }
    else:
        values = {
            "train_images_per_s": probe.steps_in_window * batch / wall,
            "setup_s": run.setup_s,
        }
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell.end_to_end
        }
    result["device"] = device
    result["window"] = {"steps": probe.steps_in_window, "seconds": wall,
                        "ledger_windows": len(run.windows)}
    phases.mark("metrics")
    result["phases_s"] = phases.report()
    # numbers read that no limit is held against (PERF.md says why)
    result["read_not_compared"] = {k: float(v) for k, v in numbers.items() if k not in checks}
    return result, checks
