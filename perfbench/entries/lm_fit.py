"""Entry ``lm_fit``: a decoder configuration through ``ClassifierTrainer.fit``
on the program's own packed token stream.

The loop is the program's, called once. The harness reaches into it at two
seams and changes none of its work: the initial state takes the harness's
weights (``_init_state``), and the train step is wrapped by the harness's
probe (``train/step.make_train_step``), which copies the first steps' feeds
and results, opens and closes the measured window at step boundaries with the
device drained, and ends the run when the window has closed.

Afterwards the plain reference follows the same first steps from the same
weights and feeds, and ``correct`` is every limit of the cell holding:
losses, the first gradient (head, router and expert leaves named apart), the
parameters' change, and the first step's per-expert routed counts.
"""

from __future__ import annotations

import importlib
import os
import shutil
import sys
import tempfile
from typing import Any, Dict

import numpy as np

from perfbench import compare, harness, lm_weights, peaks, weights, xtrace
from perfbench.harness import FOLLOWED_STEPS

class WindowClosed(Exception):
    """Raised inside the wrapped step once the window has closed: the
    program's loop unwinds through its own ``finally``."""


def model_config(cell):
    from tensorflowdistributedlearning_tpu.config import DecoderConfig, ModelConfig

    cfg = cell.config
    decoder = DecoderConfig.from_published(
        cfg, share_count=cfg["share"]["n"], share_index=cfg["share"]["s"],
        sequence_length=cfg["sequence_length"],
    )
    return ModelConfig(backbone="decoder", dtype=cfg["dtype"], decoder=decoder)


def train_config(cell):
    """The configuration's training settings on the traffic's token stream.
    The run's own seed is the stream's, fixed in the traffic file: the
    weights, which ``--seed`` draws, come from the harness."""
    from tensorflowdistributedlearning_tpu.config import TokenStreamConfig, TrainConfig

    return TrainConfig(
        seed=cell.traffic["stream_seed"], n_devices=cell.chips,
        token_stream=TokenStreamConfig(**cell.traffic["stream"]), **cell.config["train"])


class Probe(harness.Probe):
    """The harness's probe around the train step (the followed steps' feeds,
    losses, Adam's moments and parameters; the window's clock and trace), with
    what a decoder adds: the followed steps' routed counts, the programs
    compiled or loaded from the cache while the window was open (there should
    be none), and the run ended by an exception once the window has closed —
    the preemption request the image cells end by would have the loop write a
    6.4 GB checkpoint first."""

    def __init__(self, **kw):
        from jax import monitoring

        super().__init__(on_close=self._end, **kw)
        self.routed = []
        self.compiles_inside = 0

        def on_duration(name, seconds, **_):
            # fires for a compile and for a load from the cache alike
            if (self.t_open is not None and not self.closed
                    and name == "/jax/core/compile/backend_compile_duration"):
                self.compiles_inside += 1

        monitoring.register_event_duration_secs_listener(on_duration)

    @staticmethod
    def _end():
        raise WindowClosed()

    def wrap_step(self, real):
        import jax

        def counting(state, batch):
            new_state, metrics = real(state, batch)
            if len(self.routed) < FOLLOWED_STEPS:
                self.routed.append(np.asarray(jax.device_get(metrics["moe/expert_tokens"].total)))
            return new_state, metrics

        return super().wrap_step(counting)

    def observed(self) -> Dict[str, Any]:
        return {
            "losses": self.losses,
            "moments": {k: weights.flatten(v) for k, v in self.moments_after_one.items()},
            "params": weights.flatten(self.params_after_followed),
            "routed1": self.routed[0],
        }


def observed_class(base, flat_weights):
    """``base`` with the harness's weights in its initial state."""
    import jax

    class Observed(base):
        def _init_state(self):
            state = super()._init_state()
            poured = weights.unflatten_like(state.params, flat_weights)
            placed = jax.tree.map(
                lambda new, old: jax.device_put(new, old.sharding), poured, state.params
            )
            return state.replace(params=placed)

    Observed.__name__ = base.__name__
    return Observed


def lm_numbers(reference, cfg, observed, ref_out, params0, where=None) -> Dict[str, float]:
    """``compare.training_numbers`` (losses, first gradient, the parameters'
    change) and what a decoder adds: the first gradient's worst leaf among the
    routers and among the experts' matrices, and the share of the first
    step's (token, expert) pairs the program routed to another expert than
    the reference did — half the summed gap of the per-expert counts over
    the pairs the reference counts here, a lower bound of the flips."""
    numbers = compare.training_numbers(observed, ref_out, params0, where,
                                       head=reference.head_leaves(cfg))
    norm = lambda tree: {k: float(np.linalg.norm(np.asarray(v, np.float64)))  # noqa: E731
                         for k, v in tree.items()}
    g_prog, g_ref = norm(observed["grad1"]), norm(ref_out["grad1"])
    numbers["grad1_router_gap"], _ = compare.worst_leaf_gap(g_prog, g_ref, reference.router_leaves(cfg))
    numbers["grad1_expert_gap"], _ = compare.worst_leaf_gap(g_prog, g_ref, reference.expert_leaves(cfg))
    want = np.asarray(ref_out["routed1"], np.float64)
    got = np.asarray(observed["routed1"], np.float64)
    numbers["routed_flip_share"] = float(np.abs(got - want).sum() / 2.0 / max(want.sum(), 1.0))
    return numbers


def run(cell, *, seed, seconds, trace, process_t0, warmup_steps=None, keep_trace=None,
        keep_xplane=None, collect=None):
    import jax

    from tensorflowdistributedlearning_tpu.train import step as step_lib
    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

    devices = harness.require_chips(cell.chips)
    chip_peaks = peaks.peaks_of(devices[0].device_kind)
    reference = importlib.import_module("perfbench.reference." + cell.config["reference"])
    cfg = cell.config
    flat_weights = lm_weights.make_weights(reference.param_spec(cfg), seed)
    params0 = jax.device_get(flat_weights)  # the step donates its state

    phases = harness.Phases()
    workdir = tempfile.mkdtemp(prefix="perfbench_run_")
    trace_dir = os.path.join(workdir, "trace") if trace else None
    probe = Probe(
        warmup_steps=cell.traffic["warmup_steps"] if warmup_steps is None else warmup_steps,
        seconds=seconds, trace_dir=trace_dir,
        trace_seconds=min(cell.traffic["trace_seconds"], seconds),
    )
    real_factory = step_lib.make_train_step
    step_lib.make_train_step = lambda *a, **k: probe.wrap_step(real_factory(*a, **k))
    try:
        trainer = observed_class(ClassifierTrainer, flat_weights)(
            workdir, None, model_config(cell), train_config(cell))
        try:
            trainer.fit(batch_size=cell.traffic["global_batch"], steps=10**9)
            raise RuntimeError("the program's loop ended before the window closed")
        except WindowClosed:
            pass
    finally:
        step_lib.make_train_step = real_factory
    phases.mark("trainer_window_stop")

    device = harness.device_report(devices)
    ledger = harness.read_ledger(workdir)
    trace_data = xtrace.load_xplane(trace_dir) if trace else None
    if keep_trace and trace_data is not None:
        trace_data.to_json(keep_trace)
    if keep_xplane and trace_dir is not None:
        shutil.copytree(trace_dir, keep_xplane, dirs_exist_ok=True)
    observed = probe.observed()
    phases.mark("read_ledger_trace")
    del trainer, flat_weights
    harness.free_device_memory()
    shutil.rmtree(workdir, ignore_errors=True)

    ref_out = reference.train_steps(cfg, jax.device_put(params0), probe.fed)
    phases.mark("reference")
    observed["grad1"] = reference.first_gradient(cfg, observed["moments"])
    worst: Dict[str, str] = {}
    numbers = lm_numbers(reference, cfg, observed, ref_out, params0, worst)
    for name, leaf in worst.items():
        print(f"perfbench: {name} is widest at {leaf}", file=sys.stderr)
    # the proof of no drop: the program's own counter over every window of the run
    numbers["moe_pairs_dropped"] = float(sum(
        e.get("moe_pairs_dropped", 0) for e in ledger if e.get("event") == "step_window"))
    if collect is not None:
        collect.update(fed=probe.fed, params0=params0, reference=ref_out, numbers=numbers)
    checks, correct = compare.verdict(numbers, cell.limits)
    phases.mark("compare")

    batch = int(cell.traffic["global_batch"])
    wall = probe.t_close - probe.t_open
    first = probe.warmup_steps
    run_ = harness.Run(
        cell=cell, ledger=ledger,
        windows=harness.windows_inside(ledger, first, first + probe.steps_in_window),
        global_batch=batch, steps=probe.steps_in_window, wall_s=wall,
        setup_s=probe.t_open - process_t0, trace=trace_data, trace_steps=probe.trace_steps,
        trace_wall_s=(probe.trace_span[1] - probe.trace_span[0]) if probe.trace_span else 0.0,
        device=device, peaks=chip_peaks,
    )
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": probe.steps_in_window,
        "failed": 0,
    }
    if trace:
        result["metrics"] = harness.read_metrics(run_, cell.per_layer)
        device["busy_s"] = xtrace.busy_s(trace_data) if trace_data else 0.0
        device["window_s"] = run_.trace_wall_s
        result["breakdown"] = {
            "device_ops": xtrace.top_ops(trace_data) if trace_data else [],
            "idle_gaps": xtrace.idle_gaps(trace_data) if trace_data else [],
        }
    else:
        values = {"train_images_per_s": probe.steps_in_window * batch / wall,
                  "setup_s": run_.setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell.end_to_end
        }
    result["device"] = device
    result["window"] = {
        "steps": probe.steps_in_window, "seconds": wall, "ledger_windows": len(run_.windows),
        "compiles_inside": probe.compiles_inside,
        # is the work steady over the run? (token, expert) pairs a step and
        # the worst load ratio, window by window from the first
        "moe_pairs_per_step": [round(e["moe_pairs"] / e["steps"]) for e in ledger
                               if e.get("event") == "step_window" and "moe_pairs" in e],
        "moe_load_max_over_mean": [e["moe_load_max_over_mean"] for e in ledger
                                   if e.get("event") == "step_window" and "moe_pairs" in e],
    }
    phases.mark("metrics")
    result["phases_s"] = phases.report()
    result["read_not_compared"] = {k: float(v) for k, v in numbers.items() if k not in checks}
    return result, checks
