"""Async host loop (train/async_loop.py) and its satellites.

The overlap layer must be a pure latency optimization: dispatch-ahead plus
deferred window fetch may change WHEN host work happens, never WHAT the run
computes. The pins here:

- sync (``dispatch_ahead_steps=0``) vs async fit() runs produce bit-identical
  final params and identical ledger scalar values (modulo event ordering);
- an eval pass performs exactly ONE host transfer of metrics regardless of
  batch count (device-resident accumulation), counted with a device_get spy;
- a preemption mid-window flushes the deferred window to the ledger BEFORE the
  preemption checkpoint/events, so resilience reporting stays complete;
- the host-side lr schedule mirror matches the optax schedules it replaces;
- ``device_prefetch`` releases its producer thread when the consumer abandons
  iteration early (or never iterates at all), and records its queue depth so
  underruns reach ``telemetry-report``.
"""

import gc
import itertools
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowdistributedlearning_tpu import obs
from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.data import pipeline as pipeline_lib
from tensorflowdistributedlearning_tpu.obs.telemetry import (
    PREFETCH_DEPTH_HISTOGRAM,
    SPAN_FETCH_WAIT,
    Telemetry,
)
from tensorflowdistributedlearning_tpu.ops import metrics as metrics_lib
from tensorflowdistributedlearning_tpu.resilience import preempt
from tensorflowdistributedlearning_tpu.train import async_loop
from tensorflowdistributedlearning_tpu.train import step as step_lib
from tensorflowdistributedlearning_tpu.train.checkpoint import CheckpointManager
from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

TINY = dict(
    num_classes=4,
    input_shape=(16, 16),
    input_channels=3,
    n_blocks=(1, 1, 1),
    base_depth=8,
    width_multiplier=0.125,
    output_stride=None,
)


def _tiny_tcfg(dispatch_ahead: int) -> TrainConfig:
    return TrainConfig(
        seed=7,
        train_log_every_steps=2,
        checkpoint_every_steps=4,
        eval_every_steps=4,
        dispatch_ahead_steps=dispatch_ahead,
    )


# -- HostOverlap unit behavior -------------------------------------------------


def _mean(v: float) -> metrics_lib.Mean:
    return metrics_lib.Mean(
        total=jnp.asarray(v, jnp.float32), count=jnp.asarray(1.0, jnp.float32)
    )


def _window(step: int, value: float) -> async_loop.PendingWindow:
    return async_loop.PendingWindow(
        step=step, metrics={"loss": _mean(value)}, steps=2, lr=0.1
    )


def test_sync_mode_emits_in_place(tmp_path):
    tel = Telemetry(str(tmp_path), run_info={})
    emitted = []
    overlap = async_loop.HostOverlap(
        tel, dispatch_ahead=0, emit=lambda rec, scalars: emitted.append((rec.step, scalars))
    )
    assert not overlap.async_mode
    overlap.track({"loss": _mean(1.0)})  # no-op in sync mode
    overlap.window(_window(2, 3.0))
    assert [s for s, _ in emitted] == [2]
    assert emitted[0][1]["loss"] == pytest.approx(3.0)
    overlap.flush()  # nothing pending
    assert len(emitted) == 1
    tel.close()


def test_async_mode_defers_one_window_and_flushes(tmp_path):
    tel = Telemetry(str(tmp_path), run_info={})
    emitted = []
    overlap = async_loop.HostOverlap(
        tel, dispatch_ahead=2, emit=lambda rec, scalars: emitted.append((rec.step, scalars))
    )
    overlap.window(_window(2, 1.0))
    assert emitted == []  # deferred
    overlap.window(_window(4, 2.0))
    assert [s for s, _ in emitted] == [2]  # boundary N emits window N-1
    overlap.flush()
    assert [s for s, _ in emitted] == [2, 4]
    overlap.flush()  # idempotent
    assert len(emitted) == 2
    assert emitted[0][1]["loss"] == pytest.approx(1.0)
    assert emitted[1][1]["loss"] == pytest.approx(2.0)
    tel.close()


def test_dispatch_ahead_budget_blocks_and_records_fetch_wait(tmp_path):
    tel = Telemetry(str(tmp_path), run_info={})
    overlap = async_loop.HostOverlap(tel, dispatch_ahead=2, emit=lambda *_: None)
    for i in range(5):
        overlap.track({"loss": _mean(float(i))})
    waits = tel.drain_window_samples()[SPAN_FETCH_WAIT]
    # 5 tracked steps against a budget of 2: three blocking retirements
    assert len(waits) == 3
    tel.close()


def test_eval_budget_bounds_inflight_even_in_sync_mode(tmp_path):
    tel = Telemetry(str(tmp_path), run_info={})
    # sync mode (dispatch_ahead 0) still bounds eval to 1 in flight — the
    # legacy per-batch device_get throttled eval as a side effect, and
    # device-resident accumulation must not unbound it
    assert async_loop.eval_budget(tel, 0).budget == 1
    # the train-loop tracker records its blocking as fetch_wait samples...
    budget = async_loop.DispatchBudget(tel, 4)
    for i in range(6):
        budget.track({"loss": _mean(float(i))})
    assert len(tel.drain_window_samples()[SPAN_FETCH_WAIT]) == 2
    # ...the EVAL budget does NOT: its waits happen inside the eval span
    # (already counted as eval time) and a fetch_wait sample would drain into
    # the NEXT train window, double-counting eval in the goodput split
    ebudget = async_loop.eval_budget(tel, 4)
    assert ebudget.budget == 4
    for i in range(6):
        ebudget.track({"loss": _mean(float(i))})
    assert tel.drain_window_samples()[SPAN_FETCH_WAIT] == []
    tel.close()


# -- device-resident eval accumulation ----------------------------------------


def test_merge_metrics_device_matches_host_merge():
    a = {"loss": _mean(1.0), "metrics/top1": _mean(0.5)}
    b = {"loss": _mean(3.0), "metrics/top1": _mean(1.0)}
    acc = async_loop.merge_metrics_device(None, a)
    acc = async_loop.merge_metrics_device(acc, b)
    host = step_lib.merge_metrics(jax.device_get(a), jax.device_get(b))
    assert step_lib.compute_metrics(jax.device_get(acc)) == pytest.approx(
        step_lib.compute_metrics(host)
    )


def test_merge_metrics_device_rejects_non_mean_leaf():
    with pytest.raises(TypeError, match="not a .*Mean"):
        async_loop.merge_metrics_device(None, {"loss": jnp.zeros(())})


def test_fetch_metrics_counts_the_single_transfer(tmp_path):
    tel = Telemetry(str(tmp_path), run_info={})
    acc = async_loop.merge_metrics_device(None, {"loss": _mean(2.0)})
    out = async_loop.fetch_metrics(acc, telemetry=tel)
    assert out["loss"] == pytest.approx(2.0)
    assert tel.registry.counter(async_loop.EVAL_FETCH_COUNTER).value == 1
    with pytest.raises(ValueError, match="no eval batches"):
        async_loop.fetch_metrics(None)
    tel.close()


# -- host-side lr schedule mirror ---------------------------------------------


@pytest.mark.parametrize(
    "cfg",
    [
        TrainConfig(lr=0.01, lr_schedule="exponential", lr_decay_steps=100, lr_decay_rate=0.5),
        TrainConfig(lr=0.02, lr_schedule="cosine", lr_warmup_steps=0, lr_decay_steps=200),
        TrainConfig(lr=0.03, lr_schedule="cosine", lr_warmup_steps=10, lr_decay_steps=200),
    ],
    ids=["exponential", "cosine", "cosine_warmup"],
)
def test_host_lr_schedule_matches_optax(cfg):
    device = step_lib.make_lr_schedule(cfg)
    host = step_lib.make_host_lr_schedule(cfg)
    for step in [0, 1, 5, 9, 10, 11, 50, 150, 199, 200, 500]:
        # the optax schedules evaluate in float32; the host mirror in float64 —
        # float32-level agreement is the contract (this is the logging path)
        assert host(step) == pytest.approx(float(device(step)), rel=1e-3, abs=1e-8)


# -- device_prefetch shutdown + depth gauge -----------------------------------


def _spawn_prefetch(**kwargs):
    before = set(threading.enumerate())
    gen = pipeline_lib.device_prefetch(**kwargs)
    (thread,) = [
        t
        for t in threading.enumerate()
        if t not in before and t.name == "device_prefetch"
    ]
    return gen, thread


def test_device_prefetch_rejects_bad_depth_eagerly():
    with pytest.raises(ValueError, match="depth"):
        pipeline_lib.device_prefetch(iter([1]), place=lambda b: b, depth=0)


def test_device_prefetch_abandon_mid_stream_releases_producer():
    gen, thread = _spawn_prefetch(
        iterator=itertools.count(), place=lambda b: b, depth=2
    )
    assert next(gen) == 0
    # the producer is now blocked on a full queue of an infinite stream; an
    # abandoning consumer (preemption raise mid-epoch) must still release it
    gen.close()
    thread.join(timeout=5)
    assert not thread.is_alive()


def test_device_prefetch_dropped_unused_releases_producer():
    gen, thread = _spawn_prefetch(
        iterator=itertools.count(), place=lambda b: b, depth=1
    )
    del gen  # never iterated: the generator finalizer must signal stop
    gc.collect()
    thread.join(timeout=5)
    assert not thread.is_alive()


def test_device_prefetch_records_queue_depth():
    from tensorflowdistributedlearning_tpu.obs.metrics import MetricsRegistry

    registry = MetricsRegistry()
    out = list(
        pipeline_lib.device_prefetch(
            iter(range(6)), place=lambda b: b, depth=2, registry=registry
        )
    )
    assert out == list(range(6))
    depths = registry.histogram(PREFETCH_DEPTH_HISTOGRAM).drain()
    assert len(depths) == 6
    assert all(0 <= d <= 2 for d in depths)


# -- config / CLI knobs --------------------------------------------------------


def test_config_validates_overlap_knobs():
    with pytest.raises(ValueError, match="prefetch_depth"):
        TrainConfig(prefetch_depth=0)
    with pytest.raises(ValueError, match="dispatch_ahead_steps"):
        TrainConfig(dispatch_ahead_steps=-1)
    assert TrainConfig(dispatch_ahead_steps=0).dispatch_ahead_steps == 0


def test_cli_exposes_overlap_flags():
    from tensorflowdistributedlearning_tpu.cli import build_parser

    parser = build_parser()
    args = parser.parse_args(
        ["train", "--model-dir", "/tmp/m", "--data-dir", "/tmp/d",
         "--prefetch-depth", "4", "--dispatch-ahead", "0"]
    )
    assert args.prefetch_depth == 4 and args.dispatch_ahead == 0
    args = parser.parse_args(
        ["fit", "--preset", "cifar10_smoke", "--model-dir", "/tmp/m"]
    )
    assert args.prefetch_depth is None and args.dispatch_ahead is None


# -- e2e: sync vs async parity on the 8-device mesh ---------------------------


def _run_fit(model_dir: str, dispatch_ahead: int, monkeypatch_ctx):
    """One synthetic fit() run; returns the params of the FINAL checkpoint
    save, captured bitwise via a CheckpointManager.save spy."""
    captured = {}
    orig_save = CheckpointManager.save

    def spy(self, state, *, force=False):
        captured["params"] = jax.device_get(state.params)
        return orig_save(self, state, force=force)

    with monkeypatch_ctx() as m:
        m.setattr(CheckpointManager, "save", spy)
        trainer = ClassifierTrainer(
            model_dir, None, ModelConfig(**TINY), _tiny_tcfg(dispatch_ahead)
        )
        result = trainer.fit(batch_size=8, steps=8)
    return result, captured["params"]


@pytest.fixture(scope="module")
def parity_runs(tmp_path_factory):
    from _pytest.monkeypatch import MonkeyPatch

    def ctx():
        return MonkeyPatch.context()

    sync_dir = str(tmp_path_factory.mktemp("fit_sync"))
    async_dir = str(tmp_path_factory.mktemp("fit_async"))
    sync_res, sync_params = _run_fit(sync_dir, 0, ctx)
    async_res, async_params = _run_fit(async_dir, 2, ctx)
    return {
        "sync": (sync_dir, sync_res, sync_params),
        "async": (async_dir, async_res, async_params),
    }


def test_async_final_params_bit_identical(parity_runs):
    _, _, sync_params = parity_runs["sync"]
    _, _, async_params = parity_runs["async"]
    s_leaves = jax.tree.leaves(sync_params)
    a_leaves = jax.tree.leaves(async_params)
    assert len(s_leaves) == len(a_leaves) > 0
    for s, a in zip(s_leaves, a_leaves):
        np.testing.assert_array_equal(np.asarray(s), np.asarray(a))


def _window_scalars(workdir: str):
    out = {}
    for e in obs.read_ledger(workdir):
        if e["event"] != "step_window":
            continue
        scalars = dict(e.get("scalars", {}))
        # wall-clock throughput is the one legitimately timing-dependent scalar
        scalars.pop("throughput/images_per_sec", None)
        out[e["step"]] = scalars
    return out


def test_async_ledger_scalars_identical(parity_runs):
    sync_dir, _, _ = parity_runs["sync"]
    async_dir, _, _ = parity_runs["async"]
    sync_w, async_w = _window_scalars(sync_dir), _window_scalars(async_dir)
    assert set(sync_w) == set(async_w) == {2, 4, 6, 8}
    for step in sync_w:
        assert sync_w[step] == async_w[step], f"window scalars differ @ {step}"


def test_async_eval_metrics_identical(parity_runs):
    def evals(workdir):
        return {
            e["step"]: e["metrics"]
            for e in obs.read_ledger(workdir)
            if e["event"] == "eval"
        }

    sync_e = evals(parity_runs["sync"][0])
    async_e = evals(parity_runs["async"][0])
    assert set(sync_e) == set(async_e) and sync_e
    for step in sync_e:
        assert sync_e[step] == async_e[step]


def test_async_windows_carry_overlap_telemetry(parity_runs):
    async_dir, _, _ = parity_runs["async"]
    windows = [
        e for e in obs.read_ledger(async_dir) if e["event"] == "step_window"
    ]
    assert windows
    for w in windows:
        assert "fetch_wait_s" in w
        # the prefetch gauge rides the window events (trainers pass their
        # registry into device_prefetch)
        assert "prefetch_queue_depth" in w
        assert w["prefetch_queue_depth"]["min"] >= 0


def test_eval_pass_single_host_transfer(tmp_path, monkeypatch):
    """The acceptance pin: one host transfer per eval pass regardless of
    batch count, asserted with a jax.device_get call counter scoped to
    ``_eval_pass`` (the jitted per-batch merges must not transfer)."""
    transfer_counts, batch_counts = [], []
    orig_pass = ClassifierTrainer._eval_pass

    def spy(self, state, batches, step_no=None):
        seen = [0]

        def counting_batches():
            for b in batches:
                seen[0] += 1
                yield b

        real_get = jax.device_get
        calls = [0]

        def counting_get(x):
            calls[0] += 1
            return real_get(x)

        jax.device_get = counting_get
        try:
            result = orig_pass(self, state, counting_batches(), step_no)
        finally:
            jax.device_get = real_get
        transfer_counts.append(calls[0])
        batch_counts.append(seen[0])
        return result

    monkeypatch.setattr(ClassifierTrainer, "_eval_pass", spy)
    trainer = ClassifierTrainer(
        str(tmp_path), None, ModelConfig(**TINY), _tiny_tcfg(2)
    )
    trainer.fit(batch_size=8, steps=4)
    assert transfer_counts and all(n == 1 for n in transfer_counts)
    # the synthetic eval split streams 4 batches — the single transfer above
    # really amortized a multi-batch pass
    assert all(n == 4 for n in batch_counts)


# -- telemetry-report surfacing ------------------------------------------------


def test_report_surfaces_fetch_wait_and_prefetch(parity_runs):
    from tensorflowdistributedlearning_tpu.obs.report import (
        build_report,
        render_report,
    )

    async_dir, _, _ = parity_runs["async"]
    report = build_report(async_dir)
    ts = report["time_split"]
    assert "fetch_wait_s" in ts and "fetch_wait_frac" in ts
    assert report["prefetch"]["windows"] == 4
    assert report["prefetch"]["min_queue_depth"] >= 0
    rendered = render_report(report)
    assert "input prefetch" in rendered


def test_report_flags_prefetch_underruns(tmp_path):
    from tensorflowdistributedlearning_tpu.obs.report import (
        build_report,
        render_report,
    )
    from tensorflowdistributedlearning_tpu.obs.ledger import LEDGER_FILENAME

    events = [
        {"event": "run_header", "t": 0.0, "run": {}},
        {
            "event": "step_window", "t": 1.0, "step": 2, "steps": 2,
            "data_wait_s": 0.4, "compute_s": 0.5, "fetch_wait_s": 0.1,
            "data_wait_frac": 0.4, "dirty": False,
            "prefetch_queue_depth": {"mean": 0.5, "min": 0},
        },
        {
            "event": "step_window", "t": 2.0, "step": 4, "steps": 2,
            "data_wait_s": 0.1, "compute_s": 0.8, "fetch_wait_s": 0.0,
            "data_wait_frac": 0.1, "dirty": False,
            "prefetch_queue_depth": {"mean": 1.8, "min": 1},
        },
        {"event": "run_end", "t": 3.0},
    ]
    with open(os.path.join(str(tmp_path), LEDGER_FILENAME), "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    report = build_report(str(tmp_path))
    assert report["prefetch"]["underrun_windows"] == 1
    assert report["prefetch"]["min_queue_depth"] == 0
    assert report["time_split"]["fetch_wait_s"] == pytest.approx(0.1)
    assert "underran" in render_report(report)
