"""The one host timeline inside ``Telemetry``: start-up phases, per-step
completion times, and the loop's unnamed time, on a tiny model on the CPU
mesh. Counts and orderings only — a CPU run says nothing about device time.

Also pins the four seams the benchmark (``perfbench/``) reaches the program
through, so that a rename fails here and not in every cell on the driver.
"""

import glob
import json
import os
import time

import jax
import pytest

from tensorflowdistributedlearning_tpu import obs as obs_lib
from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.obs import telemetry as telemetry_lib
from tensorflowdistributedlearning_tpu.train import async_loop
from tensorflowdistributedlearning_tpu.train import step as step_lib
from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer
from tensorflowdistributedlearning_tpu.train.trainer import Trainer

SHAPE = (32, 32)
STEPS = 9  # two whole windows of 4 and one step after them
FOLD_PHASES = ["init_state", "restore", "build_step", "first_step"]
WINDOW_SPAN_FIELDS = [field for _, field in telemetry_lib._WINDOW_SPAN_FIELDS]


def _events(workdir, kind=None):
    events = obs_lib.read_ledger(workdir)
    return [e for e in events if kind is None or e["event"] == kind]


@pytest.fixture(scope="module")
def kfold_run(tmp_path_factory):
    """One K-fold run (2 folds, async loop) with every Telemetry it built
    kept, so that the detector's own events can be set against the ledger."""
    from _pytest.monkeypatch import MonkeyPatch

    from tests.conftest import make_salt_dataset

    data, _, ids = make_salt_dataset(
        tmp_path_factory.mktemp("salt"), n_images=16, shape=SHAPE
    )
    model_dir = str(tmp_path_factory.mktemp("model"))
    built = []

    class Kept(obs_lib.Telemetry):
        def __init__(self, *args, **kwargs):
            # the detector's compile count each time a start-up phase closed
            self.closed_at = []
            super().__init__(*args, **kwargs)
            built.append(self)

        def _startup_event(self, mark, t1):
            super()._startup_event(mark, t1)
            self.closed_at.append(self.detector.compile_count)

    trainer = Trainer(
        model_dir,
        data,
        train_config=TrainConfig(
            n_folds=2, seed=0, train_log_every_steps=4,
            checkpoint_every_steps=100, eval_throttle_secs=0,
        ),
        input_shape=SHAPE,
        n_blocks=(1, 1, 1),
        base_depth=8,
        width_multiplier=0.0625,
    )
    # the run has to compile: a test file before this one in the same worker
    # may have built the same tiny programs, and the suite's disk cache serves
    # them in under the ledger's 10 ms — then no compile event has a phase
    jax.clear_caches()
    disk_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with MonkeyPatch.context() as m:
            m.setattr(obs_lib, "Telemetry", Kept)
            trainer.train(ids, batch_size=8, steps=STEPS)
    finally:
        jax.config.update("jax_enable_compilation_cache", disk_cache)
    (tel,) = built
    return model_dir, tel


# -- start-up phases ----------------------------------------------------------


def test_startup_phases_once_per_fold_in_order(kfold_run):
    model_dir, _ = kfold_run
    phases = _events(model_dir, "startup_phase")
    assert [(p["name"], p["fold"]) for p in phases] == (
        [("load_dataset", None), ("folds", None), ("plan", None)]
        + [(name, fold) for fold in (0, 1) for name in FOLD_PHASES]
    )
    assert all(p["parent"] == "startup" for p in phases)


def test_startup_phases_do_not_overlap(kfold_run):
    model_dir, _ = kfold_run
    phases = _events(model_dir, "startup_phase")
    for before, after in zip(phases, phases[1:]):
        assert before["t0_mono"] + before["duration_s"] <= after["t0_mono"] + 1e-5
    # inside a fold they lie back to back: nothing before the first step is
    # unnamed (each gap is a few statements; the CPU mesh is slow, so allow
    # tens of milliseconds)
    fold0 = [p for p in phases if p["fold"] == 0]
    for before, after in zip(fold0, fold0[1:]):
        gap = after["t0_mono"] - before["t0_mono"] - before["duration_s"]
        assert gap < 0.05, (before["name"], after["name"], gap)


def test_header_comes_first_with_plan_and_process_age(kfold_run):
    model_dir, _ = kfold_run
    events = _events(model_dir)
    header = events[0]
    assert header["event"] == "run_header"
    assert "layout" in header["plan"]  # added after the phases before it ran
    # the ledger keeps the time each held event was made
    assert [e["t"] for e in events[:6]] == sorted(e["t"] for e in events[:6])
    assert events[1]["event"] in ("compile", "startup_phase")
    if os.path.exists("/proc/self/stat"):
        assert 0 < header["process_age_s"] < 24 * 3600


def test_compile_events_carry_a_startup_phase(kfold_run):
    model_dir, tel = kfold_run
    phases = {e.phase for e in tel.detector.events}
    assert "startup/init_state" in phases
    ledgered = {e["phase"] for e in _events(model_dir, "compile")}
    assert any(p.startswith("startup/") for p in ledgered)
    # the train step and the input program compile inside `dispatch_step` /
    # `dispatch_prepare`, and are the loop span's all the same: `step` is
    # what the trainers mark warm
    assert obs_lib.SPAN_STEP in phases
    assert not phases & {obs_lib.SPAN_DISPATCH_PREPARE, obs_lib.SPAN_DISPATCH_STEP}
    assert tel.detector.post_warmup_count == 0


def test_programs_add_up_to_the_detectors_count(kfold_run):
    """Σ ``programs`` over the phases = the detector's compile count at the
    end of ``first_step``, less what compiled outside any phase. The phases
    count by the detector's index at both ends; the check walks the
    detector's own list with the index at which each phase closed."""
    model_dir, tel = kfold_run
    phases = [
        p for p in _events(model_dir, "startup_phase") if p["fold"] in (None, 0)
    ]
    assert phases[-1]["name"] == "first_step"
    closes = tel.closed_at[: len(phases)]
    events = tel.detector.events
    outside, prev = [], 0
    for p, end in zip(phases, closes):
        begin = end - p["programs"]
        assert begin >= prev, p["name"]  # no compile is counted twice
        outside += events[prev:begin]
        if p["name"] != "first_step":  # its compiles are the loop spans'
            other = {
                e.phase for e in events[begin:end] if e.phase.startswith("startup/")
            }
            assert other <= {"startup/" + p["name"]}
        assert p["cache_hits"] + p["cache_misses"] <= p["programs"]
        assert p["compile_s"] >= 0
        prev = end
    assert sum(p["programs"] for p in phases) == closes[-1] - len(outside)
    assert sum(p["programs"] for p in phases) > 0
    # nothing that compiles outside a phase belongs to one by attribution
    assert not any(e.phase.startswith("startup/") for e in outside)


def test_a_recompile_under_the_dispatch_spans_is_post_warmup(tmp_path, caplog):
    """The trainers' nesting (``step`` > ``dispatch_prepare`` /
    ``dispatch_step``) and their ``mark_warm(SPAN_STEP, SPAN_DATA_WAIT)``: a
    shape change after the first window is a post-warm-up recompile of the
    ``step`` span, counted in the window and warned about."""
    import jax.numpy as jnp

    tel = obs_lib.Telemetry(str(tmp_path), is_main=True, run_info={"task": "t"})

    @jax.jit
    def prepare(x):
        return x * 2 + 1

    @jax.jit
    def train_step(x):
        return (x * 3 + 1).sum()

    def loop_step(n):
        with tel.span(obs_lib.SPAN_STEP):
            with tel.span(obs_lib.SPAN_DISPATCH_PREPARE):
                batch = prepare(jnp.ones((n,)))
            with tel.span(obs_lib.SPAN_DISPATCH_STEP):
                train_step(batch)

    try:
        loop_step(4)  # the expected compiles
        tel.window_event(1, steps=1, dirty=True)
        tel.mark_warm(obs_lib.SPAN_STEP, obs_lib.SPAN_DATA_WAIT)
        loop_step(4)  # warm: nothing compiles
        assert tel.detector.post_warmup_count == 0
        loop_step(6)  # shape drift in both programs
        tel.window_event(3, steps=2)
    finally:
        tel.close(steps=3)
    flagged = [e for e in tel.detector.events if e.post_warmup]
    assert len(flagged) >= 2 and {e.phase for e in flagged} == {obs_lib.SPAN_STEP}
    ledgered = [e for e in _events(str(tmp_path), "compile") if e["post_warmup"]]
    assert len(ledgered) == len(flagged)  # however short the compile was
    last = _events(str(tmp_path), "step_window")[-1]
    assert last["recompiles_post_warmup"] >= 2
    assert _events(str(tmp_path), "run_end")[0]["recompiles_post_warmup"] >= 2
    assert any("recompilation" in r.message.lower() for r in caplog.records)


# -- per-step completion times and the window's wall --------------------------


def test_step_done_matches_the_steps_retired(kfold_run):
    model_dir, _ = kfold_run
    windows = [w for w in _events(model_dir, "step_window") if w["fold"] == 0]
    assert [w["step"] for w in windows] == [4, 8]
    # dispatch_ahead_steps=2: at the boundary of step 4 steps 1-2 have retired
    first, second = windows
    assert first["step_done_first"] == 1 and len(first["step_done_mono"]) == 2
    assert second["step_done_first"] == 3 and len(second["step_done_mono"]) == 4
    done = first["step_done_mono"] + second["step_done_mono"]
    assert done == sorted(done)
    assert second["step_interval_ms"]["n"] == 3
    assert second["step_interval_ms"]["max"] >= second["step_interval_ms"]["p50"] > 0
    for w in windows:
        assert len(w["step_start_mono"]) == w["steps"] == 4
        assert w["step_start_mono"] == sorted(w["step_start_mono"])
    # a step is done after it was started
    assert second["step_start_mono"][0] < second["step_done_mono"][-1]
    assert first["step_start_mono"][0] < first["step_done_mono"][0]


def test_first_step_ends_when_step_one_retires(kfold_run):
    model_dir, _ = kfold_run
    phase = next(
        p for p in _events(model_dir, "startup_phase")
        if p["name"] == "first_step" and p["fold"] == 0
    )
    window = next(w for w in _events(model_dir, "step_window") if w["fold"] == 0)
    assert phase["t0_mono"] + phase["duration_s"] == pytest.approx(
        window["step_done_mono"][0], abs=2e-6
    )
    assert phase["t0_mono"] <= window["step_start_mono"][0]


def test_wall_is_named_spans_plus_host_other(kfold_run):
    model_dir, _ = kfold_run
    windows = _events(model_dir, "step_window")
    assert windows
    for w in windows:
        named = sum(w[field] for field in WINDOW_SPAN_FIELDS)
        assert w["wall_s"] == pytest.approx(named + w["host_other_s"], abs=1e-3)
        assert w["wall_s"] > 0
        # the children of `step` are inside it, not beside it
        assert w["dispatch_prepare_s"] + w["dispatch_step_s"] <= w["compute_s"] + 1e-5
        for child in ("dispatch_prepare_ms", "dispatch_step_ms"):
            assert 0 < w[child]["p50"] <= w[child]["max"]
    # the write-out of window 4 and the image grids after its boundary are
    # host time of the window that ends at step 8
    second = next(w for w in windows if w["fold"] == 0 and w["step"] == 8)
    assert second["window_emit_s"] > 0 and second["image_summary_s"] > 0
    # the first window of fold 1 starts with its loop, not with fold 0's end
    fold1 = next(w for w in windows if w["fold"] == 1)
    assert fold1["eval_s"] == 0 and fold1["checkpoint_s"] == 0


def test_a_nested_span_is_subtracted_once(tmp_path):
    """``barrier_wait`` opens inside ``eval`` on a multi-host run
    (``multihost.fetch``): its field reads its own seconds, and
    ``host_other_s`` takes the window's named time from the spans that closed
    at the top of the stack, so it does not go negative."""
    tel = obs_lib.Telemetry(str(tmp_path), is_main=True, run_info={"task": "t"})
    tel.drain_window_samples()
    with tel.span(obs_lib.SPAN_EVAL):
        with tel.span(obs_lib.SPAN_BARRIER):
            time.sleep(0.05)
    with tel.span(obs_lib.SPAN_BARRIER):  # and one at the top: counted too
        time.sleep(0.01)
    time.sleep(0.02)  # in no span
    tel.window_event(1, steps=1)
    tel.close()
    (w,) = _events(str(tmp_path), "step_window")
    assert w["barrier_wait_s"] >= 0.06 and w["eval_s"] >= 0.05
    assert w["host_other_s"] >= 0.02
    top_level = w["eval_s"] + w["barrier_wait_s"] - 0.05
    assert w["wall_s"] == pytest.approx(top_level + w["host_other_s"], abs=5e-3)


def test_synchronous_loop_retires_nothing(tmp_path):
    trainer = ClassifierTrainer(
        str(tmp_path), None,
        ModelConfig(num_classes=4, input_shape=(16, 16), input_channels=3,
                    n_blocks=(1, 1, 1), base_depth=8, width_multiplier=0.125,
                    output_stride=None),
        TrainConfig(seed=7, train_log_every_steps=2, checkpoint_every_steps=100,
                    eval_every_steps=100, dispatch_ahead_steps=0),
    )
    trainer.fit(batch_size=8, steps=4)
    windows = _events(str(tmp_path), "step_window")
    assert len(windows) == 2
    for w in windows:
        assert "step_done_mono" not in w and "step_interval_ms" not in w
        # the window fetch runs under the `step` span and is not a step
        assert len(w["step_start_mono"]) == w["steps"] == 2
        assert "mfu" not in w
    names = [p["name"] for p in _events(str(tmp_path), "startup_phase")]
    assert names == ["load_dataset", "plan"] + FOLD_PHASES
    # with nothing to retire, the phase ends at the loop's first boundary
    first_step = _events(str(tmp_path), "startup_phase")[-1]
    assert first_step["t0_mono"] + first_step["duration_s"] >= windows[0][
        "step_start_mono"
    ][-1]


# -- off means off -------------------------------------------------------------


def test_disabled_telemetry_writes_nothing_and_asks_jax_nothing(
    tmp_path, monkeypatch
):
    tel = obs_lib.Telemetry(str(tmp_path), enabled=False, hold_header=True)

    def forbidden(*args, **kwargs):
        raise AssertionError("a disabled span touched jax")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    with tel.span("startup/load_dataset"):
        with tel.span(obs_lib.SPAN_STEP):
            pass
    tel.begin_first_step()
    tel.step_done(1)
    assert tel.drain_window_samples() == {}
    tel.window_event(1, steps=1)
    tel.finish_header(plan={})
    tel.close()
    assert glob.glob(os.path.join(str(tmp_path), "*")) == []
    # the shared disabled instance, through the loop's own tracker
    overlap = async_loop.HostOverlap(
        obs_lib.NULL_TELEMETRY, dispatch_ahead=1, emit=lambda rec, scalars: None
    )
    for step in (1, 2, 3):
        overlap.track({"x": jax.numpy.zeros(())}, step)
    assert len(obs_lib.NULL_TELEMETRY._steps_done) == 0


def test_a_run_that_dies_in_its_start_still_tells_how_far_it_got(tmp_path):
    tel = obs_lib.Telemetry(str(tmp_path), hold_header=True, run_info={"task": "t"})
    with pytest.raises(RuntimeError):
        with tel.span("startup/load_dataset"):
            raise RuntimeError("no such directory")
    assert not _events(str(tmp_path))  # the header is still held
    tel.close(interrupted=True)
    kinds = [e["event"] for e in _events(str(tmp_path))]
    assert kinds == ["run_header", "startup_phase", "run_end"]


# -- mfu: priced where the proxy holds, absent elsewhere -----------------------


def test_mfu_for_a_vit_and_none_for_a_convolutional_model(
    tmp_path, monkeypatch, kfold_run
):
    # a peak small enough that a toy model's share of it survives rounding
    monkeypatch.setenv("TFDL_PEAK_FLOPS", "1e9")
    model_dir, _ = kfold_run
    assert all("mfu" not in w for w in _events(model_dir, "step_window"))

    def fit(workdir, **model):
        ClassifierTrainer(
            workdir, None,
            ModelConfig(num_classes=4, input_shape=(16, 16), input_channels=3,
                        **model),
            TrainConfig(seed=7, train_log_every_steps=2,
                        checkpoint_every_steps=100, eval_every_steps=100,
                        augmentation="none"),
        ).fit(batch_size=8, steps=4)
        return _events(workdir, "step_window")

    conv = fit(str(tmp_path / "conv"), n_blocks=(1, 1, 1), base_depth=8,
               width_multiplier=0.125, output_stride=None)
    assert conv and all("mfu" not in w for w in conv)
    vit = fit(str(tmp_path / "vit"), backbone="vit", patch_size=4, embed_dim=32,
              vit_layers=2, num_heads=4)
    assert vit and all(0 < w["mfu"] for w in vit)


# -- what the benchmark reaches for --------------------------------------------


def test_the_seams_the_benchmark_reaches_through(tmp_path, monkeypatch):
    """``perfbench`` overrides ``_init_state`` and ``_make_prepare_train``,
    swaps ``train.step.make_train_step`` on the module, and finds the two
    programs in a device trace as ``jit_prepare`` and ``jit_step``
    (``perfbench/entries/training.py``, ``perfbench/traffic/*.json``)."""
    from tests.conftest import make_salt_dataset

    data, _, ids = make_salt_dataset(tmp_path / "salt", n_images=16, shape=SHAPE)
    for cls in (Trainer, ClassifierTrainer):
        assert callable(getattr(cls, "_init_state"))
        assert callable(getattr(cls, "_make_prepare_train"))

    calls = {"init": 0, "prepare": 0, "factory": 0}
    lowered = {}
    real_factory = step_lib.make_train_step

    class Observed(Trainer):
        def _init_state(self):
            calls["init"] += 1
            return super()._init_state()

        def _make_prepare_train(self, *args):
            calls["prepare"] += 1
            inner = super()._make_prepare_train(*args)

            def prepare(step, batch):
                if "prepare" not in lowered:
                    from tensorflowdistributedlearning_tpu.train import trainer as t

                    jitted = t._prepare_train_cached(self.augment_config)
                    lowered["prepare"] = jitted.lower(
                        jax.random.PRNGKey(0), step, batch
                    ).as_text()
                return inner(step, batch)

            return prepare

    def factory(*args, **kwargs):
        calls["factory"] += 1
        real = real_factory(*args, **kwargs)

        def step(state, batch):
            if "step" not in lowered:
                lowered["step"] = real.lower(state, batch).as_text()
            return real(state, batch)

        return step

    # looked up on the module when the fold starts, not bound at import: the
    # loop's module is long imported by now, and holds no name of its own for
    # the factory that a swap on `train.step` would miss
    import sys

    loop_lib = sys.modules["tensorflowdistributedlearning_tpu.train.loop"]
    assert loop_lib.step_lib is step_lib
    assert not hasattr(loop_lib, "make_train_step")
    monkeypatch.setattr(step_lib, "make_train_step", factory)
    Observed(
        str(tmp_path / "model"), data,
        train_config=TrainConfig(n_folds=2, seed=0, train_log_every_steps=2,
                                 checkpoint_every_steps=100),
        input_shape=SHAPE, n_blocks=(1, 1, 1), base_depth=8,
        width_multiplier=0.0625,
    ).train(ids, batch_size=8, steps=2)
    assert calls == {"init": 2, "prepare": 2, "factory": 2}
    assert "jit_prepare" in lowered["prepare"]
    assert "jit_step" in lowered["step"]


# -- what a span costs ---------------------------------------------------------


def test_span_cost_is_reported_not_asserted(tmp_path, capsys):
    """A count for PERF.md: microseconds per enter/exit of an enabled span on
    this CPU. Five spans a step (data_wait, step, its two children, and
    fetch_wait once the budget is full)."""
    tel = obs_lib.Telemetry(str(tmp_path), run_info={"task": "t"})
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with tel.span(obs_lib.SPAN_DISPATCH_STEP):
            pass
    per_span_us = (time.perf_counter() - t0) / n * 1e6
    tel.close()
    print(json.dumps({"span_enter_exit_us": round(per_span_us, 2)}))
    assert per_span_us < 1000  # a span is not a millisecond
