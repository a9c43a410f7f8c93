"""The one training loop (``train/loop.py``), driven through both trainers
that run it: ``Trainer.train`` on a tiny segmentation preset and
``ClassifierTrainer.fit`` on a tiny classifier. Each case holds for both —
the loop is one function, and what the trainers hand it (the Task's window
fields, when an eval is due, the ``fold`` on events) must not change what it
does. Orderings and counts only: a CPU run says nothing about device time."""

import dataclasses
import glob
import os
import shutil

import pytest

from tensorflowdistributedlearning_tpu import obs as obs_lib
from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.resilience import faults as faults_lib
from tensorflowdistributedlearning_tpu.resilience import preempt as preempt_lib
from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer
from tensorflowdistributedlearning_tpu.train.trainer import Trainer

SHAPE = (32, 32)
TRAINERS = ["kfold", "fit"]
TINY_CLASSIFIER = dict(
    num_classes=4,
    input_shape=(16, 16),
    input_channels=3,
    n_blocks=(1, 1, 1),
    base_depth=8,
    width_multiplier=0.125,
    output_stride=None,
)
# the events a run's story is told in; what else a ledger holds (compiles,
# memory, cost) depends on the backend and the cache
STORY = (
    "run_header", "startup_phase", "resumed", "step_window", "checkpoint",
    "eval", "preempted", "run_end",
)


@pytest.fixture(scope="module")
def salt(tmp_path_factory):
    from tests.conftest import make_salt_dataset

    data, _, ids = make_salt_dataset(
        tmp_path_factory.mktemp("salt"), n_images=16, shape=SHAPE
    )
    return data, ids


def _run(kind, model_dir, salt, steps, **train_config):
    """One run of ``kind``'s trainer over ``model_dir``: log windows of 2
    steps, a checkpoint (and with it an eval) every 2 unless told otherwise."""
    tcfg = TrainConfig(**{
        "seed": 0, "train_log_every_steps": 2, "checkpoint_every_steps": 2,
        "eval_throttle_secs": 0, **train_config,
    })
    if kind == "kfold":
        data, ids = salt
        return Trainer(
            model_dir, data, train_config=dataclasses.replace(tcfg, n_folds=2),
            input_shape=SHAPE, n_blocks=(1, 1, 1), base_depth=8,
            width_multiplier=0.0625,
        ).train(ids, batch_size=8, steps=steps)
    return ClassifierTrainer(
        model_dir, None, ModelConfig(**TINY_CLASSIFIER), tcfg
    ).fit(batch_size=8, steps=steps)


def _run_dir(kind, model_dir):
    """Where the (first) loop of the run keeps its checkpoints."""
    return os.path.join(model_dir, "fold0") if kind == "kfold" else model_dir


def _story(model_dir, fold=0):
    """The last session's story events; of a K-fold run the run's own and
    ``fold``'s."""
    events = obs_lib.read_ledger(model_dir)
    last = max(i for i, e in enumerate(events) if e["event"] == "run_header")
    return [
        e for e in events[last:]
        if e["event"] in STORY and e.get("fold") in (None, fold)
    ]


def _kinds(story):
    """Event kinds in order, a start-up phase with its name; ``folds`` is the
    one phase only the K-fold trainer has."""
    return [
        e["event"] + (":" + e["name"] if e["event"] == "startup_phase" else "")
        for e in story
        if not (e["event"] == "startup_phase" and e["name"] == "folds")
    ]


@pytest.fixture(scope="module", params=TRAINERS)
def trained(request, salt, tmp_path_factory):
    """A finished 2-step run of each trainer."""
    model_dir = str(tmp_path_factory.mktemp("trained_" + request.param))
    _run(request.param, model_dir, salt, steps=2)
    return request.param, model_dir


def _copy_of(model_dir, tmp_path):
    copy = str(tmp_path / "model")
    shutil.copytree(model_dir, copy)
    return copy


# -- (a) one story, from both ---------------------------------------------------

EXPECTED_STORY = [
    "run_header",
    "startup_phase:load_dataset", "startup_phase:plan",
    "startup_phase:init_state", "startup_phase:restore",
    "startup_phase:build_step", "startup_phase:first_step",
    # the deferred window reaches the ledger before the checkpoint that
    # flushed it; the eval follows the save; the forced save ends the loop
    "step_window", "checkpoint", "eval", "checkpoint",
    "run_end",
]


def test_both_trainers_tell_the_same_story(trained):
    kind, model_dir = trained
    story = _story(model_dir)
    assert _kinds(story) == EXPECTED_STORY
    header = story[0]
    assert header["task"] == {"kfold": "segmentation", "fit": "classification"}[kind]
    assert header["steps"] == 2 and header["global_batch"] == 8
    assert ("n_folds" in header) == (kind == "kfold")
    # every event of the loop carries the fold, or none does
    looped = [e for e in story if e["event"] in ("step_window", "checkpoint", "eval")]
    assert all(("fold" in e) == (kind == "kfold") for e in looped)
    cadence, final = [e for e in story if e["event"] == "checkpoint"]
    assert cadence["step"] == final["step"] == 2
    assert final.get("final") is True and "final" not in cadence
    (window,) = [e for e in story if e["event"] == "step_window"]
    assert window["step"] == 2 and window["steps"] == 2
    assert "loss" in window["scalars"] and "lr" in window["scalars"]
    assert story[-1].get("interrupted") is not True


# -- (b) preemption ---------------------------------------------------------------


@pytest.mark.parametrize("kind", TRAINERS)
def test_preemption_writes_window_then_checkpoint_then_event(
    kind, salt, tmp_path, monkeypatch
):
    """A preemption landing while a window is deferred: the window reaches
    the ledger BEFORE the forced checkpoint and the ``preempted`` event
    (resilience reporting depends on ledger completeness at that boundary),
    and the run ends in ``PreemptedError`` with the step it stopped at."""
    at = 3  # the step AFTER the first log window: window@2 is deferred then
    seen = [0]

    def fire(site, step=None, **kw):
        if site == faults_lib.SITE_STEP:
            seen[0] = step

    monkeypatch.setattr(faults_lib, "fire", fire)
    monkeypatch.setattr(preempt_lib, "requested", lambda: seen[0] >= at)
    monkeypatch.setattr(preempt_lib, "reason", lambda: "test:forced")
    model_dir = str(tmp_path)
    with pytest.raises(preempt_lib.PreemptedError) as stopped:
        _run(kind, model_dir, salt, steps=8, checkpoint_every_steps=4,
             eval_every_steps=4)
    assert stopped.value.step == at
    story = _story(model_dir)
    kinds = [e["event"] for e in story]
    assert kinds[-4:] == ["step_window", "checkpoint", "preempted", "run_end"]
    window, checkpoint, preempted, end = story[-4:]
    assert window["step"] == 2
    assert checkpoint["step"] == at and checkpoint["preempted"] is True
    assert preempted["step"] == at and preempted["reason"] == "test:forced"
    assert end["interrupted"] is True
    if kind == "kfold":
        assert window["fold"] == checkpoint["fold"] == preempted["fold"] == 0
    assert os.path.isdir(os.path.join(_run_dir(kind, model_dir), "checkpoints", str(at)))


# -- (c) an abort at the end still leaves the last steps on disk ------------------


@pytest.mark.parametrize("kind", TRAINERS)
def test_abort_from_the_final_flush_keeps_the_final_checkpoint(
    kind, salt, tmp_path, monkeypatch
):
    steps = 4
    real = obs_lib.Telemetry.window_event

    def window_event(self, step, **kw):
        if step == steps:  # deferred at the last boundary: the final flush's
            raise obs_lib.HealthAbortError("test: the last window is not finite")
        return real(self, step, **kw)

    monkeypatch.setattr(obs_lib.Telemetry, "window_event", window_event)
    model_dir = str(tmp_path)
    with pytest.raises(obs_lib.HealthAbortError):
        # no cadence save, no eval inside the loop: nothing flushes before its end
        _run(kind, model_dir, salt, steps=steps, checkpoint_every_steps=100,
             eval_every_steps=100)
    story = _story(model_dir)
    kinds = [e["event"] for e in story]
    assert kinds[-3:] == ["step_window", "checkpoint", "run_end"]
    assert story[-3]["step"] == 2
    assert story[-2]["step"] == steps and story[-2]["final"] is True
    assert "eval" not in kinds  # the abort outranks the final eval
    assert story[-1]["interrupted"] is True
    assert os.path.isdir(
        os.path.join(_run_dir(kind, model_dir), "checkpoints", str(steps))
    )


# -- (d) a second run over the same directory trains the remainder ---------------


def test_a_second_run_resumes_and_trains_the_remainder(trained, salt, tmp_path):
    kind, model_dir = trained
    model_dir = _copy_of(model_dir, tmp_path)
    _run(kind, model_dir, salt, steps=4)
    story = _story(model_dir)
    (resumed,) = [e for e in story if e["event"] == "resumed"]
    assert resumed["step"] == 2
    assert resumed.get("fold") == (0 if kind == "kfold" else None)
    assert [e["step"] for e in story if e["event"] == "step_window"] == [4]
    kinds = _kinds(story)
    assert kinds.index("startup_phase:restore") < kinds.index("resumed")
    assert kinds.index("resumed") < kinds.index("startup_phase:build_step")
    assert [e["step"] for e in story if e["event"] == "eval"] == [4]


# -- (e) a run already at its step count evaluates and trains nothing ------------


def test_a_run_already_at_its_steps_only_evaluates(trained, salt, tmp_path):
    kind, model_dir = trained
    model_dir = _copy_of(model_dir, tmp_path)
    before = sorted(glob.glob(os.path.join(_run_dir(kind, model_dir), "checkpoints", "*")))
    result = _run(kind, model_dir, salt, steps=2)
    story = _story(model_dir)
    assert _kinds(story) == [
        "run_header", "startup_phase:load_dataset", "startup_phase:plan",
        "startup_phase:init_state", "startup_phase:restore", "eval", "run_end",
    ]
    assert story[-2]["step"] == 2
    assert before == sorted(
        glob.glob(os.path.join(_run_dir(kind, model_dir), "checkpoints", "*"))
    )
    if kind == "kfold":
        assert len(result) == 2 and "metrics/mean_iou" in result[0]
    else:
        assert result.steps == 2 and "metrics/top1" in result.final_metrics


# -- the loop lives in one module --------------------------------------------------


def test_the_step_is_dispatched_from_one_module():
    """The per-layer host metrics (``dispatch_*_p50_ms``, ``fetch_wait_pct``,
    ``host_unnamed_pct``) read spans the loop opens: one module opens them,
    so a host-side change is made, and measured, once."""
    from tensorflowdistributedlearning_tpu import train

    package = os.path.dirname(train.__file__)
    opens = {}
    for path in sorted(glob.glob(os.path.join(package, "*.py"))):
        with open(path, encoding="utf-8") as f:
            source = f.read()
        opens[os.path.basename(path)] = (
            source.count("SPAN_DISPATCH_STEP"), source.count("while True:")
        )
    assert {name for name, (spans, _) in opens.items() if spans} == {"loop.py"}
    assert opens["loop.py"] == (1, 1)
    assert opens["trainer.py"][1] == 0 and opens["fit.py"][1] == 0
