"""The readers of the program's host timeline (start-up phases, per-step
completion times, the loop's named and unnamed time), each against a value
worked out by hand on a ledger small enough to write down, and each reading
nothing on a ledger written with the field set the program had before it
kept that timeline. No jax: a ledger is a list of dicts."""

import importlib

import pytest

from perfbench import harness

WARMUP = 4  # the measured window opens in the call of step 5
TRACE_STEPS = 2  # the harness's capture stops in the call of step 7

NEW_METRICS = {
    # start-up: load_dataset 10 + folds 0.5
    "setup_load_dataset_s": 10.5,
    # plan 0.25 + fold 0's init_state 20 + restore 1 + build_step 0.75
    "setup_state_s": 22.0,
    "setup_first_step_s": 30.0,
    # 3 + 0 + 0 + 100 + 2 + 0 + 176; fold 1's 999 are not of this start
    "setup_programs_loaded": 281,
    # setup_s 100 - (start of step 5 at 1070.0 - first phase's entry at 1000.0)
    "setup_outside_train_s": 30.0,
    # completions after step 4 + 2 + 2 = 8: steps 9..14 at 1073.0, 1073.5,
    # 1074.75, 1075.25, 1075.5, 1076.0 -> gaps 0.5, 1.25, 0.5, 0.25, 0.5 s;
    # the 1.5 s gap into step 8 (the capture's stop) is left out
    "step_interval_max_ms.train": 1250.0,
    # windows' medians 0.4, 0.6, 0.5 / 140, 150, 145
    "dispatch_prepare_p50_ms.train": 0.5,
    "dispatch_step_p50_ms.train": 145.0,
    # (0.02 + 0.5) + (0.04 + 0.44) + (0.1 + 0.4) = 1.5 s over 12 steps
    "host_emit_ms_per_step.train": 125.0,
    # (0.01 + 0.03 + 0.02) / (3 x 2.0)
    "host_unnamed_pct.train": 1.0,
}


def _phase(name, t0, duration, programs, fold=None):
    return {"event": "startup_phase", "name": name, "parent": "startup", "fold": fold,
            "t0_mono": t0, "duration_s": duration, "programs": programs,
            "cache_hits": programs, "cache_misses": 0, "compile_s": 0.01 * programs}


def _window(step, starts, done_first, done, emit, image, other, prepare_p50, step_p50,
            timeline=True):
    w = {"event": "step_window", "step": step, "steps": 4, "fold": 0,
         "data_wait_s": 0.001, "compute_s": 0.6, "fetch_wait_s": 1.4,
         "barrier_wait_s": 0.0, "dirty": False, "images_per_sec": 8.0}
    if timeline:
        w.update({
            "wall_s": 2.0, "host_other_s": other, "window_emit_s": emit,
            "image_summary_s": image, "checkpoint_s": 0.0, "eval_s": 0.0,
            "dispatch_prepare_ms": {"p50": prepare_p50, "max": 9.0},
            "dispatch_step_ms": {"p50": step_p50, "max": 900.0},
            "step_start_mono": starts, "step_done_first": done_first,
            "step_done_mono": done,
        })
    return w


def _ledger(timeline=True):
    windows = [
        # steps 1-4: the warm-up
        _window(4, [1062.0, 1064.0, 1066.0, 1068.0], 1, [1066.5, 1068.5],
                0.0, 0.0, 0.2, 1.0, 500.0, timeline),
        # steps 5-8: opens the measured window; the capture stops in step 7
        _window(8, [1070.0, 1070.2, 1070.4, 1070.6], 3,
                [1070.1, 1070.3, 1070.5, 1070.7], 0.02, 0.5, 0.01, 0.4, 140.0,
                timeline),
        # steps 9-12: completions of steps 7-10; step 8's came 1.5 s late
        _window(12, [1072.0, 1072.2, 1072.4, 1072.6], 7,
                [1071.0, 1072.5, 1073.0, 1073.5], 0.04, 0.44, 0.03, 0.6, 150.0,
                timeline),
        # steps 13-16: completions of steps 11-14; step 11's came 1.25 s late
        _window(16, [1074.0, 1074.2, 1074.4, 1074.6], 11,
                [1074.75, 1075.25, 1075.5, 1076.0], 0.1, 0.4, 0.02, 0.5, 145.0,
                timeline),
        # steps 17-20: closes after the measured window did
        _window(20, [1076.0, 1076.2, 1076.4, 1076.6], 15,
                [1086.0, 1096.0, 1106.0, 1116.0], 9.0, 9.0, 1.0, 7.0, 700.0,
                timeline),
    ]
    compiles = [{"event": "compile", "duration_s": 0.5, "phase": "", "post_warmup": False}]
    if not timeline:
        return [{"event": "run_header"}] + compiles + windows
    phases = [
        _phase("load_dataset", 1000.0, 10.0, 3),
        _phase("folds", 1010.0, 0.5, 0),
        _phase("plan", 1010.5, 0.25, 0),
        _phase("init_state", 1010.75, 20.0, 100, fold=0),
        _phase("restore", 1030.75, 1.0, 2, fold=0),
        _phase("build_step", 1031.75, 0.75, 0, fold=0),
        _phase("first_step", 1032.5, 30.0, 176, fold=0),
        _phase("init_state", 2000.0, 7.0, 999, fold=1),
    ]
    return [{"event": "run_header", "process_age_s": 25.0}] + compiles + phases + windows


def _run(ledger):
    cell = harness.Cell(
        name="paper", chips=1, config={}, traffic={"warmup_steps": WARMUP},
        limits={}, end_to_end=[], per_layer=[],
    )
    return harness.Run(
        cell=cell, ledger=ledger,
        # 13 steps in the measured window: the windows of steps 5-8, 9-12
        # and 13-16 lie wholly inside
        windows=harness.windows_inside(ledger, WARMUP, WARMUP + 13),
        global_batch=4, steps=13, wall_s=6.5, setup_s=100.0, trace=None,
        trace_steps=TRACE_STEPS, trace_wall_s=0.4, device={}, peaks=None,
    )


def _read(metric, run):
    module = importlib.import_module("perfbench.metrics." + metric.replace(".", "_"))
    return module.read(run)


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_reader_on_a_paper_ledger(metric):
    run = _run(_ledger())
    assert [w["step"] for w in run.windows] == [8, 12, 16]
    assert _read(metric, run) == pytest.approx(NEW_METRICS[metric])


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_reader_finds_nothing_in_the_parents_ledger(metric):
    run = _run(_ledger(timeline=False))
    assert len(run.windows) == 3
    assert _read(metric, run) is None


def test_every_new_metric_is_declared_for_the_cell_with_a_reader():
    import json
    import os

    root = os.path.dirname(harness.HERE)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == ["tgs_kfold_train"]
        assert per_layer[name]["source"] in ("program_span", "program_counter")
    # harness.read_metrics leaves a metric out where its reader reads nothing
    wanted = [per_layer[name] for name in NEW_METRICS]
    assert harness.read_metrics(_run(_ledger(timeline=False)), wanted) == {}
    got = harness.read_metrics(_run(_ledger()), wanted)
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(NEW_METRICS)


def test_the_old_ledger_readers_still_read_the_new_ledger():
    run = _run(_ledger())
    # compute_s 0.6 s over 4 steps; fetch_wait 1.4 s of a 2 s window (4 steps
    # of 4 images at 8 images/s)
    assert _read("host_dispatch_ms_per_step.train", run) == pytest.approx(150.0)
    assert _read("fetch_wait_pct.train", run) == pytest.approx(70.0)
    assert _read("cache_load_s", run) == pytest.approx(0.5)
