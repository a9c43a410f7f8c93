"""``perfbench/scope_trace.py`` and the seven ``scope_*`` readers on small
recorded traces with a made-up ``program_scopes`` record beside them: what is
skipped, what is unnamed, that the parts sum to the program's op time, and
that ``BENCHMARK.json`` names files that exist. A recorded trace's times are
the chip's of another day; nothing here is a measurement."""

import importlib
import json
import os

import pytest

from perfbench import harness, peaks, scope_trace, xtrace
from perfbench.tests import tiny_lm
from tests.conftest import SCOPE_RECORD_DEAL, make_scope_record

HERE = os.path.join(tiny_lm.REPO, "perfbench", "tests")
SCOPE_METRICS = [
    "scope_optimizer_device_ms_per_step", "scope_unnamed_device_pct",
    "scope_recompute_device_ms_per_step", "scope_attention_device_ms_per_step",
    "scope_moe_device_ms_per_step", "scope_head_loss_device_ms_per_step",
    "scope_loss_device_ms_per_step",
]
CELLS = ["tgs_kfold_train", "mellum2_share4_train_8k", "keye_share8_train_16k",
         "laguna_share8_train_16k"]
TRACES = {"tgs_kfold_train": "recorded_trace.json",
          "mellum2_share4_train_8k": "recorded_trace_lm.json",
          "keye_share8_train_16k": "recorded_trace_sparse.json",
          "laguna_share8_train_16k": "recorded_trace_mixed.json"}


def _run(workload, ledger=None, trace=True, leave_out=()):
    """The cell's recorded trace with a made-up record in its ledger
    (``ledger=[]``: none)."""
    cell = harness.load_cell(workload)
    recorded = xtrace.Trace.from_json(os.path.join(HERE, TRACES[workload]))
    if ledger is None:
        ledger = [{"event": "run_header"}, make_scope_record(recorded, leave_out=leave_out)]
    return harness.Run(
        cell=cell, ledger=ledger, windows=[], global_batch=2, steps=20, wall_s=5.0,
        setup_s=1.0, trace=recorded if trace else None, trace_steps=2, trace_wall_s=0.5,
        device={}, peaks=peaks.PEAKS["TPU v5 lite"])


def _ops(run):
    """(name, text, seconds) of the step program's ops in the recorded trace."""
    return [(xtrace.short_name(e[0]).lstrip("%"), e[0], e[2] / 1e9)
            for e in xtrace.ops_inside(run.trace, "jit_step")]


@pytest.mark.parametrize("workload", CELLS)
def test_parts_sum_to_the_programs_op_time(workload):
    run = _run(workload)
    seconds, calls = scope_trace.by_scope(run)
    assert calls == xtrace.module_time_s(run.trace, "jit_step")[1] >= 1
    ops = _ops(run)
    loops = [o for o in ops if " while(" in o[1] or " conditional(" in o[1]]
    # a container's span holds its body's ops, which are events of their own
    assert sum(seconds.values()) == pytest.approx(
        sum(s for _, _, s in ops) - sum(s for _, _, s in loops))
    if workload != "tgs_kfold_train":
        assert loops  # the decoders' head is a scan; Keye's tie search a conditional
    # every (scope, pass) the record deals out took some time
    dealt = {(chain[-1] if chain else "", which) for chain, which in SCOPE_RECORD_DEAL}
    assert set(seconds) == dealt


def test_an_op_the_record_lacks_is_unnamed():
    whole = _run("mellum2_share4_train_8k")
    ops = [o for o in _ops(whole) if " while(" not in o[1]]
    # the longest op of the step, left out of the record
    name, _, _ = max(ops, key=lambda o: o[2])
    lacking = _run("mellum2_share4_train_8k", leave_out=(name,))
    left_out = sum(s for n, _, s in ops if n == name)
    seconds, calls = scope_trace.by_scope(lacking)
    assert seconds[("", "")] == pytest.approx(left_out)
    assert ("", "") not in scope_trace.by_scope(whole)[0]
    unnamed = importlib.import_module("perfbench.metrics.scope_unnamed_device_pct")
    total = sum(seconds.values())
    assert unnamed.read(lacking) == pytest.approx(
        100.0 * (left_out + seconds[("", "forward")]) / total)
    assert unnamed.read(lacking) > unnamed.read(whole) > 0


def test_containers_go_by_the_record_and_by_the_text():
    run = _run("keye_share8_train_16k")
    record = scope_trace.record_of(run)
    texts = {n: t for n, t, _ in _ops(run)}
    conditionals = [n for n, t in texts.items() if " conditional(" in t]
    # a conditional is named for its predicate (``cond.14.clone``), not its opcode
    assert conditionals and not any(n.startswith("conditional") for n in conditionals)
    assert set(conditionals) <= set(record["containers"])
    assert all(scope_trace.is_container(texts[n], record) for n in conditionals)
    # a record that lists none: the text still tells
    assert all(scope_trace.is_container(texts[n], {}) for n in conditionals)
    plain = next(t for n, t in texts.items() if n.startswith("fusion"))
    assert not scope_trace.is_container(plain, record)


@pytest.mark.parametrize("workload", CELLS)
def test_the_seven_readers(workload):
    run = _run(workload)
    seconds, calls = scope_trace.by_scope(run)
    per_step = {key: 1e3 * value / calls for key, value in seconds.items()}
    out = harness.read_metrics(run, [m for m in run.cell.per_layer if m["name"] in SCOPE_METRICS])
    want = {
        "scope_optimizer_device_ms_per_step": per_step[("optimizer", "forward")],
        "scope_unnamed_device_pct":
            100.0 * seconds[("", "forward")] / sum(seconds.values()),
    }
    if workload == "tgs_kfold_train":
        want["scope_loss_device_ms_per_step"] = per_step[("loss", "backward")]
    else:
        want.update({
            "scope_recompute_device_ms_per_step": per_step[("decoder/attn_proj", "recompute")],
            # the whole block: projections and a sparse layer's parts too
            "scope_attention_device_ms_per_step": per_step[("decoder/attn_proj", "recompute")]
            + per_step[("decoder/attn_sparse/indexer", "forward")],
            "scope_moe_device_ms_per_step": per_step[("decoder/moe/experts", "backward")],
            "scope_head_loss_device_ms_per_step": per_step[("decoder/head_loss", "forward")],
        })
    assert set(out) == set(want)
    for name, value in want.items():
        assert out[name] == {"value": pytest.approx(value), "unit": "%" if "pct" in name else "ms"}


def test_nothing_to_read_gives_nothing():
    """A parent that writes no record, a run with no trace, a record that
    went to a file beside a ledger this run no longer has, a record of
    another program: no metric, no error."""
    cell_metrics = harness.load_cell("mellum2_share4_train_8k").per_layer
    mine = [m for m in cell_metrics if m["name"] in SCOPE_METRICS]
    assert len(mine) == 6
    record = scope_trace.record_of(_run("mellum2_share4_train_8k"))
    beside = {k: v for k, v in record.items() if k not in ("ops", "chains", "mixed")}
    for run in (
        _run("mellum2_share4_train_8k", ledger=[{"event": "run_header"}]),
        _run("mellum2_share4_train_8k", trace=False),
        _run("mellum2_share4_train_8k", ledger=[dict(beside, file="program_scopes-0.json")]),
        _run("mellum2_share4_train_8k", ledger=[dict(record, program="jit_prepare")]),
    ):
        assert scope_trace.by_scope(run) is None
        assert harness.read_metrics(run, mine) == {}
    # a scope the program never opens: that metric alone is left out
    run = _run("mellum2_share4_train_8k")
    assert scope_trace.ms_per_step(run, lambda scope, which: scope == "seg/aspp") is None
    assert scope_trace.ms_per_step(run, lambda scope, which: scope == "optimizer") > 0


def test_the_newest_record_of_the_step_decides():
    run = _run("mellum2_share4_train_8k")
    newest = dict(scope_trace.record_of(run), ops=[], instructions=0)
    run.ledger = run.ledger + [newest]
    seconds, _ = scope_trace.by_scope(run)
    assert set(seconds) == {("", "")}


def test_benchmark_names_the_readers_files():
    with open(os.path.join(tiny_lm.REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"].startswith("scope_")]
    # the new entries stand at the end of the list, in the issue's order
    assert [m["name"] for m in bench["per_layer"]][-7:] == SCOPE_METRICS == [m["name"] for m in mine]
    decoders = CELLS[1:]
    for metric in mine:
        module = importlib.import_module("perfbench.metrics." + metric["name"])
        assert callable(module.read) and module.__doc__
        assert (metric["source"], metric["moves"], metric["better"]) == (
            "device_trace", "train_images_per_s", "lower")
        assert metric["unit"] == ("%" if metric["name"].endswith("_pct") else "ms")
        assert sorted(metric) == ["better", "layer", "moves", "name", "source", "unit", "workloads"]
    lists = {m["name"]: m["workloads"] for m in mine}
    assert lists["scope_optimizer_device_ms_per_step"] == CELLS == lists["scope_unnamed_device_pct"]
    assert lists["scope_loss_device_ms_per_step"] == ["tgs_kfold_train"]
    for name in SCOPE_METRICS[2:6]:
        assert lists[name] == decoders, name
    layers = {m["name"]: m["layer"] for m in mine}
    assert {layers[n] for n in SCOPE_METRICS[3:6]} == {"models and kernels"}
    assert {layers[n] for n in SCOPE_METRICS[:3] + SCOPE_METRICS[6:]} == {"step"}
    assert os.path.exists(os.path.join(tiny_lm.REPO, "perfbench", "scope_trace.py"))
