"""The configuration files hold what the program's presets hold, and the
reference's parameter list is the program's."""

import dataclasses
import json
import os

import pytest

import tiny  # noqa: F401
from perfbench.entries import training
from perfbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("workload", sorted(tiny.CELLS))
def test_file_is_the_preset(workload, tmp_path):
    from tensorflowdistributedlearning_tpu.configs import get_preset

    # the files as committed, entered into a BENCHMARK.json of the test's own
    # where the real one does not hold the cell yet
    root = str(tmp_path / "root")
    os.makedirs(root)
    os.symlink(os.path.join(tiny.REPO, "perfbench"), os.path.join(root, "perfbench"))
    spec = tiny.CELLS[workload]
    limits = os.path.join(tiny.REPO, "perfbench", "limits", workload + ".json")
    if not os.path.exists(limits):
        pytest.skip("no limits yet: the cell is not in BENCHMARK.json")
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump({
            "paths": ["perfbench"], "end_to_end": [], "per_layer": [],
            "configs": [{"name": spec["config"],
                         "file": "perfbench/configs/" + spec["config"] + ".json"}],
            "workloads": [dict(spec, name=workload)],
        }, f)
    cell = harness.load_cell(workload, root)
    preset = get_preset(cell.config["preset"])
    assert training.model_config(cell) == preset.model
    want = dataclasses.replace(preset.train, seed=5, n_devices=cell.chips)
    assert training.train_config(cell, 5) == want


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    for metric in bench["per_layer"]:
        path = os.path.join(HERE, "..", "metrics", metric["name"].replace(".", "_") + ".py")
        assert os.path.exists(path), metric["name"]
    for cell in bench["workloads"]:
        loaded = harness.load_cell(cell["name"])
        assert loaded.traffic["entry"] in ("train", "fit")
        assert set(loaded.limits), cell["name"]


def test_parameter_counts():
    import importlib
    import math

    cell = harness.load_cell("tgs_kfold_train")
    reference = importlib.import_module("perfbench.reference." + cell.config["reference"])
    spec = reference.param_spec(cell.reference_cfg)
    assert sum(math.prod(s) for s, _ in spec.values()) == cell.config["n_params"] == 41_722_497
