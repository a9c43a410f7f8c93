"""A whole run at a tiny size on the CPU, past the look for a chip: the shape
of the result, and ``correct`` true for the sound program. No number of such
a run is a device number; only counts and the verdict are looked at."""

import json
import os

import pytest

import tiny
from perfbench import harness


@pytest.mark.parametrize("workload", ["tgs_kfold_train", "resnet50_fit"])
def test_sound_run_is_correct(tmp_path, monkeypatch, capsys, workload):
    got = {}
    result, checks = tiny.run_cell(tmp_path, monkeypatch, workload, collect=got)
    assert result["correct"] is True, checks
    committed = os.path.join(tiny.REPO, "perfbench", "limits", workload + ".json")
    if os.path.exists(committed):  # every limit names a number the run reads
        with open(committed, encoding="utf-8") as f:
            assert set(json.load(f)) <= set(got["numbers"])
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_images_per_s", "setup_s"}
    assert result["device"]["count"] == 1
    harness.emit(result, checks)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    for name, (value, limit) in line["checks"].items():
        assert f"check {name} = " in err and value <= limit


def test_data_parallel_run_is_correct(tmp_path, monkeypatch):
    result, checks = tiny.run_cell(tmp_path, monkeypatch, "resnet50_fit_dp4", chips=4)
    assert result["correct"] is True, checks
    assert result["device"]["count"] == 4
