"""A tiny copy of the benchmark for CPU tests: the real BENCHMARK.json and
files, with depth, input size, batch and warm-up cut so that a whole run fits
a test. Widths stay as published — the reference has no other."""

from __future__ import annotations

import importlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# the cells the harness can drive: the ones in BENCHMARK.json and the ones
# whose files wait under perfbench/ for a later PR to enter them there
CELLS = {
    "tgs_kfold_train": {"config": "tgs_salt_bf16", "traffic": "kfold_b256", "chips": 1},
    "resnet50_fit": {"config": "resnet50_classic_imagenet", "traffic": "fit_b256", "chips": 1},
    "resnet50_fit_dp4": {"config": "resnet50_classic_imagenet", "traffic": "fit_dp4_b1024", "chips": 4},
}

# limits for the tiny float32 runs: a sound run reads under a thousandth
TINY_LIMITS = {
    "segmentation": {
        "loss1_gap": 0.01, "loss2_gap": 0.01, "loss3_gap": 0.01,
        "grad1_gap": 0.05, "grad1_head_diff": 0.05, "delta_gap": 0.05,
        "root_norm_var_gap": 0.001, "root_norm_mean_gap": 0.001,
        "prep_image_gap": 0.01, "prep_mask_gap": 0.01, "prep_laplace_gap": 0.01,
    },
    "classification": {
        "loss1_gap": 0.01, "loss2_gap": 0.01, "loss3_gap": 0.01,
        "grad1_gap": 0.05, "grad1_head_diff": 0.05, "delta_gap": 0.05,
        "root_norm_var_gap": 0.001, "root_norm_mean_gap": 0.001,
        "prep_rows_unmatched": 0, "prep_modal_offset_share": 0.5,
    },
}


def tiny_root(tmp_path, workload: str, chips: int = 1, dtype: str = "float32") -> str:
    """Eight rows a chip: fewer leave BatchNorm too few to be steady."""
    root = str(tmp_path / "root")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(root, "perfbench", sub))
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = dict(CELLS[workload], name=workload, chips=chips, why="a tiny copy for the CPU")
    bench["workloads"] = [cell]
    bench["configs"] = [{"name": cell["config"], "reduced": [], "why": "tiny", "source": "tiny",
                         "file": "perfbench/configs/" + cell["config"] + ".json"}]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        metric.pop("workloads", None)
    with open(os.path.join(REPO, "perfbench", "configs", cell["config"] + ".json")) as f:
        cfg = json.load(f)
    if cfg["task"] == "segmentation":
        cfg["model"].update(n_blocks=[1, 1, 1])  # the PNGs are 101x101
    else:
        cfg["model"].update(n_blocks=[1, 1, 1, 1], input_shape=[32, 32], num_classes=10)
    cfg["model"]["dtype"] = dtype
    with open(os.path.join(root, "perfbench", "configs", cell["config"] + ".json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(REPO, "perfbench", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    traffic.update(global_batch=8 * chips, warmup_steps=4, trace_seconds=1.0)
    if "n_images" in traffic["dataset"]:
        traffic["dataset"]["n_images"] = 40
    with open(os.path.join(root, "perfbench", "traffic", cell["traffic"] + ".json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "perfbench", "limits", workload + ".json"), "w") as f:
        json.dump(TINY_LIMITS[cfg["task"]], f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def run_cell(tmp_path, monkeypatch, workload: str, chips: int = 1, seed: int = 7, **options):
    """Drive one run past the look for a chip: (result, checks)."""
    import jax

    from perfbench import harness, peaks

    root = tiny_root(tmp_path, workload, chips)
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind, peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(harness, "WORK", str(tmp_path / "work"))
    cell = harness.load_cell(workload, root)
    entry = importlib.import_module("perfbench.entries." + cell.traffic["entry"])
    return entry.run(cell, seed=seed, seconds=0.5, trace=False, process_t0=0.0, **options)
