"""A tiny copy of the Kimi-Linear cell for CPU tests: the real BENCHMARK.json
and files with every size cut (hidden 64; KDA with 2 heads of 16; MLA with 2
heads, a compressed key-value of 16, q.k of 8 + 4 and v of 8; a dense first
layer of 96, a shared expert of 32 beside 8 experts of 32 top-2 with 4 held;
vocabulary 256 with 128 held; float32, sequences of 64 tokens), so that a whole
run fits a test. The cell's own sizes run only on the chip."""

from __future__ import annotations

import importlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

WORKLOAD = "kimi_linear_share32_train_16k"
CONFIG = "kimi_linear_48b_a3b_share32"
TINY_SIZES = dict(
    hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    num_experts=4, num_experts_per_token=2, moe_intermediate_size=32, intermediate_size=96,
    vocab_size=128, sequence_length=64, dtype="float32",
)
TINY_LINEAR = dict(num_heads=2, head_dim=16)
TINY_STREAM = {"median_length": 20.0, "sigma": 1.0, "min_length": 4, "max_length": 64,
               "zipf_exponent": 1.0}
# the numbers the committed limits file names, and no other: what a fault or a
# control fails here it fails by a comparison the cell makes. A sound float32
# run reads under a thousandth everywhere
TINY_LIMITS = {
    "loss1_gap": 0.001, "loss2_gap": 0.001,
    "grad1_head_diff": 0.01, "grad1_median_gap": 0.001, "grad1_router_gap": 0.01,
    "grad1_expert_gap": 0.01, "delta_gap": 0.02, "delta_median_gap": 0.002,
    "moe_pairs_dropped": 0,
    "grad1_gate_gap": 0.01, "grad1_shared_gap": 0.01, "grad1_dense_gap": 0.01,
    "grad1_kda_gap": 0.01, "grad1_latent_gap": 0.01,
}


def committed_config() -> dict:
    with open(os.path.join(REPO, "perfbench", "configs", CONFIG + ".json"), encoding="utf-8") as f:
        return json.load(f)


def tiny_config(share_n: int = 2, share_s: int = 1, **sizes) -> dict:
    cfg = committed_config()
    cfg.update(TINY_SIZES)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"], **TINY_LINEAR)
    cfg.update(sizes)
    cfg["share"] = {"n": share_n, "s": share_s}
    cfg["train"] = dict(cfg["train"], lr=3e-3)
    return cfg


def tiny_root(tmp_path, **sizes) -> str:
    root = str(tmp_path / "root")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(root, "perfbench", sub))
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == WORKLOAD)
    bench["workloads"] = [cell]
    bench["configs"] = [c for c in bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(root, "perfbench", "configs", cell["config"] + ".json"), "w") as f:
        json.dump(tiny_config(**sizes), f)
    with open(os.path.join(REPO, "perfbench", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    traffic.update(global_batch=2, sequence_length=TINY_SIZES["sequence_length"],
                   stream=TINY_STREAM, stream_seed=5, warmup_steps=4, trace_seconds=1.0)
    with open(os.path.join(root, "perfbench", "traffic", cell["traffic"] + ".json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "perfbench", "limits", WORKLOAD + ".json"), "w") as f:
        json.dump(TINY_LIMITS, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def load_cell(tmp_path, **sizes):
    from perfbench import harness

    return harness.load_cell(WORKLOAD, tiny_root(tmp_path, **sizes))


def run_cell(tmp_path, monkeypatch, seed: int = 7, seconds: float = 0.5, **options):
    """Drive one run past the look for a chip: (result, checks)."""
    import jax

    from perfbench import harness, peaks

    cell = load_cell(tmp_path)
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind, peaks.PEAKS["TPU v5 lite"])
    entry = importlib.import_module("perfbench.entries." + cell.traffic["entry"])
    return entry.run(cell, seed=seed, seconds=seconds, trace=False, process_t0=0.0, **options)
