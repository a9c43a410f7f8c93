"""The decoder cell at a tiny size on the CPU: its files against the catalog
and the preset, a whole sound run, each fault and each control failing the
comparison, the FLOP counts against hand counts, the trace readers on a small
recorded trace, and the exit without a chip. No number of such a run is a
device number."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from perfbench import compare, flops_lm, harness, lm_trace, lm_weights, peaks, xtrace
from perfbench.entries import lm_fit
from perfbench.reference import mellum_decoder as reference
from perfbench.tests import tiny_lm

HERE = os.path.dirname(os.path.abspath(__file__))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "num_attention_heads", "num_key_value_heads",
           "vocab_size"]


def _committed():
    with open(os.path.join(tiny_lm.REPO, "perfbench", "configs",
                           "mellum2_12b_a2p5b_share4.json"), encoding="utf-8") as f:
        return json.load(f)


# -- the files -------------------------------------------------------------------


def test_configuration_holds_the_catalogs_keys():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    cfg = _committed()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg["published"][key] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    n = cfg["share"]["n"]
    assert cfg["num_experts"] * n == cfg["published"]["num_experts"]
    assert cfg["num_attention_heads"] * n == cfg["published"]["num_attention_heads"]
    assert cfg["num_key_value_heads"] * n == cfg["published"]["num_key_value_heads"]
    assert cfg["vocab_size"] * n == cfg["published"]["vocab_size"]
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]


def test_file_is_the_preset_and_counts_its_parameters():
    import dataclasses
    import math

    from tensorflowdistributedlearning_tpu.configs import get_preset

    cell = harness.load_cell(tiny_lm.WORKLOAD)
    preset = get_preset(cell.config["preset"])
    assert lm_fit.model_config(cell) == preset.model
    from tensorflowdistributedlearning_tpu.config import TokenStreamConfig

    # the traffic's stream is the program's default one, under a seed of its own
    assert lm_fit.train_config(cell) == dataclasses.replace(
        preset.train, seed=cell.traffic["stream_seed"], n_devices=1,
        token_stream=TokenStreamConfig())
    assert cell.traffic["warmup_steps"] == 20 and "routing_seed" not in cell.traffic
    assert cell.traffic["global_batch"] == preset.global_batch
    assert cell.traffic["sequence_length"] == cell.config["sequence_length"]
    spec = reference.param_spec(cell.config)
    assert sum(math.prod(s) for s, _ in spec.values()) == cell.config["n_params"] == 531_452_160


def test_benchmark_names_the_cells_files():
    with open(os.path.join(tiny_lm.REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [tiny_lm.WORKLOAD]]
    assert len(mine) == 8
    for metric in mine:
        path = os.path.join(HERE, "..", "metrics", metric["name"].replace(".", "_") + ".py")
        assert os.path.exists(path), metric["name"]
        assert metric["moves"] == "train_images_per_s"
    cell = harness.load_cell(tiny_lm.WORKLOAD)
    assert cell.chips == 1 and cell.traffic["entry"] == "lm_fit" and set(cell.limits)


def test_refuses_without_a_chip():
    done = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", tiny_lm.WORKLOAD, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tiny_lm.REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 2 and done.stdout.strip() == ""
    assert "no accelerator" in done.stderr


# -- a whole run -------------------------------------------------------------------


def test_sound_run_is_correct(tmp_path, monkeypatch, capsys):
    got = {}
    result, checks = tiny_lm.run_cell(tmp_path, monkeypatch, seconds=2.0, collect=got)
    assert result["correct"] is True, checks
    with open(os.path.join(tiny_lm.REPO, "perfbench", "limits", tiny_lm.WORKLOAD + ".json")) as f:
        assert set(json.load(f)) <= set(got["numbers"])  # every limit names a number read
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_images_per_s", "setup_s"}
    # the window holds the run's first window emission (warm-up 4, a window
    # every 20 steps), and nothing compiles inside it
    assert result["window"]["steps"] > 20 and result["window"]["compiles_inside"] == 0
    # the same work whatever the seed: of each token's 2 experts 1 is this
    # share's, in each of 4 layers (routed_flip_share 0: the program's counts
    # are the reference's)
    assert np.asarray(got["reference"]["routed1"]).sum(-1).tolist() == [2 * 64] * 4
    harness.emit(result, checks)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks" and "check routed_flip_share = " in err


def _plant(monkeypatch, breaker):
    from tensorflowdistributedlearning_tpu.train import step as step_lib

    real_factory = step_lib.make_train_step
    monkeypatch.setattr(
        step_lib, "make_train_step", lambda *a, **k: breaker(real_factory(*a, **k))
    )


def unchanged_state(real):
    import jax.numpy as jnp

    def step(state, batch):
        kept = jax.tree.map(jnp.copy, state)  # the real step donates its input
        _, metrics = real(state, batch)
        return kept, metrics

    return step


def one_sequence_left_out(real):
    import jax.numpy as jnp

    def step(state, batch):
        first = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        return real(state, jax.tree.map(lambda x: jnp.concatenate([x, x]), first))

    return step


@pytest.mark.parametrize("breaker", [unchanged_state, one_sequence_left_out])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, breaker):
    _plant(monkeypatch, breaker)
    result, checks = tiny_lm.run_cell(tmp_path, monkeypatch)
    assert result["correct"] is False, checks
    if breaker is unchanged_state:
        assert checks["delta_gap"][0] == pytest.approx(1.0)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
@pytest.mark.parametrize("shares,k", [(4, 8), (2, 2), (1, 2)])
def test_every_share_is_routed_the_same_number_of_pairs(seed, shares, k):
    """The routers' columns are one share's, repeated: whatever the seed and
    the token, k / shares of its k experts are each share's."""
    held, d = 16 // shares, 32
    spec = {"layers_0/moe/router": ((d, held * shares), f"router:{shares}")}
    router = np.asarray(lm_weights.make_weights(spec, seed)["layers_0/moe/router"])
    for share in range(1, shares):
        np.testing.assert_array_equal(router[:, :held], router[:, share * held:(share + 1) * held])
    assert np.unique(router[0, :held]).size == held  # inside a share the columns differ
    other = np.asarray(lm_weights.make_weights(spec, seed + 1)["layers_0/moe/router"])
    assert not np.allclose(router, other)  # and they follow the seed
    u = np.random.default_rng(0).standard_normal((500, d)).astype(np.float32)
    from tensorflowdistributedlearning_tpu.parallel import expert

    _, chosen = expert.top_k_routing(u @ router, k)
    per_share = np.stack([((np.asarray(chosen) // held) == s).sum(-1) for s in range(shares)])
    assert (per_share == k // shares).all()


# -- the reference with a fault or a precision below in the program's place -------


@pytest.fixture(scope="module")
def followed(tmp_path_factory):
    cell = tiny_lm.load_cell(tmp_path_factory.mktemp("lm"))
    cfg = cell.config
    params0 = jax.device_get(lm_weights.make_weights(reference.param_spec(cfg), 3))
    from tensorflowdistributedlearning_tpu.data import tokens

    stream = tokens.packed_token_batches(
        2, cfg["sequence_length"], cfg["vocab_size"],
        tokens.TokenStreamConfig(**tiny_lm.TINY_STREAM), seed=3)
    batches = [next(stream) for _ in range(harness.FOLLOWED_STEPS)]
    sound = reference.train_steps(cfg, dict(params0), batches)
    return cell, cfg, params0, batches, sound


def _numbers(cfg, planted, sound, params0):
    observed = dict(planted)
    return lm_fit.lm_numbers(reference, cfg, observed, sound, params0)


FAULTS = ["top_k_minus_one", "no_renorm", "no_window", "no_yarn", "cross_documents",
          "capacity", "drop_half", "unchanged"]


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_fails_the_comparison(followed, fault):
    cell, cfg, params0, batches, sound = followed
    planted = reference.train_steps(cfg, dict(params0), batches, faults=(fault,))
    numbers = _numbers(cfg, planted, sound, params0)
    numbers["moe_pairs_dropped"] = 0.0
    checks, correct = compare.verdict(numbers, cell.limits)
    assert not correct, (fault, numbers)


@pytest.mark.parametrize("quant", ["quant_int8", "quant_e4m3"])
def test_lower_precision_fails_and_the_reference_again_passes(followed, quant):
    cell, cfg, params0, batches, sound = followed
    planted = reference.train_steps(cfg, dict(params0), batches, quant=getattr(reference, quant))
    numbers = dict(_numbers(cfg, planted, sound, params0), moe_pairs_dropped=0.0)
    assert not compare.verdict(numbers, cell.limits)[1], numbers
    assert numbers["grad1_head_diff"] > cell.limits["grad1_head_diff"]
    if quant == "quant_int8":  # and the reference again, in its own place, passes
        again = reference.train_steps(cfg, dict(params0), batches)
        numbers = dict(_numbers(cfg, again, sound, params0), moe_pairs_dropped=0.0)
        assert compare.verdict(numbers, cell.limits)[1], numbers


# -- the counts --------------------------------------------------------------------


def test_flops_against_hand_counts():
    cfg = _committed()
    traffic = {"global_batch": 2, "sequence_length": 8192}
    tokens = 16384
    assert flops_lm.tokens_per_step(traffic) == tokens
    # q and o 2304x1024, k and v 2304x128, two operations a multiply-add
    assert flops_lm.projection_flops(cfg, tokens) == 2 * tokens * (2 * 2304 * 1024 + 2 * 2304 * 128)
    # a pair costs QK^T and PV, 2 x 128 each, on each of the 8 heads held
    assert flops_lm.attention_flops(cfg, 1000.0) == 1000 * 8 * 4 * 128
    assert flops_lm.expert_product_flops(cfg, 7.0) == 7 * 2 * 2304 * 896
    assert flops_lm.router_flops(cfg, tokens) == 2 * tokens * 2304 * 64
    assert flops_lm.head_flops(cfg, tokens) == 2 * tokens * 2304 * 24576
    keys = {"sliding_attention": 1000.0, "full_attention": 2500.0}
    parts = flops_lm.step_flops(cfg, traffic, keys, moe_pairs_per_step=4 * 32768.0)
    forward = (
        4 * flops_lm.projection_flops(cfg, tokens)
        + (3 * 1000 + 2500) * tokens * 8 * 4 * 128
        + 3 * 4 * 32768 * 2 * 2304 * 896
        + 4 * flops_lm.router_flops(cfg, tokens) + flops_lm.head_flops(cfg, tokens)
    )
    assert parts["total"] == pytest.approx(3 * forward)
    # the issue's reckoning: 278 MFLOP a token forward, 13.7 TFLOP a step
    assert forward / tokens == pytest.approx(278e6, rel=0.03)
    assert parts["head"] / parts["total"] == pytest.approx(0.41, abs=0.02)
    chip = peaks.PEAKS["TPU v5 lite"]
    # 32,768 pairs a layer: each product is bound by compute (0.69 ms against
    # 0.30 ms of traffic), nine products a layer, four layers
    one = 32768 * 2 * 2304 * 896 / chip.bf16_flops
    assert flops_lm.experts_floor_s(cfg, 4 * 32768.0, chip) == pytest.approx(36 * one)
    work = (3 * 1000 + 2500) * tokens * 8 * 4 * 128
    assert flops_lm.attention_floor_s(cfg, traffic, keys, chip) == pytest.approx(
        3 * work / chip.bf16_flops)


def test_window_counters_are_the_hand_counts():
    """What the program's window fields count, against counts by hand on the
    fed batch: the readers price the step from them."""
    from tensorflowdistributedlearning_tpu.data import tokens

    batch = next(tokens.packed_token_batches(
        2, 64, 128, tokens.TokenStreamConfig(**tiny_lm.TINY_STREAM), seed=1))
    window, full, sliding = 8, 0, 0
    for seg in batch["segment_ids"]:
        for i in range(len(seg)):
            seen = [j for j in range(i + 1) if seg[j] == seg[i]]
            full += len(seen)
            sliding += len([j for j in seen if i - j < window])
    pos = batch["positions"].astype(np.float64) + 1
    assert pos.sum() == full and np.minimum(pos, window).sum() == sliding
    pairs = flops_lm.attention_pairs({"full_attention": full / 128}, 128)
    assert pairs["full_attention"] == pytest.approx(full)


# -- the trace readers on a small recorded trace ------------------------------------


def _recorded_run(tmp_path):
    cell = harness.load_cell(tiny_lm.WORKLOAD)
    trace = xtrace.Trace.from_json(os.path.join(HERE, "recorded_trace_lm.json"))
    windows = [{"event": "step_window", "step": 40, "steps": 20, "moe_pairs": 20 * 16000,  # the traced run routed few pairs here (PERF.md §6)
                "moe_pairs_dropped": 0, "moe_load_max_over_mean": 1.5, "data_wait_s": 0.1,
                "images_per_sec": 8.0,
                "attn_keys_per_query": {"sliding_attention": 900.0, "full_attention": 2400.0}}]
    return harness.Run(cell=cell, ledger=windows, windows=windows, global_batch=2, steps=20,
                       wall_s=5.0, setup_s=1.0, trace=trace, trace_steps=2, trace_wall_s=0.5,
                       device={}, peaks=peaks.PEAKS["TPU v5 lite"])


def test_parts_of_the_recorded_trace(tmp_path):
    """The shape rules against the kernels' own names (which the v5e's trace
    keeps: ``splash_mqa_*``, ``gmm``, ``tgmm``), and nothing counted twice."""
    run = _recorded_run(tmp_path)
    seconds, calls = lm_trace.part_seconds(run)
    with open(os.path.join(HERE, "recorded_trace_lm.json"), encoding="utf-8") as f:
        by_name = json.load(f)["expected_by_kernel_name_ms_per_step"]
    assert calls == by_name["calls"] == 3
    for part in ("attention", "moe_experts"):
        assert 1e3 * seconds[part] / calls == pytest.approx(by_name[part]), part
    ops = xtrace.ops_inside(run.trace, "jit_step")
    z = lm_trace.sizes(run.cell)
    loops = [e for e in ops if lm_trace.part_of(e[0], z) == "container"]
    assert loops and all(e[0].startswith("%while") for e in loops)
    # a loop's span holds its body's ops, which are events of their own
    assert sum(seconds.values()) == pytest.approx(
        sum(e[2] for e in ops if e not in loops) / 1e9)
    # the head's loss is a scan over four chunks of 4,096 tokens, twice (the
    # backward pass recomputes the logits): its ops move [4096, 24576] logits
    logits = sum(e[2] for e in ops if "[4096,24576]" in e[0] and e not in loops) / 1e9
    assert 0.7 * seconds["head_loss"] <= logits <= seconds["head_loss"]
    # routing, sort, gather, activation, combine: over the 131,072 sorted pair
    # rows, the same pairs still by token [16384, 8, ...], the router's outputs
    needles = ("[131072,", "[131072]", "[16384,8,", "[16384,8]", "[16384,64]")
    rows = sum(e[2] for e in ops if any(n in e[0] for n in needles)
               and not e[0].lstrip("%").startswith(("gmm", "tgmm", "while"))) / 1e9
    assert seconds["moe_other"] == pytest.approx(rows, rel=0.02)


def test_readers_on_the_recorded_trace(tmp_path):
    run = _recorded_run(tmp_path)
    cell = run.cell
    out = harness.read_metrics(run, cell.per_layer)
    assert {m["name"] for m in cell.per_layer} == set(out)
    for share in ("lm_step_mfu", "moe_experts_roofline", "attention_roofline"):
        assert 0 < out[share]["value"] <= 100, (share, out[share])
    assert out["moe_load_max_over_mean"]["value"] == 1.5
    assert out["data_wait_pct.lm"]["value"] == pytest.approx(100 * 0.1 / 5.0)
    seen = lm_trace.counters(run)
    work = flops_lm.step_flops(cell.config, cell.traffic, seen["keys_per_query"],
                               seen["moe_pairs"])["total"]
    assert out["lm_step_mfu"]["value"] == pytest.approx(100 * work / (0.25 * 197e12))


def test_readers_return_nothing_where_the_program_writes_nothing(tmp_path):
    """On the parent the windows hold no decoder fields: no metric, no error."""
    run = _recorded_run(tmp_path)
    run.windows = [{"event": "step_window", "step": 40, "steps": 20, "images_per_sec": 8.0,
                    "data_wait_s": 0.0}]
    out = harness.read_metrics(run, run.cell.per_layer)
    assert "lm_step_mfu" not in out and "moe_experts_roofline" not in out
    assert "moe_load_max_over_mean" not in out
