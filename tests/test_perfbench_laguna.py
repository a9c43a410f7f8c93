"""The mixed-layer decoder cell (``laguna_share8_train_16k``) at a tiny size on
the CPU: its files against the preset and the issue's traffic, a whole sound
run, each planted fault and each precision below failing the comparison, the
FLOP counts against hand counts, the trace reader's shape rules on op lines of
the chip's own trace and on a small recorded trace, the counters' metrics, and
the readers' silence where the program writes nothing. No number of such a run
is a device number."""

import dataclasses
import importlib
import json
import os

import jax
import numpy as np
import pytest

from perfbench import compare, flops_lm, flops_mixed, harness, lm_mixed_trace, lm_weights, \
    peaks, xtrace
from perfbench.entries import lm_mixed_fit
from perfbench.reference import laguna_decoder as reference
from perfbench.tests import tiny_mixed

HERE = os.path.join(tiny_mixed.REPO, "perfbench", "tests")
NEW_METRICS = [
    "lm_mixed_step_mfu", "mixed_attention_window_roofline", "mixed_attention_full_roofline",
    "small_experts_roofline", "mixed_attention_window_device_ms_per_step",
    "mixed_attention_full_device_ms_per_step", "small_experts_device_ms_per_step",
    "shared_dense_mlp_device_ms_per_step", "mixed_head_loss_device_ms_per_step",
    "small_experts_tile_fill_pct", "attn_gate_open_pct"]


# -- the files -------------------------------------------------------------------


def test_file_is_the_preset_and_the_traffic_is_the_issues():
    from tensorflowdistributedlearning_tpu.configs import get_preset

    cell = harness.load_cell(tiny_mixed.WORKLOAD)
    preset = get_preset(cell.config["preset"])
    assert lm_mixed_fit.model_config(cell) == preset.model
    assert lm_mixed_fit.train_config(cell) == dataclasses.replace(
        preset.train, seed=cell.traffic["stream_seed"], n_devices=1)
    traffic = cell.traffic
    assert traffic["entry"] == "lm_mixed_fit" and cell.chips == 1
    assert (traffic["global_batch"], traffic["sequence_length"]) == (1, 16384)
    assert traffic["global_batch"] == preset.global_batch
    assert traffic["stream"] == {"median_length": 8192.0, "sigma": 1.0, "min_length": 256,
                                 "max_length": 16384, "zipf_exponent": 1.0}
    assert (traffic["stream_seed"], traffic["warmup_steps"], traffic["trace_seconds"]) == (
        20261004, 10, 8.0)
    assert traffic["sequence_length"] == cell.config["sequence_length"]
    # each held expert sees 512 tokens a step, one row tile, as the deployment's group gives it
    cfg = cell.config
    assert 16384 * cfg["num_experts_per_tok"] // (cfg["num_experts"] * cfg["share"]["n"]) \
        == 512 == flops_mixed.ROW_TILE
    assert flops_mixed.segment_rows(cfg, traffic) == 20480


def test_benchmark_names_the_cells_files():
    with open(os.path.join(tiny_mixed.REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == tiny_mixed.CONFIG)
    cfg = tiny_mixed.committed_config()
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"perfbench/configs/{tiny_mixed.CONFIG}.json"
    work = next(w for w in bench["workloads"] if w["name"] == tiny_mixed.WORKLOAD)
    assert (work["config"], work["traffic"], work["chips"]) == (
        tiny_mixed.CONFIG, "lm_code_16k_b1", 1)
    for said in ("8", "512 tokens", "dense", "shared", "head", "more than their share"):
        assert said in work["why"], said
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [tiny_mixed.WORKLOAD]]
    assert [m["name"] for m in mine] == NEW_METRICS
    for metric in mine:
        assert metric["moves"] == "train_images_per_s"
        assert metric["layer"] == ("step" if metric["name"] == "lm_mixed_step_mfu"
                                   else "models and kernels")
        module = importlib.import_module("perfbench.metrics." + metric["name"])
        assert callable(module.read)
    # the cell's entries, each once, its metrics one after another in the
    # issue's order (later PRs append after them)
    assert [c["name"] for c in bench["configs"]].count(tiny_mixed.CONFIG) == 1
    assert [w["name"] for w in bench["workloads"]].count(tiny_mixed.WORKLOAD) == 1
    first = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][first:first + len(mine)] == mine
    # and the tiny cell of these tests compares the same numbers
    assert set(tiny_mixed.TINY_LIMITS) == set(harness.load_cell(tiny_mixed.WORKLOAD).limits)


# -- a whole run -------------------------------------------------------------------


def test_sound_run_is_correct(tmp_path, monkeypatch, capsys):
    got = {}
    result, checks = tiny_mixed.run_cell(tmp_path, monkeypatch, seconds=2.0, collect=got)
    assert result["correct"] is True, checks
    assert set(checks) == set(tiny_mixed.TINY_LIMITS)
    for name in ("grad1_gate_gap", "grad1_shared_gap", "grad1_dense_gap", "routed_flip_share"):
        assert name in got["numbers"]
    assert result["read_not_compared"]["routed_flip_share"] == 0.0
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_images_per_s", "setup_s"}
    assert result["window"]["steps"] > 20 and result["window"]["compiles_inside"] == 0
    assert np.asarray(got["reference"]["routed1"]).shape == (4, 4)  # sparse layers x held
    harness.emit(result, checks)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks" and "check grad1_gate_gap = " in err


def test_a_program_without_heads_by_layer_fails_at_once(monkeypatch):
    from tensorflowdistributedlearning_tpu import config

    @dataclasses.dataclass(frozen=True)
    class Before:
        num_attention_heads: int = 32

    monkeypatch.setattr(config, "DecoderConfig", Before)
    monkeypatch.setattr(harness, "require_chips", lambda n: pytest.fail("looked for a chip"))
    with pytest.raises(SystemExit, match="num_attention_heads_per_layer"):
        lm_mixed_fit.run(harness.load_cell(tiny_mixed.WORKLOAD), seed=1, seconds=1.0,
                         trace=False, process_t0=0.0)


# -- the reference with a fault or a precision below in the program's place -------


@pytest.fixture(scope="module")
def followed(tmp_path_factory):
    cell = tiny_mixed.load_cell(tmp_path_factory.mktemp("mixed"))
    cfg = cell.config
    params0 = jax.device_get(lm_weights.make_weights(reference.param_spec(cfg), 3))
    from tensorflowdistributedlearning_tpu.data import tokens

    stream = tokens.packed_token_batches(
        2, cfg["sequence_length"], cfg["vocab_size"],
        tokens.TokenStreamConfig(**tiny_mixed.TINY_STREAM), seed=3)
    batches = [next(stream) for _ in range(harness.FOLLOWED_STEPS)]
    sound = reference.train_steps(cfg, dict(params0), batches)
    return cell, cfg, params0, batches, sound


def _verdict(cell, cfg, planted, sound, params0):
    numbers = lm_mixed_fit.mixed_numbers(reference, cfg, dict(planted), sound, params0)
    numbers["moe_pairs_dropped"] = 0.0
    checks, correct = compare.verdict(numbers, cell.limits)
    return numbers, checks, correct


# the tiny window is 8 and a document about 20 tokens: a window of 1,024 is no window
FAULTS = list(reference.FAULTS) + ["top_k_minus_one", "capacity", "no_renorm", "drop_half",
                                   "unchanged"]


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_fails_the_comparison(followed, fault):
    cell, cfg, params0, batches, sound = followed
    planted = reference.train_steps(cfg, dict(params0), batches, faults=(fault,))
    numbers, checks, correct = _verdict(cell, cfg, planted, sound, params0)
    assert not correct, (fault, numbers)
    over = {name for name, (value, limit) in checks.items() if not value <= limit}
    if fault == "no_gate":
        assert "grad1_gate_gap" in over, numbers  # the gate's leaves get no gradient at all
    if fault == "no_shared":
        assert "grad1_shared_gap" in over, numbers
    if fault in ("softmax_scores", "scale_one", "top_k_minus_one", "capacity"):
        assert over & {"grad1_expert_gap", "grad1_router_gap"}, (fault, numbers)


@pytest.mark.parametrize("quant", ["quant_int8", "quant_e4m3"])
def test_lower_precision_fails_and_the_reference_again_passes(followed, quant):
    cell, cfg, params0, batches, sound = followed
    planted = reference.train_steps(cfg, dict(params0), batches, quant=getattr(reference, quant))
    numbers, _, correct = _verdict(cell, cfg, planted, sound, params0)
    assert not correct, numbers
    if quant == "quant_int8":  # and the reference again, in its own place, passes
        again = reference.train_steps(cfg, dict(params0), batches)
        assert _verdict(cell, cfg, again, sound, params0)[2]


def test_a_routers_columns_repeat_over_the_shares_so_each_holds_an_eighth_of_the_pairs():
    """The committed sizes' router kind, at a width that fits a test: under
    sigmoid scores too a token's 8 experts are the best one of each share."""
    spec = {"layers_1/moe/router": ((64, 256), "router:8")}
    router = np.asarray(lm_weights.make_weights(spec, 2**31 + 5)["layers_1/moe/router"])
    np.testing.assert_array_equal(router[:, :32], router[:, 32:64])
    u = np.random.default_rng(0).standard_normal((512, 64)).astype(np.float32)
    _, chosen = reference.route({"num_experts_per_tok": 8, "moe_routed_scaling_factor": 2.5},
                                jax.numpy.asarray(u @ router))
    assert np.asarray(chosen)[:, :32].sum() == 512


# -- the counts --------------------------------------------------------------------


def test_flops_against_hand_counts():
    cell = harness.load_cell(tiny_mixed.WORKLOAD)
    cfg, traffic = cell.config, cell.traffic
    t = 16384
    # per token, forward (ISSUE 32): layer 0 108 MFLOP, a sparse window layer 23,
    # the sparse full layer 21, the head 51
    assert flops_mixed.layer_heads(cfg) == [6, 8, 8, 8, 6]
    assert flops_mixed.heads_of_kind(cfg, "sliding_attention") == [8, 8, 8]
    assert flops_mixed.projection_flops(cfg, 1, 6) == 2 * 2048 * (2 * 768 + 2 * 128 + 6)
    assert flops_mixed.projection_flops(cfg, 1, 8) == 2 * 2048 * (2 * 1024 + 2 * 128 + 8)
    dense = flops_mixed.gated_mlp_flops(cfg, 1, 8192)
    shared = flops_mixed.gated_mlp_flops(cfg, 1, 512)
    experts = 3 * flops_lm.expert_product_flops(cfg, 1)  # one held pair a token a layer
    router = flops_lm.router_flops(cfg, 1)
    assert dense == 6 * 2048 * 8192 and shared == experts == 6 * 2048 * 512
    assert router == 2 * 2048 * 256
    layer0 = flops_mixed.projection_flops(cfg, 1, 6) + dense
    window = flops_mixed.projection_flops(cfg, 1, 8) + experts + shared + router
    full = flops_mixed.projection_flops(cfg, 1, 6) + experts + shared + router
    assert round(layer0 / 1e6) == 108 and round(window / 1e6) == 23 and round(full / 1e6) == 21
    assert round(flops_lm.head_flops(cfg, 1) / 1e6) == 51
    keys = {"full_attention": 3000.0, "sliding_attention": 400.0}
    parts = flops_mixed.step_flops(cfg, traffic, keys, 4 * t)
    products = 3 * t * (layer0 + 3 * window + full + flops_lm.head_flops(cfg, 1))
    attention = 3 * 4 * 128 * t * (2 * 6 * 3000.0 + 3 * 8 * 400.0)
    assert parts["attention"] == pytest.approx(attention)
    assert parts["total"] == pytest.approx(products + attention)
    assert 12.2e12 < products < 12.4e12  # the issue's 12.3 TFLOP of products a step
    assert parts["dense"] / products == pytest.approx(0.40, abs=0.01)
    assert parts["shared"] / products == pytest.approx(0.10, abs=0.01)
    assert parts["head"] / products == pytest.approx(0.21, abs=0.01)
    # floors: both kinds are bound by operations at these lengths (a window
    # layer's 8 heads over 400 keys a query: 0.136 ms of products against
    # 0.092 ms of bytes), forward once and backward at twice the operations
    pk = peaks.PEAKS["TPU v5 lite"]
    work = 4 * 128 * 8 * 400.0 * t
    moved = 2 * t * 128 * (2 * 8 + 2)
    assert work / pk.bf16_flops > 2.5 * moved / pk.hbm_bytes_per_s / 2
    assert flops_mixed.attention_floor_s(cfg, traffic, "sliding_attention", 400.0, pk) == \
        pytest.approx(3 * 3 * work / pk.bf16_flops)
    work = 4 * 128 * 6 * 3000.0 * t
    assert flops_mixed.attention_floor_s(cfg, traffic, "full_attention", 3000.0, pk) == \
        pytest.approx(2 * 3 * work / pk.bf16_flops)
    # a window of few keys is bound by bytes: 40 keys a query
    assert flops_mixed.attention_floor_s(cfg, traffic, "sliding_attention", 40.0, pk) == \
        pytest.approx(3 * (1 + 2.5) * moved / pk.hbm_bytes_per_s)
    # experts: four sparse layers (not five), nine products each
    one = max(2 * t * 2048 * 512 / pk.bf16_flops,
              2 * (t * (2048 + 512) + 32 * 2048 * 512) / pk.hbm_bytes_per_s)
    assert flops_mixed.experts_floor_s(cfg, 4 * t, pk) == pytest.approx(4 * 9 * one)


def test_window_counters_are_the_hand_counts(tmp_path):
    """The tiny cell's ledger after a short fit: the fields the readers take,
    against counts by hand from the stream's own batches."""
    from tensorflowdistributedlearning_tpu.data import tokens as tokens_lib
    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

    cell = tiny_mixed.load_cell(tmp_path)
    tcfg = dataclasses.replace(lm_mixed_fit.train_config(cell), train_log_every_steps=4)
    trainer = ClassifierTrainer(str(tmp_path / "run"), None, lm_mixed_fit.model_config(cell), tcfg)
    trainer.fit(batch_size=2, steps=9)
    ledger = harness.read_ledger(str(tmp_path / "run"))
    windows = [e for e in ledger if e.get("event") == "step_window"]
    assert len(windows) == 2
    stream = tokens_lib.packed_token_batches(
        2, 64, cell.config["vocab_size"], tokens_lib.TokenStreamConfig(**tiny_mixed.TINY_STREAM),
        seed=cell.traffic["stream_seed"])
    batches = [next(stream) for _ in range(8)]
    for window, last in zip(windows, (batches[3], batches[7])):  # a window's are its last step's
        seen = np.asarray(last["positions"], np.float64) + 1
        keys = window["attn_keys_per_query"]
        assert keys["full_attention"] == pytest.approx(seen.mean(), abs=0.01)
        assert keys["sliding_attention"] == pytest.approx(np.minimum(seen, 8).mean(), abs=0.01)
        counts = np.asarray(window["moe_expert_tokens"], np.float64) / window["steps"] / 2
        assert counts.shape == (4, 4)
        # by hand from the routed counts: the tiles the held groups overlap
        visits = 0
        for layer in counts * 2:  # a step's two sequences are routed as one batch
            ends = np.cumsum(np.round(layer))
            starts = ends - np.round(layer)
            visits += sum((e - 1) // 512 - s // 512 + 1 for s, e in zip(starts, ends) if e > s)
        assert window["moe_tile_visits"] == pytest.approx(visits)
        assert set(window["attn_gate_mean"]) == {"full_attention", "sliding_attention"}
    run = harness.Run(cell=cell, ledger=ledger, windows=windows, global_batch=2, steps=8,
                      wall_s=1.0, setup_s=1.0, trace=None, trace_steps=0, trace_wall_s=0.0,
                      device={}, peaks=peaks.PEAKS["TPU v5 lite"])
    seen = lm_mixed_trace.counters(run)
    assert seen["tile_visits"] == pytest.approx(
        sum(w["moe_tile_visits"] * w["steps"] for w in windows) / 8)
    fill = importlib.import_module("perfbench.metrics.small_experts_tile_fill_pct").read(run)
    assert fill == pytest.approx(100 * seen["moe_pairs"] / (seen["tile_visits"] * 512))
    gate = importlib.import_module("perfbench.metrics.attn_gate_open_pct").read(run)
    means = seen["gate_mean"]
    # full layers hold 2 + 2 of the tiny model's 16 gated heads, window layers 3 x 4
    assert gate == pytest.approx(100 * (4 * means["full_attention"]
                                        + 12 * means["sliding_attention"]) / 16)


# -- the trace readers ---------------------------------------------------------------

# op lines as the v5e's trace names them (cut from the traced run of this cell,
# my chip runs, PR 32), with the part each belongs to
OPS = json.load(open(os.path.join(HERE, "recorded_ops_mixed.json"), encoding="utf-8"))


@pytest.mark.parametrize("part", sorted({row["part"] for row in OPS["ops"]}))
def test_shape_rules_file_the_chips_own_op_lines(part):
    z = lm_mixed_trace.sizes(harness.load_cell(tiny_mixed.WORKLOAD))
    assert (z["group_window"], z["group_full"], z["segment"]) == ([8], [6], 20480)
    rows = [row for row in OPS["ops"] if row["part"] == part]
    assert rows
    for row in rows:
        assert lm_mixed_trace.part_of(row["op"], z) == part, row["op"][:300]


def _recorded_run():
    with open(os.path.join(HERE, "recorded_trace_mixed.json"), encoding="utf-8") as f:
        recorded = json.load(f)
    trace = xtrace.Trace(recorded["planes"])
    cell = harness.load_cell(tiny_mixed.WORKLOAD)
    windows = [{"event": "step_window", "step": 30, "steps": 10, "images_per_sec": 3.4,
                "moe_pairs": 10 * 65536, "moe_pairs_dropped": 0, "moe_load_max_over_mean": 1.3,
                "moe_tile_visits": 250.0,
                "attn_keys_per_query": {"full_attention": 3000.0, "sliding_attention": 400.0},
                "attn_gate_mean": {"full_attention": 0.5, "sliding_attention": 0.52}}]
    return harness.Run(cell=cell, ledger=windows, windows=windows, global_batch=1, steps=20,
                       wall_s=6.0, setup_s=1.0, trace=trace, trace_steps=recorded["calls"],
                       trace_wall_s=recorded["calls"] * 0.3, device={},
                       peaks=peaks.PEAKS["TPU v5 lite"])


def test_readers_on_the_recorded_trace():
    run = _recorded_run()
    out = harness.read_metrics(run, run.cell.per_layer)
    assert set(out) == set(NEW_METRICS)
    for share in [m for m in NEW_METRICS if m.endswith("_roofline") or m.endswith("_mfu")]:
        assert 0 < out[share]["value"] <= 100, (share, out[share])
    seconds, calls = lm_mixed_trace.part_seconds(run)
    with open(os.path.join(HERE, "recorded_trace_mixed.json"), encoding="utf-8") as f:
        by_name = json.load(f)["expected_by_kernel_name_ms_per_step"]
    assert calls == by_name["calls"]
    for part in ("attention_window", "attention_full", "experts"):
        assert 1e3 * seconds[part] / calls == pytest.approx(by_name[part]), part
    assert out["mixed_attention_window_device_ms_per_step"]["value"] == pytest.approx(
        by_name["attention_window"])
    assert out["small_experts_device_ms_per_step"]["value"] == pytest.approx(
        1e3 * (seconds["experts"] + seconds["experts_other"]) / calls)
    assert out["shared_dense_mlp_device_ms_per_step"]["value"] == pytest.approx(
        1e3 * seconds["shared_dense"] / calls)
    assert out["mixed_head_loss_device_ms_per_step"]["value"] == pytest.approx(
        1e3 * seconds["head_loss"] / calls)
    work = flops_mixed.step_flops(run.cell.config, run.cell.traffic,
                                  run.windows[0]["attn_keys_per_query"], 65536)["total"]
    assert out["lm_mixed_step_mfu"]["value"] == pytest.approx(100 * work / (0.3 * 197e12))
    assert out["small_experts_tile_fill_pct"]["value"] == pytest.approx(100 * 65536 / (250 * 512))
    assert out["attn_gate_open_pct"]["value"] == pytest.approx(100 * (12 * 0.5 + 24 * 0.52) / 36)
    # nothing counted twice: the parts add up to the ops that are no containers
    ops = xtrace.ops_inside(run.trace, "jit_step")
    z = lm_mixed_trace.sizes(run.cell)
    loops = [e for e in ops if lm_mixed_trace.part_of(e[0], z) == "container"]
    assert sum(seconds.values()) == pytest.approx(sum(e[2] for e in ops if e not in loops) / 1e9)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_reader_returns_nothing_where_its_field_is_absent(metric):
    """On a program without these layers the windows hold none of the new
    fields, and an untraced run has no trace: no metric, no error."""
    reader = importlib.import_module("perfbench.metrics." + metric)
    run = _recorded_run()
    run.windows = [{"event": "step_window", "step": 30, "steps": 10, "images_per_sec": 3.4}]
    if metric.endswith("device_ms_per_step"):
        run.trace = None  # these read the trace alone
    assert reader.read(run) is None
    if "device_ms" in metric or "roofline" in metric or "mfu" in metric:
        # another decoder's cell: its configuration has no head counts by layer
        other = _recorded_run()
        other.cell = harness.load_cell("mellum2_share4_train_8k")
        assert reader.read(other) is None
    if metric in ("small_experts_tile_fill_pct", "attn_gate_open_pct"):
        # the parent's windows: the decoder's older fields alone
        older = _recorded_run()
        older.windows = [{k: v for k, v in older.windows[0].items()
                          if k not in ("moe_tile_visits", "attn_gate_mean")}]
        assert reader.read(older) is None


def test_calibration_judges_a_row_by_the_cells_limits():
    from perfbench import calibrate_mixed, calibrate_sparse

    limits = harness.load_cell(tiny_mixed.WORKLOAD).limits
    sound = {name: 0.0 for name in limits if name != "moe_pairs_dropped"}
    rows = [{"kind": "program", "seed": 1, "numbers": dict(sound, moe_pairs_dropped=0.0)},
            {"kind": "no_gate", "seed": 1, "numbers": dict(sound, grad1_gate_gap=1.0,
                                                          routed_flip_share=0.5)}]
    first, second = calibrate_sparse.judge(rows, limits)
    assert first["correct"] is True and first["over"] == []
    assert second["correct"] is False and second["over"] == ["grad1_gate_gap"]
    assert set(calibrate_mixed.QUANTS) == {"int8", "e4m3"}
