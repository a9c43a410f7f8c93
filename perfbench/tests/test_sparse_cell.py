"""The sparse decoder cell at a tiny size on the CPU: its files against the
catalog and the preset, a whole sound run, each planted fault and each control
failing the comparison, the FLOP counts against hand counts, the trace readers
on a small recorded trace, and the readers' silence where the program writes
nothing. No number of such a run is a device number."""

import json
import os

import jax
import numpy as np
import pytest

from perfbench import compare, flops_lm, flops_sparse, harness, lm_sparse_trace, \
    lm_sparse_weights, lm_weights, peaks, xtrace
from perfbench.entries import lm_sparse_fit
from perfbench.reference import keye_decoder as reference
from perfbench.tests import tiny_sparse

HERE = os.path.dirname(os.path.abspath(__file__))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "num_local_experts", "num_attention_heads",
           "num_key_value_heads", "vocab_size"]
NEW_METRICS = ["lm_sparse_step_mfu", "sparse_indexer_roofline", "sparse_attention_roofline",
               "sparse_indexer_device_ms_per_step", "sparse_select_device_ms_per_step",
               "sparse_attention_device_ms_per_step", "sparse_align_device_ms_per_step",
               "sparse_selected_share_pct"]


# -- the files -------------------------------------------------------------------


def test_configuration_holds_the_catalogs_keys():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
    cfg = tiny_sparse.committed_config()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg["published"][key] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    n = cfg["share"]["n"]
    for key in ("num_experts", "num_local_experts", "num_attention_heads", "vocab_size"):
        assert cfg[key] * n == cfg["published"][key], key
    # four key-value heads over eight shares: each is held twice
    assert cfg["num_key_value_heads"] == 1 and cfg["published"]["num_key_value_heads"] * 2 == n
    assert cfg["layer_types"] == ["sparse_attention"] * cfg["published"]["num_hidden_layers"]
    for key in ("assumed", "departures", "derived", "kept", "held", "deployment", "precision"):
        assert cfg[key], key


def test_file_is_the_preset_and_the_traffic_is_the_issues():
    import dataclasses

    from tensorflowdistributedlearning_tpu.configs import get_preset

    cell = harness.load_cell(tiny_sparse.WORKLOAD)
    preset = get_preset(cell.config["preset"])
    assert lm_sparse_fit.model_config(cell) == preset.model
    assert lm_sparse_fit.train_config(cell) == dataclasses.replace(
        preset.train, seed=cell.traffic["stream_seed"], n_devices=1)
    traffic = cell.traffic
    assert (traffic["global_batch"], traffic["sequence_length"]) == (1, 16384)
    assert traffic["global_batch"] == preset.global_batch
    assert traffic["stream"] == {"median_length": 16384.0, "sigma": 1.0, "min_length": 1024,
                                 "max_length": 16384, "zipf_exponent": 1.0}
    assert (traffic["stream_seed"], traffic["warmup_steps"], traffic["trace_seconds"]) == (
        20261003, 10, 8.0)
    assert traffic["sequence_length"] == cell.config["sequence_length"]


def test_benchmark_names_the_cells_files():
    with open(os.path.join(tiny_sparse.REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == tiny_sparse.CONFIG)
    assert entry["reduced"] == REDUCED and entry["file"].endswith(tiny_sparse.CONFIG + ".json")
    work = next(w for w in bench["workloads"] if w["name"] == tiny_sparse.WORKLOAD)
    assert (work["config"], work["traffic"], work["chips"]) == (
        tiny_sparse.CONFIG, "lm_long_16k_b1", 1)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [tiny_sparse.WORKLOAD]]
    assert [m["name"] for m in mine] == NEW_METRICS
    for metric in mine:
        path = os.path.join(HERE, "..", "metrics", metric["name"].replace(".", "_") + ".py")
        assert os.path.exists(path), metric["name"]
        assert metric["moves"] == "train_images_per_s"
    cell = harness.load_cell(tiny_sparse.WORKLOAD)
    assert cell.chips == 1 and cell.traffic["entry"] == "lm_sparse_fit"
    # thirteen of the fourteen numbers read are compared. The routed flips are
    # read only: the chip's readings do not tell bfloat16 from int8 and no fault
    # reads far above a sound run's (PERF.md section 6, PR 30)
    assert set(cell.limits) == {
        "loss1_gap", "loss2_gap", "loss3_gap", "grad1_head_diff", "grad1_median_gap",
        "grad1_router_gap", "grad1_expert_gap", "delta_gap", "delta_median_gap",
        "moe_pairs_dropped", "align_loss1_gap", "grad1_indexer_gap", "selected_flip_share"}
    # and the tiny cell of these tests compares the same numbers
    assert set(tiny_sparse.TINY_LIMITS) == set(cell.limits)


def test_a_leaf_both_weight_files_know_is_the_same_array():
    spec = reference.param_spec(tiny_sparse.tiny_config())
    drawn = lm_sparse_weights.make_weights(spec, 2**31 + 9)
    shared = {k: v for k, v in spec.items() if v[1] != "norm_bias"}
    again = lm_weights.make_weights(shared, 2**31 + 9)
    for name in shared:
        np.testing.assert_array_equal(np.asarray(drawn[name]), np.asarray(again[name]))
    biases = [k for k, v in spec.items() if v[1] == "norm_bias"]
    assert len(biases) == 4 and all(float(np.abs(drawn[k]).max()) > 0 for k in biases)
    with pytest.raises(ValueError):
        lm_weights.make_weights({biases[0]: spec[biases[0]]}, 1)


# -- a whole run -------------------------------------------------------------------


def test_sound_run_is_correct(tmp_path, monkeypatch, capsys):
    got = {}
    result, checks = tiny_sparse.run_cell(tmp_path, monkeypatch, seconds=2.0, collect=got)
    assert result["correct"] is True, checks
    with open(os.path.join(tiny_sparse.REPO, "perfbench", "limits",
                           tiny_sparse.WORKLOAD + ".json")) as f:
        assert set(json.load(f)) <= set(got["numbers"])  # every limit names a number read
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_images_per_s", "setup_s"}
    assert result["window"]["steps"] > 20 and result["window"]["compiles_inside"] == 0
    # what the reference counts: every query reads min(topk, visible) keys
    fed = got["fed"][0]
    visible = np.asarray(fed["positions"]).astype(np.int64) + 1
    reads = np.asarray(got["reference"]["selected1"])
    assert reads.shape == (4, 64) and (reads.sum(-1) == np.minimum(visible, 16).sum()).all()
    harness.emit(result, checks)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks" and "check selected_flip_share = " in err


def test_a_program_without_the_layer_type_fails_at_once(monkeypatch):
    from tensorflowdistributedlearning_tpu import config

    monkeypatch.delattr(config, "DECODER_LAYER_TYPES")
    with pytest.raises(SystemExit, match="sparse_attention"):
        lm_sparse_fit.run(harness.load_cell(tiny_sparse.WORKLOAD), seed=1, seconds=1.0,
                          trace=False, process_t0=0.0)


# -- the reference with a fault or a precision below in the program's place -------


@pytest.fixture(scope="module")
def followed(tmp_path_factory):
    cell = tiny_sparse.load_cell(tmp_path_factory.mktemp("sparse"))
    cfg = cell.config
    params0 = jax.device_get(lm_sparse_weights.make_weights(reference.param_spec(cfg), 3))
    from tensorflowdistributedlearning_tpu.data import tokens

    stream = tokens.packed_token_batches(
        2, cfg["sequence_length"], cfg["vocab_size"],
        tokens.TokenStreamConfig(**tiny_sparse.TINY_STREAM), seed=3)
    batches = [next(stream) for _ in range(harness.FOLLOWED_STEPS)]
    sound = reference.train_steps(cfg, dict(params0), batches)
    return cell, cfg, params0, batches, sound


def _verdict(cell, cfg, planted, sound, params0):
    numbers = lm_sparse_fit.sparse_numbers(reference, cfg, dict(planted), sound, params0)
    numbers["moe_pairs_dropped"] = 0.0
    checks, correct = compare.verdict(numbers, cell.limits)
    return numbers, checks, correct


FAULTS = list(reference.FAULTS) + ["top_k_minus_one", "no_renorm", "capacity", "drop_half",
                                   "unchanged"]


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_fails_the_comparison(followed, fault):
    cell, cfg, params0, batches, sound = followed
    planted = reference.train_steps(cfg, dict(params0), batches, faults=(fault,))
    numbers, checks, correct = _verdict(cell, cfg, planted, sound, params0)
    assert not correct, (fault, numbers)
    over = {name for name, (value, limit) in checks.items() if not value <= limit}
    if fault in ("topk_minus_one", "half_indexer_heads", "no_relu", "indexer_sees_later"):
        assert "selected_flip_share" in over, (fault, numbers)
    if fault == "align_all_visible":
        assert "align_loss1_gap" in over and "selected_flip_share" not in over, numbers


@pytest.mark.parametrize("quant", ["quant_int8", "quant_e4m3"])
def test_lower_precision_fails_and_the_reference_again_passes(followed, quant):
    cell, cfg, params0, batches, sound = followed
    planted = reference.train_steps(cfg, dict(params0), batches, quant=getattr(reference, quant))
    numbers, _, correct = _verdict(cell, cfg, planted, sound, params0)
    assert not correct, numbers
    if quant == "quant_int8":  # and the reference again, in its own place, passes
        again = reference.train_steps(cfg, dict(params0), batches)
        assert _verdict(cell, cfg, again, sound, params0)[2]


# -- the counts --------------------------------------------------------------------


def test_flops_against_hand_counts():
    cfg = tiny_sparse.committed_config()
    traffic = {"global_batch": 1, "sequence_length": 16384}
    tokens = 16384
    assert flops_sparse.sparse_layers(cfg) == 4
    # 16 x 64 queries, one 64-wide key and 16 weights from a 2,048-wide input
    assert flops_sparse.indexer_projection_flops(cfg, tokens) == 2 * tokens * 2048 * (1024 + 64 + 16)
    # a pair costs one 64-long product on each of the 16 heads
    assert flops_sparse.indexer_score_flops(cfg, 1000.0) == 1000 * 2 * 16 * 64
    # one whole 16,384-token document: 134.2 M visible pairs, 31.5 M selected
    visible = tokens * (tokens + 1) / 2
    selected = 2048 * 2049 / 2 + (tokens - 2048) * 2048
    assert visible == pytest.approx(134.2e6, rel=1e-3) and selected == pytest.approx(31.5e6, rel=2e-3)
    assert 100 * selected / visible == pytest.approx(23.4, abs=0.1)
    parts = flops_sparse.step_flops(cfg, traffic, 4 * visible, 4 * selected, 4 * 16384.0)
    # the issue's reckoning, forward: indexer scores 0.275 TFLOP a layer,
    # attention over the selection 0.26 in all, experts 0.62, head 1.27
    assert parts["indexer_scores"] == pytest.approx(
        4 * visible * 2048 + 2 * 4 * selected * 2048)
    assert visible * 2048 == pytest.approx(0.275e12, rel=0.01)
    assert parts["attention"] / 3 == pytest.approx(4 * selected * 4 * 4 * 128)
    assert parts["attention"] / 3 == pytest.approx(0.26e12, rel=0.02)
    assert parts["experts"] / 3 == pytest.approx(0.62e12, rel=0.01)
    assert parts["head"] / 3 == pytest.approx(1.27e12, rel=0.01)
    assert parts["projections"] / 3 + parts["indexer_projections"] / 3 == pytest.approx(
        0.64e12, rel=0.02)
    assert parts["total"] == pytest.approx(sum(v for k, v in parts.items() if k != "total"))
    assert parts["total"] == pytest.approx(10.5e12, rel=0.05)
    chip = peaks.PEAKS["TPU v5 lite"]
    # the indexer is bound by compute: 1.40 ms a layer forward, 2 x 0.33 backward
    floor = flops_sparse.indexer_floor_s(cfg, traffic, 4 * visible, 4 * selected, chip)
    assert floor == pytest.approx(4 * (visible + 2 * selected) * 2048 / chip.bf16_flops)
    assert floor == pytest.approx(4 * 2.05e-3, rel=0.02)
    # attention over the selection: flops_lm's floor at the selected keys a query
    keys = {"sparse_attention": selected / tokens}
    assert flops_sparse.attention_floor_s(cfg, traffic, 4 * selected, chip) == pytest.approx(
        flops_lm.attention_floor_s(cfg, traffic, keys, chip))
    assert flops_sparse.attention_floor_s(cfg, traffic, 4 * selected, chip) == pytest.approx(
        3 * 4 * selected * 4 * 4 * 128 / chip.bf16_flops)


def test_window_counters_are_the_hand_counts(tmp_path):
    """``sparse_pairs_scored`` is the visible pairs and ``sparse_pairs_selected``
    min(topk, visible) a query, both times the sparse layers, by hand on the
    batch the model is given."""
    import jax.numpy as jnp

    from tensorflowdistributedlearning_tpu.data import tokens
    from tensorflowdistributedlearning_tpu.models import build_model
    from tensorflowdistributedlearning_tpu.train import step as step_lib

    cell = tiny_sparse.load_cell(tmp_path)
    mcfg = lm_sparse_fit.model_config(cell)
    stream = tokens.TokenStreamConfig(**tiny_sparse.TINY_STREAM)
    batch = next(tokens.packed_token_batches(2, 64, 128, stream, seed=1))
    scored = selected = 0
    for seg in batch["segment_ids"]:
        for i in range(len(seg)):
            seen = sum(1 for j in range(i + 1) if seg[j] == seg[i])
            scored += seen
            selected += min(seen, 16)
    model = build_model(mcfg)
    params = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    out = model.apply({"params": params}, {k: jnp.asarray(v) for k, v in batch.items()}, train=True)
    task = step_lib.SequenceTask(mcfg.decoder, stream)
    scalars, vectors = step_lib.split_scalars(
        step_lib.compute_metrics(task.metric_deltas(out, None)))
    fields = task.window_fields(2, scalars, vectors, None)
    assert fields["sparse_pairs_scored"] == 4 * scored
    assert fields["sparse_pairs_selected"] == 4 * selected
    assert fields["attn_keys_per_query"] == {"sparse_attention": round(selected / 128, 2)}
    assert fields["align_loss"] > 0


# -- the trace readers on a small recorded trace ------------------------------------


def _recorded_run():
    cell = harness.load_cell(tiny_sparse.WORKLOAD)
    trace = xtrace.Trace.from_json(os.path.join(HERE, "recorded_trace_sparse.json"))
    windows = [{"event": "step_window", "step": 30, "steps": 20, "moe_pairs": 20 * 65536,
                "moe_pairs_dropped": 0, "moe_load_max_over_mean": 1.5, "images_per_sec": 1.6,
                "sparse_pairs_scored": 20 * 270_000_000, "sparse_pairs_selected": 20 * 117_000_000,
                "align_loss": 0.5, "attn_keys_per_query": {"sparse_attention": 1785.0}}]
    return harness.Run(cell=cell, ledger=windows, windows=windows, global_batch=1, steps=20,
                       wall_s=12.0, setup_s=1.0, trace=trace, trace_steps=3, trace_wall_s=1.8,
                       device={}, peaks=peaks.PEAKS["TPU v5 lite"])


def test_parts_of_the_recorded_trace():
    """The shape rules against the kernels' own names, which the v5e's trace
    keeps, and nothing counted twice."""
    run = _recorded_run()
    seconds, calls = lm_sparse_trace.part_seconds(run)
    with open(os.path.join(HERE, "recorded_trace_sparse.json"), encoding="utf-8") as f:
        by_name = json.load(f)["expected_by_kernel_name_ms_per_step"]
    assert calls == by_name["calls"]
    ops = xtrace.ops_inside(run.trace, "jit_step")
    z = lm_sparse_trace.sizes(run.cell)

    def named(*prefixes):
        return sum(e[2] for e in ops if xtrace.short_name(e[0]).lstrip("%").startswith(prefixes)) / 1e9

    assert seconds["indexer"] == pytest.approx(named("sparse_indexer_"))
    assert seconds["attention"] == pytest.approx(named("sparse_attend"))
    # the loss's kernel, and its gradient scaled by the cotangent (the one XLA
    # op that writes a float [T, T])
    assert seconds["align"] == pytest.approx(
        named("sparse_align") + named("broadcast_multiply_fusion"))
    assert named("broadcast_multiply_fusion") > 0
    # the threshold search, and the XLA ops of the tie search (and, in this
    # capture of an earlier tree, of the per-key counts) around it
    assert named("sparse_select_threshold") < seconds["select"] < 2 * named("sparse_select_threshold")
    for part in ("indexer", "attention"):
        assert 1e3 * seconds[part] / calls == pytest.approx(by_name[part]), part
    # the other decoder's rules still find the experts and the head
    assert seconds["moe_experts"] > 0 and seconds["head_loss"] > 0
    loops = [e for e in ops if lm_sparse_trace.part_of(e[0], z) == "container"]
    assert sum(seconds.values()) == pytest.approx(sum(e[2] for e in ops if e not in loops) / 1e9)
    # no op over the [16384, 16384] scores falls to the parts the layer shares
    for e in ops:
        if "[16384,16384]" in e[0].split(", kind=", 1)[0] and e not in loops:
            assert lm_sparse_trace.part_of(e[0], z) in lm_sparse_trace.SPARSE_PARTS, e[0][:200]


def test_readers_on_the_recorded_trace():
    run = _recorded_run()
    out = harness.read_metrics(run, run.cell.per_layer)
    assert set(out) == set(NEW_METRICS)
    for share in ("lm_sparse_step_mfu", "sparse_indexer_roofline", "sparse_attention_roofline"):
        assert 0 < out[share]["value"] <= 100, (share, out[share])
    assert out["sparse_selected_share_pct"]["value"] == pytest.approx(100 * 117 / 270)
    seen = lm_sparse_trace.counters(run)
    work = flops_sparse.step_flops(run.cell.config, run.cell.traffic, seen["scored"],
                                   seen["selected"], seen["moe_pairs"])["total"]
    assert out["lm_sparse_step_mfu"]["value"] == pytest.approx(100 * work / (0.6 * 197e12))
    seconds, calls = lm_sparse_trace.part_seconds(run)
    for part in lm_sparse_trace.SPARSE_PARTS:
        assert out[f"sparse_{part}_device_ms_per_step"]["value"] == pytest.approx(
            1e3 * seconds[part] / calls)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_reader_returns_nothing_where_its_field_is_absent(metric):
    """On a program without the layer the windows hold no sparse fields, and an
    untraced run has no trace: no metric, no error."""
    import importlib

    reader = importlib.import_module("perfbench.metrics." + metric)
    run = _recorded_run()
    run.windows = [{"event": "step_window", "step": 30, "steps": 20, "images_per_sec": 1.6,
                    "moe_pairs": 20 * 65536}]
    if metric.endswith("device_ms_per_step"):
        run.trace = None  # these read the trace alone
    assert reader.read(run) is None
    if "device_ms" in metric or "roofline" in metric:
        # the other decoder's cell: its configuration has no indexer to tell ops by
        other = _recorded_run()
        other.cell = harness.load_cell("mellum2_share4_train_8k")
        assert reader.read(other) is None


def test_calibration_judges_a_row_by_the_cells_limits():
    """``calibrate_sparse.judge``: the harness's own comparison with the
    committed limits on a calibration row, the program's counter standing at 0
    where the row is a planted reference's."""
    from perfbench import calibrate_sparse

    limits = harness.load_cell(tiny_sparse.WORKLOAD).limits
    sound = {name: 0.0 for name in limits if name != "moe_pairs_dropped"}
    rows = [{"kind": "program", "seed": 1, "numbers": dict(sound, moe_pairs_dropped=0.0)},
            {"kind": "capacity", "seed": 1, "numbers": dict(sound, grad1_expert_gap=0.28,
                                                           routed_flip_share=0.5)}]
    first, second = calibrate_sparse.judge(rows, limits)
    assert first["correct"] is True and first["over"] == []
    assert second["correct"] is False and second["over"] == ["grad1_expert_gap"]
    assert second["kind"] == "capacity" and second["numbers"] == rows[1]["numbers"]
