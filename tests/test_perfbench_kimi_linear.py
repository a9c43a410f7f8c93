"""The Kimi-Linear cell (``kimi_linear_share32_train_16k``) at a tiny size on the
CPU: its files against the preset and the issue's traffic, a whole sound run,
each planted fault and each precision below failing the comparison, the
router's repeated columns, the FLOP counts against hand counts, the cell's
readers on a recorded trace with a made-up record of the program's scopes,
and their silence where the program writes nothing. No number of such a run
is a device number."""

import dataclasses
import importlib
import json
import os

import jax
import numpy as np
import pytest

from perfbench import compare, flops_linear, harness, lm_linear_weights, peaks, scope_trace, \
    xtrace
from perfbench.entries import lm_linear_fit
from perfbench.reference import kimi_linear_decoder as reference
from perfbench.tests import tiny_linear

HERE = os.path.join(tiny_linear.REPO, "perfbench", "tests")
NEW_METRICS = [
    "lm_linear_step_mfu", "kda_roofline", "kda_device_ms_per_step",
    "linear_attention_device_ms_per_step", "latent_attention_roofline",
    "latent_attention_device_ms_per_step", "kda_decay_mean_pct"]


# -- the files -------------------------------------------------------------------


def test_file_is_the_preset_and_the_traffic_is_the_issues():
    from tensorflowdistributedlearning_tpu.configs import get_preset

    cell = harness.load_cell(tiny_linear.WORKLOAD)
    preset = get_preset(cell.config["preset"])
    assert lm_linear_fit.model_config(cell) == preset.model
    assert lm_linear_fit.train_config(cell) == dataclasses.replace(
        preset.train, seed=cell.traffic["stream_seed"], n_devices=1)
    traffic = cell.traffic
    assert traffic["entry"] == "lm_linear_fit" and cell.chips == 1
    assert (traffic["global_batch"], traffic["sequence_length"]) == (1, 16384)
    assert traffic["stream"] == {"median_length": 8192.0, "sigma": 1.0, "min_length": 256,
                                 "max_length": 16384, "zipf_exponent": 1.0}
    assert (traffic["stream_seed"], traffic["warmup_steps"], traffic["trace_seconds"]) == (
        20261015, 10, 8.0)
    # a new stream seed: no other cell's
    others = [harness.load_cell(w).traffic["stream_seed"] for w in
              ("mellum2_share4_train_8k", "keye_share8_train_16k", "laguna_share8_train_16k")]
    assert traffic["stream_seed"] not in others
    cfg = cell.config
    assert traffic["sequence_length"] == cfg["sequence_length"]
    # the deployment's exchange brings each expert 16,384 tokens a step (32
    # chips' 16,384 x 8 experts over 256); chip 0's held experts see what its
    # own tokens route to them (on the CPU one pair a token: 2,048 an expert)
    n = cfg["share"]["n"]
    assert n * 16384 * 8 // (cfg["num_experts"] * n) == 16384
    assert 16384 // cfg["num_experts"] == 2048


def test_benchmark_names_the_cells_files():
    with open(os.path.join(tiny_linear.REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == tiny_linear.CONFIG)
    cfg = tiny_linear.committed_config()
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"perfbench/configs/{tiny_linear.CONFIG}.json"
    work = next(w for w in bench["workloads"] if w["name"] == tiny_linear.WORKLOAD)
    assert (work["config"], work["traffic"], work["chips"]) == (
        tiny_linear.CONFIG, "lm_linear_16k_b1", 1)
    for said in ("16,384", "whole", "more than their share", "all-to-all"):
        assert said in work["why"], said
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [tiny_linear.WORKLOAD]]
    assert [m["name"] for m in mine] == NEW_METRICS
    for metric in mine:
        assert metric["moves"] == "train_images_per_s"
        assert metric["layer"] == ("step" if metric["name"] == "lm_linear_step_mfu"
                                   else "models and kernels")
        module = importlib.import_module("perfbench.metrics." + metric["name"])
        assert callable(module.read) and module.__doc__
    # the cell's entries, each once, its metrics one after another in the
    # issue's order (later PRs append after them)
    assert [c["name"] for c in bench["configs"]].count(tiny_linear.CONFIG) == 1
    assert [w["name"] for w in bench["workloads"]].count(tiny_linear.WORKLOAD) == 1
    first = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][first:first + len(mine)] == mine
    # and the tiny cell of these tests compares the same numbers
    assert set(tiny_linear.TINY_LIMITS) == set(harness.load_cell(tiny_linear.WORKLOAD).limits)


# -- a whole run -------------------------------------------------------------------


def test_sound_run_is_correct(tmp_path, monkeypatch, capsys):
    got = {}
    result, checks = tiny_linear.run_cell(tmp_path, monkeypatch, seconds=2.0, collect=got)
    assert result["correct"] is True, checks
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_images_per_s", "setup_s"}
    assert result["window"]["compiles_inside"] == 0
    assert np.asarray(got["reference"]["routed1"]).shape == (4, 4)  # sparse layers x held
    for name in ("grad1_kda_gap", "grad1_latent_gap", "grad1_gate_gap"):
        assert got["numbers"][name] < 1e-3, (name, got["numbers"][name])
    harness.emit(result, checks)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks" and "check grad1_kda_gap = " in err


def test_a_program_without_linear_layers_fails_at_once(monkeypatch):
    from tensorflowdistributedlearning_tpu import config

    @dataclasses.dataclass(frozen=True)
    class Before:
        num_attention_heads: int = 32

    monkeypatch.setattr(config, "DecoderConfig", Before)
    monkeypatch.setattr(harness, "require_chips", lambda n: pytest.fail("looked for a chip"))
    with pytest.raises(SystemExit, match="linear_attn_config"):
        lm_linear_fit.run(harness.load_cell(tiny_linear.WORKLOAD), seed=1, seconds=1.0,
                          trace=False, process_t0=0.0)


# -- the reference with a fault or a precision below in the program's place -------


@pytest.fixture(scope="module")
def followed(tmp_path_factory):
    cell = tiny_linear.load_cell(tmp_path_factory.mktemp("linear"))
    cfg = cell.config
    params0 = jax.device_get(lm_linear_weights.make_weights(reference.param_spec(cfg), 3))
    from tensorflowdistributedlearning_tpu.data import tokens

    stream = tokens.packed_token_batches(
        2, cfg["sequence_length"], cfg["vocab_size"],
        tokens.TokenStreamConfig(**tiny_linear.TINY_STREAM), seed=3)
    batches = [next(stream) for _ in range(harness.FOLLOWED_STEPS)]
    sound = reference.train_steps(cfg, dict(params0), batches)
    return cell, cfg, params0, batches, sound


def _verdict(cell, cfg, planted, sound, params0):
    numbers = lm_linear_fit.linear_numbers(reference, cfg, dict(planted), sound, params0)
    numbers["moe_pairs_dropped"] = 0.0
    checks, correct = compare.verdict(numbers, cell.limits)
    return numbers, checks, correct


FAULTS = list(reference.FAULTS) + ["top_k_minus_one", "capacity", "unchanged"]


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_fails_the_comparison(followed, fault):
    cell, cfg, params0, batches, sound = followed
    planted = reference.train_steps(cfg, dict(params0), batches, faults=(fault,))
    numbers, checks, correct = _verdict(cell, cfg, planted, sound, params0)
    assert not correct, (fault, numbers)
    over = {name for name, (value, limit) in checks.items() if not value <= limit}
    if fault in ("no_delta", "decay_by_head", "no_reset"):
        assert "grad1_kda_gap" in over, numbers
    if fault == "rotate_pe":
        assert "grad1_latent_gap" in over, numbers


@pytest.mark.parametrize("quant", ["quant_int8", "quant_e4m3"])
def test_lower_precision_fails_and_the_reference_again_passes(followed, quant):
    cell, cfg, params0, batches, sound = followed
    planted = reference.train_steps(cfg, dict(params0), batches, quant=getattr(reference, quant))
    numbers, _, correct = _verdict(cell, cfg, planted, sound, params0)
    assert not correct, numbers
    if quant == "quant_int8":  # and the reference again, in its own place, passes
        again = reference.train_steps(cfg, dict(params0), batches)
        assert _verdict(cell, cfg, again, sound, params0)[2]


def test_a_routers_columns_repeat_over_32_shares_so_chip_0_takes_one_pair_a_token():
    """The committed router kind, at a width that fits a test: one share's 8
    columns repeated 32 times, so a token's 8 experts are copies of its best
    column in shares 0-7 (ties to the lower index) and chip 0 holds exactly
    one of them, whatever the seed."""
    spec = {"layers_1/moe/router": ((64, 256), "router:32")}
    router = np.asarray(lm_linear_weights.make_weights(spec, 2**31 + 5)["layers_1/moe/router"])
    np.testing.assert_array_equal(router[:, :8], router[:, 8:16])
    u = np.random.default_rng(0).standard_normal((512, 64)).astype(np.float32)
    weights, chosen = reference.route(
        {"num_experts_per_token": 8, "routed_scaling_factor": 2.446},
        jax.numpy.asarray(u @ router))
    chosen = np.asarray(chosen)
    assert chosen[:, :8].sum() == 512 and chosen[:, 64:].sum() == 0
    assert chosen[:, :64].reshape(512, 8, 8).sum(axis=(1, 2)).tolist() == [8] * 512
    np.testing.assert_allclose(np.asarray(weights)[chosen > 0], 2.446 / 8, rtol=1e-6)


def test_the_kda_decay_parameters_are_drawn_as_the_family_does():
    spec = {"a": ((32,), "a_log"), "b": ((4096,), "dt_bias")}
    drawn = lm_linear_weights.make_weights(spec, 7)
    a = np.exp(np.asarray(drawn["a"]))
    dt = np.log1p(np.exp(np.asarray(drawn["b"])))  # softplus
    assert a.min() >= 1.0 and a.max() <= 16.0
    assert dt.min() >= 0.99e-3 and dt.max() <= 1.01e-1


# -- the counts --------------------------------------------------------------------


def test_flops_against_hand_counts():
    cell = harness.load_cell(tiny_linear.WORKLOAD)
    cfg, traffic = cell.config, cell.traffic
    t = 16384
    assert flops_linear.layer_kinds(cfg) == ["linear"] * 3 + ["latent", "linear"]
    assert flops_linear.mlp_kinds(cfg) == ["dense"] + ["sparse"] * 4
    # per token, forward: a KDA layer's projections, the MLA layer's
    kda_proj = 2 * (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32)
    mla_proj = 2 * (2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304)
    assert flops_linear.linear_projection_flops(cfg, 1) == kda_proj
    assert flops_linear.latent_projection_flops(cfg, 1) == mla_proj
    # the chunkwise form at 64: per chunk and head
    c, k = 64, 128
    chunk = 4 * c * c * k + 2 * c**3 / 3 + 4 * c * c * k + 6 * c * k * k + 2 * c * c * k
    assert flops_linear.kda_flops(cfg, t) == pytest.approx(32 * chunk * t / c)
    assert flops_linear.kda_bytes(cfg, 1) == 32 * (4 * 128 * 2 + 128 * 4 + 4)
    keys = {"latent_attention": 5000.0}
    parts = flops_linear.step_flops(cfg, traffic, keys, 4 * t)
    assert parts["attention"] == pytest.approx(3 * 2 * 5000.0 * t * 32 * (192 + 128))
    assert parts["experts"] == pytest.approx(3 * 3 * 2 * 4 * t * 2304 * 1024)
    assert parts["dense"] == pytest.approx(3 * 6 * t * 2304 * 9216)
    assert parts["shared"] == pytest.approx(3 * 4 * 6 * t * 2304 * 1024)
    assert parts["router"] == pytest.approx(3 * 4 * 2 * t * 2304 * 256)
    assert parts["head"] == pytest.approx(3 * 2 * t * 2304 * 20480)
    assert parts["total"] == pytest.approx(sum(v for n, v in parts.items() if n != "total"))
    # the mixers carry most of the model FLOPs (the issue: about 59% forward)
    mixers = parts["projections"] + parts["kda"] + parts["attention"]
    assert 0.5 < mixers / parts["total"] < 0.65
    # floors: KDA is bound by bytes at a chunk of 64, latent attention by operations
    pk = peaks.PEAKS["TPU v5 lite"]
    kda_one = max(flops_linear.kda_flops(cfg, t) / pk.bf16_flops,
                  flops_linear.kda_bytes(cfg, t) / pk.hbm_bytes_per_s)
    assert kda_one == flops_linear.kda_bytes(cfg, t) / pk.hbm_bytes_per_s
    assert flops_linear.kda_floor_s(cfg, traffic, pk) == pytest.approx(4 * 3 * kda_one)
    work = 2 * 5000.0 * t * 32 * 320
    assert flops_linear.latent_floor_s(cfg, traffic, 5000.0, pk) == pytest.approx(
        3 * work / pk.bf16_flops)


# -- the trace readers ---------------------------------------------------------------

# the record deals the recorded trace's ops to these (chain, pass) in turn
DEAL = [
    (("decoder/attn_linear", "decoder/attn_linear/kda"), "forward"),
    (("decoder/attn_linear", "decoder/attn_linear/kda"), "backward"),
    (("decoder/attn_linear", "decoder/attn_linear/conv"), "recompute"),
    (("decoder/attn_linear", "decoder/attn_linear/out_norm"), "backward"),
    (("decoder/attn_latent", "decoder/attn_latent/attend"), "backward"),
    (("decoder/attn_latent",), "forward"),
    (("decoder/moe/experts",), "backward"),
    ((), "forward"),
]


def _record(trace, needle="jit_step"):
    from tensorflowdistributedlearning_tpu.obs import scopes

    texts = {xtrace.short_name(e[0]).lstrip("%"): e[0] for e in xtrace.ops_inside(trace, needle)}
    chains, groups = [()], {}
    for i, name in enumerate(sorted(texts)):
        chain, which = DEAL[i % len(DEAL)]
        if chain not in chains:
            chains.append(chain)
        groups.setdefault((chains.index(chain), scopes.PASSES.index(which)), []).append(name)
    return {
        "event": scopes.PROGRAM_SCOPES_EVENT, "program": needle,
        "scopes": list(scopes.SCOPES), "passes": list(scopes.PASSES),
        "chains": [[scopes.SCOPES.index(s) for s in chain] for chain in chains],
        "ops": [[c, p, names] for (c, p), names in sorted(groups.items())],
        "containers": [n for n, text in texts.items()
                       if " while(" in text or " conditional(" in text],
    }


WINDOW = {"event": "step_window", "step": 30, "steps": 10, "images_per_sec": 1.8,
          "moe_pairs": 10 * 4 * 16384, "moe_pairs_dropped": 0, "moe_load_max_over_mean": 1.3,
          "attn_keys_per_query": {"latent_attention": 5000.0}, "kda_decay_mean": 0.8}


def _recorded_run(record=True):
    with open(os.path.join(HERE, "recorded_trace_mixed.json"), encoding="utf-8") as f:
        recorded = json.load(f)
    trace = xtrace.Trace(recorded["planes"])
    cell = harness.load_cell(tiny_linear.WORKLOAD)
    ledger = [dict(WINDOW)] + ([_record(trace)] if record else [])
    return harness.Run(cell=cell, ledger=ledger, windows=[dict(WINDOW)], global_batch=1,
                       steps=20, wall_s=6.0, setup_s=1.0, trace=trace,
                       trace_steps=recorded["calls"], trace_wall_s=recorded["calls"] * 0.6,
                       device={}, peaks=peaks.PEAKS["TPU v5 lite"])


def test_readers_on_the_recorded_trace():
    run = _recorded_run()
    out = harness.read_metrics(run, run.cell.per_layer)
    assert set(out) == set(NEW_METRICS)
    seconds, calls = scope_trace.by_scope(run)
    per_step = {scope: 1e3 * sum(v for (s, _), v in seconds.items() if s == scope) / calls
                for scope, _ in seconds}
    assert out["kda_device_ms_per_step"]["value"] == pytest.approx(
        per_step["decoder/attn_linear/kda"])
    assert out["linear_attention_device_ms_per_step"]["value"] == pytest.approx(
        sum(v for s, v in per_step.items() if s.startswith("decoder/attn_linear")))
    assert out["latent_attention_device_ms_per_step"]["value"] == pytest.approx(
        per_step["decoder/attn_latent/attend"])
    pk, cfg, traffic = run.peaks, run.cell.config, run.cell.traffic
    assert out["kda_roofline"]["value"] == pytest.approx(
        100 * flops_linear.kda_floor_s(cfg, traffic, pk)
        / (out["kda_device_ms_per_step"]["value"] / 1e3))
    assert out["latent_attention_roofline"]["value"] == pytest.approx(
        100 * flops_linear.latent_floor_s(cfg, traffic, 5000.0, pk)
        / (out["latent_attention_device_ms_per_step"]["value"] / 1e3))
    work = flops_linear.step_flops(cfg, traffic, WINDOW["attn_keys_per_query"], 4 * 16384)
    assert out["lm_linear_step_mfu"]["value"] == pytest.approx(
        100 * work["total"] / (0.6 * 197e12))
    assert out["kda_decay_mean_pct"]["value"] == pytest.approx(80.0)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_reader_returns_nothing_where_its_field_is_absent(metric):
    """The parent's program writes no scope record, no decay and no latent
    layer's keys; an untraced run has no trace: no metric, no error."""
    reader = importlib.import_module("perfbench.metrics." + metric)
    run = _recorded_run(record=False)
    run.windows = [{"event": "step_window", "step": 30, "steps": 10, "images_per_sec": 1.8}]
    assert reader.read(run) is None
    untraced = _recorded_run()
    untraced.trace = None
    if metric != "kda_decay_mean_pct":
        assert reader.read(untraced) is None
    if metric in ("lm_linear_step_mfu", "kda_roofline"):
        # another decoder's cell: its configuration has no linear layers
        other = _recorded_run()
        other.cell = harness.load_cell("laguna_share8_train_16k")
        assert reader.read(other) is None


def test_calibration_judges_a_row_by_the_cells_limits():
    from perfbench import calibrate_linear, calibrate_sparse

    limits = harness.load_cell(tiny_linear.WORKLOAD).limits
    sound = {name: 0.0 for name in limits if name != "moe_pairs_dropped"}
    rows = [{"kind": "program", "seed": 1, "numbers": dict(sound, moe_pairs_dropped=0.0)},
            {"kind": "no_delta", "seed": 1, "numbers": dict(sound, grad1_kda_gap=1.0)}]
    first, second = calibrate_sparse.judge(rows, limits)
    assert first["correct"] is True and first["over"] == []
    assert second["correct"] is False and second["over"] == ["grad1_kda_gap"]
    assert set(calibrate_linear.QUANTS) == {"int8", "e4m3"}
    assert set(reference.FAULTS) == {"no_delta", "decay_by_head", "rotate_pe", "no_reset"}
