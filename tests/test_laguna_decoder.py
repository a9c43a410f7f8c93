"""The decoder's mixed layers (the Laguna family) at a tiny size on the CPU:
program against the plain reference — loss, every leaf's gradient, three AdamW
steps — with head counts by layer type, a per-head output gate, a dense first
layer, a shared expert and sigmoid routing; the shares' sum against the uncut
layer; the partial rotation against ``transformers``' YaRN; the routing's
weights; the expert layer's computed-row check under sigmoid scores; the
configuration's checks, the published file and its parameter count."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import weights
from perfbench.reference import laguna_decoder as reference
from perfbench.tests import tiny_mixed
from tensorflowdistributedlearning_tpu import configs
from tensorflowdistributedlearning_tpu.config import DecoderConfig, ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.data import tokens as tokens_lib
from tensorflowdistributedlearning_tpu.models import build_model, decoder as decoder_lib
from tensorflowdistributedlearning_tpu.parallel import expert as expert_lib

STREAM = tokens_lib.TokenStreamConfig(**tiny_mixed.TINY_STREAM)
# the uncut tiny model: 4 / 8 query heads by layer type on 2 key-value heads, 8 experts, 256 ids
FULL = dict(num_attention_heads=4, num_key_value_heads=2, num_experts=8, vocab_size=256)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg(n: int, s: int, **sizes) -> dict:
    held = {k: v // n for k, v in FULL.items()}
    held["num_attention_heads_per_layer"] = [h * 2 // n for h in
                                             tiny_mixed.TINY_SIZES["num_attention_heads_per_layer"]]
    held.update(sizes)
    return tiny_mixed.tiny_config(n, s, **held)


def _model_config(cfg: dict) -> ModelConfig:
    decoder = DecoderConfig.from_published(
        cfg, share_count=cfg["share"]["n"], share_index=cfg["share"]["s"],
        sequence_length=cfg["sequence_length"])
    return ModelConfig(backbone="decoder", dtype="float32", decoder=decoder)


@pytest.fixture(scope="module")
def full_weights():
    spec = reference.param_spec(_cfg(1, 0))
    key = jax.random.key(0)
    return {
        name: (1.0 if kind == "norm_scale" else 0.0) + 0.2 * jax.random.normal(
            jax.random.fold_in(key, i), shape)
        for i, (name, (shape, kind)) in enumerate(sorted(spec.items()))
    }


def _batch(vocab: int, seed: int = 3, length: int = 64, rows: int = 2):
    return {k: jnp.asarray(v) for k, v in next(tokens_lib.packed_token_batches(
        rows, length, vocab, STREAM, seed=seed)).items()}


def _program(cfg, flat):
    model = build_model(_model_config(cfg))
    template = model.init(jax.random.key(1), np.zeros((1, 8), np.int32))["params"]
    return model, weights.unflatten_like(template, flat)


def test_the_tiny_model_has_every_kind_of_layer():
    dcfg = _model_config(_cfg(2, 1)).decoder
    assert [dcfg.heads(i) for i in range(5)] == [2, 4, 4, 4, 2]
    assert [dcfg.mlp_type(i) for i in range(5)] == ["dense"] + ["sparse"] * 4
    assert dcfg.layer_types[:5] == ("full_attention",) + ("sliding_attention",) * 3 + (
        "full_attention",)
    assert dcfg.rotary_dim("full_attention") == 8 and dcfg.rotary_dim("sliding_attention") == 16
    assert dcfg.gating is True and dcfg.scoring_func == "sigmoid"
    assert dcfg.moe_routed_scaling_factor == 2.5 and dcfg.shared_expert_intermediate_size == 32


@pytest.mark.parametrize("n,s", [(2, 0), (2, 1), (1, 0)])
def test_program_matches_reference(full_weights, n, s):
    """The loss, the routed counts of the four sparse layers and every
    gradient leaf, for both shares and the uncut model, on seeded weights."""
    cfg = _cfg(n, s)
    flat = reference.share_of(full_weights, _cfg(1, 0), n, s)
    model, params = _program(cfg, flat)
    assert set(weights.flatten(params)) == set(reference.param_spec(cfg))
    batch = _batch(cfg["vocab_size"])

    def loss(p):
        out = model.apply({"params": p}, batch, train=True)
        return out["loss_sum"] / out["n_targets"], out

    (got, out), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    want, want_grads, want_counts = jax.jit(
        lambda p: reference.batch_loss_and_grad(cfg, p, batch))(flat)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert out["expert_tokens"].shape == (4, cfg["num_experts"])  # a dense layer adds no row
    np.testing.assert_array_equal(np.asarray(out["expert_tokens"]), np.asarray(want_counts))
    assert float(out["pairs_dropped"]) == 0.0
    flat_grads = weights.flatten(grads)
    assert set(flat_grads) == set(want_grads)
    for name, g in want_grads.items():
        gap = float(jnp.linalg.norm(flat_grads[name] - g) / (jnp.linalg.norm(g) + 1e-30))
        assert gap < 2e-4, (name, gap)
    # the gates' means by layer type: sigmoids, so inside (0, 1), and of the
    # gates the model has (positions x heads x layers of the type)
    positions = batch["tokens"].size
    heads = cfg["num_attention_heads_per_layer"]
    assert float(out["attn_gate_n_full"]) == positions * (heads[0] + heads[4])
    assert float(out["attn_gate_n_sliding"]) == positions * 3 * heads[1]
    for kind in ("full", "sliding"):
        assert 0.3 < float(out[f"attn_gate_sum_{kind}"] / out[f"attn_gate_n_{kind}"]) < 0.7


def test_three_adamw_steps_follow_the_reference(full_weights):
    """The program's own train step (``make_train_step`` with ``SequenceTask``)
    against ``reference.train_steps``: each step's loss, and the parameters
    after three updates."""
    from tensorflowdistributedlearning_tpu.parallel import mesh as mesh_lib
    from tensorflowdistributedlearning_tpu.train import step as step_lib
    from tensorflowdistributedlearning_tpu.train.state import create_train_state

    cfg = _cfg(2, 1)
    # to the host: the reference donates what it is given
    flat = jax.device_get(reference.share_of(full_weights, _cfg(1, 0), 2, 1))
    mcfg = _model_config(cfg)
    tcfg = TrainConfig(n_devices=1, token_stream=STREAM, **cfg["train"])
    task = step_lib.fit_task(mcfg, tcfg)
    mesh = mesh_lib.make_mesh(devices=jax.devices()[:1])
    state = create_train_state(build_model(mcfg), step_lib.make_optimizer(tcfg),
                               jax.random.key(0), np.zeros((1, 8), np.int32))
    state = state.replace(params=weights.unflatten_like(state.params, flat))
    step = step_lib.make_train_step(mesh, task, donate=False)
    stream = tokens_lib.packed_token_batches(2, 64, cfg["vocab_size"], STREAM, seed=11)
    batches = [next(stream) for _ in range(3)]
    got = []
    for batch in batches:
        state, metrics = step(state, {k: jnp.asarray(v) for k, v in batch.items()})
        got.append(float(metrics["loss"].total / metrics["loss"].count))
    assert float(metrics["moe/tile_visits"].total) >= 4.0  # a tile a sparse layer at the least
    assert 0.3 < float(metrics["attn/gate_mean_full"].total
                       / metrics["attn/gate_mean_full"].count) < 0.7
    want = reference.train_steps(cfg, dict(flat), batches)
    np.testing.assert_allclose(got, want["losses"], rtol=2e-5)
    after = weights.flatten(state.params)
    for name, w in want["params"].items():
        moved = np.asarray(w) - np.asarray(flat[name])
        gap = np.linalg.norm(np.asarray(after[name]) - np.asarray(w)) / (
            np.linalg.norm(moved) + 1e-30)
        assert gap < 2e-2, (name, gap)


def test_shares_add_up_to_the_uncut_layer(full_weights):
    """The share test, for a dense full layer, a sparse window layer and the
    sparse full layer: the shares' attention parts (each with its own heads'
    gates) and routed parts add up to the uncut reference's, and the whole
    layer is the residual stream plus those sums with the norms, the shared
    expert and the dense MLP — which every share holds whole — counted once."""
    cfg1 = _cfg(1, 0)
    batch = _batch(64)
    seg, pos = batch["segment_ids"][0], batch["positions"][0]
    x = jax.random.normal(jax.random.key(5), (64, cfg1["hidden_size"]))
    eps = cfg1["rms_norm_eps"]
    for i in (0, 1, 4):
        sparse = cfg1["mlp_layer_types"][i] == "sparse"
        u = reference.rms_norm(x, full_weights[f"layers_{i}/attn_norm/scale"], eps)
        whole_attn = reference.attention_part(cfg1, full_weights, i, u, seg, pos)
        attn = 0.0
        for s in range(2):
            cfg = _cfg(2, s)
            flat = reference.share_of(full_weights, cfg1, 2, s)
            assert flat[f"layers_{i}/attn/wq/kernel"].shape[1] * 2 == \
                full_weights[f"layers_{i}/attn/wq/kernel"].shape[1]
            whole = [k for k in flat if "norm" in k or "/shared/" in k or "/mlp/" in k
                     or k.endswith("/router")]
            assert all(flat[k] is full_weights[k] for k in whole)
            attn = attn + reference.attention_part(cfg, flat, i, u, seg, pos)
        np.testing.assert_allclose(np.asarray(attn), np.asarray(whole_attn), atol=2e-5)
        h = x + attn
        if sparse:
            u2 = reference.rms_norm(h, full_weights[f"layers_{i}/moe_norm/scale"], eps)
            whole_routed = reference.routed_part(cfg1, full_weights, i, u2)
            parts = sum(reference.routed_part(
                _cfg(2, s), reference.share_of(full_weights, cfg1, 2, s), i, u2) for s in range(2))
            np.testing.assert_allclose(np.asarray(parts), np.asarray(whole_routed), atol=2e-5)
            mlp = parts + reference.shared_part(cfg1, full_weights, i, u2)  # once, not twice
        else:
            u2 = reference.rms_norm(h, full_weights[f"layers_{i}/mlp_norm/scale"], eps)
            mlp = reference.dense_part(cfg1, full_weights, i, u2)
        y, _ = reference.layer(cfg1, full_weights, i, x, seg, pos)
        np.testing.assert_allclose(np.asarray(h + mlp), np.asarray(y), atol=5e-5)
        assert float(jnp.max(jnp.abs(mlp))) > 1e-3


def _published_decoder() -> DecoderConfig:
    return DecoderConfig.from_published(tiny_mixed.committed_config(), share_count=8,
                                        share_index=0, sequence_length=16384)


def test_rotation_touches_the_first_half_of_a_full_layers_head_only():
    """At the published sizes: a full layer rotates 64 of a head's 128
    dimensions with YaRN's frequencies computed for ``dim = 64`` — equal to
    ``transformers``' — and passes the other 64 unrotated and unscaled; a
    window layer rotates all 128 with plain frequencies at theta 1e4."""
    dcfg = _published_decoder()
    inv, scale = decoder_lib.rope_constants(dcfg, "full_attention")
    assert inv.shape == (32,) and scale == pytest.approx(1.4158883083359672)
    x = jax.random.normal(jax.random.key(0), (1, 6, 2, 128))
    positions = jnp.asarray([[0, 1, 5, 4095, 4096, 16383]], jnp.int32)
    out = decoder_lib.apply_rope(x, positions, inv, scale)
    np.testing.assert_array_equal(np.asarray(out[..., 64:]), np.asarray(x[..., 64:]))
    np.testing.assert_allclose(np.asarray(out[0, 0, :, :64]), np.asarray(x[0, 0, :, :64]) * scale,
                               rtol=1e-6)  # position 0: cos = the factor, sin = 0
    assert float(jnp.max(jnp.abs(out[0, 1:, :, :64] - x[0, 1:, :, :64] * scale))) > 0.1
    # the reference's constants are the same numbers, written apart
    ref_inv, ref_scale, ref_dim = reference.rope_parameters(
        tiny_mixed.committed_config(), "full_attention")
    assert ref_dim == 64 and ref_scale == scale
    np.testing.assert_array_equal(np.asarray(ref_inv), inv)
    want = reference.rotate(x[0], positions[0], ref_inv, ref_scale, ref_dim)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want), atol=1e-6)
    inv_w, scale_w = decoder_lib.rope_constants(dcfg, "sliding_attention")
    assert inv_w.shape == (64,) and scale_w == 1.0
    np.testing.assert_allclose(inv_w, 1e4 ** (-np.arange(0, 128, 2) / 128), rtol=1e-6)
    # transformers' YaRN over dim = 64
    torch = pytest.importorskip("torch")
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")
    published = tiny_mixed.committed_config()["rope_parameters"]["full_attention"]

    class Published:
        rope_theta = published["rope_theta"]
        partial_rotary_factor = published["partial_rotary_factor"]
        head_dim, hidden_size, num_attention_heads = 128, 2048, 48
        max_position_embeddings = 262144
        rope_scaling = {k: v for k, v in published.items()
                        if k not in ("rope_theta", "partial_rotary_factor")}

    theirs, factor = rope_utils._compute_yarn_parameters(Published(), torch.device("cpu"))
    assert factor == scale
    np.testing.assert_allclose(inv, theirs.numpy(), rtol=2e-6)


def test_routing_weights_are_the_chosen_sigmoids_renormalised_and_scaled():
    """``w = 2.5 s / sum(s)`` over the ``k`` largest sigmoids, equal scores to
    the lower index; the softmax routing is what it was."""
    logits = jnp.asarray([[0.5, 2.0, 2.0, -1.0, 2.0, 0.1],
                          [-3.0, -3.0, -3.0, -3.0, -3.0, -3.0],
                          [9.0, -9.0, 0.0, 1.0, 0.5, 8.0]], jnp.float32)
    w, e = expert_lib.top_k_routing(logits, 2, True, score="sigmoid", scale=2.5)
    np.testing.assert_array_equal(np.asarray(e), [[1, 2], [0, 1], [0, 5]])
    s = np.asarray(jax.nn.sigmoid(logits))
    for row, (chosen, got) in enumerate(zip(np.asarray(e), np.asarray(w))):
        np.testing.assert_allclose(got, 2.5 * s[row, chosen] / s[row, chosen].sum(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.5, rtol=1e-6)
    # against the reference's routing, which spreads the weights over all experts
    dense, chosen = reference.route({"num_experts_per_tok": 2, "moe_routed_scaling_factor": 2.5},
                                    logits)
    for row in range(3):
        np.testing.assert_allclose(np.asarray(dense)[row, np.asarray(e)[row]], np.asarray(w)[row],
                                   rtol=1e-6)
        assert set(np.flatnonzero(np.asarray(chosen)[row])) == set(np.asarray(e)[row])
    w1, _ = expert_lib.top_k_routing(logits, 2, False, score="sigmoid")
    np.testing.assert_allclose(np.asarray(w1)[0], s[0, [1, 2]], rtol=1e-6)
    soft, e_soft = expert_lib.top_k_routing(logits, 2)
    p = np.asarray(jax.nn.softmax(logits, axis=-1))
    np.testing.assert_array_equal(np.asarray(e_soft)[0], [1, 2])
    np.testing.assert_allclose(np.asarray(soft)[0], p[0, [1, 2]] / p[0, [1, 2]].sum(), rtol=1e-6)


def test_the_computed_row_check_holds_under_sigmoid_scores():
    """A sigmoid is positive for every logit float32 can hold above -87, and
    the chosen are a token's largest: no chosen pair has weight 0, so every
    held pair's row is written, the check counts none as dropped, and the
    layer is the dense sum over the held experts — also where every logit is
    far below zero."""
    t, d, f, held, total, k = 96, 32, 16, 4, 8, 2
    keys = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(keys[0], (t, d))
    w_gate, w_up = (jax.random.normal(kk, (held, d, f)) * 0.3 for kk in keys[1:3])
    w_down = jax.random.normal(keys[3], (held, f, d)) * 0.3
    for shift in (0.0, -60.0):
        logits = jax.random.normal(keys[4], (t, total)) + shift
        weights_, experts = expert_lib.top_k_routing(logits, k, True, score="sigmoid", scale=2.5)
        assert float(jnp.min(weights_)) > 0.0
        out, counts, dropped = expert_lib.dropless_experts(
            x, weights_, experts, w_gate, w_up, w_down, num_experts_total=total, first_expert=4)
        assert int(dropped) == 0 and int(jnp.sum(counts)) > 0
        want = jnp.zeros((t, d))
        for j in range(held):
            w_j = jnp.sum(jnp.where(experts == 4 + j, weights_, 0.0), axis=1)
            want = want + w_j[:, None] * ((jax.nn.silu(x @ w_gate[j]) * (x @ w_up[j])) @ w_down[j])
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-4)
        assert int(jnp.sum(counts)) == int(jnp.sum((experts >= 4)))


def test_row_tile_visits_counts_the_tiles_a_group_overlaps():
    tile = expert_lib._GMM_TILE_M
    # 512 each: every group is one whole tile
    assert int(expert_lib.row_tile_visits(jnp.full((32,), tile))) == 32
    # off by one row: the second group straddles an edge, and so on
    counts = jnp.asarray([tile - 1, tile, 1, 0, 3 * tile], jnp.int32)
    # rows [0,511) 1 tile; [511,1023) 2; [1023,1024) 1; empty 0; [1024,2560) 3
    assert int(expert_lib.row_tile_visits(counts)) == 1 + 2 + 1 + 0 + 3
    assert int(expert_lib.row_tile_visits(jnp.zeros((4,), jnp.int32))) == 0


def test_the_configuration_checks_its_lists():
    base = dict(num_hidden_layers=2, layer_types=("full_attention", "sliding_attention"),
                num_key_value_heads=2, num_attention_heads=4)
    DecoderConfig(**base, num_attention_heads_per_layer=(4, 6))
    with pytest.raises(ValueError, match="multiple of num_key_value_heads"):
        DecoderConfig(**base, num_attention_heads_per_layer=(4, 5))
    with pytest.raises(ValueError, match="num_attention_heads_per_layer names 1 layers"):
        DecoderConfig(**base, num_attention_heads_per_layer=(4,))
    with pytest.raises(ValueError, match="mlp_layer_types names 1 layers"):
        DecoderConfig(**base, mlp_layer_types=("sparse",))
    with pytest.raises(ValueError, match="intermediate_size"):
        DecoderConfig(**base, mlp_layer_types=("dense", "sparse"))
    with pytest.raises(ValueError, match="at least one sparse"):
        DecoderConfig(**base, mlp_layer_types=("dense", "dense"), intermediate_size=8)
    with pytest.raises(ValueError, match="scoring_func"):
        DecoderConfig(**base, scoring_func="sqrtsoftplus")
    with pytest.raises(ValueError, match="gating"):
        DecoderConfig(**base, gating="per-channel")
    assert DecoderConfig(**base, gating="per-head").gating


def test_from_published_passes_over_what_is_no_layer_types_rope():
    published = tiny_mixed.committed_config()
    assert published["rope_parameters"]["original_max_position_embeddings"] == 4096
    dcfg = _published_decoder()
    assert [kind for kind, _ in dcfg.rope_parameters] == ["full_attention", "sliding_attention"]
    assert dcfg.rope("full_attention")["partial_rotary_factor"] == 0.5
    assert dcfg.rope("sliding_attention")["rope_theta"] == 10000


def test_the_preset_is_the_published_file_and_counts_540_637_184_parameters():
    cfg = tiny_mixed.committed_config()
    preset = configs.get_preset("laguna_xs2_33b_a3b_share8")
    assert preset.model.decoder == _published_decoder()
    assert preset.model.dtype == cfg["dtype"] and preset.global_batch == 1
    for key, value in cfg["train"].items():
        assert getattr(preset.train, key) == value, key
    spec = reference.param_spec(cfg)
    count = lambda names: sum(int(np.prod(spec[k][0])) for k in names)  # noqa: E731
    assert count(spec) == cfg["n_params"] == 540_637_184
    layer = lambda i: [k for k in spec if k.startswith(f"layers_{i}/")]  # noqa: E731
    attn = lambda i: [k for k in layer(i) if "/attn/" in k]  # noqa: E731
    # ISSUE 32's table: attention with its gate, by layer type
    assert count(attn(0)) == 3_682_304 and count(attn(1)) == 4_734_976
    assert count(reference.dense_leaves(cfg)) == 50_331_648
    assert count(k for k in reference.shared_leaves(cfg) if k.startswith("layers_1/")) == 3_145_728
    assert count(k for k in reference.expert_leaves(cfg)
                 if k.startswith("layers_1/")) == 100_663_296
    assert count(["layers_1/moe/router"]) == 524_288
    assert [count(layer(i)) for i in range(5)] == [
        54_018_048, 109_072_384, 109_072_384, 109_072_384, 108_019_712]
    assert count(["embed/embedding", "head/kernel"]) == 51_380_224
    # the program's tree holds the same leaves
    model = build_model(preset.model)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), np.zeros((1, 8), np.int32)))
    program = {k: tuple(v.shape) for k, v in weights.flatten(shapes["params"]).items()}
    assert program == {k: tuple(shape) for k, (shape, _) in spec.items()}


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="the catalog is not on this machine")
def test_the_file_holds_every_published_key_but_the_reduced_ones():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    cfg = tiny_mixed.committed_config()
    assert cfg["source"] == row["source_url"]
    differing = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differing == sorted(cfg["reduced"])
    assert {k: row["config"][k] for k in cfg["published"] if k != "num_attention_heads_per_layer"} \
        == {k: v for k, v in cfg["published"].items() if k != "num_attention_heads_per_layer"}
    assert [h * 8 for h in cfg["num_attention_heads_per_layer"]] == \
        row["config"]["num_attention_heads_per_layer"]
    for key in ("share", "deployment", "assumed", "departures", "held", "kept"):
        assert cfg[key]


def test_fit_trains_the_mixed_decoder(tmp_path):
    """Through ``ClassifierTrainer.fit``: the loss falls, and every window
    carries the new fields beside the decoder's own — the experts' rows for the
    four sparse layers only."""
    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

    cfg = _cfg(2, 1)
    tcfg = TrainConfig(optimizer="adam", lr=3e-3, augmentation="none", train_log_every_steps=5,
                       n_devices=1, token_stream=STREAM)
    trainer = ClassifierTrainer(str(tmp_path), None, _model_config(cfg), tcfg)
    result = trainer.fit(batch_size=4, steps=30)
    assert result.steps == 30 and np.isfinite(result.final_metrics["loss"])
    with open(os.path.join(str(tmp_path), "telemetry.jsonl"), encoding="utf-8") as f:
        events = [json.loads(line) for line in f if line.strip()]
    header = next(e for e in events if e.get("event") == "run_header")
    assert header["decoder"]["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert header["decoder"]["attention_heads"] == [2, 4, 4, 4, 2]
    windows = [e for e in events if e.get("event") == "step_window"]
    assert len(windows) >= 4
    losses = [w["scalars"]["loss"] for w in windows]
    assert losses[-1] < losses[0] - 0.2, losses
    for w in windows:
        assert w["moe_pairs_dropped"] == 0
        assert len(w["moe_expert_tokens"]) == 4 and len(w["moe_buffer_rows"]) == 4
        assert w["moe_tile_visits"] >= 1.0
        assert set(w["attn_gate_mean"]) == {"full_attention", "sliding_attention"}
        assert all(0.2 < g < 0.8 for g in w["attn_gate_mean"].values())
        assert set(w["attn_keys_per_query"]) == {"full_attention", "sliding_attention"}
        assert w["attn_keys_per_query"]["sliding_attention"] <= 8.0
