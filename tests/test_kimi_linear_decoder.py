"""The decoder's Kimi-Linear layers at a tiny size on the CPU: program against
the plain reference — loss, every leaf's gradient, three AdamW steps — with
Kimi Delta Attention on three layers in four, NoPE latent attention on the
fourth, a dense first layer, a shared expert and sigmoid routing; the shares'
sum against the uncut layer over 32 shares; latent attention's kernel path
(queries and keys wider than the values) against the XLA path; the
configuration's checks, the published file and its parameter count.

Tolerances: program and reference compute the same float32 mathematics in a
different order (the chunked form against the recurrence one position at a
time, the grouped expert product against a dense one): a sound tiny model
reads under 1e-5 on the loss and under 1e-4 on every gradient leaf."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import weights
from perfbench.reference import kimi_linear_decoder as reference
from perfbench.tests import tiny_linear
from tensorflowdistributedlearning_tpu import configs
from tensorflowdistributedlearning_tpu.config import DecoderConfig, ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.data import tokens as tokens_lib
from tensorflowdistributedlearning_tpu.models import build_model
from tensorflowdistributedlearning_tpu.ops import blocked_attention

STREAM = tokens_lib.TokenStreamConfig(**tiny_linear.TINY_STREAM)
# the uncut tiny model: 8 experts, 256 ids (attention is whole on every share)
FULL = dict(num_experts=8, vocab_size=256)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg(n: int, s: int, full=FULL, **sizes) -> dict:
    held = {k: v // n for k, v in full.items()}
    held.update(sizes)
    return tiny_linear.tiny_config(n, s, **held)


def _model_config(cfg: dict) -> ModelConfig:
    decoder = DecoderConfig.from_published(
        cfg, share_count=cfg["share"]["n"], share_index=cfg["share"]["s"],
        sequence_length=cfg["sequence_length"])
    return ModelConfig(backbone="decoder", dtype="float32", decoder=decoder)


def _weights(cfg: dict, seed: int = 0):
    """Seeded weights at the tiny size: 0.2-wide normals, norms about 1, and
    the decay's parameters as the family draws them."""
    key = jax.random.key(seed)
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(reference.param_spec(cfg).items())):
        leaf = jax.random.fold_in(key, i)
        if kind == "a_log":
            out[name] = jnp.log(jax.random.uniform(leaf, shape, jnp.float32, 1.0, 16.0))
        elif kind == "dt_bias":
            out[name] = jnp.log(jnp.expm1(jax.random.uniform(leaf, shape, jnp.float32, 0.01, 0.1)))
        else:
            centre = 1.0 if kind == "norm_scale" else 0.0
            out[name] = centre + 0.2 * jax.random.normal(leaf, shape)
    return out


@pytest.fixture(scope="module")
def full_weights():
    return _weights(_cfg(1, 0))


def _batch(vocab: int, seed: int = 3, length: int = 64, rows: int = 2):
    return {k: jnp.asarray(v) for k, v in next(tokens_lib.packed_token_batches(
        rows, length, vocab, STREAM, seed=seed)).items()}


def _program(cfg, flat):
    model = build_model(_model_config(cfg))
    template = model.init(jax.random.key(1), np.zeros((1, 8), np.int32))["params"]
    return model, weights.unflatten_like(template, flat)


def test_the_tiny_model_has_every_kind_of_layer():
    dcfg = _model_config(_cfg(2, 1)).decoder
    assert dcfg.layer_types[:5] == ("linear_attention",) * 3 + ("latent_attention",
                                                               "linear_attention")
    assert [dcfg.mlp_type(i) for i in range(5)] == ["dense"] + ["sparse"] * 4
    assert dcfg.scoring_func == "sigmoid" and dcfg.norm_topk_prob is True
    assert dcfg.moe_routed_scaling_factor == 2.446 and dcfg.shared_expert_intermediate_size == 32
    assert dcfg.num_experts_per_tok == 2 and dcfg.linear == {
        "head_dim": 16, "num_heads": 2, "short_conv_kernel_size": 4}
    assert (dcfg.kv_lora_rank, dcfg.qk_nope_head_dim, dcfg.qk_rope_head_dim, dcfg.v_head_dim) == (
        16, 8, 4, 8)


@pytest.mark.parametrize("n,s", [(2, 0), (2, 1), (1, 0)])
def test_program_matches_reference(full_weights, n, s):
    """The loss, the routed counts of the four sparse layers, every gradient
    leaf and the decay's mean, for both shares and the uncut model."""
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
    flat_grads = weights.flatten(grads)
    assert set(flat_grads) == set(want_grads)
    for name, g in want_grads.items():
        gap = float(jnp.linalg.norm(flat_grads[name] - g) / (jnp.linalg.norm(g) + 1e-30))
        assert gap < 1e-4, (name, gap)
    # keys a query reads on the latent layer (its document's earlier ones),
    # and the decays' mean over 4 linear layers x 2 heads x 16 channels
    positions = np.asarray(batch["positions"], np.float64)
    assert float(out["attn_keys_latent"]) == pytest.approx((positions + 1).sum())
    assert "attn_keys_linear" not in out
    assert float(out["kda_decay_n"]) == batch["tokens"].size * 4 * 2 * 16
    assert 0.0 < float(out["kda_decay_sum"] / out["kda_decay_n"]) < 1.0


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
    decay = metrics["attn/kda_decay_mean"]
    assert 0.0 < float(decay.total / decay.count) < 1.0
    want = reference.train_steps(cfg, dict(flat), batches)
    np.testing.assert_allclose(got, want["losses"], rtol=2e-5)
    after = weights.flatten(state.params)
    for name, w in want["params"].items():
        moved = np.asarray(w) - np.asarray(flat[name])
        gap = np.linalg.norm(np.asarray(after[name]) - np.asarray(w)) / (
            np.linalg.norm(moved) + 1e-30)
        assert gap < 2e-2, (name, gap)


def test_32_shares_add_up_to_the_uncut_layer():
    """The share test over the deployment's 32 shares (64 experts, 2 held
    each, top-2): each share's routed part of a sparse layer, summed, is the
    uncut reference's routed sum, and the whole layer is the residual stream
    plus attention — whole on every share, counted once — plus that sum and
    the shared expert, counted once."""
    full = dict(num_experts=64, vocab_size=256)
    cfg1 = _cfg(1, 0, full=full)
    w = _weights(cfg1, seed=4)
    batch = _batch(64)
    seg, pos = batch["segment_ids"][0], batch["positions"][0]
    x = jax.random.normal(jax.random.key(5), (64, cfg1["hidden_size"]))
    eps = cfg1["rms_norm_eps"]
    for i in (1, 3):  # a KDA layer and the MLA layer, both sparse
        u = reference.rms_norm(x, w[f"layers_{i}/attn_norm/scale"], eps)
        h = x + reference.mixer(cfg1, w, i, u, seg, pos)
        u2 = reference.rms_norm(h, w[f"layers_{i}/moe_norm/scale"], eps)
        parts = 0.0
        for s in range(32):
            cfg = _cfg(32, s, full=full)
            flat = reference.share_of(w, cfg1, 32, s)
            assert flat[f"layers_{i}/moe/w_up"].shape[0] == 2
            whole = [k for k in flat if "/attn/" in k or "norm" in k or "/shared/" in k
                     or "/mlp/" in k or k.endswith("/router")]
            assert all(flat[k] is w[k] for k in whole)
            parts = parts + reference.routed_part(cfg, flat, i, u2)
        np.testing.assert_allclose(np.asarray(parts),
                                   np.asarray(reference.routed_part(cfg1, w, i, u2)), atol=2e-5)
        y, _ = reference.layer(cfg1, w, i, x, seg, pos)
        np.testing.assert_allclose(np.asarray(h + parts + reference.shared_part(cfg1, w, i, u2)),
                                   np.asarray(y), atol=5e-5)
        assert float(jnp.max(jnp.abs(parts))) > 1e-3


def test_latent_attentions_kernel_path_is_the_xla_paths():
    """Queries and keys of 192 against values of 128 (the published head):
    the splash kernel in interpret mode against the XLA path, over packed
    documents."""
    r = np.random.default_rng(0)
    t, h = 256, 2
    q, k = (jnp.asarray(r.normal(size=(1, t, h, 192)), jnp.float32) for _ in range(2))
    v = jnp.asarray(r.normal(size=(1, t, h, 128)), jnp.float32)
    seg = jnp.asarray(np.repeat([0, 1, 2], [40, 100, 116])[None], jnp.int32)
    assert blocked_attention.kernel_serves(t, 192, 128)
    assert not blocked_attention.kernel_serves(t, 96, 128)
    got = blocked_attention.splash_attention(q, k, v, seg, interpret=True)
    want = blocked_attention.masked_attention_reference(q, k, v, seg)
    assert got.shape == (1, t, h, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_configuration_checks_its_layers():
    dcfg = _model_config(_cfg(2, 1)).decoder
    import dataclasses

    with pytest.raises(ValueError, match="mla_use_nope"):
        dataclasses.replace(dcfg, mla_use_nope=False)
    with pytest.raises(ValueError, match="linear_attn_config"):
        dataclasses.replace(dcfg, linear_attn_config=(("num_heads", 2),))
    with pytest.raises(ValueError, match="kv_lora_rank"):
        dataclasses.replace(dcfg, kv_lora_rank=0)


def _published_decoder() -> DecoderConfig:
    return DecoderConfig.from_published(tiny_linear.committed_config(), share_count=32,
                                        share_index=0, sequence_length=16384)


def test_the_preset_is_the_published_file_and_counts_602_433_408_parameters():
    cfg = tiny_linear.committed_config()
    preset = configs.get_preset("kimi_linear_48b_a3b_share32")
    assert preset.model.decoder == _published_decoder()
    assert preset.model.dtype == cfg["dtype"] and preset.global_batch == 1
    for key, value in cfg["train"].items():
        assert getattr(preset.train, key) == value, key
    # the published keys read by their own names
    dcfg = preset.model.decoder
    assert dcfg.layer_types[:27] == tuple(
        "linear_attention" if i + 1 in cfg["linear_attn_config"]["kda_layers"] else
        "latent_attention" for i in range(27))
    assert dcfg.layer_types[26] == "latent_attention"  # full_attn_layers ends with 27
    assert (dcfg.num_experts_per_tok, dcfg.scoring_func, dcfg.norm_topk_prob,
            dcfg.moe_routed_scaling_factor) == (8, "sigmoid", True, 2.446)
    assert dcfg.mlp_layer_types[:3] == ("dense", "sparse", "sparse")
    spec = reference.param_spec(cfg)
    count = lambda names: sum(int(np.prod(spec[k][0])) for k in names)  # noqa: E731
    assert count(spec) == cfg["n_params"] == 602_433_408
    layer = lambda i: [k for k in spec if k.startswith(f"layers_{i}/")]  # noqa: E731
    mixer = lambda i: [k for k in layer(i) if "/attn/" in k]  # noqa: E731
    # ISSUE 36's table
    assert count(mixer(0)) == 39_514_272 and count(mixer(3)) == 29_114_880
    assert count(reference.dense_leaves(cfg)) == 63_700_992
    assert count(k for k in layer(1) if "/moe/" in k) == 64_290_816
    assert count(["layers_1/moe/router"]) == 589_824
    assert count(["embed/embedding", "head/kernel"]) == 2 * 47_185_920
    # the program's tree holds the same leaves
    model = build_model(preset.model)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), np.zeros((1, 8), np.int32)))
    program = {k: tuple(v.shape) for k, v in weights.flatten(shapes["params"]).items()}
    assert program == {k: tuple(shape) for k, (shape, _) in spec.items()}


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="the catalog is not on this machine")
def test_the_file_holds_every_published_key_but_the_reduced_ones():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    cfg = tiny_linear.committed_config()
    assert cfg["source"] == row["source_url"]
    differing = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differing == sorted(cfg["reduced"])
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["num_experts"] * cfg["share"]["n"] == row["config"]["num_experts"]
    for key in ("share", "deployment", "assumed", "departures", "held", "kept"):
        assert cfg[key]


def test_fit_trains_the_linear_decoder(tmp_path):
    """Through ``ClassifierTrainer.fit``: the loss falls, and every window
    carries the decay's mean and the latent layer's keys a query."""
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
    windows = [e for e in events if e.get("event") == "step_window"]
    assert len(windows) >= 4
    losses = [w["scalars"]["loss"] for w in windows]
    assert losses[-1] < losses[0] - 0.2, losses
    for w in windows:
        assert w["moe_pairs_dropped"] == 0 and len(w["moe_expert_tokens"]) == 4
        assert set(w["attn_keys_per_query"]) == {"latent_attention"}
        assert 0.0 < w["kda_decay_mean"] < 1.0
