"""End-to-end SPMD train-step tests on the 8-device CPU mesh — the minimum slice of
SURVEY §7: loss decreases, metrics flow, state stays replicated, runs are
deterministic (the determinism check SURVEY §5.2 calls for in place of race detection)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.data import synthetic_batches
from tensorflowdistributedlearning_tpu.models import build_model
from tensorflowdistributedlearning_tpu.parallel import make_mesh, replicate, shard_batch
from tensorflowdistributedlearning_tpu.train import (
    ClassificationTask,
    SegmentationTask,
    create_train_state,
    make_eval_step,
    make_optimizer,
    make_predict_step,
    make_train_step,
)
from tensorflowdistributedlearning_tpu.train.step import (
    compute_metrics,
    merge_metrics,
)

SMALL_SEG = ModelConfig(
    n_blocks=(1, 1, 1), input_shape=(32, 32), base_depth=8, width_multiplier=0.0625
)
SMALL_CLS = ModelConfig(
    n_blocks=(1, 1, 1),
    input_shape=(32, 32),
    input_channels=3,
    num_classes=4,
    base_depth=8,
    width_multiplier=0.0625,
    output_stride=None,
)


def _setup(cfg, task, mesh, batch_shape):
    model = build_model(cfg)
    tx = make_optimizer(TrainConfig(lr=0.003))
    state = create_train_state(
        model, tx, jax.random.key(0), jnp.ones(batch_shape, jnp.float32)
    )
    state = replicate(state, mesh)
    return state


def test_segmentation_loss_decreases_on_mesh():
    mesh = make_mesh(8)
    task = SegmentationTask()
    state = _setup(SMALL_SEG, task, mesh, (1, 32, 32, 2))
    train_step = make_train_step(mesh, task)
    batches = synthetic_batches(
        "segmentation", 16, seed=1, input_shape=(32, 32), steps=6
    )
    losses = []
    for batch in batches:
        state, metrics = train_step(state, shard_batch(batch, mesh))
        losses.append(compute_metrics(metrics)["loss"])
    assert int(state.step) == 6
    assert np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_eval_and_predict_steps():
    mesh = make_mesh(8)
    task = SegmentationTask()
    state = _setup(SMALL_SEG, task, mesh, (1, 32, 32, 2))
    eval_step = make_eval_step(mesh, task)
    predict_step = make_predict_step(mesh, task)
    batch = next(synthetic_batches("segmentation", 8, seed=2, input_shape=(32, 32)))
    sharded = shard_batch(batch, mesh)

    acc = None
    for _ in range(2):
        acc = merge_metrics(acc, eval_step(state, sharded))
    values = compute_metrics(acc)
    assert set(values) == {"metrics/mean_iou", "metrics/mean_acc", "loss"}
    assert acc["metrics/mean_iou"].count == 16  # 8 images x 2 passes

    preds = predict_step(state, sharded)
    assert preds["probabilities"].shape == (8, 32, 32, 1)
    assert preds["mask"].shape == (8, 32, 32, 1)
    probs = np.asarray(preds["probabilities"])
    assert np.all((probs >= 0) & (probs <= 1))


def test_classification_loss_decreases_on_mesh():
    mesh = make_mesh(8)
    task = ClassificationTask()
    state = _setup(SMALL_CLS, task, mesh, (1, 32, 32, 3))
    train_step = make_train_step(mesh, task)
    batches = synthetic_batches(
        "classification", 16, seed=3, input_shape=(32, 32), num_classes=4, steps=12
    )
    losses = []
    for batch in batches:
        state, metrics = train_step(state, shard_batch(batch, mesh))
        losses.append(compute_metrics(metrics)["loss"])
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_sharded_step_matches_single_device():
    """DP invariance: the 8-way sharded step must produce the same new params as a
    1-device run on the identical global batch (per-shard BN stats make batch_stats the
    one intentional difference — compare params and loss only).

    Note: with BN computing per-shard statistics, forward activations differ between
    1-way and 8-way; so we compare a BN-stat-free configuration... instead we compare
    8-way vs 8-way determinism here and cross-degree equivalence in
    test_cross_degree_grads for a BN-free model.
    """
    mesh = make_mesh(8)
    task = SegmentationTask()
    state_a = _setup(SMALL_SEG, task, mesh, (1, 32, 32, 2))
    state_b = _setup(SMALL_SEG, task, mesh, (1, 32, 32, 2))
    train_step = make_train_step(mesh, task, donate=False)
    batch = next(synthetic_batches("segmentation", 16, seed=4, input_shape=(32, 32)))
    sharded = shard_batch(batch, mesh)
    new_a, m_a = train_step(state_a, sharded)
    new_b, m_b = train_step(state_b, sharded)
    la, lb = compute_metrics(m_a)["loss"], compute_metrics(m_b)["loss"]
    assert la == pytest.approx(lb, abs=0.0)  # bitwise determinism
    flat_a = jax.tree.leaves(new_a.params)
    flat_b = jax.tree.leaves(new_b.params)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cross_degree_grads():
    """True MirroredStrategy semantics: training on the SAME global batch must
    produce the same parameter update at data-parallel degree 1 and 8 (grads are
    the global-batch MEAN, not a per-shard sum — reference: MirroredStrategy's
    cross-device gradient aggregation, model.py:115-121). Uses a BN-free model so
    per-shard batch statistics cannot introduce a legitimate difference."""
    import flax.linen as nn

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = nn.Conv(8, (3, 3), padding="SAME")(x)
            x = nn.relu(x)
            x = x.mean(axis=(1, 2))
            return nn.Dense(4)(x)

    task = ClassificationTask()
    batch = next(
        synthetic_batches(
            "classification", 16, seed=9, input_shape=(8, 8), num_classes=4
        )
    )
    tx = make_optimizer(TrainConfig(lr=0.01))
    results = {}
    for n in (1, 8):
        mesh = make_mesh(n)
        model = Tiny()
        state = replicate(
            create_train_state(
                model, tx, jax.random.PRNGKey(0), np.zeros((1, 8, 8, 3), np.float32)
            ),
            mesh,
        )
        step = make_train_step(mesh, task, donate=False)
        new_state, _ = step(state, shard_batch(batch, mesh))
        results[n] = jax.tree.leaves(jax.device_get(new_state.params))
    for a, b in zip(results[1], results[8]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_state_stays_replicated_after_step():
    mesh = make_mesh(8)
    task = SegmentationTask()
    state = _setup(SMALL_SEG, task, mesh, (1, 32, 32, 2))
    train_step = make_train_step(mesh, task)
    batch = next(synthetic_batches("segmentation", 8, seed=5, input_shape=(32, 32)))
    state, _ = train_step(state, shard_batch(batch, mesh))
    leaf = jax.tree.leaves(state.params)[0]
    assert leaf.sharding.is_fully_replicated


def test_eval_step_valid_mask_excludes_padding():
    """Eval metrics with a `valid` mask must equal metrics computed over only the
    valid rows — the wrap-around-padding exclusion contract of eval_batches."""
    mesh = make_mesh(8)
    task = SegmentationTask()
    state = _setup(SMALL_SEG, task, mesh, (1, 32, 32, 2))
    eval_step = make_eval_step(mesh, task)
    batch = next(synthetic_batches("segmentation", 16, seed=6, input_shape=(32, 32)))

    # full batch, but only the first 10 rows are real
    valid = np.zeros(16, np.float32)
    valid[:10] = 1.0
    masked = dict(batch)
    masked["valid"] = valid
    got = compute_metrics(eval_step(state, shard_batch(masked, mesh)))

    # reference: build a 16-row batch whose rows are the 10 real ones wrapped around,
    # all valid -- metrics over exactly the same multiset requires matching rows, so
    # instead compare against a masked run with the padded rows REPLACED by garbage:
    # results must be identical since weight 0 excludes them.
    garbage = dict(masked)
    garbage["images"] = batch["images"].copy()
    garbage["images"][10:] = 999.0
    got_garbage = compute_metrics(eval_step(state, shard_batch(garbage, mesh)))
    for k in got:
        assert got[k] == pytest.approx(got_garbage[k], rel=1e-6), k
    # and the count only reflects valid rows
    acc = eval_step(state, shard_batch(masked, mesh))
    assert float(acc["metrics/mean_iou"].count) == 10.0


def test_bfloat16_train_step_close_to_float32():
    """The bf16 compute path (MXU dtype) trains: finite losses, and the first
    step's loss stays close to the float32 path on identical data/params."""
    import dataclasses

    mesh = make_mesh(8)
    task = SegmentationTask()
    batch = next(synthetic_batches("segmentation", 16, seed=21, input_shape=(32, 32)))
    losses = {}
    for dtype in ("float32", "bfloat16"):
        cfg = dataclasses.replace(SMALL_SEG, dtype=dtype)
        state = _setup(cfg, task, mesh, (1, 32, 32, 2))
        step = make_train_step(mesh, task, donate=False)
        new_state, metrics = step(state, shard_batch(batch, mesh))
        losses[dtype] = compute_metrics(metrics)["loss"]
        # params stay float32 regardless of compute dtype
        assert all(
            leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(new_state.params)
        )
    assert np.isfinite(losses["bfloat16"])
    assert losses["bfloat16"] == pytest.approx(losses["float32"], rel=0.05)


def test_sgd_optimizer_trains():
    """TrainConfig.optimizer='sgd' (Nesterov momentum, the ImageNet recipe):
    loss decreases on the mesh like the Adam default."""
    mesh = make_mesh(8)
    task = ClassificationTask()
    model = build_model(SMALL_CLS)
    tx = make_optimizer(TrainConfig(optimizer="sgd", lr=0.05))
    state = replicate(
        create_train_state(
            model, tx, jax.random.key(1), jnp.ones((1, 32, 32, 3), jnp.float32)
        ),
        mesh,
    )
    train_step = make_train_step(mesh, task)
    losses = []
    for batch in synthetic_batches(
        "classification", 16, seed=30, input_shape=(32, 32), num_classes=4, steps=12
    ):
        state, metrics = train_step(state, shard_batch(batch, mesh))
        losses.append(compute_metrics(metrics)["loss"])
    assert np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_unknown_optimizer_rejected():
    with pytest.raises(ValueError, match="Unknown optimizer"):
        TrainConfig(optimizer="adagrad")


def _toy_params():
    return {
        "conv": {"kernel": jnp.ones((3, 3, 2, 4), jnp.float32)},
        "bn": {"scale": jnp.ones((4,), jnp.float32), "bias": jnp.zeros((4,), jnp.float32)},
    }


def test_weight_decay_update_differs_and_masks_kernels():
    """The decayed SGD chain produces a different update from the undecayed one
    (VERDICT round-2 task #2), and the decay touches ONLY kernel leaves: with
    zero gradients the kernel shrinks toward zero while BN scale/bias —
    excluded by the mask, per the recipe (arXiv:1706.02677 §5.3) — stay put."""
    params = _toy_params()
    grads = jax.tree.map(jnp.zeros_like, params)

    plain = make_optimizer(TrainConfig(optimizer="sgd", lr=0.1))
    decayed = make_optimizer(TrainConfig(optimizer="sgd", lr=0.1, weight_decay=1e-2))

    up_plain, _ = plain.update(grads, plain.init(params), params)
    up_decayed, _ = decayed.update(grads, decayed.init(params), params)

    # undecayed + zero grads = zero update everywhere
    assert all(np.all(leaf == 0) for leaf in jax.tree.leaves(up_plain))
    # decayed: kernel moves (toward zero), non-kernels still untouched
    assert np.all(np.asarray(up_decayed["conv"]["kernel"]) < 0)
    assert np.all(np.asarray(up_decayed["bn"]["scale"]) == 0)
    assert np.all(np.asarray(up_decayed["bn"]["bias"]) == 0)


def test_weight_decay_mask_covers_moe_expert_weights():
    """The decay mask treats MoE expert matrices (w_in/w_out) and the router as
    weight matrices — they replace dense mlp kernels and must regularize like
    them — while expert biases stay excluded (code review r3)."""
    from tensorflowdistributedlearning_tpu.train.step import kernel_decay_mask

    params = {
        "moe": {
            "w_in": jnp.ones((2, 4, 8)),
            "b_in": jnp.zeros((2, 8)),
            "w_out": jnp.ones((2, 8, 4)),
            "b_out": jnp.zeros((2, 4)),
            "router": jnp.ones((4, 2)),
        },
        "ln": {"scale": jnp.ones((4,))},
    }
    mask = kernel_decay_mask(params)
    assert mask["moe"]["w_in"] and mask["moe"]["w_out"] and mask["moe"]["router"]
    assert not mask["moe"]["b_in"] and not mask["moe"]["b_out"]
    assert not mask["ln"]["scale"]


def test_weight_decay_adam_is_adamw():
    """weight_decay>0 with adam switches the chain to AdamW (decoupled decay),
    again masked to kernels only."""
    params = _toy_params()
    grads = jax.tree.map(jnp.zeros_like, params)
    tx = make_optimizer(TrainConfig(optimizer="adam", lr=0.1, weight_decay=1e-2))
    updates, _ = tx.update(grads, tx.init(params), params)
    assert np.all(np.asarray(updates["conv"]["kernel"]) < 0)
    assert np.all(np.asarray(updates["bn"]["scale"]) == 0)


def test_imagenet_presets_carry_weight_decay():
    """Every ImageNet preset ships the weight decay its cited recipe requires
    (Goyal et al. 1e-4 for the SGD/LARS ResNets, DeiT 0.1 for ViT); the
    reference-parity presets keep 0 — the reference never minimized its
    declared l2 (reference: model.py:462-467)."""
    from tensorflowdistributedlearning_tpu.configs import PRESETS

    assert PRESETS["resnet50_imagenet"].train.weight_decay == 1e-4
    assert PRESETS["resnet101_imagenet"].train.weight_decay == 1e-4
    assert PRESETS["resnet152_imagenet"].train.weight_decay == 1e-4
    assert PRESETS["xception41_imagenet"].train.weight_decay == 1e-4
    assert PRESETS["vit_s16_imagenet"].train.weight_decay == 0.1
    assert PRESETS["resnet50_bf16_8k"].train.weight_decay == 1e-4
    assert PRESETS["resnet50_bf16_8k"].train.optimizer == "lars"
    assert PRESETS["tgs_salt"].train.weight_decay == 0.0


def test_xception_classifier_trains():
    """Regression: Xception41's pre-logits dropout is live in train mode, so
    the train step must supply a 'dropout' PRNG stream — before the fix,
    train-mode apply raised InvalidRngError and the xception41 preset could
    not train a single step."""
    mesh = make_mesh(8)
    cfg = ModelConfig(
        backbone="xception",
        num_classes=4,
        input_shape=(32, 32),
        input_channels=3,
        width_multiplier=0.125,
    )
    task = ClassificationTask()
    state = _setup(cfg, task, mesh, (1, 32, 32, 3))
    train_step = make_train_step(mesh, task)
    batches = synthetic_batches(
        "classification", 16, seed=5, input_shape=(32, 32), num_classes=4, steps=8
    )
    losses = []
    for batch in batches:
        state, metrics = train_step(state, shard_batch(batch, mesh))
        losses.append(compute_metrics(metrics)["loss"])
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_xception_trains_under_grad_accum():
    """The accum scan threads a per-chunk index into the dropout stream; a
    dropout-bearing model must run under accum > 1 too (learning-rate descent
    is asserted by the non-accum test — with 0.5 dropout a handful of accum
    steps is too noisy for a monotonicity check)."""
    mesh = make_mesh(8)
    cfg = ModelConfig(
        backbone="xception",
        num_classes=4,
        input_shape=(32, 32),
        input_channels=3,
        width_multiplier=0.125,
    )
    task = ClassificationTask()
    state = _setup(cfg, task, mesh, (1, 32, 32, 3))
    train_step = make_train_step(mesh, task, accum=2)
    batches = synthetic_batches(
        "classification", 16, seed=5, input_shape=(32, 32), num_classes=4, steps=2
    )
    for batch in batches:
        state, metrics = train_step(state, shard_batch(batch, mesh))
        assert np.isfinite(compute_metrics(metrics)["loss"])


def test_dropout_stream_follows_configured_seed():
    """The dropout PRNG roots at the configured seed (TrainConfig.seed in the
    drivers), not a hardcoded key: same seed ⇒ bitwise-identical update,
    different seed ⇒ different dropout masks ⇒ different params."""
    mesh = make_mesh(8)
    cfg = ModelConfig(
        backbone="xception",
        num_classes=4,
        input_shape=(32, 32),
        input_channels=3,
        width_multiplier=0.125,
    )
    task = ClassificationTask()
    state = _setup(cfg, task, mesh, (1, 32, 32, 3))
    batch = shard_batch(
        next(
            synthetic_batches(
                "classification", 16, seed=5, input_shape=(32, 32), num_classes=4
            )
        ),
        mesh,
    )
    leaves = lambda s: jax.tree.leaves(jax.device_get(s.params))  # noqa: E731
    out_a = leaves(make_train_step(mesh, task, donate=False)(state, batch)[0])
    out_a2 = leaves(make_train_step(mesh, task, donate=False)(state, batch)[0])
    out_b = leaves(
        make_train_step(mesh, task, donate=False, seed=123)(state, batch)[0]
    )
    for a, a2 in zip(out_a, out_a2):
        np.testing.assert_array_equal(a, a2)
    assert any(not np.array_equal(a, b) for a, b in zip(out_a, out_b))


def test_lars_optimizer_trains():
    """TrainConfig.optimizer='lars' (large-batch layer-wise scaling,
    arXiv:1708.03888 — the 8k preset's optimizer) trains on the CPU mesh:
    loss decreases and stays finite."""
    mesh = make_mesh(8)
    task = ClassificationTask()
    model = build_model(SMALL_CLS)
    # kernels ride the trust-ratio-scaled update; BN/bias (excluded from trust
    # scaling, per the recipe) take the raw lr — keep it moderate, and use a
    # real per-shard batch (8): LARS is a large-batch method, and per-shard
    # BN over 2 images makes the raw-lr BN updates noisy enough to diverge
    tx = make_optimizer(TrainConfig(optimizer="lars", lr=0.2, weight_decay=1e-4))
    state = replicate(
        create_train_state(
            model, tx, jax.random.key(1), jnp.ones((1, 32, 32, 3), jnp.float32)
        ),
        mesh,
    )
    train_step = make_train_step(mesh, task)
    losses = []
    for batch in synthetic_batches(
        "classification", 64, seed=31, input_shape=(32, 32), num_classes=4, steps=12
    ):
        state, metrics = train_step(state, shard_batch(batch, mesh))
        losses.append(compute_metrics(metrics)["loss"])
    assert np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_sync_batch_norm_matches_global_batch_oracle():
    """TrainConfig.sync_batch_norm semantics: BN statistics span the GLOBAL
    batch (flax BN pmean over the batch mesh axis), so one train step on the
    8-shard mesh must reproduce the same step on a 1-device mesh where BN
    sees the full batch natively — params, BN stats, and loss. The per-shard
    default (the reference's per-tower semantics) measurably diverges: the
    negative control asserts it, and DIGITS_RUN.json's xception rows price
    it at up to 10 points of real accuracy."""
    from tensorflowdistributedlearning_tpu.parallel.mesh import BATCH_AXIS

    def setup(model, mesh):
        tx = make_optimizer(TrainConfig(optimizer="sgd", lr=0.01))
        st = create_train_state(
            model, tx, jax.random.key(0), jnp.ones((1, 32, 32, 2), jnp.float32)
        )
        return replicate(st, mesh)

    task = SegmentationTask()
    batch = next(
        synthetic_batches("segmentation", 16, seed=9, input_shape=(32, 32), steps=1)
    )

    mesh1 = make_mesh(1)
    oracle_model = build_model(SMALL_SEG)
    st = setup(oracle_model, mesh1)
    st, m_oracle = make_train_step(mesh1, task, donate=False)(
        st, shard_batch(batch, mesh1)
    )
    oracle = st

    mesh8 = make_mesh(8)
    sync_model = build_model(SMALL_SEG, bn_axis_name=BATCH_AXIS)
    st = setup(sync_model, mesh8)
    st, m_sync = make_train_step(mesh8, task, donate=False)(
        st, shard_batch(batch, mesh8)
    )

    def maxdiff(ta, tb):
        return max(
            float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb))
        )

    assert maxdiff(oracle.params, st.params) < 1e-4
    assert maxdiff(oracle.batch_stats, st.batch_stats) < 1e-5
    np.testing.assert_allclose(
        compute_metrics(m_sync)["loss"], compute_metrics(m_oracle)["loss"],
        rtol=1e-5,
    )

    # negative control: per-shard BN (the default) does NOT match the oracle
    plain_model = build_model(SMALL_SEG)
    st_p = setup(plain_model, mesh8)
    st_p, _ = make_train_step(mesh8, task, donate=False)(
        st_p, shard_batch(batch, mesh8)
    )
    assert maxdiff(oracle.batch_stats, st_p.batch_stats) > 1e-4
