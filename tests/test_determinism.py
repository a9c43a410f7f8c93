"""Determinism regression (SURVEY §5.2's plan; VERDICT r1 #6): a fixed PRNG seed
must give a bitwise-stable loss sequence across two runs in one process — the SPMD
replacement for the race-freedom guarantees the reference got from synchronous
in-graph replication — plus a golden-value assertion to catch silent numerics
drift in the model/loss/augmentation stack."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.data import augment as augment_lib
from tensorflowdistributedlearning_tpu.data.synthetic import (
    synthetic_segmentation_batch,
)
from tensorflowdistributedlearning_tpu.models import build_model
from tensorflowdistributedlearning_tpu.parallel import mesh as mesh_lib
from tensorflowdistributedlearning_tpu.train import step as step_lib
from tensorflowdistributedlearning_tpu.train.state import create_train_state

STEPS = 3


def _run_losses(seed: int) -> list:
    """The trainer's full per-step recipe (on-device augmentation keyed by
    fold_in(seed, step) -> SPMD train step) on tiny shapes, returning the float32
    loss value of every step."""
    cfg = ModelConfig(
        input_shape=(16, 16), n_blocks=(1, 1, 1), base_depth=8, width_multiplier=0.0625
    )
    tcfg = TrainConfig(seed=seed)
    mesh = mesh_lib.make_mesh(8)
    model = build_model(cfg)
    state = mesh_lib.replicate(
        create_train_state(
            model,
            step_lib.make_optimizer(tcfg),
            jax.random.PRNGKey(seed),
            np.zeros((1, 16, 16, 2), np.float32),
        ),
        mesh,
    )
    train_step = step_lib.make_train_step(
        mesh, step_lib.SegmentationTask(), donate=False
    )
    acfg = augment_lib.AugmentConfig(crop_probability=0.0)

    @jax.jit
    def prepare(step, batch):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return augment_lib.augment_batch(key, batch["images"], batch["masks"], acfg)

    rng = np.random.default_rng(seed)
    losses = []
    for step_no in range(STEPS):
        # single-channel source images: augment_batch appends the Laplacian
        # channel to reach the model's input_channels=2
        raw = synthetic_segmentation_batch(rng, 8, input_shape=(16, 16), channels=1)
        batch = {"images": raw["images"], "masks": raw["labels"]}
        batch = prepare(jnp.asarray(step_no), mesh_lib.shard_batch(batch, mesh))
        state, metrics = train_step(state, batch)
        losses.append(float(step_lib.compute_metrics(jax.device_get(metrics))["loss"]))
    return losses


@pytest.fixture(scope="module")
def runs():
    """The minimum set of runs every assertion below needs: seed 0 twice (bitwise
    stability) and seed 1 once (seed sensitivity). Shared at module scope — each
    run pays a full train-step compile."""
    return _run_losses(0), _run_losses(0), _run_losses(1)


def test_fixed_seed_bitwise_stable_losses(runs):
    a, b, _ = runs
    assert a == b  # exact float equality, not approx


def test_different_seed_differs(runs):
    a, _, c = runs
    assert a != c


def test_golden_loss_after_k_steps(runs):
    """Golden regression: catches silent numerics drift (model structure, loss,
    augmentation, optimizer). Recorded on the 8-device CPU mesh; loosen only with
    an understood numerics change."""
    losses, *_ = runs
    golden = GOLDEN_LOSSES
    assert losses == pytest.approx(golden, rel=1e-4), (
        f"loss sequence drifted: {losses} != golden {golden}"
    )


# Recorded 2026-10-01, jax 0.9.0, 8-device CPU mesh, width_multiplier=1/16 fixture
# (re-recorded when the fixture architecture gained width_multiplier, and when the
# augmentation's warp became dense weights over the kept pixels: the same pixels to
# 6e-5, tests/test_augment.py, but this fixture answers a 1e-7 perturbation of the
# gathered warp's images with 0.2% in the first loss and 3% in the third)
GOLDEN_LOSSES = [1.5611239671707153, 1.5893504619598389, 1.33280611038208]
