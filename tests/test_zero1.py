"""ZeRO-1 cross-replica weight-update sharding (parallel/zero.py,
``TrainConfig.weight_update_sharding``).

What must hold, on the forced 8-device CPU mesh:

- spec rule: every optimizer-state leaf partitions along the ``batch`` axis on
  its LARGEST dp-divisible dimension; scalars/indivisible leaves replicate;
  under tensor parallelism the batch shard composes with (never collides
  with) the model-axis channel sharding;
- placement: Adam moments AND the EMA tracker land sharded (1/dp per-chip
  bytes), params stay replicated;
- equivalence: a sharded-update run matches the replicated-update run
  STEP-FOR-STEP within tolerance — with donation on and through gradient
  accumulation (acceptance criteria of ISSUE 4);
- checkpoints: a sharded run's checkpoint restores into a replicated template
  and vice versa (the resume-across-modes contract), with values intact and
  the target placement honored.
"""

import os
import subprocess
import sys
import tempfile

if __name__ == "__main__":
    # subprocess worker mode (test_fit_end_to_end_with_weight_update_sharding
    # runs the e2e in a fresh interpreter): repo root onto sys.path — a
    # script invocation gets tests/ there instead
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
from tensorflowdistributedlearning_tpu.data import synthetic_batches
from tensorflowdistributedlearning_tpu.models import build_model
from tensorflowdistributedlearning_tpu.parallel import zero as zero_lib
from tensorflowdistributedlearning_tpu.parallel.mesh import (
    BATCH_AXIS,
    MODEL_AXIS,
    largest_divisible_dim,
    make_mesh,
    replicate,
    shard_batch,
)
from tensorflowdistributedlearning_tpu.train import step as step_lib
from tensorflowdistributedlearning_tpu.train.state import (
    create_train_state,
    tree_bytes_per_device,
)

TINY_VIT = ModelConfig(
    backbone="vit",
    num_classes=4,
    input_shape=(16, 16),
    input_channels=3,
    patch_size=4,
    embed_dim=32,
    vit_layers=2,
    num_heads=4,
    output_stride=None,
)
# the everything-on optimizer chain: clip -> AdamW(kernels-only decay) -> EMA
FULL_CHAIN = TrainConfig(
    optimizer="adam", lr=0.01, weight_decay=1e-4, ema_decay=0.9,
    grad_clip_norm=1.0,
)


def _state(tcfg, mesh=None, cfg=TINY_VIT, zero=False):
    from flax.core import unfreeze

    model = build_model(cfg)
    tx = step_lib.make_optimizer(tcfg)
    shape = (1,) + cfg.input_shape + (cfg.input_channels,)
    state = create_train_state(
        model, tx, jax.random.key(0), jnp.ones(shape, jnp.float32)
    )
    # plain-dict batch_stats: flax's mutable apply returns dicts, and a
    # donated step wants one stable pytree type in and out (the same
    # normalization bench.py's ViT section applies)
    state = state.replace(batch_stats=unfreeze(state.batch_stats))
    if mesh is None:
        return state
    if zero:
        return zero_lib.shard_state_weight_update(state, mesh)
    return replicate(state, mesh)


def _batches(n_steps, batch=32, seed=0):
    return list(
        synthetic_batches(
            "classification", batch, seed=seed, steps=n_steps,
            input_shape=(16, 16), channels=3, num_classes=4,
        )
    )


# -- spec rule ---------------------------------------------------------------


def test_largest_divisible_dim():
    assert largest_divisible_dim((16, 8), 8) == 0
    assert largest_divisible_dim((4, 16), 8) == 1
    assert largest_divisible_dim((3, 5), 8) is None
    assert largest_divisible_dim((), 8) is None
    # `taken` dims are skipped even when they divide
    assert largest_divisible_dim((16, 8), 8, taken={0}) == 1
    assert largest_divisible_dim((16, 5), 8, taken={0}) is None


def test_weight_update_spec_partitions_largest_dim():
    mesh = make_mesh(8)
    assert zero_lib.weight_update_spec((16, 8), mesh) == P(BATCH_AXIS, None)
    assert zero_lib.weight_update_spec((4, 16), mesh) == P(None, BATCH_AXIS)
    assert zero_lib.weight_update_spec((3, 3, 8, 16), mesh) == P(
        None, None, None, BATCH_AXIS
    )
    # scalars and indivisible leaves replicate (the cheap tail)
    assert zero_lib.weight_update_spec((), mesh) == P()
    assert zero_lib.weight_update_spec((3, 5), mesh) == P()
    assert zero_lib.weight_update_spec((7,), mesh) == P()


def test_weight_update_spec_composes_with_tensor_parallel():
    mesh = make_mesh(8, model_parallel=2)  # dp=4, tp=2
    # trailing dim goes to the model axis (the TP channel rule); the batch
    # axis takes the largest FREE dim that divides dp
    spec = zero_lib.weight_update_spec((3, 3, 8, 16), mesh, tensor_parallel=True)
    assert spec == P(None, None, BATCH_AXIS, MODEL_AXIS)
    # nothing free divides dp -> batch stacks onto the channel dim
    spec = zero_lib.weight_update_spec((5, 16), mesh, tensor_parallel=True)
    assert spec == P(None, (MODEL_AXIS, BATCH_AXIS))
    # nothing divides at all -> TP-only
    spec = zero_lib.weight_update_spec((5, 6), mesh, tensor_parallel=True)
    assert spec == P(None, MODEL_AXIS)


def test_opt_state_specs_cover_moments_and_ema():
    """The spec tree derived from a real optimizer chain: Adam mu/nu and the
    EMA tracker shard; schedule counters stay replicated."""
    mesh = make_mesh(8)
    state = _state(FULL_CHAIN)
    specs = zero_lib.weight_update_specs(state.opt_state, mesh)
    flat = {
        jax.tree_util.keystr(path): spec
        for path, spec in jax.tree_util.tree_leaves_with_path(specs)
    }
    sharded = [k for k, s in flat.items() if s != P()]
    scalar = [k for k, s in flat.items() if s == P()]
    # the bulk of the slots shard: mu, nu, and the EMA all mirror params
    assert sum(".mu" in k for k in sharded) > 5
    assert sum(".nu" in k for k in sharded) > 5
    assert sum(".ema" in k for k in sharded) > 5
    # the schedule step counter is scalar and must replicate
    assert any("count" in k for k in scalar)


# -- placement + accounting --------------------------------------------------


def test_placement_shards_opt_state_not_params():
    mesh = make_mesh(8)
    state = _state(FULL_CHAIN, mesh, zero=True)
    for leaf in jax.tree.leaves(state.params):
        assert leaf.sharding.spec == P()
    flat = jax.tree_util.tree_leaves_with_path(state.opt_state)
    n_sharded = sum(1 for _, leaf in flat if leaf.sharding.spec != P())
    assert n_sharded > 0.8 * len(flat)  # only scalars/tiny leaves replicate
    # a sharded leaf really holds 1/8 per device
    sharded_leaf = next(
        leaf for _, leaf in flat if leaf.sharding.spec != P()
    )
    shard_elems = np.prod(sharded_leaf.sharding.shard_shape(sharded_leaf.shape))
    assert shard_elems * 8 == np.prod(sharded_leaf.shape)


def test_per_device_bytes_drop_by_dp():
    mesh = make_mesh(8)
    rep = _state(FULL_CHAIN, mesh)
    zero = _state(FULL_CHAIN, mesh, zero=True)
    rep_bytes = tree_bytes_per_device(rep.opt_state)
    zero_bytes = tree_bytes_per_device(zero.opt_state)
    # ~dp-fold reduction (the replicated scalar tail keeps it under exactly 8)
    assert rep_bytes / zero_bytes > 6.0
    # params are replicated in both modes
    assert tree_bytes_per_device(rep.params) == tree_bytes_per_device(zero.params)


# -- equivalence (the acceptance criterion) ----------------------------------


def _assert_states_close(a, b, atol):
    for x, y in zip(
        jax.tree.leaves(jax.device_get(a.params)),
        jax.tree.leaves(jax.device_get(b.params)),
    ):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)


def test_sharded_update_matches_replicated_step_for_step():
    """3 donated steps, full optimizer chain (clip -> AdamW -> EMA): params
    agree within float32 tolerance after EVERY step and the metric streams
    are identical. Adam's eps-divide amplifies reduction-order noise in the
    early steps, hence the 1e-3 bound (SGD below pins a much tighter one)."""
    mesh = make_mesh(8)
    task = step_lib.ClassificationTask()
    rep_step = step_lib.make_train_step(mesh, task)  # donate=True default
    zero_step = step_lib.make_train_step(
        mesh, task, weight_update_sharding=True
    )
    rep = _state(FULL_CHAIN, mesh)
    zero = _state(FULL_CHAIN, mesh, zero=True)
    for raw in _batches(3):
        batch = shard_batch(raw, mesh)
        rep, m_rep = rep_step(rep, batch)
        zero, m_zero = zero_step(zero, batch)
        _assert_states_close(rep, zero, atol=1e-3)
        assert step_lib.compute_metrics(jax.device_get(m_rep))[
            "loss"
        ] == pytest.approx(
            step_lib.compute_metrics(jax.device_get(m_zero))["loss"], rel=1e-5
        )
    assert int(jax.device_get(zero.step)) == 3
    # the carried opt_state stayed sharded through the donated updates
    flat = jax.tree_util.tree_leaves_with_path(zero.opt_state)
    assert sum(1 for _, leaf in flat if leaf.sharding.spec != P()) > 0.8 * len(flat)
    # the EMA tracker rode along sharded and matches the replicated one
    ema_rep = step_lib.find_ema_params(rep.opt_state)
    ema_zero = step_lib.find_ema_params(zero.opt_state)
    for x, y in zip(jax.tree.leaves(ema_rep), jax.tree.leaves(ema_zero)):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y)),
            atol=1e-3,
        )


def test_sharded_update_matches_replicated_sgd_tight():
    """SGD+momentum (no eps-divide): the sharded update is the same math in a
    different layout, so the agreement bound is near-bitwise."""
    mesh = make_mesh(8)
    tcfg = TrainConfig(optimizer="sgd", lr=0.05, weight_decay=1e-4)
    task = step_lib.ClassificationTask()
    rep_step = step_lib.make_train_step(mesh, task)
    zero_step = step_lib.make_train_step(
        mesh, task, weight_update_sharding=True
    )
    rep = _state(tcfg, mesh)
    zero = _state(tcfg, mesh, zero=True)
    for raw in _batches(3, seed=11):
        batch = shard_batch(raw, mesh)
        rep, _ = rep_step(rep, batch)
        zero, _ = zero_step(zero, batch)
        _assert_states_close(rep, zero, atol=1e-5)


def test_grad_accum_with_sharded_update():
    """accum=4 microbatches + ZeRO-1 == accum=4 replicated (BN-free model:
    the accumulated mean gradient is identical, the update is the same math
    sharded)."""
    mesh = make_mesh(8)
    tcfg = TrainConfig(optimizer="sgd", lr=0.01, weight_decay=1e-4)
    task = step_lib.ClassificationTask()
    raw = _batches(1)[0]
    batch = shard_batch(raw, mesh)
    rep_step = step_lib.make_train_step(mesh, task, donate=False, accum=4)
    zero_step = step_lib.make_train_step(
        mesh, task, donate=False, accum=4, weight_update_sharding=True
    )
    rep, m_rep = rep_step(_state(tcfg, mesh), batch)
    zero, m_zero = zero_step(_state(tcfg, mesh, zero=True), batch)
    _assert_states_close(rep, zero, atol=1e-5)
    assert step_lib.compute_metrics(jax.device_get(m_rep))[
        "loss"
    ] == pytest.approx(
        step_lib.compute_metrics(jax.device_get(m_zero))["loss"], rel=1e-5
    )


def test_gspmd_tensor_parallel_composition():
    """fit()'s TP path: optimizer slots shard over (model, batch) jointly and
    the constrained GSPMD update matches the plain TP update."""
    from tensorflowdistributedlearning_tpu.data.synthetic import (
        synthetic_classification_batch,
    )
    from tensorflowdistributedlearning_tpu.parallel import tensor as tp_lib

    cfg = ModelConfig(
        num_classes=8, input_shape=(16, 16), input_channels=3,
        n_blocks=(1, 1, 1), base_depth=16, width_multiplier=0.125,
        output_stride=None,
    )
    mesh = make_mesh(8, model_parallel=2)  # dp=4, tp=2
    state = _state(TrainConfig(), cfg=cfg)
    placed = tp_lib.shard_state_weight_update(state, mesh)
    mu = placed.opt_state[0].mu["backbone"]["conv1_3"]["conv"]["kernel"]
    assert BATCH_AXIS in jax.tree.leaves(tuple(mu.sharding.spec)) or any(
        BATCH_AXIS in (axes if isinstance(axes, tuple) else (axes,))
        for axes in mu.sharding.spec
        if axes is not None
    )
    batch = synthetic_classification_batch(
        np.random.default_rng(0), 8, input_shape=(16, 16), channels=3,
        num_classes=8,
    )
    zero_step = tp_lib.make_train_step_gspmd(
        mesh, step_lib.ClassificationTask(), donate=False,
        weight_update_sharding=True,
    )
    new_zero, m_zero = zero_step(placed, tp_lib.place_batch_gspmd(batch, mesh))
    # slots stay (model, batch)-sharded after the constrained update
    mu2 = new_zero.opt_state[0].mu["backbone"]["conv1_3"]["conv"]["kernel"]
    spec_axes = [
        a for axes in mu2.sharding.spec if axes is not None
        for a in (axes if isinstance(axes, tuple) else (axes,))
    ]
    assert BATCH_AXIS in spec_axes and MODEL_AXIS in spec_axes

    rep_step = tp_lib.make_train_step_gspmd(
        mesh, step_lib.ClassificationTask(), donate=False
    )
    new_rep, m_rep = rep_step(
        tp_lib.shard_state_tensor_parallel(_state(TrainConfig(), cfg=cfg), mesh),
        tp_lib.place_batch_gspmd(batch, mesh),
    )
    assert step_lib.compute_metrics(jax.device_get(m_zero))[
        "loss"
    ] == pytest.approx(
        step_lib.compute_metrics(jax.device_get(m_rep))["loss"], rel=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(
            jax.device_get(new_zero.params["backbone"]["conv1_3"]["conv"]["kernel"])
        ),
        np.asarray(
            jax.device_get(new_rep.params["backbone"]["conv1_3"]["conv"]["kernel"])
        ),
        atol=1e-3,
    )


# -- checkpoint round trip across sharding modes -----------------------------


def _ckpt(directory):
    from tensorflowdistributedlearning_tpu.train.checkpoint import (
        CheckpointManager,
    )

    return CheckpointManager(directory, save_every_steps=1)


def test_checkpoint_roundtrip_sharded_to_replicated_and_back():
    mesh = make_mesh(8)
    task = step_lib.ClassificationTask()
    zero_step = step_lib.make_train_step(
        mesh, task, donate=False, weight_update_sharding=True
    )
    zero = _state(FULL_CHAIN, mesh, zero=True)
    zero, _ = zero_step(zero, shard_batch(_batches(1)[0], mesh))

    with tempfile.TemporaryDirectory() as d:
        ckpt = _ckpt(os.path.join(d, "a"))
        try:
            assert ckpt.save(zero, force=True)
            # sharded run's checkpoint -> REPLICATED template
            rep = ckpt.restore_latest(_state(FULL_CHAIN, mesh))
        finally:
            ckpt.close()
    assert int(jax.device_get(rep.step)) == 1
    for a, b in zip(
        jax.tree.leaves(jax.device_get(zero.opt_state)),
        jax.tree.leaves(jax.device_get(rep.opt_state)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for leaf in jax.tree.leaves(rep.opt_state):
        assert leaf.sharding.spec == P()

    with tempfile.TemporaryDirectory() as d:
        ckpt = _ckpt(os.path.join(d, "b"))
        try:
            assert ckpt.save(rep, force=True)
            # replicated checkpoint -> ZERO-sharded template
            zero2 = ckpt.restore_latest(_state(FULL_CHAIN, mesh, zero=True))
        finally:
            ckpt.close()
    flat = jax.tree_util.tree_leaves_with_path(zero2.opt_state)
    assert sum(1 for _, leaf in flat if leaf.sharding.spec != P()) > 0.8 * len(flat)
    for a, b in zip(
        jax.tree.leaves(jax.device_get(rep.opt_state)),
        jax.tree.leaves(jax.device_get(zero2.opt_state)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the restored-into-sharded state keeps TRAINING correctly
    zero2, m = zero_step(zero2, shard_batch(_batches(1, seed=9)[0], mesh))
    assert np.isfinite(step_lib.compute_metrics(jax.device_get(m))["loss"])
    assert int(jax.device_get(zero2.step)) == 2


# -- trainer wiring ----------------------------------------------------------


def test_fit_end_to_end_with_weight_update_sharding(tmp_path):
    """ClassifierTrainer.fit() trains, checkpoints, evaluates, and RESUMES
    through the ZeRO-1 path — and the run ledger records the per-device
    opt-state bytes the mode exists to shrink.

    Runs in a FRESH SUBPROCESS interpreter (the resilience e2e's isolation
    pattern): compiling this BN-backbone double-fit inside a long-lived
    suite process flakily crashes this box's XLA:CPU — the root-conftest-
    documented cumulative-compile crash, seen here as SIGSEGV or SIGABRT at
    either fit's compile, with the persistent-cache writer thread one of the
    triggers — while a fresh interpreter passes deterministically. The
    worker is this file's ``__main__`` mode; compile cache off in the child
    for the same reason the resilience worker keeps it off."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ""
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["TFDL_NO_COMPILE_CACHE"] = "1"
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), str(tmp_path)],
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
    )
    assert out.returncode == 0 and "FIT_E2E_OK" in (out.stdout or ""), (
        f"fit e2e worker failed rc={out.returncode}\n"
        f"stdout:{(out.stdout or '')[-3000:]}\n"
        f"stderr:{(out.stderr or '')[-2000:]}"
    )


def _run_fit_e2e(tmp_path):
    import json

    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

    model_cfg = ModelConfig(
        num_classes=3, input_shape=(8, 8), input_channels=1,
        n_blocks=(1, 1, 1), block_type="basic_block", width_multiplier=0.25,
        output_stride=None,
    )
    train_cfg = TrainConfig(
        optimizer="adam", lr=0.01, weight_update_sharding=True,
        checkpoint_every_steps=2, ema_decay=0.9,
    )
    workdir = str(tmp_path / "run")
    trainer = ClassifierTrainer(workdir, None, model_cfg, train_cfg)
    result = trainer.fit(batch_size=16, steps=3, eval_every_steps=3)
    assert result.steps == 3
    assert np.isfinite(result.final_metrics["loss"])

    # the memory event carries the exact per-device opt-state accounting
    events = [
        json.loads(line)
        for line in open(os.path.join(workdir, "telemetry.jsonl"))
    ]
    mem = [e for e in events if e.get("event") == "memory"]
    assert any(e.get("weight_update_sharding") for e in mem)
    tracked = [e for e in mem if "opt_state_bytes_per_device" in e]
    assert tracked
    # sharded slots are well under the replicated footprint (~3x params
    # with adam+ema; sharded ~3x/8 + replicated tail)
    assert (
        tracked[-1]["opt_state_bytes_per_device"]
        < tracked[-1]["params_bytes_per_device"]
    )

    # resume continues through the zero path (restore into sharded template)
    trainer2 = ClassifierTrainer(workdir, None, model_cfg, train_cfg)
    result2 = trainer2.fit(batch_size=16, steps=5, eval_every_steps=5)
    assert result2.steps == 5


def test_config_validation():
    with pytest.raises(ValueError, match="weight_update_sharding"):
        TrainConfig(weight_update_sharding=True, pipeline_parallel=2)
    # the modes it composes with all construct
    TrainConfig(weight_update_sharding=True, grad_accum_steps=2)
    TrainConfig(weight_update_sharding=True, sequence_parallel=2)
    TrainConfig(weight_update_sharding=True, model_parallel=2)
    TrainConfig(weight_update_sharding=True, sync_batch_norm=True)


def test_merge_stacked_metrics_rejects_non_mean_leaf():
    """The one shared merge of both scan paths fails loudly on anything that
    is not a Mean state — a blind sum would silently mis-merge it."""
    from tensorflowdistributedlearning_tpu.ops import metrics as metrics_lib

    stacked = {
        "loss": metrics_lib.Mean(
            total=jnp.ones((3,)), count=jnp.ones((3,))
        ),
        "rogue": jnp.ones((3,)),
    }
    with pytest.raises(TypeError, match="rogue"):
        step_lib._merge_stacked_metrics(stacked)
    ok = step_lib._merge_stacked_metrics(
        {"loss": metrics_lib.Mean(total=jnp.ones((3,)), count=jnp.ones((3,)))}
    )
    assert float(ok["loss"].total) == 3.0


if __name__ == "__main__":
    # worker mode for test_fit_end_to_end_with_weight_update_sharding's
    # subprocess: run the double-fit e2e against the given workdir and print
    # a sentinel the parent asserts on (any assert/crash surfaces via rc)
    import pathlib

    _run_fit_e2e(pathlib.Path(sys.argv[1]))
    print("FIT_E2E_OK", flush=True)
