"""Multi-host helper tests — single-process semantics on the 8-device CPU mesh
(the multi-process path differs only in which rows each process contributes;
jax.make_array_from_process_local_data handles the assembly either way)."""

import jax
import numpy as np

from tensorflowdistributedlearning_tpu.parallel import multihost
from tensorflowdistributedlearning_tpu.parallel.mesh import (
    BATCH_AXIS,
    make_mesh,
    shard_batch,
)


def test_a_run_that_names_no_coordinator_joins_nothing(monkeypatch, tmp_path):
    """One process over its host's chips never calls
    jax.distributed.initialize(): jax's auto-discovery asks a metadata server
    a sealed single-host machine does not have. Both trainers build without
    it; the world is the one process."""
    import pytest

    def joined(*args, **kwargs):
        raise AssertionError("jax.distributed.initialize() was called")

    monkeypatch.setattr(jax.distributed, "initialize", joined)
    from tensorflowdistributedlearning_tpu.config import ModelConfig
    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer
    from tensorflowdistributedlearning_tpu.train.trainer import Trainer

    Trainer(str(tmp_path / "seg"), "", n_blocks=(1, 1, 1), base_depth=8)
    ClassifierTrainer(
        str(tmp_path / "cls"),
        None,
        ModelConfig(
            num_classes=10, input_shape=(32, 32), input_channels=3,
            n_blocks=(1, 1, 1), base_depth=8,
        ),
    )
    info = multihost.process_info()
    assert info["process_count"] == 1
    assert info["process_index"] == 0
    assert info["global_device_count"] >= 8
    # an explicit world is the only way in: the arguments are not optional
    with pytest.raises(TypeError):
        multihost.initialize()


def test_global_shard_batch_matches_shard_batch():
    mesh = make_mesh(8)
    rng = np.random.default_rng(0)
    batch = {
        "images": rng.normal(0, 1, (16, 4, 4, 2)).astype(np.float32),
        "labels": rng.integers(0, 2, (16, 4, 4, 1)).astype(np.float32),
    }
    a = multihost.global_shard_batch(batch, mesh)
    b = shard_batch(batch, mesh)
    for k in batch:
        assert a[k].sharding.spec == b[k].sharding.spec
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(a[k])), np.asarray(jax.device_get(b[k]))
        )


def test_global_shard_batch_feeds_train_shapes():
    mesh = make_mesh(8)
    x = np.zeros((8, 2, 2, 1), np.float32)
    arr = multihost.global_shard_batch({"x": x}, mesh)["x"]
    assert arr.shape == (8, 2, 2, 1)
    # each device owns exactly one row
    assert len(arr.sharding.device_set) == 8


def test_process_local_rows_single_process_is_all_rows():
    mesh = make_mesh(8)
    np.testing.assert_array_equal(
        multihost.process_local_rows(16, mesh), np.arange(16)
    )


def test_shard_replicated_batch_and_fetch_roundtrip():
    mesh = make_mesh(8)
    x = np.random.default_rng(0).normal(size=(16, 3, 3, 1)).astype(np.float32)
    placed = multihost.shard_replicated_batch({"x": x}, mesh)["x"]
    np.testing.assert_array_equal(multihost.fetch(placed), x)


def test_per_process_batch_size_requires_divisibility(monkeypatch):
    import jax as jax_mod

    monkeypatch.setattr(jax_mod, "process_count", lambda: 4)
    assert multihost.per_process_batch_size(64) == 16
    import pytest

    with pytest.raises(ValueError):
        multihost.per_process_batch_size(62)


def test_eval_num_batches_equal_across_processes(monkeypatch):
    """Every process must run the SAME number of eval steps even when the
    round-robin host shards differ in size — the count comes only from global
    quantities, so it is identical on every process by construction."""
    import jax as jax_mod

    monkeypatch.setattr(jax_mod, "process_count", lambda: 4)
    # 13 examples over 4 processes: shards of 4,3,3,3; local batch 2 ⇒ largest
    # shard needs ceil(4/2)=2 steps, so EVERY process runs 2
    assert multihost.eval_num_batches(13, 2) == 2
    # empty-shard edge (3 examples, 4 processes): still at least 1 step each
    assert multihost.eval_num_batches(3, 1) == 1


def test_trainer_batch_assembly_under_mocked_processes(monkeypatch):
    """Simulate the trainer's per-process batch math for P=4 mocked processes:
    host shards are a disjoint cover of the fold, each process draws exactly
    batch/P examples per train step, and one eval pass counts every example
    exactly once across processes with equal step counts."""
    import jax as jax_mod

    from tensorflowdistributedlearning_tpu.data import pipeline as pipeline_lib

    P_COUNT = 4
    ids = [f"ex{i}" for i in range(13)]
    monkeypatch.setattr(jax_mod, "process_count", lambda: P_COUNT)

    global_batch = 8
    local_bs = multihost.per_process_batch_size(global_batch)
    assert local_bs == 2

    shards = []
    for p in range(P_COUNT):
        monkeypatch.setattr(jax_mod, "process_index", lambda p=p: p)
        shards.append(pipeline_lib.host_shard(ids))
    # disjoint cover
    flat = [i for s in shards for i in s]
    assert sorted(flat) == sorted(ids)
    assert len(set(flat)) == len(ids)

    # one training step: each process contributes exactly local_bs of ITS shard
    for shard in shards:
        images = np.arange(len(shard), dtype=np.float32).reshape(-1, 1, 1, 1)
        ds = pipeline_lib.InMemoryDataset(images, images.copy(), list(shard))
        batch = next(pipeline_lib.train_batches(ds, local_bs, seed=0))
        assert batch["images"].shape[0] == local_bs

    # one eval pass: equal step counts; every example counted exactly once
    num = multihost.eval_num_batches(len(ids), local_bs)
    seen = []
    for shard in shards:
        images = np.asarray(
            [float(ids.index(i)) for i in shard], np.float32
        ).reshape(-1, 1, 1, 1)
        ds = pipeline_lib.InMemoryDataset(images, images.copy(), list(shard))
        batches = list(pipeline_lib.eval_batches(ds, local_bs, num_batches=num))
        assert len(batches) == num
        for b in batches:
            seen.extend(
                b["images"][b["valid"].astype(bool), 0, 0, 0].tolist()
            )
    assert sorted(seen) == list(map(float, range(len(ids))))


def test_eval_batches_dataset_smaller_than_batch():
    """Regression (ADVICE r1): n < batch_size used to index out of bounds."""
    from tensorflowdistributedlearning_tpu.data import pipeline as pipeline_lib

    n, bs = 5, 64
    images = np.arange(n, dtype=np.float32).reshape(-1, 1, 1, 1)
    ds = pipeline_lib.InMemoryDataset(images, images.copy(), [str(i) for i in range(n)])
    (batch,) = list(pipeline_lib.eval_batches(ds, bs))
    assert batch["images"].shape[0] == bs
    assert batch["valid"].sum() == n
    np.testing.assert_array_equal(
        batch["images"][: n, 0, 0, 0], np.arange(n, dtype=np.float32)
    )


def test_eval_batches_empty_dataset():
    """Regression (code review r2): an empty host shard (global_n < process_count)
    must still emit the forced number of all-padding batches instead of crashing —
    the other processes are blocked in collective-bearing eval steps."""
    from tensorflowdistributedlearning_tpu.data import pipeline as pipeline_lib

    images = np.zeros((0, 2, 2, 1), np.float32)
    ds = pipeline_lib.InMemoryDataset(images, images.copy(), [])
    batches = list(pipeline_lib.eval_batches(ds, 4, num_batches=2))
    assert len(batches) == 2
    for b in batches:
        assert b["images"].shape == (4, 2, 2, 1)
        assert b["valid"].sum() == 0


def test_imagefolder_eval_batches_empty_dataset(tmp_path):
    from tensorflowdistributedlearning_tpu.data import imagefolder

    ds = imagefolder.ImageFolder(
        str(tmp_path), (2, 2), channels=3, paths=[], labels=np.zeros(0, np.int32),
        class_names=["a"],
    )
    batches = list(imagefolder.eval_batches(ds, 4, num_batches=3))
    assert len(batches) == 3
    for b in batches:
        assert b["images"].shape == (4, 2, 2, 3)
        assert b["labels"].shape == (4,)
        assert b["valid"].sum() == 0
