"""Entry ``train``: the K-fold segmentation trainer, ``Trainer.train``."""

from __future__ import annotations

import os


from perfbench import compare, dataset, harness
from perfbench.entries import training


def expected_feeds(reference, augment, program_seed, raw_rows, **plant):
    """What the reference's augmentation makes of the rows the loop fed the
    input program, step by step."""
    import jax

    @jax.jit
    def expected_of(seed, step, images, masks):
        # the program keys each fold's augmentation by (seed + fold), then by
        # step; the window lies in fold 0. The seed is an argument: as a
        # constant it would make every seed a program of its own to compile
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return reference.augment_seg(augment, key, images, masks, **plant)

    return [
        jax.device_get(expected_of(program_seed, step, raw["images"], raw["masks"]))
        for step, raw in raw_rows
    ]


def _prepare_numbers(reference, probe, config, program_seed):
    """The augmentation program against the reference's, on its own feeds."""
    import jax

    expected = expected_feeds(reference, config["augment"], program_seed, probe.raw)
    return compare.seg_prepare_numbers(
        probe.fed, expected, jax.jit(reference.laplace_channel)
    )


def run(cell, *, seed, seconds, trace, process_t0, **options):
    from tensorflowdistributedlearning_tpu.data.augment import AugmentConfig
    from tensorflowdistributedlearning_tpu.train.trainer import Trainer

    data = cell.traffic["dataset"]
    data_dir = os.path.join(harness.WORK, "data", f"tgs_{data['n_images']}_{data['data_seed']}")
    ids = dataset.ensure_dataset(data_dir, data["n_images"], data["data_seed"])
    tcfg = training.train_config(cell, seed)
    mcfg = training.model_config(cell)
    model_kwargs = {
        k: getattr(mcfg, k) for k in cell.config["model"]
    }

    def make_trainer(cls, workdir):
        return cls(
            workdir,
            data_dir,
            train_config=tcfg,
            augment_config=AugmentConfig(**cell.config["augment"]),
            **model_kwargs,
        )

    def start(trainer):
        trainer.train(ids, None, batch_size=cell.traffic["global_batch"], steps=10**9)

    def prepare_numbers(reference, probe, config):
        return _prepare_numbers(reference, probe, config, tcfg.seed)

    return training.run_training(
        cell, seed=seed, seconds=seconds, trace=trace, process_t0=process_t0,
        trainer_base=Trainer, make_trainer=make_trainer, start=start,
        prepare_numbers=prepare_numbers, **options,
    )
