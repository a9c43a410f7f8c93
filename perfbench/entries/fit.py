"""Entry ``fit``: the classification trainer, ``ClassifierTrainer.fit``, on
the program's synthetic stream (``fit`` with no data directory)."""

from __future__ import annotations

import numpy as np

from perfbench import compare
from perfbench.entries import training


def _prepare_numbers(reference, probe, config):
    """Every row the step was fed has to be a mirror-and-crop of the row the
    loop gave the input program, and the rows' offsets have to differ."""
    pad = min(4, max(config["model"]["input_shape"][0] // 8, 1))
    import jax

    offsets = jax.jit(reference.flip_crop_offsets, static_argnums=2)
    matches, first = [], []
    for (_, raw), fed in zip(probe.raw, probe.fed):
        m, i = offsets(raw["images"], fed["images"], pad)
        matches.append(np.asarray(m))
        first.append(np.asarray(i))
        if not np.array_equal(np.asarray(raw["labels"]), np.asarray(fed["labels"])):
            matches[-1] = np.zeros_like(matches[-1])
    return compare.flip_crop_numbers(matches, first)


def run(cell, *, seed, seconds, trace, process_t0, **options):
    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

    tcfg = training.train_config(cell, seed)
    mcfg = training.model_config(cell)

    def make_trainer(cls, workdir):
        return cls(workdir, None, mcfg, tcfg)

    def start(trainer):
        trainer.fit(batch_size=cell.traffic["global_batch"], steps=10**9)

    return training.run_training(
        cell, seed=seed, seconds=seconds, trace=trace, process_t0=process_t0,
        trainer_base=ClassifierTrainer, make_trainer=make_trainer, start=start,
        prepare_numbers=_prepare_numbers, **options,
    )
