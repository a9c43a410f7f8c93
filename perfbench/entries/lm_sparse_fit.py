"""Entry ``lm_sparse_fit``: a decoder configuration whose layers are
``sparse_attention`` through ``ClassifierTrainer.fit`` — ``lm_fit``'s run (the
same probe around the same loop, the same eleven numbers) with what the
indexer adds:

- the weights come from ``lm_sparse_weights`` (``lm_weights`` plus the
  LayerNorm's bias);
- the probe also copies the followed steps' ``align_loss`` and the first
  step's ``sparse/key_reads`` (how many queries read each key position, by
  layer);
- three more numbers: ``align_loss1_gap`` (step 1's ``L_I`` against the
  reference's), ``grad1_indexer_gap`` (the worst indexer leaf of the first
  gradient, ``compare.worst_leaf_gap``) and ``selected_flip_share`` (half the
  summed gap between the program's and the reference's per-key selection
  counts of step 1, over the selections the reference counts: a lower bound
  of the share of (query, key) selections that differ).

``lm_fit.run`` is called with those three of its names standing for these;
nothing else of it changes. A program without the layer type fails here at
once, before it looks for a chip.
"""

from __future__ import annotations

from typing import Any, Dict
from unittest import mock

import numpy as np

from perfbench import compare, lm_sparse_weights
from perfbench.entries import lm_fit
from perfbench.harness import FOLLOWED_STEPS

model_config, train_config = lm_fit.model_config, lm_fit.train_config
_lm_numbers = lm_fit.lm_numbers  # the shared eleven, whatever stands in for the name later


def require_layer_type() -> None:
    from tensorflowdistributedlearning_tpu import config

    if "sparse_attention" not in getattr(config, "DECODER_LAYER_TYPES", ()):
        raise SystemExit("perfbench: this program's decoder has no 'sparse_attention' layer type")


class Probe(lm_fit.Probe):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.align_losses = []
        self.key_reads = None

    def wrap_step(self, real):
        import jax

        def reading(state, batch):
            new_state, metrics = real(state, batch)
            if len(self.align_losses) < FOLLOWED_STEPS:
                align = jax.device_get(metrics["align_loss"])
                self.align_losses.append(float(align.total) / float(align.count))
                if self.key_reads is None:
                    self.key_reads = np.asarray(jax.device_get(metrics["sparse/key_reads"].total))
            return new_state, metrics

        return super().wrap_step(reading)

    def observed(self) -> Dict[str, Any]:
        seen = dict(super().observed(), align_losses=self.align_losses, selected1=self.key_reads)
        # handed over, not kept: jax's monitoring holds every probe of a process
        # through its listener, and a calibration makes one a seed (6 GB each)
        self.moments_after_one = self.params_after_followed = None
        return seen


def sparse_numbers(reference, cfg, observed, ref_out, params0, where=None) -> Dict[str, float]:
    """``lm_fit.lm_numbers`` and the indexer's three."""
    numbers = _lm_numbers(reference, cfg, observed, ref_out, params0, where)
    want = float(ref_out["align_losses"][0])
    numbers["align_loss1_gap"] = abs(float(observed["align_losses"][0]) - want) / max(abs(want), 1e-30)
    norm = lambda tree: {k: float(np.linalg.norm(np.asarray(v, np.float64)))  # noqa: E731
                         for k, v in tree.items()}
    numbers["grad1_indexer_gap"], _ = compare.worst_leaf_gap(
        norm(observed["grad1"]), norm(ref_out["grad1"]), reference.indexer_leaves(cfg))
    want = np.asarray(ref_out["selected1"], np.float64)
    got = np.asarray(observed["selected1"], np.float64)
    numbers["selected_flip_share"] = float(np.abs(got - want).sum() / 2.0 / max(want.sum(), 1.0))
    return numbers


def run(cell, **options):
    require_layer_type()
    with mock.patch.multiple(lm_fit, lm_weights=lm_sparse_weights, Probe=Probe,
                             lm_numbers=sparse_numbers):
        return lm_fit.run(cell, **options)
