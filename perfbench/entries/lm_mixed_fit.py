"""Entry ``lm_mixed_fit``: a decoder configuration whose layers carry head
counts of their own under a per-head output gate, with a dense MLP layer and a
shared expert beside the routed ones, through ``ClassifierTrainer.fit`` —
``lm_fit``'s run (the same probe around the same loop, the same weights, the
same eleven numbers) with three more numbers: ``grad1_gate_gap``,
``grad1_shared_gap`` and ``grad1_dense_gap``, the first gradient's worst leaf
(``compare.worst_leaf_gap``) among the head gates, the shared experts'
matrices and the dense layer's.

``lm_fit.run`` is called with two of its names standing for these; nothing
else of it changes. A program whose ``DecoderConfig`` has no
``num_attention_heads_per_layer`` fails here at once, before it looks for a
chip.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict
from unittest import mock

import numpy as np

from perfbench import compare
from perfbench.entries import lm_fit

model_config, train_config = lm_fit.model_config, lm_fit.train_config
_lm_numbers = lm_fit.lm_numbers  # the shared eleven, whatever stands in for the name later


def require_heads_by_layer() -> None:
    from tensorflowdistributedlearning_tpu.config import DecoderConfig

    if "num_attention_heads_per_layer" not in {f.name for f in dataclasses.fields(DecoderConfig)}:
        raise SystemExit(
            "perfbench: this program's DecoderConfig has no 'num_attention_heads_per_layer'")


class Probe(lm_fit.Probe):
    def observed(self) -> Dict[str, Any]:
        seen = super().observed()
        # handed over, not kept: jax's monitoring holds every probe of a process
        # through its listener, and a calibration makes one a seed (6.5 GB each)
        self.moments_after_one = self.params_after_followed = None
        return seen


def mixed_numbers(reference, cfg, observed, ref_out, params0, where=None) -> Dict[str, float]:
    """``lm_fit.lm_numbers`` and the three kinds of leaf this family adds."""
    numbers = _lm_numbers(reference, cfg, observed, ref_out, params0, where)
    norm = lambda tree: {k: float(np.linalg.norm(np.asarray(v, np.float64)))  # noqa: E731
                         for k, v in tree.items()}
    g_prog, g_ref = norm(observed["grad1"]), norm(ref_out["grad1"])
    for name, leaves in (("gate", reference.gate_leaves(cfg)),
                         ("shared", reference.shared_leaves(cfg)),
                         ("dense", reference.dense_leaves(cfg))):
        numbers[f"grad1_{name}_gap"], _ = compare.worst_leaf_gap(g_prog, g_ref, leaves)
    return numbers


def run(cell, **options):
    require_heads_by_layer()
    with mock.patch.multiple(lm_fit, Probe=Probe, lm_numbers=mixed_numbers):
        return lm_fit.run(cell, **options)
