"""Entry ``lm_linear_fit``: a decoder configuration whose layers are mostly
Kimi Delta Attention with a latent-attention layer among them, a dense MLP
layer and a shared expert beside the routed ones, through
``ClassifierTrainer.fit`` — ``lm_fit``'s run (the same probe around the same
loop, the same eleven numbers; ``lm_mixed_fit``'s probe, which hands the
followed state over instead of keeping it) with five more numbers: the first
gradient's worst leaf (``compare.worst_leaf_gap``) among the KDA layers' output
gates (``grad1_gate_gap``), the shared experts' matrices, the dense layer's,
the KDA layers' other leaves (``grad1_kda_gap``) and the latent layer's
(``grad1_latent_gap``). The weights are ``lm_weights``' with the KDA's decay
parameters drawn as the family initialises them (``lm_linear_weights``).

``lm_fit.run`` is called with three of its names standing for these; nothing
else of it changes. A program whose ``DecoderConfig`` has no
``linear_attn_config`` fails here at once, before it looks for a chip.
"""

from __future__ import annotations

import dataclasses
from typing import Dict
from unittest import mock

import numpy as np

from perfbench import compare, lm_linear_weights
from perfbench.entries import lm_fit, lm_mixed_fit

model_config, train_config = lm_fit.model_config, lm_fit.train_config
_lm_numbers = lm_fit.lm_numbers  # the shared eleven, whatever stands in for the name later


def require_linear_layers() -> None:
    from tensorflowdistributedlearning_tpu.config import DecoderConfig

    if "linear_attn_config" not in {f.name for f in dataclasses.fields(DecoderConfig)}:
        raise SystemExit("perfbench: this program's DecoderConfig has no 'linear_attn_config'")


def linear_numbers(reference, cfg, observed, ref_out, params0, where=None) -> Dict[str, float]:
    """``lm_fit.lm_numbers`` and the five kinds of leaf this family adds."""
    numbers = _lm_numbers(reference, cfg, observed, ref_out, params0, where)
    norm = lambda tree: {k: float(np.linalg.norm(np.asarray(v, np.float64)))  # noqa: E731
                         for k, v in tree.items()}
    g_prog, g_ref = norm(observed["grad1"]), norm(ref_out["grad1"])
    for name, leaves in (("gate", reference.gate_leaves(cfg)),
                         ("shared", reference.shared_leaves(cfg)),
                         ("dense", reference.dense_leaves(cfg)),
                         ("kda", reference.kda_leaves(cfg)),
                         ("latent", reference.latent_leaves(cfg))):
        numbers[f"grad1_{name}_gap"], _ = compare.worst_leaf_gap(g_prog, g_ref, leaves)
    return numbers


def run(cell, **options):
    require_linear_layers()
    with mock.patch.multiple(lm_fit, Probe=lm_mixed_fit.Probe, lm_numbers=linear_numbers,
                             lm_weights=lm_linear_weights):
        return lm_fit.run(cell, **options)
