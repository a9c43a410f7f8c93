"""``lm_weights.py``'s weights for a configuration whose leaves include a
kind that file lacks: ``norm_bias`` (a LayerNorm's bias, 0.1 n — away from the
zero a fresh model has, so that a bias left out shows). Every other kind is
drawn by ``lm_weights`` itself; a bias's stream is keyed by its name as the
streams there are, so adding one moves no other leaf."""

from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp

from perfbench import lm_weights
from perfbench.weights import Spec, seed_key


def make_weights(spec: Spec, seed: int) -> Dict[str, jax.Array]:
    biases = {name for name, (_, kind) in spec.items() if kind == "norm_bias"}
    drawn = dict(lm_weights.make_weights({k: v for k, v in spec.items() if k not in biases}, seed))
    key = jax.random.fold_in(seed_key(seed), 7)
    for name in sorted(biases):
        leaf = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        drawn[name] = 0.1 * jax.random.normal(leaf, spec[name][0], jnp.float32)
    return drawn
