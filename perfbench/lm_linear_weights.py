"""``lm_weights.py``'s scheme with the two leaf kinds a Kimi Delta Attention
layer adds, as the family initialises them:

- ``a_log``: ``log A`` with ``A`` uniform in [1, 16];
- ``dt_bias``: ``softplus^-1(dt)`` with ``dt`` log-uniform in [1e-3, 1e-1].

Every other kind is ``lm_weights``'s, drawn the same way from the same key: a
leaf's stream is keyed by its name."""

from __future__ import annotations

import math
import zlib
from typing import Dict

import jax
import jax.numpy as jnp

from perfbench import lm_weights
from perfbench.weights import Spec, seed_key


def _draw(key, shape, kind):
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return lm_weights._draw(key, shape, kind)


def make_weights(spec: Spec, seed: int) -> Dict[str, jax.Array]:
    names = sorted(spec)

    @jax.jit
    def draw_all(key):
        return {
            name: _draw(
                jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
                spec[name][0],
                spec[name][1],
            )
            for name in names
        }

    return draw_all(jax.random.fold_in(seed_key(seed), 7))
