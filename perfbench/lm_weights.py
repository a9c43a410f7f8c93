"""Weights of a language-model configuration from the seed, made on the
device in one jitted call — ``weights.py``'s scheme with the leaf kinds a
decoder has. The leaf list is the reference's ``param_spec``; each leaf's
stream is keyed by its name, so adding a leaf moves no other.

- ``embedding``: N(0, 1), so the residual stream starts at the size the
  layers add to it;
- ``dense``: N(0, 1 / fan_in) with fan_in the second-to-last dimension (the
  contraction of ``x @ w``, also for a stack of expert matrices [E, in, out]);
- ``norm_scale``: 1 + 0.1 n;
- ``router:<n>``: as ``dense`` for the experts of one share, the same columns
  for each of the ``n`` shares of a layer: expert ``j`` of every share scores a
  token alike, so of a token's ``k`` experts ``k / n`` are each share's, its
  ``k / n`` best (``n`` = 1 is a plain ``dense`` draw);
- ``residual_out:<2 x layers>``: as ``dense`` over the square root of twice
  the layers — the projections back into the residual stream (attention's
  output, the experts' down), scaled as GPT-2 initialises them, so the stream
  stays of the embedding's size.

Every leaf is drawn from ``seed``. With random weights a token's route is a
function of its id (in layer 0 exactly, deeper nearly) and the ids are Zipf:
under a router of independent columns the (token, expert) pairs that fell to
the experts held here — the cell's work — read 116k-163k a step from seed to
seed, and a run's time with them (PERF.md §6, PR 26). A router whose shares
score alike gives every chip of the group a quarter of the pairs whatever the
seed, which is what a router trained to balance its load comes to over a
group in lockstep; which experts a token takes inside a share, and so how
unevenly the held experts are loaded, still follows the seed. That is the
routing as drawn: training moves a share's own columns apart from their
copies (at the cell's rate, 0.4-1.7% of the pairs in a run, PR 26).
"""

from __future__ import annotations

import math
import zlib
from typing import Dict

import jax
import jax.numpy as jnp

from perfbench.weights import Spec, seed_key


def _draw(key, shape, kind):
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "embedding":
        return n
    if kind == "dense":
        return n * math.sqrt(1.0 / shape[-2])
    if kind.startswith("router:"):
        shares = int(kind.split(":")[1])
        one = n[..., : shape[-1] // shares] * math.sqrt(1.0 / shape[-2])
        return jnp.tile(one, (1,) * (n.ndim - 1) + (shares,))
    if kind.startswith("residual_out:"):
        return n * math.sqrt(1.0 / (shape[-2] * int(kind.split(":")[1])))
    if kind == "norm_scale":
        return 1.0 + 0.1 * n
    raise ValueError(f"unknown leaf kind {kind!r}")


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
