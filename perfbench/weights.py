"""Weights from the seed, made on the device in one jitted call.

The harness, not the program, draws every trainable leaf, so the program and
the plain reference start from the same numbers and the reference takes
nothing the program made. The leaf list is the reference's ``param_spec``;
the program's tree has to name and shape its leaves alike, or the run stops.
"""

from __future__ import annotations

import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Spec = Dict[str, Tuple[Tuple[int, ...], str]]


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, also one past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def _draw(key, shape, kind):
    n = jax.random.normal(key, shape, jnp.float32)
    if kind in ("conv", "dense"):
        fan_in = math.prod(shape[:-1])
        return n * math.sqrt(2.0 / fan_in)
    if kind == "depthwise":
        return n * 0.33
    if kind == "pointwise":
        return n * 0.06
    if kind == "bn_scale":
        return 1.0 + 0.1 * n
    if kind == "bn_bias":
        return 0.1 * n
    if kind == "bias":
        return 0.01 * n
    raise ValueError(f"unknown leaf kind {kind!r}")


def make_weights(spec: Spec, seed: int) -> Dict[str, jax.Array]:
    """Every leaf of ``spec`` from ``seed``; each leaf's stream is keyed by its
    name, so adding a leaf moves no other."""
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


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


def flatten(tree) -> Dict[str, jax.Array]:
    """A nested tree of arrays as one '/'-keyed dict."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {_name(path): leaf for path, leaf in leaves}


def unflatten_like(template, flat: Dict[str, jax.Array]):
    """``flat`` poured into the structure of ``template``; every leaf of either
    side has to have its twin, name and shape."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    names = [_name(path) for path, _ in leaves]
    extra = set(names) - set(flat)
    if extra:
        raise KeyError(f"the program has leaves the reference lacks: {sorted(extra)[:5]}")
    missing = set(flat) - set(names)
    if missing:
        raise KeyError(f"the reference has leaves the program lacks: {sorted(missing)[:5]}")
    for name, (_, leaf) in zip(names, leaves):
        if tuple(flat[name].shape) != tuple(leaf.shape):
            raise ValueError(
                f"{name}: program {tuple(leaf.shape)}, reference {tuple(flat[name].shape)}"
            )
    return jax.tree_util.tree_unflatten(treedef, [flat[n] for n in names])
