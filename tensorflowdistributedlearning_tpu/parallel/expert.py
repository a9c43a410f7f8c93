"""Expert parallelism: top-1-gated mixture-of-experts with all-to-all dispatch.

The reference had no experts (SURVEY §2.3: data parallelism only), so — like the
tensor, sequence, and pipeline axes — this is a beyond-parity primitive that
completes the framework's strategy set (dp / tp / pp / sp / ep). It is built the
TPU way: experts live one-per-shard on a mesh axis, and tokens move to their
expert and back via ``lax.all_to_all`` — the single collective XLA lowers to the
ICI all-to-all that makes MoE practical on pods.

Design (the Switch-style top-1 regime, fixed shapes throughout):

- ``gate``: a linear router produces per-token expert logits; top-1 assignment
  with a per-expert capacity ``C = ceil(tokens/E * capacity_factor)``;
- tokens are bucketed into a dense [E, C, D] dispatch buffer per shard (dropped
  beyond capacity — the standard fixed-shape trade), sent with all-to-all so
  each shard holds every shard's tokens for ITS expert, processed by the local
  expert, and returned by the inverse all-to-all;
- combine scales by the gate probability; dropped tokens fall back to a zero
  update (residual-style callers add the input back).

Everything is shape-static and jit/shard_map-compatible; autodiff flows through
both all-to-alls (their transpose is the reverse all-to-all).

The second half of the file is the decoder family's expert layer
(models/decoder.py): ``top_k_routing`` (k experts a token, weights
renormalised over the chosen) and ``dropless_experts``, which drops no token
whatever the imbalance — the token-expert pairs are sorted by expert and the
experts' matrices applied as grouped products over the sorted rows
(``grouped_matmul``: the Pallas ``megablox`` kernel on a TPU,
``lax.ragged_dot`` elsewhere). The layer is told which experts it holds
(``first_expert`` and the leading size of its matrices), routes over all of
them and computes its own experts' part; the ViT family keeps the top-1
capacity path above.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from tensorflowdistributedlearning_tpu.parallel.mesh import MODEL_AXIS


def top1_dispatch(
    gate_logits: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Greedy top-1 routing with per-expert capacity.

    ``gate_logits``: [T, E]. Returns ``(expert, slot, keep, prob)`` each [T]:
    the chosen expert, the token's slot within that expert's capacity buffer,
    whether it fit (slot < capacity), and the softmax gate probability.
    """
    probs = jax.nn.softmax(gate_logits, axis=-1)
    expert = jnp.argmax(gate_logits, axis=-1)
    prob = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    # position of each token within its expert's arrival order
    one_hot = jax.nn.one_hot(expert, gate_logits.shape[-1], dtype=jnp.int32)
    slot = jnp.cumsum(one_hot, axis=0) * one_hot  # [T, E], 1-based where chosen
    slot = jnp.sum(slot, axis=-1) - 1  # [T], 0-based
    keep = slot < capacity
    return expert, slot, keep, prob


def _dispatch_buffers(
    gate_logits: jax.Array, x: jax.Array, n_experts: int, capacity_factor: float
):
    """Shared routing + dispatch-buffer build for BOTH execution strategies
    (one source of truth — the dense/EP numerical parity the tests assert
    depends on these staying in lockstep).

    Returns ``(buffer [E, C, D], flat_idx, keep, prob)``: the dense per-expert
    capacity buffer, each token's slot index, its keep mask, and its gate
    probability. Capacity is the documented ``C = ceil(tokens/E * factor)``."""
    import math

    t, d = x.shape
    capacity = max(1, math.ceil(t * capacity_factor / n_experts))
    expert, slot, keep, prob = top1_dispatch(gate_logits, capacity)
    # dense dispatch buffer [E, C, D]: token -> (its expert, its slot)
    flat_idx = expert * capacity + jnp.minimum(slot, capacity - 1)
    buffer = jnp.zeros((n_experts * capacity, d), x.dtype)
    buffer = buffer.at[flat_idx].add(jnp.where(keep[:, None], x, 0.0))
    return buffer.reshape(n_experts, capacity, d), flat_idx, keep, prob


def _combine(
    returned: jax.Array, flat_idx: jax.Array, keep: jax.Array, prob: jax.Array
) -> jax.Array:
    """Gather expert outputs back to token order, scale by the gate
    probability, zero the capacity-dropped tokens (shared by both paths)."""
    out = returned[flat_idx]
    return jnp.where(keep[:, None], out * prob[:, None].astype(out.dtype), 0.0)


def moe_apply(
    expert_fn: Callable[[Any, jax.Array], jax.Array],
    my_expert_params: Any,
    gate_kernel: jax.Array,
    x: jax.Array,
    *,
    capacity_factor: float = 1.25,
    axis_name: str = MODEL_AXIS,
    gate_logits: jax.Array = None,
) -> jax.Array:
    """Expert-parallel MoE layer inside ``shard_map``.

    ``x``: this shard's tokens [T, D] (e.g. a data-parallel shard's flattened
    activations); ``my_expert_params``: THIS shard's expert parameters (one
    expert per shard on ``axis_name``); ``gate_kernel``: [D, E] router weights,
    replicated. Returns [T, D]: each token processed by its chosen expert and
    scaled by the gate probability (zero where dropped by capacity).

    ``gate_logits`` ([T, E], optional) supplies precomputed router logits —
    e.g. a caller's float32 routing that must agree exactly with its
    load-balancing statistics; default recomputes ``x @ gate_kernel``.
    """
    n_experts = lax.axis_size(axis_name)
    if gate_kernel.shape[-1] != n_experts:
        raise ValueError(
            f"gate_kernel routes over {gate_kernel.shape[-1]} experts but the "
            f"{axis_name!r} mesh axis has {n_experts} shards (one expert each); "
            "an over-wide router would dispatch out of the capacity buffer"
        )
    if gate_logits is None:
        gate_logits = x @ gate_kernel  # [T, E]
    buffer, flat_idx, keep, prob = _dispatch_buffers(
        gate_logits, x, n_experts, capacity_factor
    )
    capacity = buffer.shape[1]
    d = buffer.shape[-1]

    # all-to-all: shard e receives every shard's bucket for expert e ->
    # [n_shards, C, D] worth of tokens for MY expert
    incoming = lax.all_to_all(buffer, axis_name, split_axis=0, concat_axis=0)
    processed = expert_fn(
        my_expert_params, incoming.reshape(n_experts * capacity, d)
    ).reshape(n_experts, capacity, d)
    # inverse all-to-all returns each shard its own tokens, expert-processed
    returned = lax.all_to_all(processed, axis_name, split_axis=0, concat_axis=0)
    return _combine(returned.reshape(n_experts * capacity, d), flat_idx, keep, prob)


def dense_moe_apply(
    expert_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_expert_params: Any,
    gate_kernel: jax.Array,
    x: jax.Array,
    *,
    capacity_factor: float = 1.25,
    gate_logits: jax.Array = None,
) -> jax.Array:
    """The all-experts-local twin of ``moe_apply``: identical routing, capacity,
    and combine semantics (shared helpers above), with every expert computed
    on-device (vmap over the stacked [E, ...] param tree) instead of
    one-expert-per-shard all-to-alls.

    This is what makes MoE *trainable on any mesh* (pure data parallelism, the
    CPU test mesh, a single chip) with numerics identical to the
    expert-parallel execution — the strategies differ only in where the expert
    FLOPs run."""
    n_experts = gate_kernel.shape[-1]
    if gate_logits is None:
        gate_logits = x @ gate_kernel
    buffer, flat_idx, keep, prob = _dispatch_buffers(
        gate_logits, x, n_experts, capacity_factor
    )
    capacity = buffer.shape[1]
    d = buffer.shape[-1]
    processed = jax.vmap(expert_fn)(stacked_expert_params, buffer)  # [E, C, D]
    return _combine(processed.reshape(n_experts * capacity, d), flat_idx, keep, prob)


def load_balance_loss(gate_logits: jax.Array) -> jax.Array:
    """Switch Transformer load-balancing auxiliary loss (arXiv:2101.03961 eq. 4):
    ``E * sum_e f_e * P_e`` where ``f_e`` is the fraction of tokens whose top-1
    choice is expert ``e`` and ``P_e`` the mean router probability for ``e``.
    Minimized (value 1) at a uniform distribution; without it, top-1 routing
    with capacity drops collapses onto few experts."""
    n_experts = gate_logits.shape[-1]
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    chosen = jnp.argmax(gate_logits, axis=-1)
    f = jnp.mean(jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * p)


# ---------------------------------------------------------------------------
# top-k routing, no token dropped (the decoder family)
# ---------------------------------------------------------------------------

# rows of a megablox tile; the sorted pair buffer has to be a multiple of it
_GMM_TILE_M = 512
# leading lanes of an output row that say whether the row was computed
_WRITTEN_LANES = 128


def top_k_routing(
    router_logits: jax.Array, k: int, renormalise: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """[T, E] logits -> (weights [T, k] float32, experts [T, k] int32): the
    softmax over all E experts, its k largest, renormalised over the chosen."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, experts = lax.top_k(probs, k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


def _tile(extent: int, limit: int) -> int:
    """The largest multiple of 128 that divides ``extent`` and is at most
    ``limit``; ``extent`` itself where there is none."""
    for tile in range(limit - limit % 128, 0, -128):
        if extent % tile == 0:
            return tile
    return extent


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Tiles of one grouped product (megablox asks per problem: the forward,
    the transposed backward and the weight gradient differ in k and n). Whole
    divisors only, so no tile hangs over an edge."""
    return min(_GMM_TILE_M, m), _tile(k, 1024), _tile(n, 1024)


def gmm_kernel_serves(rows: int, k: int, n: int) -> bool:
    """The shapes the Pallas grouped product takes (on a TPU)."""
    return rows % _GMM_TILE_M == 0 and k % 128 == 0 and n % 128 == 0


def grouped_matmul(
    lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, first_group: int = 0
) -> jax.Array:
    """``lhs`` [R, K] holds rows sorted by group over ``len(group_sizes)``
    groups; ``rhs`` [G, K, N] holds the matrices of groups ``first_group`` to
    ``first_group + G``. Row r of group g gives ``lhs[r] @ rhs[g -
    first_group]``; rows of the groups not held give 0. Float32 accumulation,
    the result in ``lhs``'s dtype."""
    rows, k = lhs.shape
    held, n = rhs.shape[0], rhs.shape[2]
    from tensorflowdistributedlearning_tpu.ops import pallas_kernels

    if pallas_kernels.pallas_platform_ok() and gmm_kernel_serves(rows, k, n):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(
            lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype, _gmm_tiling,
            jnp.asarray(first_group, jnp.int32),
        )
    # ragged_dot wants a matrix for every group: the rows before and after
    # the held groups go to a zero matrix each
    sizes = group_sizes.astype(jnp.int32)
    before = jnp.sum(sizes[:first_group])
    mine = sizes[first_group : first_group + held]
    after = rows - before - jnp.sum(mine)
    zero = jnp.zeros((1,) + rhs.shape[1:], rhs.dtype)
    return lax.ragged_dot(
        lhs,
        jnp.concatenate([zero, rhs, zero]),
        jnp.concatenate([before[None], mine, after[None]]),
        preferred_element_type=jnp.float32,
    ).astype(lhs.dtype)


@jax.custom_vjp
def _permute_rows(x: jax.Array, perm: jax.Array, inverse: jax.Array) -> jax.Array:
    """``x[perm]`` for a permutation whose inverse is at hand: the backward
    pass is a gather too (``g[inverse]``), where autodiff would scatter."""
    return x[perm]


_permute_rows.defvjp(
    lambda x, perm, inverse: (x[perm], (perm, inverse)),
    lambda res, g: (g[res[1]], None, None),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_pairs(x: jax.Array, order: jax.Array, inverse: jax.Array, k: int):
    """[T, D] tokens -> [T * k, D]: row r is the token of sorted pair r
    (pair p = token p // k). Backward: un-sort, then sum a token's k pairs."""
    return x[order // k]


def _rows_of_pairs_bwd(k, res, g):
    inverse = res
    back = g[inverse]
    return back.reshape(-1, k, back.shape[-1]).sum(axis=1), None, None


_rows_of_pairs.defvjp(
    lambda x, order, inverse, k: (x[order // k], inverse), _rows_of_pairs_bwd
)


def dropless_experts(
    x: jax.Array,
    weights: jax.Array,
    experts: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    num_experts_total: int,
    first_expert: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """SiLU-gated experts over the tokens routed to them, none dropped.

    ``x`` [T, D] in the compute dtype; ``weights``/``experts`` [T, k] from
    ``top_k_routing`` over all ``num_experts_total`` experts; the matrices
    [E, D, F], [E, D, F], [E, F, D] of the experts held here, which are
    ``first_expert`` to ``first_expert + E``. Returns (the held experts' part
    of the weighted sum [T, D] float32, tokens routed to each held expert [E],
    pairs routed to a held expert that the grouped products did not compute).

    That last count is read off the products' own output: a row of the sorted
    buffer was computed if the down projection wrote something other than 0
    into its first lanes (a row the kernel passes over is left 0), and the
    rows that should have been are those of the held experts by the routing's
    counts. 0 as long as the buffer holds every pair and the kernel visits
    every group it is handed.

    All T * k pairs are sorted by expert (stable, so a token's order inside
    an expert is its arrival order); the grouped products compute the rows of
    the held experts and give 0 for the rest."""
    t, k = experts.shape
    held = w_gate.shape[0]
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32), unique_indices=True
    )
    group_sizes = jnp.bincount(flat, length=num_experts_total).astype(jnp.int32)
    rows = _rows_of_pairs(x, order, inverse, k)  # [T*k, D]
    gate = grouped_matmul(rows, w_gate, group_sizes, first_expert)
    up = grouped_matmul(rows, w_up, group_sizes, first_expert)
    hidden = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(x.dtype)
    out_rows = grouped_matmul(hidden, w_down, group_sizes, first_expert)
    by_pair = _permute_rows(out_rows, inverse, order).reshape(t, k, -1)
    local = experts - first_expert
    mine = (local >= 0) & (local < held)
    out = jnp.sum(
        by_pair.astype(jnp.float32) * jnp.where(mine, weights, 0.0)[..., None], axis=1
    )
    counts = group_sizes[first_expert : first_expert + held]
    start = jnp.sum(group_sizes[:first_expert])
    row = jnp.arange(out_rows.shape[0], dtype=jnp.int32)
    due = (row >= start) & (row < start + jnp.sum(counts))
    written = jnp.any(out_rows[:, :_WRITTEN_LANES] != 0, axis=-1)
    dropped = jnp.sum(due & ~written)
    return out, counts, dropped
