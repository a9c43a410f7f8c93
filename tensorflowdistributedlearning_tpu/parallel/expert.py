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
whatever the imbalance. The layer is told which experts it holds
(``first_expert`` and the leading size of its matrices) and routes over all
of them. The token-expert pairs are sorted so that the held experts' come
first, grouped by expert, and the layer works over that sorted buffer a
segment at a time — a segment is 1.25 times the pairs expected here
(``_segment_rows``) — in a loop over as many segments as hold the held pairs,
a number the device reads off the routing's counts. So the rows the layer
gathers, multiplies and sums back follow what this chip holds (one segment
where the routing is even), a routing that sends everything here is served
by more turns of the same loop, and the program holds the layer once. The
experts' matrices are applied as grouped products over the buffer's rows
(``grouped_matmul``: the Pallas ``megablox`` kernel on a TPU,
``lax.ragged_dot`` elsewhere), and the rows are summed into their tokens by
one more (``_sum_rows_into_tokens``). The ViT family keeps the top-1
capacity path above.
"""

from __future__ import annotations

import functools
import math
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
# a segment of the sorted pair buffer, over the pairs expected here
_SEGMENT_OVER_EXPECTED = 1.25
# tokens a group when a buffer's rows are summed into their tokens
_TOKEN_BLOCK = 256
# leading lanes of an output row that say whether the row was computed
_WRITTEN_LANES = 128


def top_k_routing(
    router_logits: jax.Array, k: int, renormalise: bool = True, *,
    score: str = "softmax", scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """[T, E] logits -> (weights [T, k] float32, experts [T, k] int32): the
    scores of all E experts — their softmax, or under ``score="sigmoid"`` each
    logit's sigmoid, which no other expert's logit moves — the k largest
    (equal scores to the lower index), renormalised over the chosen, times
    ``scale`` (the routed scaling factor that sigmoid-routed families publish:
    their renormalised weights sum to it, not to 1)."""
    logits = router_logits.astype(jnp.float32)
    scores = jax.nn.sigmoid(logits) if score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(scores, k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
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


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``lhs`` [R, K] holds rows sorted by group; ``rhs`` [G, K, N] holds the
    matrices of the first G of the ``len(group_sizes)`` groups. Row r of group
    g gives ``lhs[r] @ rhs[g]``; rows of the groups past G, which no matrix
    serves, give 0. Float32 accumulation, the result in ``lhs``'s dtype."""
    rows, k = lhs.shape
    held, n = rhs.shape[0], rhs.shape[2]
    sizes = group_sizes.astype(jnp.int32)
    from tensorflowdistributedlearning_tpu.ops import pallas_kernels

    if pallas_kernels.pallas_platform_ok() and gmm_kernel_serves(rows, k, n):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(lhs, rhs, sizes, lhs.dtype, _gmm_tiling)
    # ragged_dot wants a matrix for every group: a zero one for the rest
    rest = sizes.shape[0] - held
    zero = jnp.zeros((rest,) + rhs.shape[1:], rhs.dtype)
    return lax.ragged_dot(
        lhs, jnp.concatenate([rhs, zero]), sizes, preferred_element_type=jnp.float32
    ).astype(lhs.dtype)


def _segment_rows(pairs: int, held: int, total: int) -> int:
    """Rows of one segment of the sorted pair buffer: the pairs expected here
    (``pairs * held / total``) times ``_SEGMENT_OVER_EXPECTED`` in whole
    tiles, at most all ``pairs``."""
    expected = pairs * held / total
    return min(pairs, -(-math.ceil(expected * _SEGMENT_OVER_EXPECTED) // _GMM_TILE_M) * _GMM_TILE_M)


def _segments(n_held: jax.Array, rows: int) -> jax.Array:
    """Segments of ``rows`` rows that hold ``n_held`` pairs."""
    return -(-n_held // rows)


def pair_buffer_rows(counts: jax.Array, pairs: int, num_experts_total: int) -> jax.Array:
    """Rows of the buffer ``dropless_experts`` works over when ``counts``
    tokens go to each held expert, of ``pairs`` routed over all experts."""
    rows = _segment_rows(pairs, counts.shape[0], num_experts_total)
    return _segments(jnp.sum(counts), rows) * rows


def row_tile_visits(counts: jax.Array) -> jax.Array:
    """Row tiles of ``_GMM_TILE_M`` sorted pairs that the held experts'
    groups overlap in ``dropless_experts``' buffer, summed over the groups
    (segments are whole tiles, so a tile lies in one segment): what the
    grouped products visit for the held pairs. A group of ``n`` pairs overlaps
    at least ``ceil(n / tile)`` tiles and one more where it straddles an
    edge, so ``sum(counts) / (visits * tile)`` says how full the visited tiles
    are when a group is about one tile."""
    ends = jnp.cumsum(counts.astype(jnp.int32))
    starts = ends - counts
    tiles = (ends - 1) // _GMM_TILE_M - starts // _GMM_TILE_M + 1
    return jnp.sum(jnp.where(counts > 0, tiles, 0), dtype=jnp.int32)


def _sum_rows_on_mxu(rows, tok, t: int, block: int, interpret: bool = False) -> jax.Array:
    """``_sum_rows_into_tokens`` as one transposed grouped product: the rows
    sorted by token, the tokens in blocks of ``block`` as its groups, and a
    left side that says which token of its block a row belongs to — 0 or 1,
    so every product is exact and a token's sum is float32."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    b, d = rows.shape
    by_token, perm = lax.sort((tok, jnp.arange(b, dtype=jnp.int32)), num_keys=1)
    blocks = jnp.arange(t // block, dtype=jnp.int32)
    sizes = jnp.sum(by_token[:, None] // block == blocks, axis=0, dtype=jnp.int32)
    which = (by_token % block == jnp.arange(block, dtype=jnp.int32)[:, None]).astype(rows.dtype)
    out = tgmm(which, rows[perm], sizes, jnp.float32, _gmm_tiling, interpret=interpret)
    return out.reshape(t, d)  # from [t / block, block, D]


def _sum_rows_into_tokens(rows: jax.Array, tok: jax.Array, t: int) -> jax.Array:
    """[B, D] rows -> [t, D] float32: token ``tok[r]`` receives ``rows[r]``.
    On the MXU where the Pallas kernel serves the shapes; a row scatter-add
    elsewhere (which the chip runs five times slower)."""
    from tensorflowdistributedlearning_tpu.ops import pallas_kernels

    block = math.gcd(t, _TOKEN_BLOCK)
    if pallas_kernels.pallas_platform_ok() and gmm_kernel_serves(rows.shape[0], block, rows.shape[1]):
        return _sum_rows_on_mxu(rows, tok, t, block)
    return jax.ops.segment_sum(rows.astype(jnp.float32), tok, num_segments=t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_to_tokens(rows: jax.Array, tok: jax.Array, t: int) -> jax.Array:
    """``_sum_rows_into_tokens``; backward, each row takes its token's gradient."""
    return _sum_rows_into_tokens(rows, tok, t)


_rows_to_tokens.defvjp(  # the empty slice carries the rows' dtype
    lambda rows, tok, t: (_sum_rows_into_tokens(rows, tok, t), (tok, rows[:0])),
    lambda t, res, g: (g.astype(res[1].dtype)[res[0]], None),
)


@jax.custom_vjp
def _tokens_to_rows(x: jax.Array, tok: jax.Array) -> jax.Array:
    """[T, D] tokens -> [B, D]: row r is token ``tok[r]``. Backward, the rows
    summed into their tokens (``_rows_to_tokens``' other half)."""
    return x[tok]


_tokens_to_rows.defvjp(  # the empty slice carries the number of tokens
    lambda x, tok: (x[tok], (tok, x[:, :0])),
    lambda res, g: (_sum_rows_into_tokens(g, res[0], res[1].shape[0]).astype(g.dtype), None),
)


def _over_segment(rows, k, start, x, weights, w_gate, w_up, w_down, order, counts):
    """The layer over the sorted pairs ``start`` to ``start + rows``: (their
    part of the weighted sum [T, D] float32, held pairs among them not
    computed)."""
    # the held groups as far as they lie inside, then the rows no matrix serves
    ends = jnp.clip(jnp.cumsum(counts) - start, 0, rows)
    kept = ends[-1]
    sizes = jnp.concatenate([jnp.diff(ends, prepend=0), (rows - kept)[None]])
    pairs = lax.dynamic_slice(order, (start,), (rows,))
    tok = pairs // k
    live = jnp.arange(rows, dtype=jnp.int32) < kept
    w_rows = jnp.where(live, weights.reshape(-1)[pairs], 0.0)
    x_rows = _tokens_to_rows(x, tok)
    gate = grouped_matmul(x_rows, w_gate, sizes)
    up = grouped_matmul(x_rows, w_up, sizes)
    # a pair's weight goes in here, over the experts' width, so that what the
    # tokens receive is a plain sum of rows
    hidden = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32) * w_rows[:, None]
    out_rows = grouped_matmul(hidden.astype(x.dtype), w_down, sizes)
    out = _rows_to_tokens(out_rows, tok, x.shape[0])
    written = jnp.any(out_rows[:, :_WRITTEN_LANES] != 0, axis=-1)
    return out, jnp.sum(live & ~written, dtype=jnp.int32)


def _loop_over_segments(rows, k, x, weights, w_gate, w_up, w_down, order, counts):
    """``_over_segment`` over as many segments of ``rows`` sorted pairs as
    hold the held ones — a loop whose length the device reads off ``counts``:
    (the weighted sum, held pairs not computed)."""
    n_held = jnp.sum(counts)
    segments = _segments(n_held, rows)

    def one(i, carry):
        out, dropped = _over_segment(
            rows, k, i * rows, x, weights, w_gate, w_up, w_down, order, counts
        )
        return carry[0] + out, carry[1] + dropped

    start = jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.int32)
    out, dropped = lax.fori_loop(0, segments, one, start)
    return out, dropped + jnp.maximum(n_held - segments * rows, 0)


def _loop_over_segments_bwd(rows, k, args, g):
    *inputs, order, counts = args

    def one(i, grads):
        _, pull = jax.vjp(
            lambda *d: _over_segment(rows, k, i * rows, *d, order, counts)[0], *inputs
        )
        return jax.tree.map(jnp.add, grads, pull(g[0]))

    grads = lax.fori_loop(
        0, _segments(jnp.sum(counts), rows), one, tuple(jnp.zeros_like(d) for d in inputs)
    )
    return (*grads, None, None)


# differentiated as a whole: the backward pass is the same loop over the
# segments' own backward passes, and keeps nothing but the inputs
_experts_by_segment = jax.custom_vjp(_loop_over_segments, nondiff_argnums=(0, 1))
_experts_by_segment.defvjp(
    lambda rows, k, *args: (_loop_over_segments(rows, k, *args), args),
    _loop_over_segments_bwd,
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

    The T * k pairs are sorted on ``(expert - first_expert) mod
    num_experts_total`` (stable, so a token's order inside an expert is its
    arrival order): the held experts' pairs come first, grouped by expert.
    The gather of their tokens, the three grouped products, the activation
    and the sum back into the tokens run over one segment of
    ``_segment_rows`` sorted rows at a time, for as many segments as hold the
    held pairs (``_experts_by_segment``: a loop whose length the device reads
    off the counts, so whatever the routing sends here is computed). In a
    segment the rows past the held pairs form a last group that no matrix
    serves and read 0.

    A pair's weight is multiplied in before the down projection, so that the
    tokens receive a plain sum of rows (``_sum_rows_into_tokens``).

    The last count returned is read off the products' own output: a row of
    the buffer was computed if the down projection wrote something other than
    0 into its first lanes (a row the kernel passes over is left 0; so would a
    pair of weight 0 be, which neither routing gives: a softmax is positive,
    and a sigmoid is until its logit falls under float32's range, about -87
    where denormals are flushed, while the chosen are a token's largest), the
    rows that should have been are the held pairs' by the routing's counts, and held
    pairs past the last segment's end count too. 0 as long as the segments
    hold every held pair and the kernel visits every group it is handed."""
    t, k = experts.shape
    held = w_gate.shape[0]
    key = jnp.mod(experts.reshape(-1) - first_expert, num_experts_total)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype), axis=0, dtype=jnp.int32)
    rows = _segment_rows(t * k, held, num_experts_total)
    # whole segments, so that the last one's slice stays inside
    order = jnp.pad(order, (0, -(t * k) % rows))
    out, dropped = _experts_by_segment(rows, k, x, weights, w_gate, w_up, w_down, order, counts)
    return out, counts, dropped
