"""Learned sparse attention for the decoder family (models/decoder.py, layer
type ``sparse_attention``): a query reads only the ``topk`` keys of its
document that a small *indexer* scores highest — DeepSeek sparse attention
(DeepSeek-V3.2-Exp report). Four steps, each a function here:

1. ``indexer_scores``: ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``
   over the visible ``s`` (``s <= t`` in ``t``'s document), ``-inf`` elsewhere;
2. ``select``: per query the threshold of an exact top-``topk`` — the
   ``topk``-th largest score as an order-preserving integer, found bit by bit
   by counting (32 counts over the row, no sort), and the position up to which
   keys that tie at the threshold are taken, found the same way (the earlier
   key wins). The two numbers say which keys a query reads; they pass no gradient
   and a recomputed layer keeps them (``SELECT_NAME``). The kernel path's
   one kernel holds a block of rows in VMEM and runs every count over the
   columns that block can see alone — up to its last row's own position, and
   from the start of its first row's document where documents are packed —
   and searches tie positions only for the groups of rows in which a row has
   more keys at its threshold than places left: everywhere else every such
   key is taken (position ``T - 1``), and the selection is the same;
3. ``attend_selected``: softmax attention over the selection;
4. ``align_loss``: ``sum_t KL(p_t || softmax_{S_t} I[t])`` with ``p_t`` the
   attention's own distribution summed over the heads held, L1-normalised and
   cut from the graph — the loss that trains the indexer, and the only thing
   that does: the scores reach the attention through the selection alone.

On a TPU whose shapes fit the tiles (sequence a multiple of 512, head size a
multiple of 128, indexer head size 64 or 128) every step is a Pallas kernel
here: none writes the ``[heads, T, T]`` products, only the ``[T, T]`` scores
and the loss's gradient to them stand in memory, and the selection is never
written out — each kernel tells it from its tile of the scores and the two
numbers a query, and the forward attention kernel counts, tile by tile, how
many queries read each key. The attention kernels compute every visible tile
and mask inside it (flash attention: forward, dq, dkv): what reading only
the selected keys would take is ROADMAP S8. Any other shape or backend takes
the same mathematics in XLA, blocked over queries. The choice is by shape and
backend, no flag.

What a recomputed layer keeps (models/decoder.py:REMAT_POLICY): the selection's
two numbers a query (``SELECT_NAME``) and, on the kernel path, what the forward
attention kernel wrote that the backward pass reads — its output and the two
log-sum-exps (``ATTENTION_NAME``) — so the backward pass runs neither the
search nor ``sparse_attend`` again. The scores and the loss's ``[T, T]``
gradient are too large to keep: ``sparse_indexer_scores`` and ``sparse_align``
still run twice. The XLA path names the selection alone.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tensorflowdistributedlearning_tpu.obs import scopes

# the selection's two numbers carry this name: a recomputed layer keeps them
# (models/decoder.py) and so does not search for the thresholds again
SELECT_NAME = "sparse_select"
# what a forward attention kernel wrote that its backward kernels read, the
# output and the log-sum-exp, carries this name here and in
# ops/blocked_attention.py: a recomputed layer keeps them too, and so does not
# run the forward kernel a second time to have them back
ATTENTION_NAME = "attention_residual"

_INT_MIN = np.int32(-(2**31))
# queries per block of the XLA path
_BLOCK_Q = 512


def sortable(x: jax.Array) -> jax.Array:
    """float32 -> int32, order preserving (-0.0 and 0.0 are made one first)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x).astype(jnp.float32), jnp.int32)
    return jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)


def _visible(segment_ids: jax.Array, start, block: int) -> jax.Array:
    """[block, T]: key s is visible to query start + r."""
    t = segment_ids.shape[0]
    i = start + jnp.arange(block)
    mine = lax.dynamic_slice_in_dim(segment_ids, start, block, 0)
    return (jnp.arange(t)[None, :] <= i[:, None]) & (mine[:, None] == segment_ids[None, :])


# ---------------------------------------------------------------------------
# the XLA path, one sequence at a time
# ---------------------------------------------------------------------------


def _scores_xla(qi, ki, wi, segment_ids):
    """qi [T, J, di], ki [T, di], wi [T, J] float32, segment_ids [T] ->
    [T, T] float32. Products in the operands' dtype with float32
    accumulation; relu, weights and the sum over heads in float32."""
    t = qi.shape[0]
    block = math.gcd(t, _BLOCK_Q)

    @jax.checkpoint
    def rows(start):
        qb = lax.dynamic_slice_in_dim(qi, start, block, 0)
        wb = lax.dynamic_slice_in_dim(wi, start, block, 0)
        dots = jnp.einsum("qjd,sd->qjs", qb, ki, preferred_element_type=jnp.float32)
        scores = jnp.sum(wb[:, :, None] * jax.nn.relu(dots), axis=1)
        return jnp.where(_visible(segment_ids, start, block), scores, -jnp.inf)

    return lax.map(rows, jnp.arange(0, t, block)).reshape(t, t)


def _count(flags: jax.Array) -> jax.Array:
    return jnp.sum(flags.astype(jnp.int32), axis=-1)


def _threshold_xla(keys: jax.Array, topk: int) -> jax.Array:
    """Per row the largest integer with at least ``topk`` keys at or above it
    (``INT_MIN`` for a row shorter than ``topk``), built from the top bit down."""

    def body(i, tau):
        cand = tau + lax.shift_left(np.int32(1), (31 - i).astype(jnp.int32))  # bit 31 wraps to 0
        return jnp.where(_count(keys >= cand[:, None]) >= topk, cand, tau)

    return lax.fori_loop(0, 32, body, jnp.full(keys.shape[:1], _INT_MIN, jnp.int32))


def _tie_positions(scores, tau, above, topk: int) -> jax.Array:
    """Per row the last position whose score, equal to the threshold, is still
    taken: the ``topk - above``-th of them in position order (``above`` counts
    the row's keys above the threshold), found bit by bit by counting like the
    threshold itself; past the last position where the row has no more of them
    than places (a row shorter than ``topk``). The XLA path's: searched for
    every row, needed or not — float32 scores tie at the threshold in a few
    rows of nearly every step, and a search over the whole ``[T, T]`` that ran
    only then made a step's time follow the draw (PERF.md section 6, PR 30).
    The kernel path searches the few rows that need it in VMEM
    (``_select_kernel``), at a cost too small to show."""
    t = scores.shape[-1]
    ties, need = sortable(scores) == tau[:, None], topk - above
    idx = jnp.arange(t, dtype=jnp.int32)
    bits = max((t - 1).bit_length(), 1)

    def body(i, lo):
        cand = lo + lax.shift_left(np.int32(1), (bits - 1 - i).astype(jnp.int32))
        return jnp.where(_count(ties & (idx[None, :] < cand[:, None])) < need, cand, lo)

    return lax.fori_loop(0, bits, body, jnp.zeros(scores.shape[:1], jnp.int32))


def threshold_value(tau: jax.Array) -> jax.Array:
    """The float32 score an integer threshold stands for (``sortable``'s
    inverse; ``INT_MIN``, a row shorter than ``topk``, is -inf)."""
    bits = jnp.where(tau < 0, tau ^ np.int32(0x7FFFFFFF), tau)
    return jnp.where(tau == _INT_MIN, -jnp.inf, lax.bitcast_convert_type(bits, jnp.float32))


def _selected(scores, value, tie, first_key, axis):
    """The selection from scores, the queries' thresholds (as scores) and tie
    positions, broadcast along the keys, whose positions run along ``axis``
    from ``first_key``."""
    keys = first_key + lax.broadcasted_iota(jnp.int32, scores.shape, axis)
    return ((scores > value) | ((scores == value) & (keys <= tie))) & (scores > -jnp.inf)


def selection_mask(scores: jax.Array, tau: jax.Array, tie_pos: jax.Array) -> jax.Array:
    """[T, T] bool from the scores and the selection's two numbers."""
    return _selected(scores, threshold_value(tau)[:, None], tie_pos[:, None], 0, 1)


def _attend_xla(q, k, v, mask):
    """q [T, Hq, hd], k/v [T, Hkv, hd], mask [T, T] -> [T, Hq, hd]."""
    t, hq, hd = q.shape
    group = hq // k.shape[1]
    block = math.gcd(t, _BLOCK_Q)
    qs = q.reshape(t, k.shape[1], group, hd)

    @jax.checkpoint
    def rows(start):
        qb = lax.dynamic_slice_in_dim(qs, start, block, 0)
        seen = lax.dynamic_slice_in_dim(mask, start, block, 0)
        scores = jnp.einsum("qngd,knd->ngqk", qb, k, preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(jnp.where(seen, scores / math.sqrt(hd), -jnp.inf), axis=-1)
        return jnp.einsum(
            "ngqk,knd->qngd", probs.astype(v.dtype), v, preferred_element_type=jnp.float32
        ).astype(q.dtype)

    return lax.map(rows, jnp.arange(0, t, block)).reshape(t, hq, hd)


def _kl_rows(p_sum, heads: int, scores, seen):
    """Per row ``KL(p || softmax_seen(scores))`` with ``p = p_sum / heads``."""
    p = jnp.where(seen, p_sum / heads, 0.0)
    log_q = jax.nn.log_softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    terms = p * (jnp.log(jnp.where(p > 0, p, 1.0)) - jnp.where(seen, log_q, 0.0))
    return jnp.sum(terms, axis=-1)


def _align_xla(q, k, scores, mask):
    """Sum over the rows of KL(p || softmax_S I); gradient to ``scores`` only."""
    t, hq, hd = q.shape
    group = hq // k.shape[1]
    block = math.gcd(t, _BLOCK_Q)
    qs = lax.stop_gradient(q).reshape(t, k.shape[1], group, hd)
    ks = lax.stop_gradient(k)

    @jax.checkpoint
    def rows(start, scores):
        qb = lax.dynamic_slice_in_dim(qs, start, block, 0)
        seen = lax.dynamic_slice_in_dim(mask, start, block, 0)
        logits = jnp.einsum("qngd,knd->ngqk", qb, ks, preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(jnp.where(seen, logits / math.sqrt(hd), -jnp.inf), axis=-1)
        mine = lax.dynamic_slice_in_dim(scores, start, block, 0)
        return jnp.sum(_kl_rows(jnp.sum(probs, axis=(0, 1)), hq, mine, seen))

    return jnp.sum(lax.map(lambda s: rows(s, scores), jnp.arange(0, t, block)))


# ---------------------------------------------------------------------------
# the kernels (TPU; ``interpret`` runs them on the CPU for tests)
# ---------------------------------------------------------------------------

_TILE_Q, _TILE_K = 256, 512
# rows whose scores stand in VMEM while their thresholds are searched, the
# chunks of columns a pass over them reads at a time where as many are left,
# and the rows whose tie positions are searched together
_SELECT_ROWS, _SELECT_WIDE, _TIE_ROWS = 128, 4, 8
# what -inf is as an order-preserving integer: no score sorts below it
_KEY_NEG_INF = np.int32(-(2**31) + 0x7FFFFF)
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def kernels_serve(t: int, head_dim: int, indexer_dim: int) -> bool:
    """The shapes the kernel path takes (on a TPU)."""
    return t % _TILE_K == 0 and head_dim % 128 == 0 and indexer_dim in (64, 128)


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=64 * 1024 * 1024
    )


def _last_key_tile(i, bq, bk):
    """The last key tile that query tile ``i`` sees."""
    return (i * bq + bq - 1) // bk


def _seen_key_tile(i, j, *, bq, bk):
    """Key tile ``j`` where query tile ``i`` sees it, else the last one it
    sees: an index map that fetches nothing for the tiles a kernel skips."""
    return jnp.minimum(j, _last_key_tile(i, bq, bk))


def _seeing_query_tile(j, i, *, bq, bk):
    """The same for a grid whose keys are resident: query tile ``i`` where it
    sees key tile ``j``, else the first that does."""
    return jnp.maximum(i, (j * bk) // bq)


def _tile_visible(i, j, bq, bk, seg_q, seg_k):
    """[bq, bk] visibility of tile (i, j); seg_q [bq, 1], seg_k [1, bk]."""
    rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return (cols <= rows) & (seg_q == seg_k)


def _scores_kernel(qi_ref, ki_ref, wi_ref, segq_ref, segk_ref, out_ref, *, heads, bq, bk):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)
    below = j <= _last_key_tile(i, bq, bk)  # the tile holds a pair with s <= t

    @pl.when(below)
    def _():
        acc = jnp.zeros((bq, bk), jnp.float32)
        kt = ki_ref[...]
        w = wi_ref[...]
        for h in range(heads):
            dots = lax.dot_general(qi_ref[h], kt, _NT, preferred_element_type=jnp.float32)
            acc = acc + w[:, h:h + 1] * jnp.maximum(dots, 0.0)
        seen = _tile_visible(i, j, bq, bk, segq_ref[...], segk_ref[...])
        out_ref[...] = jnp.where(seen, acc, -jnp.inf)

    @pl.when(jnp.logical_not(below))
    def _():
        out_ref[...] = jnp.full((bq, bk), -jnp.inf, jnp.float32)


def _scores_pallas(qi, ki, wi, segment_ids, interpret=False):
    """qi [J, T, di], ki [T, di], wi [T, J] float32, segment_ids [T] -> [T, T]."""
    from jax.experimental import pallas as pl

    heads, t, di = qi.shape
    bq, bk = math.gcd(t, _TILE_Q), math.gcd(t, _TILE_K)
    key = functools.partial(_seen_key_tile, bq=bq, bk=bk)
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=heads, bq=bq, bk=bk),
        grid=(t // bq, t // bk),
        in_specs=[
            pl.BlockSpec((heads, bq, di), lambda i, j: (0, i, 0)),
            pl.BlockSpec((bk, di), lambda i, j: (key(i, j), 0)),
            pl.BlockSpec((bq, heads), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bk), lambda i, j: (0, key(i, j))),
        ],
        out_specs=pl.BlockSpec((bq, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, t), jnp.float32),
        compiler_params=None if interpret else _params("parallel", "parallel"),
        interpret=interpret,
        name="sparse_indexer_scores",
    )(qi, ki, wi, segment_ids[:, None], segment_ids[None, :])


def _scores_dq_kernel(qi_ref, ki_ref, wi_ref, g_ref, dqi_ref, dwi_ref, *, heads, bq, bk):
    """Rows resident, keys the reduction: dqI [J, bq, di], dw [J, bq, 1]."""
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        dqi_ref[...] = jnp.zeros_like(dqi_ref)
        dwi_ref[...] = jnp.zeros_like(dwi_ref)

    @pl.when(j <= _last_key_tile(i, bq, bk))
    def _():
        kt = ki_ref[...]
        w = wi_ref[...]
        g = g_ref[...]
        for h in range(heads):
            dots = lax.dot_general(qi_ref[h], kt, _NT, preferred_element_type=jnp.float32)
            dwi_ref[h] += jnp.sum(g * jnp.maximum(dots, 0.0), axis=1, keepdims=True)
            through = jnp.where(dots > 0, g * w[:, h:h + 1], 0.0).astype(kt.dtype)
            dqi_ref[h] += jnp.dot(through, kt, preferred_element_type=jnp.float32)


def _scores_dk_kernel(qi_ref, ki_ref, wit_ref, g_ref, dki_ref, *, heads, bq, bk):
    """Keys resident, rows the reduction: dkI [bk, di]."""
    from jax.experimental import pallas as pl

    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(j <= _last_key_tile(i, bq, bk))
    def _():
        kt = ki_ref[...]
        wt = wit_ref[...]  # [J, bq]
        gt = g_ref[...].T  # [bk, bq]
        for h in range(heads):
            qh = qi_ref[h]
            dots = lax.dot_general(kt, qh, _NT, preferred_element_type=jnp.float32)  # [bk, bq]
            through = jnp.where(dots > 0, gt * wt[h:h + 1, :], 0.0).astype(qh.dtype)
            dki_ref[...] += jnp.dot(through, qh, preferred_element_type=jnp.float32)


def _scores_grads_pallas(qi, ki, wi, g, interpret=False):
    """The gradients of ``_scores_pallas`` for the cotangent ``g`` [T, T]
    (zero where a pair is invisible): dqI [J, T, di], dkI [T, di], dw [T, J]."""
    from jax.experimental import pallas as pl

    heads, t, di = qi.shape
    bq, bk = math.gcd(t, _TILE_Q), math.gcd(t, _TILE_K)
    key = functools.partial(_seen_key_tile, bq=bq, bk=bk)
    query = functools.partial(_seeing_query_tile, bq=bq, bk=bk)
    dqi, dwi = pl.pallas_call(
        functools.partial(_scores_dq_kernel, heads=heads, bq=bq, bk=bk),
        grid=(t // bq, t // bk),
        in_specs=[
            pl.BlockSpec((heads, bq, di), lambda i, j: (0, i, 0)),
            pl.BlockSpec((bk, di), lambda i, j: (key(i, j), 0)),
            pl.BlockSpec((bq, heads), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, bk), lambda i, j: (i, key(i, j))),
        ],
        out_specs=[
            pl.BlockSpec((heads, bq, di), lambda i, j: (0, i, 0)),
            pl.BlockSpec((heads, bq, 1), lambda i, j: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((heads, t, di), jnp.float32),
            jax.ShapeDtypeStruct((heads, t, 1), jnp.float32),
        ],
        compiler_params=None if interpret else _params("parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_indexer_dq",
    )(qi, ki, wi, g)
    dki = pl.pallas_call(
        functools.partial(_scores_dk_kernel, heads=heads, bq=bq, bk=bk),
        grid=(t // bk, t // bq),
        in_specs=[
            pl.BlockSpec((heads, bq, di), lambda j, i: (0, query(j, i), 0)),
            pl.BlockSpec((bk, di), lambda j, i: (j, 0)),
            pl.BlockSpec((heads, bq), lambda j, i: (0, query(j, i))),
            pl.BlockSpec((bq, bk), lambda j, i: (query(j, i), j)),
        ],
        out_specs=pl.BlockSpec((bk, di), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((t, di), jnp.float32),
        compiler_params=None if interpret else _params("parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_indexer_dk",
    )(qi, ki, wi.T, g)
    return dqi, dki, dwi[..., 0].T


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _scores_kernel_path(qi, ki, wi, segment_ids, interpret):
    return _scores_pallas(qi, ki, wi, segment_ids, interpret)


def _scores_fwd(qi, ki, wi, segment_ids, interpret):
    return _scores_pallas(qi, ki, wi, segment_ids, interpret), (qi, ki, wi)


def _scores_bwd(interpret, res, g):
    qi, ki, wi = res
    dqi, dki, dwi = _scores_grads_pallas(qi, ki, wi, g, interpret)
    return dqi.astype(qi.dtype), dki.astype(ki.dtype), dwi, None


_scores_kernel_path.defvjp(_scores_fwd, _scores_bwd)


def _counted_chunks(segment_ids, rows: int, chunk: int):
    """Per block of ``rows`` queries the chunks of ``chunk`` key columns that
    hold every key one of them sees, as (first, one past the last), each
    [T / rows] int32. No query sees a key past itself; and where the ids never
    fall (packed documents: equal ids are one run) none before the start of
    the run its block's first query lies in. Ids that fall may come back
    later, so they bound nothing from below."""
    t = segment_ids.shape[0]
    idx = jnp.arange(t, dtype=jnp.int32)
    opens = jnp.concatenate([jnp.ones((1,), bool), segment_ids[1:] != segment_ids[:-1]])
    run_start = lax.cummax(jnp.where(opens, idx, 0))
    packed = jnp.all(segment_ids[1:] >= segment_ids[:-1])
    first = jnp.where(packed, run_start[::rows] // chunk, 0)
    return first, (idx[::rows] + rows + chunk - 1) // chunk


def _select_kernel(first_ref, last_ref, scores_ref, tau_ref, tie_ref, keys_ref, need_ref, *,
                   topk, chunk):
    """A block of rows of scores in VMEM: their thresholds (bit by bit, 32
    counts) and, where a row has more keys equal to its threshold than places
    left for them, the position of the last one taken (bit by bit again, a
    group of ``_TIE_ROWS`` rows at a time and only the groups with such a
    row); ``T - 1``, every one of them, on all other rows. Every pass runs
    over the chunks of columns the block's rows can see (``_counted_chunks``)
    and no other: what lies outside is -inf by construction and is never read."""
    from jax.experimental import pallas as pl

    rows, t = keys_ref.shape
    first, last = first_ref[pl.program_id(0)], last_ref[pl.program_id(0)]
    lanes = math.gcd(chunk, 128)

    def over_chunks(visit, carry):
        """``visit(columns, carry)`` over the counted chunks, ``_SELECT_WIDE``
        of them at a time while as many are left."""
        def span(width):
            return lambda c, x: visit(pl.ds(pl.multiple_of(c * chunk, chunk), width * chunk), x)

        wide = min(_SELECT_WIDE, t // chunk)
        spans = (last - first) // wide
        carry = lax.fori_loop(0, spans, lambda n, x: span(wide)(first + n * wide, x), carry)
        return lax.fori_loop(first + spans * wide, last, span(1), carry)

    def to_keys(columns, carry):
        keys_ref[:, columns] = sortable(scores_ref[:, columns])
        return carry

    over_chunks(to_keys, 0)

    def count(flagged, group=slice(None), size=rows):
        """Per row of the group, the counted columns that ``flagged(keys,
        first column)`` marks."""
        def add(columns, acc):
            flags = flagged(keys_ref[group, columns], columns.start).astype(jnp.int32)
            for k in range(0, columns.size, lanes):  # lanes side by side: no sum across them here
                acc = acc + flags[:, k:k + lanes]
            return acc

        acc = over_chunks(add, jnp.zeros((size, lanes), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def threshold_bit(i, tau):
        cand = tau + lax.shift_left(jnp.int32(1), 31 - i)
        return jnp.where(count(lambda keys, _: keys >= cand) >= topk, cand, tau)

    tau = lax.fori_loop(0, 32, threshold_bit, jnp.full((rows, 1), _INT_MIN, jnp.int32))
    if t >= topk:
        # a row shorter than topk: with the columns not counted, all -inf, it has T keys
        tau = jnp.maximum(tau, _KEY_NEG_INF)
    tau_ref[...] = tau
    need = topk - count(lambda keys, _: keys > tau)
    # -inf is never taken, so a row whose threshold it is has nothing to search
    surplus = (count(lambda keys, _: keys == tau) > need) & (tau > _KEY_NEG_INF)
    places = jnp.where(surplus, need, 0)  # left on the rows to search; 0 on the others
    need_ref[...] = places
    tie_ref[...] = jnp.full((rows, 1), t - 1, jnp.int32)
    bits = max((t - 1).bit_length(), 1)
    size = math.gcd(rows, _TIE_ROWS)

    def search_group(g, carry):
        group = pl.ds(pl.multiple_of(g * size, size), size)
        need = need_ref[group, :]

        @pl.when(jnp.max(need) > 0)
        def _():
            tau = tau_ref[group, :]

            def position_bit(i, pos):
                cand = pos + lax.shift_left(jnp.int32(1), bits - 1 - i)

                def earlier_ties(keys, start):
                    cols = start + lax.broadcasted_iota(jnp.int32, keys.shape, 1)
                    return (keys == tau) & (cols < cand)

                return jnp.where(count(earlier_ties, group, size) < need, cand, pos)

            pos = lax.fori_loop(0, bits, position_bit, jnp.zeros((size, 1), jnp.int32))
            tie_ref[group, :] = jnp.where(need > 0, pos, t - 1)

        return carry

    @pl.when(jnp.max(places) > 0)
    def _():
        lax.fori_loop(0, rows // size, search_group, 0)


def _select_pallas(scores, segment_ids, topk, interpret=False):
    """scores [T, T], segment_ids [T] -> (threshold [T] int32, tie position
    [T] int32, what was searched (float32 each): ``columns``, the columns the
    counts ran over, summed over the rows, and ``tie_blocks``, the groups of
    ``_TIE_ROWS`` rows whose tie positions were searched)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t = scores.shape[0]
    rows, chunk = math.gcd(t, _SELECT_ROWS), math.gcd(t, _TILE_K)
    first, last = _counted_chunks(segment_ids, rows, chunk)
    column = pl.BlockSpec((rows, 1), lambda i, first, last: (i, 0))
    tau, tie_pos = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(t // rows,),
            in_specs=[pl.BlockSpec((rows, t), lambda i, first, last: (i, 0))],
            out_specs=[column] * 2,
            scratch_shapes=[pltpu.VMEM((rows, t), jnp.int32), pltpu.VMEM((rows, 1), jnp.int32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((t, 1), jnp.int32)] * 2,
        compiler_params=None if interpret else _params("parallel"),
        interpret=interpret,
        name="sparse_select",
    )(first, last, scores)
    tau, tie_pos = tau[:, 0], tie_pos[:, 0]
    # only a row whose ties were searched stops short of the last position
    searched = jnp.any((tie_pos < t - 1).reshape(-1, math.gcd(rows, _TIE_ROWS)), axis=1)
    work = {"columns": jnp.sum((last - first).astype(jnp.float32)) * (rows * chunk),
            "tie_blocks": jnp.sum(searched.astype(jnp.float32))}
    return tau, tie_pos, work


def _attend_kernel(q_ref, k_ref, v_ref, s_ref, tau_ref, tie_ref, o_ref, lse_ref, lsei_ref,
                   reads_ref, m_sc, l_sc, acc_sc, mi_sc, li_sc, *, heads, bq, bk):
    """Softmax attention over the selection, keys the reduction (an online
    softmax a head); beside it the log-sum-exp of the indexer's scores over
    the selection, and how many of the tile's queries read each of its keys."""
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)
    below = j <= _last_key_tile(i, bq, bk)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        mi_sc[...] = jnp.full_like(mi_sc, -jnp.inf)
        for ref in (l_sc, acc_sc, li_sc):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(below)
    def _():
        scores = s_ref[...]
        seen = _selected(scores, tau_ref[...], tie_ref[...], j * bk, 1)
        reads_ref[0] = jnp.sum(jnp.where(seen, 1.0, 0.0), axis=0, keepdims=True)

        def update(m_prev, l_prev, x):
            """(new max, what the old sums are scaled by, exp(x - max) over the selection)."""
            m_new = jnp.maximum(m_prev, jnp.max(jnp.where(seen, x, -jnp.inf), axis=1, keepdims=True))
            safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            p = jnp.where(seen, jnp.exp(x - safe), 0.0)
            alpha = jnp.exp(m_prev - safe)
            return m_new, alpha, p, alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

        mi_sc[...], _, _, li_sc[...] = update(mi_sc[...], li_sc[...], scores)
        kt, vt = k_ref[...], v_ref[...]
        for h in range(heads):
            logits = lax.dot_general(q_ref[h], kt, _NT, preferred_element_type=jnp.float32)
            m_sc[h], alpha, p, l_sc[h] = update(m_sc[h], l_sc[h], logits)
            acc_sc[h] = alpha * acc_sc[h] + jnp.dot(
                p.astype(vt.dtype), vt, preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_not(below))
    def _():
        reads_ref[...] = jnp.zeros_like(reads_ref)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for h in range(heads):
            o_ref[h] = (acc_sc[h] / l_sc[h]).astype(o_ref.dtype)
            lse_ref[h] = m_sc[h] + jnp.log(l_sc[h])
        lsei_ref[...] = mi_sc[...] + jnp.log(li_sc[...])


def _attend_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, s_ref, tau_ref, tie_ref,
                      dq_ref, *, heads, bq, bk):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(j <= _last_key_tile(i, bq, bk))
    def _():
        seen = _selected(s_ref[...], tau_ref[...], tie_ref[...], j * bk, 1)
        kt, vt = k_ref[...], v_ref[...]
        for h in range(heads):
            logits = lax.dot_general(q_ref[h], kt, _NT, preferred_element_type=jnp.float32)
            p = jnp.where(seen, jnp.exp(logits - lse_ref[h]), 0.0)
            dp = lax.dot_general(do_ref[h], vt, _NT, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[h])).astype(kt.dtype)
            dq_ref[h] += jnp.dot(ds, kt, preferred_element_type=jnp.float32)


def _attend_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, s_ref, tau_ref, tie_ref,
                       dk_ref, dv_ref, *, heads, bq, bk):
    """Keys resident, queries the reduction; every tile transposed, so the
    products are plain: lse, delta, tau and tie come as rows [.., bq]."""
    from jax.experimental import pallas as pl

    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(j <= _last_key_tile(i, bq, bk))
    def _():
        seen = _selected(s_ref[...].T, tau_ref[...], tie_ref[...], j * bk, 0)
        kt, vt = k_ref[...], v_ref[...]
        lse, delta = lse_ref[...], delta_ref[...]
        for h in range(heads):
            qh, doh = q_ref[h], do_ref[h]
            logits = lax.dot_general(kt, qh, _NT, preferred_element_type=jnp.float32)  # [bk, bq]
            p = jnp.where(seen, jnp.exp(logits - lse[h:h + 1, :]), 0.0)
            dv_ref[...] += jnp.dot(p.astype(doh.dtype), doh, preferred_element_type=jnp.float32)
            dp = lax.dot_general(vt, doh, _NT, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[h:h + 1, :])).astype(qh.dtype)
            dk_ref[...] += jnp.dot(ds, qh, preferred_element_type=jnp.float32)


def _query_major_specs(heads, hd, bq, bk):
    """Block specs of a (query tile, key tile) grid, keys clamped to the last
    tile a query tile sees: q-like [H, T, hd], k-like [T, hd], a [T, T] tile,
    a column [T, 1] and a per-head column [H, T, 1]."""
    from jax.experimental import pallas as pl

    key = functools.partial(_seen_key_tile, bq=bq, bk=bk)
    return dict(
        q=pl.BlockSpec((heads, bq, hd), lambda i, j: (0, i, 0)),
        k=pl.BlockSpec((bk, hd), lambda i, j: (key(i, j), 0)),
        tile=pl.BlockSpec((bq, bk), lambda i, j: (i, key(i, j))),
        column=pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        head_column=pl.BlockSpec((heads, bq, 1), lambda i, j: (0, i, 0)),
    )


def _attend_pallas(q, k, v, scores, tau, tie, interpret=False):
    """q [H, T, hd] (scaled), k/v [T, hd], scores [T, T], tau [T] float32,
    tie [T] int32 -> (out [H, T, hd], the heads' log-sum-exp [H, T], the
    scores' log-sum-exp over the selection [T], the queries that read each key
    [T] float32: a row a query tile, summed here)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, t, hd = q.shape
    bq, bk = math.gcd(t, _TILE_Q), math.gcd(t, _TILE_K)
    spec = _query_major_specs(heads, hd, bq, bk)
    out, lse, lse_i, reads = pl.pallas_call(
        functools.partial(_attend_kernel, heads=heads, bq=bq, bk=bk),
        grid=(t // bq, t // bk),
        in_specs=[spec["q"], spec["k"], spec["k"], spec["tile"], spec["column"], spec["column"]],
        out_specs=[spec["q"], spec["head_column"], spec["column"],
                   pl.BlockSpec((1, 1, bk), lambda i, j: (i, 0, j))],
        out_shape=[
            jax.ShapeDtypeStruct((heads, t, hd), q.dtype),
            jax.ShapeDtypeStruct((heads, t, 1), jnp.float32),
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
            jax.ShapeDtypeStruct((t // bq, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, bq, 1), jnp.float32), pltpu.VMEM((heads, bq, 1), jnp.float32),
            pltpu.VMEM((heads, bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32), pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=None if interpret else _params("parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_attend",
    )(q, k, v, scores, tau[:, None], tie[:, None])
    return out, lse[..., 0], lse_i[:, 0], jnp.sum(reads[:, 0], axis=0)


def _attend_grads_pallas(q, k, v, scores, tau, tie, lse, out, do, interpret=False):
    """dq [H, T, hd], dk and dv [T, hd] (float32) of ``_attend_pallas``."""
    from jax.experimental import pallas as pl

    heads, t, hd = q.shape
    bq, bk = math.gcd(t, _TILE_Q), math.gcd(t, _TILE_K)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)  # [H, T]
    spec = _query_major_specs(heads, hd, bq, bk)
    dq = pl.pallas_call(
        functools.partial(_attend_dq_kernel, heads=heads, bq=bq, bk=bk),
        grid=(t // bq, t // bk),
        in_specs=[spec["q"], spec["k"], spec["k"], spec["q"], spec["head_column"],
                  spec["head_column"], spec["tile"], spec["column"], spec["column"]],
        out_specs=spec["q"],
        out_shape=jax.ShapeDtypeStruct((heads, t, hd), jnp.float32),
        compiler_params=None if interpret else _params("parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_attend_dq",
    )(q, k, v, do, lse[..., None], delta[..., None], scores, tau[:, None], tie[:, None])
    query = functools.partial(_seeing_query_tile, bq=bq, bk=bk)
    heads_q = pl.BlockSpec((heads, bq, hd), lambda j, i: (0, query(j, i), 0))
    keys = pl.BlockSpec((bk, hd), lambda j, i: (j, 0))
    head_row = pl.BlockSpec((heads, bq), lambda j, i: (0, query(j, i)))
    row = pl.BlockSpec((1, bq), lambda j, i: (0, query(j, i)))
    dk, dv = pl.pallas_call(
        functools.partial(_attend_dkv_kernel, heads=heads, bq=bq, bk=bk),
        grid=(t // bk, t // bq),
        in_specs=[heads_q, keys, keys, heads_q, head_row, head_row,
                  pl.BlockSpec((bq, bk), lambda j, i: (query(j, i), j)), row, row],
        out_specs=[keys, keys],
        out_shape=[jax.ShapeDtypeStruct((t, hd), jnp.float32)] * 2,
        compiler_params=None if interpret else _params("parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_attend_dkv",
    )(q, k, v, do, lse, delta, scores, tau[None, :], tie[None, :])
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _attend_kernel_path(q, k, v, scores, tau, tie, interpret):
    return _attend_pallas(q, k, v, scores, tau, tie, interpret)


def _attend_fwd(q, k, v, scores, tau, tie, interpret):
    out, lse, lse_i, reads = _attend_pallas(q, k, v, scores, tau, tie, interpret)
    # named here, before they part into the outputs and the residuals: kept,
    # they serve the backward kernels and the recomputed ``sparse_align`` alike,
    # and nothing asks for the kernel again (the counts are the first pass's)
    out, lse, lse_i = (checkpoint_name(x, ATTENTION_NAME) for x in (out, lse, lse_i))
    return (out, lse, lse_i, reads), (q, k, v, scores, tau, tie, lse, out)


def _attend_bwd(interpret, res, cts):
    q, k, v, scores, tau, tie, lse, out = res
    dq, dk, dv = _attend_grads_pallas(q, k, v, scores, tau, tie, lse, out, cts[0], interpret)
    # the selection passes no gradient; the log-sum-exps and the counts go on cut from the graph
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None, None, None


_attend_kernel_path.defvjp(_attend_fwd, _attend_bwd)


def _align_kernel(q_ref, k_ref, lse_ref, s_ref, tau_ref, tie_ref, lsei_ref, loss_ref, g_ref, *,
                  heads, group, bq, bk):
    """One tile of p = sum_h exp(q_h . k - lse_h) / heads over the selection:
    the rows' KL terms (summed over the key tiles) and the tile of
    softmax_S(I) - p, the loss's gradient to the scores."""
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        loss_ref[...] = jnp.zeros_like(loss_ref)

    below = j <= _last_key_tile(i, bq, bk)

    @pl.when(below)
    def _():
        scores = s_ref[...]
        seen = _selected(scores, tau_ref[...], tie_ref[...], j * bk, 1)
        p = jnp.zeros((bq, bk), jnp.float32)
        for h in range(heads):
            logits = lax.dot_general(q_ref[h], k_ref[h // group], _NT,
                                     preferred_element_type=jnp.float32)
            p = p + jnp.exp(logits - lse_ref[h])
        p = jnp.where(seen, p * (1.0 / heads), 0.0)
        log_q = jnp.where(seen, scores - lsei_ref[...], 0.0)
        loss_ref[...] += jnp.sum(
            p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q), axis=1, keepdims=True)
        g_ref[...] = jnp.where(seen, jnp.exp(log_q), 0.0) - p

    @pl.when(jnp.logical_not(below))
    def _():
        g_ref[...] = jnp.zeros((bq, bk), jnp.float32)


def _align_pallas(q, k, lse, lse_i, scores, tau, tie, interpret=False):
    """q [Hq, T, hd] (scaled), k [Hkv, T, hd], lse [Hq, T], lse_i [T], scores
    [T, T], tau [T] float32, tie [T] int32 -> (the rows' KL [T],
    softmax_S(I) - p [T, T])."""
    from jax.experimental import pallas as pl

    heads, t, hd = q.shape
    hkv = k.shape[0]
    bq, bk = math.gcd(t, _TILE_Q), math.gcd(t, _TILE_K)
    spec = _query_major_specs(heads, hd, bq, bk)
    keys = pl.BlockSpec((hkv, bk, hd), lambda i, j: (0, _seen_key_tile(i, j, bq=bq, bk=bk), 0))
    loss, g = pl.pallas_call(
        functools.partial(_align_kernel, heads=heads, group=heads // hkv, bq=bq, bk=bk),
        grid=(t // bq, t // bk),
        in_specs=[spec["q"], keys, spec["head_column"], spec["tile"], spec["column"],
                  spec["column"], spec["column"]],
        out_specs=[spec["column"], pl.BlockSpec((bq, bk), lambda i, j: (i, j))],
        out_shape=[
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
            jax.ShapeDtypeStruct((t, t), jnp.float32),
        ],
        compiler_params=None if interpret else _params("parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_align",
    )(q, k, lse[..., None], scores, tau[:, None], tie[:, None], lse_i[:, None])
    return loss[:, 0], g


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _align_kernel_path(q, k, lse, lse_i, scores, tau, tie, interpret):
    return jnp.sum(_align_pallas(q, k, lse, lse_i, scores, tau, tie, interpret)[0])


def _align_fwd(q, k, lse, lse_i, scores, tau, tie, interpret):
    loss, g = _align_pallas(q, k, lse, lse_i, scores, tau, tie, interpret)
    return jnp.sum(loss), g


def _align_bwd(interpret, g, ct):
    # what goes in beside the scores is cut from the graph
    return None, None, None, None, ct * g, None, None


_align_kernel_path.defvjp(_align_fwd, _align_bwd)


def _sequence_kernels(q, k, v, qi, ki, wi, segment_ids, topk, interpret):
    """One sequence on the kernel path: (out [T, Hq, hd], the rows' KL summed,
    times each key was selected [T], what the selection searched)."""
    t, hq, hd = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    with scopes.scope("decoder/attn_sparse/indexer"):
        scores = _scores_kernel_path(qi.transpose(1, 0, 2), ki, wi, segment_ids, interpret)
    with scopes.scope("decoder/attn_sparse/select"):
        frozen = lax.stop_gradient(scores)
        tau, tie_pos, work = _select_pallas(frozen, segment_ids, topk, interpret)
        tau, tie_pos = checkpoint_name(tau, SELECT_NAME), checkpoint_name(tie_pos, SELECT_NAME)
        tau = threshold_value(tau)
    qh = (q * (1.0 / math.sqrt(hd))).astype(q.dtype).reshape(t, hkv, group, hd)
    qh, kh, vh = qh.transpose(1, 2, 0, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    with scopes.scope("decoder/attn_sparse/attend"):
        # a key-value head and its group of query heads at a time; every head
        # reads the same selection, so the first's counts are the layer's
        out, lse, lse_i, reads = zip(*(
            _attend_kernel_path(qh[n], kh[n], vh[n], frozen, tau, tie_pos, interpret)
            for n in range(hkv)))
    with scopes.scope("decoder/attn_sparse/align"):
        align = _align_kernel_path(
            *map(lax.stop_gradient, (qh.reshape(hq, t, hd), kh, jnp.concatenate(lse), lse_i[0])),
            scores, tau, tie_pos, interpret)
    out = jnp.stack(out).transpose(2, 0, 1, 3).reshape(t, hq, hd)
    return out, align, reads[0], work


def _sequence_xla(q, k, v, qi, ki, wi, segment_ids, topk):
    with scopes.scope("decoder/attn_sparse/indexer"):
        scores = _scores_xla(qi, ki, wi, segment_ids)
    with scopes.scope("decoder/attn_sparse/select"):
        frozen = lax.stop_gradient(scores)
        tau, tie_pos = select(frozen, topk)
        tau, tie_pos = checkpoint_name(tau, SELECT_NAME), checkpoint_name(tie_pos, SELECT_NAME)
        mask = selection_mask(frozen, tau, tie_pos)
    with scopes.scope("decoder/attn_sparse/attend"):
        out = _attend_xla(q, k, v, mask)
    with scopes.scope("decoder/attn_sparse/align"):
        align = _align_xla(q, k, scores, mask)
    # every row's searches run over the whole row
    t = q.shape[0]
    work = {"columns": jnp.float32(t * t), "tie_blocks": jnp.float32(t // math.gcd(t, _TIE_ROWS))}
    return out, align, jnp.sum(mask.astype(jnp.float32), axis=0), work


def select(scores: jax.Array, topk: int) -> Tuple[jax.Array, jax.Array]:
    """scores [T, T] -> (threshold [T] int32, tie position [T] int32): the XLA
    path's selection, for tests and readers."""
    keys = sortable(scores)
    tau = _threshold_xla(keys, topk)
    return tau, _tie_positions(scores, tau, _count(keys > tau[:, None]), topk)


def sparse_attention(q, k, v, qi, ki, wi, segment_ids, *, topk: int, interpret=None):
    """q [B, T, Hq, hd], k/v [B, T, Hkv, hd] (rotated, in the compute dtype);
    the indexer's qi [B, T, J, di], ki [B, T, di] (rotated, compute dtype) and
    wi [B, T, J] float32 (scaled); segment_ids [B, T] ->

    - out [B, T, Hq, hd]: attention over each query's ``min(topk, visible)``
      best-scored visible keys;
    - the alignment loss summed over the batch's positions (float32): its
      gradient reaches qi, ki and wi only, and nothing else's gradient does;
    - how many queries selected each key position, summed over the batch
      [T] (float32): its sum is the (query, key) pairs read;
    - what the selection searched, summed over the batch (float32 each):
      ``columns``, the key columns its counts ran over, summed over the
      queries, and ``tie_blocks``, the blocks of ``_TIE_ROWS`` queries
      whose tie positions were searched.

    ``interpret``: None chooses by backend and shape; True or False forces the
    kernel path (interpreted on the CPU, for tests)."""
    from tensorflowdistributedlearning_tpu.ops import pallas_kernels

    t, hd, di = q.shape[1], q.shape[3], qi.shape[3]
    if interpret is None:
        kernels = pallas_kernels.pallas_platform_ok() and kernels_serve(t, hd, di)
        interpret = False
    else:
        kernels = True

    def one(args):
        if kernels:
            return _sequence_kernels(*args, topk, interpret)
        return _sequence_xla(*args, topk)

    args = (q, k, v, qi, ki, wi.astype(jnp.float32), segment_ids)
    if q.shape[0] == 1:  # no loop around one sequence
        out, align, reads, work = one(tuple(x[0] for x in args))
        return out[None], align, reads, work
    out, align, reads, work = lax.map(one, args)
    return out, jnp.sum(align), jnp.sum(reads, axis=0), jax.tree.map(jnp.sum, work)
