"""Kimi Delta Attention (KDA) for the decoder family (models/decoder.py, layer
type ``linear_attention``): a delta-rule recurrence whose decay goes by
channel, over packed documents, forward and backward.

Per head, with ``q_t, k_t`` in R^K, ``v_t`` in R^V, the log-decay ``g_t`` in
R^K (every entry <= 0) and ``beta_t`` a scalar::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``S`` [K, V] zero before every document's first token (``positions`` 0).
The caller scales ``q`` (d_k^-1/2 in the model).

**The chunkwise form** (the WY/UT form of the gated delta rule). Over a chunk
of ``C = CHUNK`` (64) tokens that starts from ``S0``, with ``G`` the chunk's
running sum of ``g`` (restarting at a document's start): ``D[t, s] = exp(G_t
- G_s)`` (a vector over the channels, s <= t), ``A[t, s] = beta_t sum_c k_t
D[t, s] k_s`` below the diagonal, ``M[t, s] = sum_c q_t D[t, s] k_s`` on and
below it, ``T = (I + A)^-1``, ``W = T (beta k exp(G))`` and ``U~ = T (beta
v)``. Then ``U = U~ - W S0``, ``O = (q exp(G)) S0 + M U`` and ``S_C =
Diag(exp(G_C)) S0 + (k exp(G_C - G))^T U``. A document start inside the chunk
masks the pairs that cross it and the rows that would read ``S0`` or carry
into ``S_C``.

**Two passes.** Everything but the products against the state — the decays,
``A``, ``M``, ``T``, ``W``, ``U~``, the decayed rows — depends on the chunk's
inputs alone (``_chunk_free``; the inverses of a block's chunks in one batched
chain of products). The serial chain (``_chain_step``) steps a chunk with two
dependent ``[64, 128] x [128, 128]`` products, ``U = U~ - W S0`` and ``S_C``;
``O`` reads the chain's ``S0`` and ``U`` but lies off it. The backward pass
recomputes the state-free parts from the inputs, rebuilds the chunks'
starting states and ``U`` from the block's, carries ``dS`` in reverse through
the products against the state alone (``_chain_back``), and then forms each
chunk's gradients (``_chunk_grads``) with no dependence between chunks.

**Exact under underflow.** Every decay is formed as ``exp`` of a sum of g
(<= 0), never ``exp(G_t) exp(-G_s)``, so a chunk whose summed log-decay
underflows float32 gives the exact result (0 where it underflows, as the
recurrence does). The pairs go by sub-chunks of ``SUB`` (16) tokens: inside
a sub-chunk pair by pair (``D`` is [16, 16, K] a sub-chunk); across them,
for the rows of sub-chunk i, through its first token ``r``: ``(x_t exp(G_t -
G_r)) . (k_s exp(G_r - G_s))``, one batched product for all sub-chunks. For
the pairs of one document both exponents are <= 0 and their product is the
pair's decay, so where one factor underflows the decay is smaller still; a
factor across a document's start is clamped to exp(0), and its pair masked.

**Exponents from sub-chunks' sums.** The kernels take L, the running sums of
g restarting at every sub-chunk (and document), not the chunk's G: a
difference ``G_t - G_s`` of two sums from the chunk's start (each near -50
at the end of a chunk of 64) would keep only float32's absolute error of
such a sum, so the decays of near pairs, and the gradients that cancel
between them, would lose what a chunk of 16 keeps. So every exponent that
spans sub-chunks is L's local terms plus whole sub-chunks' totals
(``_exponents``: a 0/1 product at the highest precision), and the
cancelling parts of a gradient meet inside one sub-chunk.

**Precision.** ``A``, ``M``, ``T`` (the product ``(I - A)(I + A^2)(I +
A^4)..``; ``A`` is nilpotent), ``W`` and ``U~`` are float32, their products
at the highest precision; the products against the state and with ``U``,
``dU`` and ``dO`` take their operands in the inputs' dtype with float32
accumulation; the state, decays, g, beta and their gradients are float32, and
the output and the gradients of q, k and v are written in the inputs' dtype.

**Paths.** On a TPU, with K and V multiples of 128, two Pallas kernels
(``kda_fwd``, ``kda_bwd``): a grid step holds ``BLOCK`` tokens of one head
and writes its chunks' state-free parts to VMEM, ``GROUP`` chunks at a time
(a batch axis, as the sub-chunks are), then the chain loops over them with
the state in VMEM, and the block's starting state is written out; the
backward kernel takes the blocks in reverse and carries ``dS`` between them.
Every loop over chunks is a ``fori_loop``, so a kernel body is traced once
whatever the chunk and the block. Anything else runs the same chunk
functions in XLA (every chunk of a head at once, the chains as scans). The
forward's output and the blocks' states carry ``KDA_NAME``: a recomputed
decoder layer keeps them (models/decoder.py:REMAT_POLICY), so its backward
pass does not run the forward kernel again.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tensorflowdistributedlearning_tpu.obs import scopes

# tokens a chunk, the step of the chain of products against the state (a
# power of two)
CHUNK = 64
# tokens a sub-chunk: the pairwise decays are [SUB, SUB, K] a sub-chunk
SUB = 16
# tokens a grid step of the kernels holds, and how often they write the state
BLOCK = 256
# chunks the kernels' state-free and gradient passes take at once (a batch
# axis: the scheduler sees their independent work side by side)
GROUP = 4
# what the forward writes that the backward reads: the output and the states
KDA_NAME = "kda_residual"
SCOPE = "decoder/attn_linear/kda"

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims, dtype):
    """A product of 2-D ``dims`` (over a leading batch axis where the operands
    are 3-D) with operands in ``dtype`` and float32 accumulation; float32
    operands at the highest precision."""
    if a.ndim == 3:
        (left, right), _ = dims
        dims = (((left[0] + 1,), (right[0] + 1,)), ((0,), (0,)))
    precision = _HIGHEST if dtype == _F32 else None
    return lax.dot_general(a.astype(dtype), b.astype(dtype), dims, precision=precision,
                           preferred_element_type=_F32)


def _last_row(x):
    """[N, C, X] -> the last row [N, 1, X] (a masked sum: Mosaic takes no
    one-row slice of a 3-D value)."""
    row = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(row == x.shape[1] - 1, x, 0), axis=1, keepdims=True)


def _masks(pos):
    """pos [N, C, 1] (the tokens' positions in their documents) -> (t sees s
    [N, C, C], the same below the diagonal, rows that read the state carried
    in [N, C, 1], rows in the last token's document [N, C, 1], whether the
    state carried in reaches the chunk's end [N, 1, 1]) — the last three as
    0/1 floats."""
    c = pos.shape[1]
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = (i - j <= pos) & (j <= i)
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    last = _last_row(pos)
    return (same, same & (j < i), (pos > row).astype(_F32),
            (row >= (c - 1) - last).astype(_F32), (last >= c).astype(_F32))


def _sub(x):
    """[N, C, X] -> [N * C // SUB, SUB, X]: every chunk's sub-chunks as one
    batch axis."""
    return x.reshape(-1, SUB, x.shape[-1])


def _chunks_of(x, n):
    """[N * C // SUB, SUB, X] -> [N, C, X]."""
    return x.reshape(n, -1, x.shape[-1])


def _sub_masks(n, c):
    """Over [N * C // SUB, 2 SUB, C] (a row sub-chunk, a row of kb's or q's,
    a column of its chunk): (the columns of earlier sub-chunks, the row
    sub-chunk's own columns as [N * C // SUB, SUB, C] 0/1 floats — a product
    with it places a diagonal block, and takes one)."""
    subs = c // SUB
    i = lax.broadcasted_iota(jnp.int32, (subs, 2 * SUB, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (subs, 2 * SUB, c), 2)
    iota = functools.partial(lax.broadcasted_iota, jnp.int32, (subs, SUB, c))
    own = (iota(2) == iota(1) + SUB * iota(0)).astype(_F32)
    every = lambda x: jnp.broadcast_to(x, (n,) + x.shape).reshape(-1, *x.shape[1:])  # noqa: E731
    return every(col < SUB * i), every(own)


def _totals(pos):
    """The 0/1 matrices that add whole sub-chunks' sums of g to a chunk's
    exponents, for chunks of positions [N, C, 1]: ``pick`` [N, 8, C] takes
    sub-chunk m's total ``B_m`` (L at its last row; rows past the last
    sub-chunk are zero), and ``spread`` [N, (2 + C // SUB) C, 8] sums them
    for (i) each row, over the earlier sub-chunks of its document — G_t = L_t
    + that; (ii) each row, over its own sub-chunk and the later ones — G_C -
    G_t = that - L_t; (iii) each row sub-chunk i and column s, over s's
    sub-chunk up to i — G_r - G_s = that - L_s + L_r for i's first row r."""
    n, c, _ = pos.shape
    subs = c // SUB
    m = lax.broadcasted_iota(jnp.int32, (8, c), 0)
    pick = (lax.broadcasted_iota(jnp.int32, (8, c), 1) == SUB * m + SUB - 1).astype(_F32)
    row = lax.broadcasted_iota(jnp.int32, (c, 8), 0)
    m = lax.broadcasted_iota(jnp.int32, (c, 8), 1)
    earlier = (m < row // SUB) & (row - (SUB * m + SUB - 1) <= pos)
    later = (m >= row // SUB) & (m < subs)
    i = lax.broadcasted_iota(jnp.int32, (subs, c, 8), 0)
    col = lax.broadcasted_iota(jnp.int32, (subs, c, 8), 1)
    m = lax.broadcasted_iota(jnp.int32, (subs, c, 8), 2)
    between = ((m >= col // SUB) & (m < i)).reshape(subs * c, 8)
    spread = jnp.concatenate(
        [earlier.astype(_F32), jnp.broadcast_to(jnp.concatenate([later, between]).astype(_F32),
                                                (n, (1 + subs) * c, 8))], axis=1)
    return jnp.broadcast_to(pick, (n, 8, c)), spread


def _exponents(lc, pos):
    """From the sub-chunks' running sums L [N, C, K] (each restarting at a
    sub-chunk's and a document's start): G [N, C, K] (the running sum from
    the chunk's start); G_C - G [N, C, K] (for the rows of the last token's
    document); G_C [N, 1, K]; and ``_totals``' sums over sub-chunks for the
    columns of each row sub-chunk [N * C // SUB, C, K]. A difference of
    exponents a few tokens apart is a sum of L's few terms, never a
    difference of sums from the chunk's start, so float32 keeps it as it
    keeps a sub-chunk's."""
    n, c, kdim = lc.shape
    pick, spread = _totals(pos)
    sums = _dot(spread, _dot(pick, lc, _NN, _F32), _NN, _F32)
    to_end = sums[:, c:2 * c]
    first = lax.broadcasted_iota(jnp.int32, to_end.shape, 1) == 0
    return (lc + sums[:, :c], to_end - lc, jnp.sum(jnp.where(first, to_end, 0.0), axis=1,
                                                  keepdims=True),
            sums[:, 2 * c:].reshape(-1, c, kdim))


def _pair_factors(k, lc, between):
    """What forms the pairs of chunks from k and L [N, C, K]: the
    sub-chunks' pairwise decays [N * C // SUB, SUB, SUB, K]; through each row
    sub-chunk's first token r, exp(G_t - G_r) for its rows [N * C // SUB,
    SUB, K], and exp(G_r - G_s) and k times it for every column of its chunk
    [N * C // SUB, C, K] (``between``: ``_exponents``' last)."""
    n, c, kdim = lc.shape
    g = _sub(lc)
    d = jnp.exp(jnp.minimum(g[:, :, None, :] - g[:, None, :, :], 0.0))
    gr = jnp.sum(jnp.where(lax.broadcasted_iota(jnp.int32, g.shape, 1) == 0, g, 0.0), axis=1,
                 keepdims=True)
    to_r = (between.reshape(n, c // SUB, c, kdim) - lc[:, None]).reshape(-1, c, kdim) + gr
    ey = jnp.exp(jnp.minimum(to_r, 0.0))
    yk = (k[:, None] * ey.reshape(n, c // SUB, c, kdim)).reshape(-1, c, kdim)
    return d, jnp.exp(jnp.minimum(g - gr, 0.0)), ey, yk


def _pair_sums(kb, q, k, factors):
    """``B_x[t, s] = sum_c x[t, c] k[s, c] exp(G_t - G_s)`` [N, C, C] for x =
    kb and x = q, right where s <= t (the caller masks): the two stacked by
    rows, every sub-chunk at once."""
    d, ex, _, yk = factors
    n, c, _ = k.shape
    x = jnp.concatenate([_sub(kb), _sub(q)], axis=1)
    e = _sub(k)[:, None, :, :] * d
    diag = jnp.sum(x[:, :, None, :] * jnp.concatenate([e, e], axis=1), axis=-1)
    off = _dot(x * jnp.concatenate([ex, ex], axis=1), yk, _NT, _F32)
    earlier, own = _sub_masks(n, c)
    b = jnp.where(earlier, off, _dot(diag, own, _NN, _F32))
    return _chunks_of(b[:, :SUB], n), _chunks_of(b[:, SUB:], n)


def _pair_grads(da, dm, kb, q, k, factors):
    """Of ``_pair_sums``, given dA and dM (masked): (d kb, dq, dk, dL, d of
    ``_pair_factors``' exponents G_r - G_s). G_r, in both factors of a pair
    across sub-chunks, cancels in their product: its two parts are left out."""
    d, ex, ey, yk = factors
    n, c, kdim = k.shape
    x = jnp.concatenate([_sub(kb), _sub(q)], axis=1)
    ex2 = jnp.concatenate([ex, ex], axis=1)
    xe = x * ex2
    db = jnp.concatenate([_sub(da), _sub(dm)], axis=1)
    earlier, own = _sub_masks(n, c)
    # across sub-chunks, through the factors: one product each way
    dbo = jnp.where(earlier, db, 0.0)
    dxe = _dot(dbo, yk, _NN, _F32)
    dyk = _dot(dbo, xe, _TN, _F32).reshape(n, c // SUB, c, kdim)
    # inside them, pair by pair
    w = _dot(db, own, _NT, _F32)[..., None] * jnp.concatenate([d, d], axis=1)
    dx = jnp.sum(w * _sub(k)[:, None, :, :], axis=2)
    dy = _chunks_of(jnp.sum(w * x[:, :, None, :], axis=1), n)
    dx_all = dx + dxe * ex2
    dg_rows = x * dx + dxe * xe
    d_to_r = dyk * yk.reshape(dyk.shape)
    dg = (_chunks_of(dg_rows[:, :SUB] + dg_rows[:, SUB:], n) - k * dy
          - jnp.sum(d_to_r, axis=1))
    dk = dy + jnp.sum(dyk * ey.reshape(dyk.shape), axis=1)
    return (_chunks_of(dx_all[:, :SUB], n), _chunks_of(dx_all[:, SUB:], n), dk, dg,
            d_to_r.reshape(-1, c, kdim))


def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower [N, C, C] ``a``: the product ``(I +
    n)(I + n^2)(I + n^4)..`` of the nilpotent ``n = -a``. A step multiplies
    the running product and the power by the power in one product (their
    rows stacked), and leaves out the rows that are zero: ``n^(2^j)`` is zero
    on and above its 2^j-th diagonal, so are its first 2^j rows and those of
    the running product times it (from a multiple of 8)."""
    c = a.shape[-1]
    i = lax.broadcasted_iota(jnp.int32, a.shape, 1)
    j = lax.broadcasted_iota(jnp.int32, a.shape, 2)
    t, p = jnp.where(i == j, 1.0, 0.0) - a, _dot(a, a, _NN, _F32)
    steps = int(math.log2(c))
    for power in range(1, steps):
        skip = 2 ** power // 8 * 8
        below = lambda x: jnp.concatenate(  # noqa: E731
            [jnp.zeros((a.shape[0], skip, c), _F32), x], axis=1) if skip else x
        if power < steps - 1:
            both = _dot(jnp.concatenate([t[:, skip:], p[:, skip:]], axis=1), p, _NN, _F32)
            tp, p = both[:, :c - skip], below(both[:, c - skip:])
        else:
            tp = _dot(t[:, skip:], p, _NN, _F32)
        t = t + below(tp)
    return t


def _column(row):
    """[N, 1, n] -> [N, n, 1]."""
    n = row.shape[-1]
    i = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(i == j, row, 0.0), axis=-1, keepdims=True)


def _chunk_free(q, k, v, lc, beta, pos):
    """The state-free part of chunks. q, k, v, L [N, C, *] float32, beta
    [N, C, 1], pos [N, C, 1] int32 -> (A, M [N, C, C]; ``[beta k exp(G) |
    beta v]`` [N, C, K + V], which T turns into ``[W | U~]``; q exp(G) [N,
    C, K]; k exp(G_C - G) [N, C, K]; the state's decay over the chunk [N, K,
    1])."""
    same, strict, seen0, keep, carry = _masks(pos)
    kb = k * beta
    gc, to_end, gl, between = _exponents(lc, pos)
    a, m = _pair_sums(kb, q, k, _pair_factors(k, lc, between))
    eg = jnp.exp(gc) * seen0
    # (a row of an earlier document is masked, and kept finite)
    kd = k * jnp.exp(jnp.minimum(to_end, 0.0)) * keep
    return (jnp.where(strict, a, 0.0), jnp.where(same, m, 0.0),
            jnp.concatenate([kb * eg, beta * v], axis=-1), q * eg, kd,
            _column(jnp.exp(gl) * carry))


def _inverted(a, rhs):
    """(T, ``[W | U~]``) of chunks, from A and ``[beta k exp(G) | beta v]``."""
    t = _unit_lower_inverse(a)
    return t, _dot(t, rhs, _NN, _F32)


def _chain_step(st, wu, kd, decay, dtype):
    """The chain forward: given the state carried in [K, V], (U [C, V], the
    state after the chunk) — or of chunks at once, each from its own state."""
    kdim = st.shape[-2]
    u = wu[..., kdim:] - _dot(wu[..., :kdim], st, _NN, dtype)
    return u, st * decay + _dot(kd, u, _TN, dtype)


def _chunk_out(st, u, qg, m, dtype):
    return _dot(qg, st, _NN, dtype) + _dot(m, u, _NN, dtype)


def _out_cotangents(do, qg, m, dtype):
    """What o's cotangent gives the chain before the state is known: (its
    part of dU, its part of the d state carried in)."""
    return _dot(m, do, _TN, dtype), _dot(qg, do, _TN, dtype)


def _chain_back(dst, wu, kd, decay, du_out, dst_out, dtype):
    """The chain backward: given the cotangent of the state after the chunk,
    (dU, the cotangent of the state carried in)."""
    du = du_out + _dot(kd, dst, _NN, dtype)
    return du, dst * decay + dst_out - _dot(wu[..., :dst.shape[-2]], du, _TN, dtype)


def _chunk_grads(st, dst, u, du, do, t, q, k, v, lc, beta, pos, dtype):
    """Chunks' forward differentiated, their state-free parts recomputed: given
    each one's starting state, the cotangent of the state after it, U, dU
    (all of it), dO and T, all [N, ...] -> (dq, dk, dv, dL, d beta)."""
    c = q.shape[1]
    same, strict, seen0, keep, carry = _masks(pos)
    kb = k * beta
    gc, to_end, gl, between = _exponents(lc, pos)
    eg = jnp.exp(gc) * seen0
    kd_decay = jnp.exp(jnp.minimum(to_end, 0.0)) * keep
    # S_C = decay S0 + kd^T U
    dkd = _dot(u, dst, _NT, dtype)
    ones = jnp.ones(st.shape[:1] + (1, st.shape[2]), _F32)
    dgl = jnp.exp(gl) * carry * _dot(ones, st * dst, _NT, _F32)
    # O = qg S0 + M U
    dm = jnp.where(same, _dot(do, u, _NT, dtype), 0.0)
    # U = T Y, Y = beta v - kbg S0: dY = T^T dU, and dA = -T^T dU Y^T T^T = -dY U^T
    dy = _dot(t, du, _TN, dtype)
    da = jnp.where(strict, -_dot(dy, u, _NT, _F32), 0.0)
    # dO S0^T and dY S0^T in one product
    dqg_kbg = _dot(jnp.concatenate([do, dy], axis=1), st, _NT, dtype)
    dqg, dkbg = dqg_kbg[:, :c], -dqg_kbg[:, c:]
    dbeta = jnp.sum(dy * v, axis=-1, keepdims=True)
    # the decayed rows: d G and d (G_C - G)
    dgc = (dqg * q + dkbg * kb) * eg
    d_to_end = dkd * k * kd_decay
    # A and M
    dkb, dq, dk, dl, d_to_r = _pair_grads(da, dm, kb, q, k, _pair_factors(k, lc, between))
    dkb = dkb + dkbg * eg
    # through _exponents: G_C is G_C - G's first row; the sums over
    # sub-chunks back to their totals, and these to L's last rows
    first = lax.broadcasted_iota(jnp.int32, d_to_end.shape, 1) == 0
    pick, spread = _totals(pos)
    d_sums = jnp.concatenate(
        [dgc, d_to_end + jnp.where(first, dgl, 0.0),
         d_to_r.reshape(dgc.shape[0], -1, dgc.shape[-1])],
        axis=1)
    dl = dl + dgc - d_to_end + _dot(pick, _dot(spread, d_sums, _TN, _F32), _TN, _F32)
    return (dq + dqg * eg, dk + dkd * kd_decay + dkb * beta, beta * dy, dl,
            dbeta + jnp.sum(dkb * k, axis=-1, keepdims=True))


# ---------------------------------------------------------------------------
# the XLA path: the state-free parts of all chunks at once, the chains as
# scans, one (sequence, head) at a time
# ---------------------------------------------------------------------------


def _per_head(fn, shared):
    """vmap ``fn`` over heads (axis 1 of [B, H, ...] arrays; the argument
    ``shared``, the positions [B, T, 1], is every head's) and sequences."""
    n = fn.__code__.co_argcount
    inner = tuple(None if i == shared else 0 for i in range(n))
    return jax.vmap(jax.vmap(fn, in_axes=inner), in_axes=(0,) * n)


def _chunked(x, c):
    return x.reshape(x.shape[0] // c, c, *x.shape[1:])


def _xla_forward(q, k, v, lc, beta, pos, dtype):
    """[B, H, T, *] inputs (beta [B, H, T, 1], pos [B, T, 1]) -> (o [B, H, T,
    V], each chunk's starting state [B, H, N, K, V], the final state [B, H, K,
    V])."""

    def one(q, k, v, lc, beta, pos):
        a, m, rhs, qg, kd, decay = _chunk_free(
            *(_chunked(x, CHUNK) for x in (q, k, v, lc, beta, pos)))
        wu = _inverted(a, rhs)[1]

        def step(st, xs):
            u, st_new = _chain_step(st, *xs, dtype)
            return st_new, (u, st)

        st0 = jnp.zeros((k.shape[-1], v.shape[-1]), _F32)
        final, (u, starts) = lax.scan(step, st0, (wu, kd, decay))
        o = _chunk_out(starts, u, qg, m, dtype)
        return o.reshape(-1, v.shape[-1]), starts, final

    return _per_head(one, 5)(q, k, v, lc, beta, pos)


def _xla_backward(q, k, v, lc, beta, pos, starts, do, dtype):
    def one(q, k, v, lc, beta, pos, starts, do):
        xs = tuple(_chunked(x, CHUNK) for x in (q, k, v, lc, beta, pos))
        do = _chunked(do, CHUNK)
        a, m, rhs, qg, kd, decay = _chunk_free(*xs)
        t, wu = _inverted(a, rhs)
        u, _ = _chain_step(starts, wu, kd, decay, dtype)

        def step(dst, xs):
            du, dst_in = _chain_back(dst, *xs, dtype)
            return dst_in, (du, dst)

        xs_back = (wu, kd, decay) + _out_cotangents(do, qg, m, dtype)
        _, (du, dst) = lax.scan(step, jnp.zeros(starts.shape[1:], _F32), xs_back, reverse=True)
        grads = _chunk_grads(starts, dst, u, du, do, t, *xs, dtype)
        return tuple(g.reshape(-1, g.shape[-1]) for g in grads)

    return _per_head(one, 5)(q, k, v, lc, beta, pos, starts, do)


# ---------------------------------------------------------------------------
# the kernel path
# ---------------------------------------------------------------------------


def kernels_serve(key_dim: int, value_dim: int) -> bool:
    """The head sizes the Pallas kernels take (on a TPU); any length does,
    padded to whole chunks."""
    return key_dim % 128 == 0 and value_dim % 128 == 0


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=64 * 1024 * 1024)


def _rows(n):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(n * CHUNK, CHUNK), CHUNK)


def _group_rows(i, group):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(i * group * CHUNK, group * CHUNK), group * CHUNK)


def _group_inputs(refs, i, group):
    """The ``i``-th ``group`` chunks of a block, [group, C, *]: q, k, v, lc as
    float32, beta, pos."""
    rows = _group_rows(i, group)
    ins = tuple(r[rows, :].astype(_F32) for r in refs[:4]) + (refs[4][rows, :], refs[5][rows, :])
    return tuple(x.reshape(group, CHUNK, x.shape[-1]) for x in ins)


def _free_scratch(chunks, kd, vd):
    """VMEM for a block's state-free parts: A, then T; ``[W | U~]``; M; q
    exp(G); kd; the state's decay."""
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((chunks, CHUNK, CHUNK), _F32), pltpu.VMEM((chunks, CHUNK, kd + vd), _F32),
            pltpu.VMEM((chunks, CHUNK, CHUNK), _F32), pltpu.VMEM((chunks, CHUNK, kd), _F32),
            pltpu.VMEM((chunks, CHUNK, kd), _F32), pltpu.VMEM((chunks, kd, 1), _F32)]


def _block_free(refs, free, chunks, group):
    """Every chunk's state-free part into ``free``, ``group`` chunks at a
    time; the inverses and ``[W | U~]`` of the block's chunks in one batched
    chain of products."""
    from jax.experimental import pallas as pl

    t_s, wu_s, m_s, qg_s, kd_s, decay_s = free

    def chunks_of(i, carry):
        at = pl.ds(i * group, group)
        t_s[at], m_s[at], wu_s[at], qg_s[at], kd_s[at], decay_s[at] = _chunk_free(
            *_group_inputs(refs, i, group))
        return carry

    lax.fori_loop(0, chunks // group, chunks_of, 0)
    t_s[...], wu_s[...] = _inverted(t_s[...], wu_s[...])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, p_ref, o_ref, s_ref, f_ref, *free, dtype,
                block, group):
    from jax.experimental import pallas as pl

    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(c > 0)
    def _():
        s_ref[...] = f_ref[...]

    chunks = block // CHUNK
    _block_free((q_ref, k_ref, v_ref, g_ref, b_ref, p_ref), free, chunks, group)
    _, wu_s, m_s, qg_s, kd_s, decay_s = free

    def chain(n, st):
        u, st_new = _chain_step(st, wu_s[n], kd_s[n], decay_s[n], dtype)
        o_ref[_rows(n), :] = _chunk_out(st, u, qg_s[n], m_s[n], dtype).astype(o_ref.dtype)
        return st_new

    # f_ref's block is the same every step of a head: it carries the state
    f_ref[...] = lax.fori_loop(0, chunks, chain, s_ref[...])


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, p_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, sts, us, dsts, dus, *free,
                dtype, block, group):
    from jax.experimental import pallas as pl

    c = pl.program_id(2)
    chunks = block // CHUNK
    refs = (q_ref, k_ref, v_ref, g_ref, b_ref, p_ref)

    @pl.when(c == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    _block_free(refs, free, chunks, group)
    t_s, wu_s, m_s, qg_s, kd_s, decay_s = free

    # the chunks' starting states and U from the block's, forward
    def forward(n, st):
        sts[n] = st
        u, st = _chain_step(st, wu_s[n], kd_s[n], decay_s[n], dtype)
        us[n] = u
        return st

    lax.fori_loop(0, chunks, forward, s_ref[...])

    # dS and dU, chunks in reverse (o's cotangent's parts lie off the chain)
    def backward(m, dst):
        n = chunks - 1 - m
        do = do_ref[_rows(n), :].astype(_F32)
        du_out, dst_out = _out_cotangents(do, qg_s[n], m_s[n], dtype)
        dsts[n] = dst
        du, dst = _chain_back(dst, wu_s[n], kd_s[n], decay_s[n], du_out, dst_out, dtype)
        dus[n] = du
        return dst

    ds_ref[...] = lax.fori_loop(0, chunks, backward, ds_ref[...])

    # every chunk's gradients, group chunks at a time
    def grads(i, carry):
        at, rows = pl.ds(i * group, group), _group_rows(i, group)
        do = do_ref[rows, :].astype(_F32).reshape(group, CHUNK, -1)
        out = _chunk_grads(sts[at], dsts[at], us[at], dus[at], do, t_s[at],
                           *_group_inputs(refs, i, group), dtype)
        dq, dk, dv, dg, db = (x.reshape(group * CHUNK, x.shape[-1]) for x in out)
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[rows, :] = dv.astype(dv_ref.dtype)
        dg_ref[rows, :], db_ref[rows, :] = dg, db
        return carry

    lax.fori_loop(0, chunks // group, grads, 0)


def _specs(b, h, t, kd, vd, block, reverse):
    """Block specs over a (sequence, head, block) grid: [B, T, H * dim] rows
    of one head, beta [B, H, T, 1], positions [B, T, 1], states [B, H, NB, K,
    V]; the blocks in reverse for the backward kernel."""
    from jax.experimental import pallas as pl

    nb = t // block
    at = (lambda c: nb - 1 - c) if reverse else (lambda c: c)
    rows = lambda dim: pl.BlockSpec((None, block, dim), lambda i, j, c: (i, at(c), j))  # noqa: E731
    return dict(
        k=rows(kd), v=rows(vd),
        beta=pl.BlockSpec((None, None, block, 1), lambda i, j, c: (i, j, at(c), 0)),
        pos=pl.BlockSpec((None, block, 1), lambda i, j, c: (i, at(c), 0)),
        state=pl.BlockSpec((None, None, None, kd, vd), lambda i, j, c: (i, j, at(c), 0, 0)),
        final=pl.BlockSpec((None, None, kd, vd), lambda i, j, c: (i, j, 0, 0)),
    )


def _pallas_forward(q, k, v, lc, beta, pos, dtype, interpret):
    """[B, T, H, *] inputs (beta [B, T, H], pos [B, T]) -> (o [B, T, H, V],
    the state each block starts from [B, H, NB, K, V], the final state [B, H,
    K, V])."""
    from jax.experimental import pallas as pl

    b, t, h, kd = q.shape
    vd = v.shape[-1]
    block = math.gcd(t, BLOCK)
    group = math.gcd(block // CHUNK, GROUP)
    spec = _specs(b, h, t, kd, vd, block, False)
    flat = lambda x: x.reshape(b, t, -1)  # noqa: E731
    o, states, final = pl.pallas_call(
        functools.partial(_fwd_kernel, dtype=dtype, block=block, group=group),
        grid=(b, h, t // block),
        in_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"], spec["pos"]],
        out_specs=[spec["v"], spec["state"], spec["final"]],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * vd), q.dtype),
                   jax.ShapeDtypeStruct((b, h, t // block, kd, vd), _F32),
                   jax.ShapeDtypeStruct((b, h, kd, vd), _F32)],
        scratch_shapes=_free_scratch(block // CHUNK, kd, vd),
        compiler_params=None if interpret else _params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="kda_fwd",
    )(flat(q), flat(k), flat(v), flat(lc), beta.transpose(0, 2, 1)[..., None], pos[..., None])
    return o.reshape(b, t, h, vd), states, final


def _pallas_backward(q, k, v, lc, beta, pos, states, do, dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, kd = q.shape
    vd = v.shape[-1]
    block = math.gcd(t, BLOCK)
    group = math.gcd(block // CHUNK, GROUP)
    chunks = block // CHUNK
    spec = _specs(b, h, t, kd, vd, block, True)
    flat = lambda x: x.reshape(b, t, -1)  # noqa: E731
    dq, dk, dv, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, dtype=dtype, block=block, group=group),
        grid=(b, h, t // block),
        in_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"], spec["pos"],
                  spec["state"], spec["v"]],
        out_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"]],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * kd), q.dtype)] * 2
        + [jax.ShapeDtypeStruct((b, t, h * vd), q.dtype),
           jax.ShapeDtypeStruct((b, t, h * kd), _F32), jax.ShapeDtypeStruct((b, h, t, 1), _F32)],
        # dS carried between blocks; per chunk the starting state, U, the
        # cotangent of the state after it, dU; the state-free parts
        scratch_shapes=[pltpu.VMEM((kd, vd), _F32), pltpu.VMEM((chunks, kd, vd), _F32),
                        pltpu.VMEM((chunks, CHUNK, vd), _F32), pltpu.VMEM((chunks, kd, vd), _F32),
                        pltpu.VMEM((chunks, CHUNK, vd), _F32)]
        + _free_scratch(chunks, kd, vd),
        compiler_params=None if interpret else _params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="kda_bwd",
    )(flat(q), flat(k), flat(v), flat(lc), beta.transpose(0, 2, 1)[..., None], pos[..., None],
      states, flat(do))
    shape = lambda x, dim: x.reshape(b, t, h, dim)  # noqa: E731
    return shape(dq, kd), shape(dk, kd), shape(dv, vd), shape(dg, kd), db[..., 0].transpose(0, 2, 1)


# ---------------------------------------------------------------------------
# the differentiable core and the entry point
# ---------------------------------------------------------------------------


def _heads_first(x):
    return jnp.moveaxis(x, 2, 1)


def _forward(q, k, v, lc, beta, pos, path):
    """(o [B, T, H, V] in the inputs' dtype, the backward pass's states, the
    final state [B, H, K, V])."""
    dtype = q.dtype
    if path == "xla":
        o, starts, final = _xla_forward(
            *map(_heads_first, (q, k, v, lc)), _heads_first(beta[..., None]), pos[..., None], dtype)
        return jnp.moveaxis(o, 1, 2).astype(dtype), starts, final
    return _pallas_forward(q, k, v, lc, beta, pos, dtype, path == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kda_core(q, k, v, lc, beta, pos, path):
    return _forward(q, k, v, lc, beta, pos, path)[0]


def _core_fwd(q, k, v, lc, beta, pos, path):
    o, states, _ = _forward(q, k, v, lc, beta, pos, path)
    o, states = checkpoint_name(o, KDA_NAME), checkpoint_name(states, KDA_NAME)
    return o, (q, k, v, lc, beta, pos, states)


def _core_bwd(path, res, do):
    q, k, v, lc, beta, pos, states = res
    dtype = q.dtype
    with scopes.scope(SCOPE):
        if path == "xla":
            dq, dk, dv, dlc, dbeta = _xla_backward(
                *map(_heads_first, (q, k, v, lc)), _heads_first(beta[..., None]),
                pos[..., None], states, _heads_first(do.astype(_F32)), dtype)
            dq, dk, dv, dlc = (jnp.moveaxis(x, 1, 2) for x in (dq, dk, dv, dlc))
            dbeta = jnp.moveaxis(dbeta[..., 0], 1, 2)
        else:
            dq, dk, dv, dlc, dbeta = _pallas_backward(
                q, k, v, lc, beta, pos, states, do.astype(_F32), dtype, path == "interpret")
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dlc, dbeta, None)


_kda_core.defvjp(_core_fwd, _core_bwd)


def _running_sums(g, positions):
    """g [B, T, H, K] -> L: the running sums of g inside each sub-chunk and
    each document, the sum restarting at a sub-chunk's and a document's
    start (the kernels add whole sub-chunks' totals where an exponent spans
    them: ``_exponents``). No gradient reaches one document's g from
    another's — a masked product at the highest precision, whose gradient is
    exactly zero across a start (a plain running sum's would cancel there
    only to rounding)."""
    b, t, h, kd = g.shape
    pos = positions.reshape(b, t // SUB, SUB)
    i = jnp.arange(SUB)[:, None]
    j = jnp.arange(SUB)[None, :]
    within = ((i - j <= pos[..., :, None]) & (j <= i)).astype(_F32)
    sums = jnp.einsum("bnts,bnsx->bntx", within, g.reshape(b, t // SUB, SUB, h * kd),
                      precision=_HIGHEST)
    return sums.reshape(g.shape)


def _prepare(q, k, v, g, beta, positions, interpret):
    """Pad the sequence to whole chunks (tokens that leave the state as it is
    and read nothing back), take the running sums of g and choose the
    path."""
    from tensorflowdistributedlearning_tpu.ops import pallas_kernels

    t = q.shape[1]
    pad = -t % CHUNK
    if pad:
        widen = lambda x: jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))  # noqa: E731
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
        positions = jnp.concatenate(
            [positions, positions[:, -1:] + 1 + jnp.arange(pad, dtype=positions.dtype)], axis=1)
    lc = _running_sums(g.astype(_F32), positions)
    if interpret is None:
        serve = pallas_kernels.pallas_platform_ok() and kernels_serve(q.shape[3], v.shape[-1])
        path = "pallas" if serve else "xla"
    else:
        path = "interpret" if interpret else "pallas"
    return (q, k, v, lc, beta.astype(_F32), positions.astype(jnp.int32)), path, t


def kda(q, k, v, g, beta, positions, *, interpret=None):
    """q, k [B, T, H, K] and v [B, T, H, V] in the compute dtype (q scaled),
    g [B, T, H, K] float32 (log-decays, <= 0), beta [B, T, H] float32,
    positions [B, T] (0 at each document's first token) -> o [B, T, H, V] in
    the compute dtype (accumulated in float32). ``interpret``: None chooses
    by backend and shape; True or False forces the kernel path (interpreted
    on the CPU, for tests)."""
    with scopes.scope(SCOPE):
        args, path, t = _prepare(q, k, v, g, beta, positions, interpret)
        return _kda_core(*args, path)[:, :t]


def kda_with_state(q, k, v, g, beta, positions, *, interpret=None):
    """``kda`` and the state after the last token [B, H, K, V], undifferentiated
    (for tests)."""
    args, path, t = _prepare(q, k, v, g, beta, positions, interpret)
    o, _, final = _forward(*args, path)
    return o[:, :t], final
