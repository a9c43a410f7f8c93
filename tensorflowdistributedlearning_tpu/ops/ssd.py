"""The Mamba-2 state-space layer's scan (state-space duality, SSD) for the
decoder family (models/decoder.py, layer type ``mamba``), over packed
documents, forward and backward.

Per head ``h`` of ``P`` channels, with ``x_t`` in R^P, the step ``dt_t > 0``,
``A_h < 0``, and ``B_t, C_t`` in R^N shared by the heads of a group::

    H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t B_t^T        (H is [P, N])
    y_t = H_t C_t + D_h x_t

with ``H`` zero before every document's first token (``positions`` 0).

**The chunked form.** Over a chunk of ``Q = CHUNK`` (128) tokens that starts
from ``H0``, with ``c_i`` the running sum of ``dt A`` from the chunk's (or
the document's) start to token ``i``::

    y_i  = sum_{j <= i} exp(c_i - c_j) (C_i . B_j) dt_j x_j      (one document)
         + exp(c_i) H0 C_i                                        (no start before i)
         + D x_i
    H_Q  = exp(c_Q) H0 + sum_j exp(c_Q - c_j) dt_j x_j B_j^T

The first sum is ``(L o C B^T)(dt x)``, one ``[Q, Q]`` matrix a head; the
state's update is one ``[P, Q] x [Q, N]`` product a head. Everything but
``H0`` depends on the chunk's inputs alone (``_chunk_free``), and the chunks
of a sequence are linked by ``H_Q = decay H0 + S`` alone: a serial pass in
float32 over ``[H, P, N]`` states, elementwise (``_states``). The output's
part that reads ``H0`` is one batched product over every chunk afterwards.

**The backward pass** keeps the inputs and the chunks' starting states (and
the starting state's decay to each token and over each chunk, a number a
token or chunk and head). It
forms the state's gradient from every chunk's output at once, carries it in
reverse over the chunks (``_states_back``, elementwise again), and then each
chunk's gradients (``_chunk_grads``) with no dependence between chunks:
nothing ``[Q, Q]`` outlives the group of chunks that is being worked on.

**Exact under underflow.** Every decay is ``exp`` of a sum of ``dt A``
(<= 0) over the tokens between two positions of one document, never a
quotient of two decays, so a chunk whose summed decay underflows float32
gives the exact result (0 where it underflows, as the recurrence does). The
running sums restart at a document's start inside a chunk (a masked product
at the highest precision), so no gradient reaches one document's ``dt`` from
another's.

**Precision.** ``C B^T``, the products with ``x``, with ``B`` and ``C``, and
against the state take their operands in the inputs' dtype with float32
accumulation; ``dt``, ``A``, ``D``, the decays, the states and every
gradient but those of ``x``, ``B`` and ``C`` (written in the inputs' dtype)
are float32.

**Paths.** On a TPU, where a group's heads fill whole lane tiles (``kernels_serve``),
two Pallas kernels, ``ssd_fwd`` and ``ssd_bwd``: a grid step holds ``BLOCK``
tokens of one group of B and C and its heads (x and y rows of heads x P
lanes), forms ``C B^T`` once for the group, and loops over its chunks and,
inside a chunk, over the group's heads, each head's ``[Q, Q]`` decays and
masks formed in VMEM and its ``[P, N]`` state carried in VMEM from chunk to
chunk and from block to block; the forward writes the state each block starts
from. The backward kernel takes the blocks in reverse, rebuilds its chunks'
starting states from the block's, carries the state's gradient in reverse,
and writes dx, dB and dC (summed over the group's heads inside the grid step)
in the inputs' dtype and, by head and token, dt's gradient, the running sums'
and ``D``'s parts. The running sums ``c`` are formed in XLA before the kernels
(the masked product above), so their gradient reaches dt and A by it; every
rounding point is the XLA form's. Every loop over chunks or heads is a
``fori_loop``, so a kernel body is traced once whatever the length. Anything
else — the CPU, a layout the kernels cannot tile — runs the batched XLA form:
the chunks' work as batched products, ``GROUP`` chunks of every head at once
in a ``lax.map``, the passes over the chunks as scans.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from tensorflowdistributedlearning_tpu.obs import scopes
from tensorflowdistributedlearning_tpu.ops.kda import _NN, _NT, _TN, _dot, _params

# tokens a chunk (the published chunk_size of the family)
CHUNK = 128
# chunks whose [Q, Q] work stands at once: a batch axis of the products
GROUP = 16
# tokens a grid step of the kernels holds, and how often the forward writes
# the state
BLOCK = 512
SCOPE = "decoder/ssm/ssd"

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def _mm(spec, a, b, dtype):
    """``einsum`` with operands in ``dtype`` and float32 accumulation."""
    precision = _HIGHEST if jnp.dtype(dtype) == _F32 else None
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), precision=precision,
                      preferred_element_type=_F32)


def _masks(pos):
    """pos [Q] -> (same [Q, Q]: j <= i of one document, carry [Q]: no
    document starts at or before i inside the chunk)."""
    q = pos.shape[0]
    i = jnp.arange(q)[:, None]
    j = jnp.arange(q)[None, :]
    same = (j <= i) & (pos[:, None] >= i - j)
    return same, pos > jnp.arange(q)


def _running(a, same):
    """a [Q, H] -> c [Q, H]: the running sums from the document's (or the
    chunk's) start, a masked product at the highest precision."""
    return jnp.einsum("ij,jh->ih", same.astype(_F32), a, precision=_HIGHEST)


def _decays(c, same, carry):
    """(L [H, Q, Q] = exp(c_i - c_j) on a document's pairs, gin [Q, H] =
    exp(c_i) where the chunk's starting state reaches i, e [Q, H] = exp(c_Q -
    c_j) where j reaches the chunk's last token, decay [H])."""
    diff = c.T[:, :, None] - c.T[:, None, :]
    low = jnp.where(same[None], diff, -jnp.inf)
    last = c[-1]
    gin = jnp.where(carry[:, None], jnp.exp(jnp.minimum(c, 0.0)), 0.0)
    e = jnp.where(same[-1][:, None], jnp.exp(jnp.minimum(last[None] - c, 0.0)), 0.0)
    decay = jnp.where(carry[-1], jnp.exp(jnp.minimum(last, 0.0)), 0.0)
    return jnp.exp(low), gin, e, decay


def _by_group(x, g):
    """[Q, H, ...] -> [Q, G, H / G, ...]: head h is head h % (H / G) of group
    h // (H / G)."""
    return x.reshape(x.shape[:1] + (g, x.shape[1] // g) + x.shape[2:])


def _chunk_free(x, dt, b, c_, pos, a, dtype):
    """One chunk: x [Q, H, P], dt [Q, H], b, c_ [Q, G, N], pos [Q], a [H] ->
    (the chunk's output apart from its starting state [Q, H, P], the state it
    adds S [H, P, N], exp(c_i) where the starting state reaches [Q, H], the
    starting state's decay over the chunk [H])."""
    q, h = dt.shape
    g = b.shape[1]
    same, carry = _masks(pos)
    el, gin, e, decay = _decays(_running(dt * a, same), same, carry)
    cb = _mm("ign,jgn->gij", c_, b, dtype)
    m = el.reshape(g, h // g, q, q) * cb[:, None] * dt.T.reshape(g, h // g, 1, q)
    xg = _by_group(x, g)
    y = _mm("grij,jgrp->igrp", m, xg, dtype).reshape(x.shape)
    s = _mm("jgrp,jgn->grpn", xg * _by_group(e * dt, g)[..., None], b, dtype)
    return y, s.reshape((h,) + s.shape[2:]), gin, decay


def _chunk_grads(x, dt, b, c_, pos, dy, h0, dh1, a, d, dtype):
    """One chunk's gradients: (dx, ddt, db, dc, dA [H], dD [H]) from the
    output's gradient dy [Q, H, P], the starting state h0 [H, P, N] and the
    gradient of the state it leaves dh1 [H, P, N]."""
    q, h = dt.shape
    g = b.shape[1]
    r = h // g
    same, carry = _masks(pos)
    el, gin, e, decay = _decays(_running(dt * a, same), same, carry)
    cb = _mm("ign,jgn->gij", c_, b, dtype)
    el, dtj = el.reshape(g, r, q, q), dt.T.reshape(g, r, 1, q)
    xg, dyg = _by_group(x, g), _by_group(dy, g)
    h0g, dh1g = h0.reshape((g, r) + h0.shape[1:]), dh1.reshape((g, r) + dh1.shape[1:])
    # the pairs inside the chunk: y_i = sum_j m_ij x_j, m = L o C B^T o dt_j
    m = el * cb[:, None] * dtj  # [G, R, Q, Q]
    dm = _mm("igrp,jgrp->grij", dyg, xg, dtype)
    dx = _mm("grij,igrp->jgrp", m, dyg, dtype)
    dmm = dm * m  # the gradient of each pair's exponent c_i - c_j
    dcb = jnp.sum(dm * el * dtj, axis=1)
    ddt = jnp.sum(dm * el * cb[:, None], axis=2)  # [G, R, Q] by j
    dcs = jnp.sum(dmm, axis=3) - jnp.sum(dmm, axis=2)  # [G, R, Q] by position
    dc = _mm("gij,jgn->ign", dcb, b, dtype)
    db = _mm("gij,ign->jgn", dcb, c_, dtype)
    # what reads the starting state: exp(c_i) (C_i . h0)
    gg = _by_group(gin, g)  # [Q, G, R]
    inter = _mm("ign,grpn->igrp", c_, h0g, dtype)
    dcs = dcs + jnp.moveaxis(jnp.sum(dyg * inter, axis=-1) * gg, 0, -1)
    dc = dc + _mm("igrp,grpn->ign", dyg * gg[..., None], h0g, dtype)
    # the state the chunk leaves: decay h0 + sum_j w_j x_j B_j^T, w = e dt
    w = _by_group(e * dt, g)
    dsb = _mm("grpn,jgn->jgrp", dh1g, b, dtype)  # dS B_j
    dx = dx + dsb * w[..., None]
    db = db + _mm("jgrp,grpn->jgn", xg * w[..., None], dh1g, dtype)
    dw = jnp.sum(xg * dsb, axis=-1)  # [Q, G, R]
    ddt = ddt + jnp.moveaxis(dw * _by_group(e, g), 0, -1)
    dww = jnp.moveaxis(dw * w, 0, -1)  # the gradient of the exponent c_Q - c_j
    dlast = jnp.sum(dww, axis=-1) + jnp.sum(dh1g * h0g, axis=(-2, -1)) * decay.reshape(g, r)
    dcs = (dcs - dww).at[..., -1].add(dlast)
    # back through the running sums and dt * A
    dcs = dcs.reshape(h, q).T
    da = jnp.einsum("ij,ih->jh", same.astype(_F32), dcs, precision=_HIGHEST)
    ddt = ddt.reshape(h, q).T + da * a
    dx = dx.reshape(x.shape) + d[:, None] * dy
    return (dx, ddt, db, dc, jnp.sum(da * dt, axis=0),
            jnp.sum(dy * x.astype(_F32), axis=(0, 2)))


def _states(s, decay):
    """s [B, K, H, P, N], decay [B, K, H] -> (the state each chunk starts
    from [B, K, H, P, N], the state after the last)."""
    def step(h, xs):
        sk, dk = xs
        return dk[..., None, None] * h + sk, h

    final, h0 = lax.scan(step, jnp.zeros_like(s[:, 0]),
                         (jnp.moveaxis(s, 1, 0), jnp.moveaxis(decay, 1, 0)))
    return jnp.moveaxis(h0, 0, 1), final


def _states_back(dh0_out, decay):
    """The gradient of the state each chunk leaves, from the gradient each
    chunk's output gives its starting state (both [B, K, H, P, N])."""
    def step(dh, xs):
        dk_out, dk = xs
        return dk_out + dk[..., None, None] * dh, dh

    _, dh1 = lax.scan(step, jnp.zeros_like(dh0_out[:, 0]),
                      (jnp.moveaxis(dh0_out, 1, 0), jnp.moveaxis(decay, 1, 0)), reverse=True)
    return jnp.moveaxis(dh1, 0, 1)


def _by_chunk(fn, *xs):
    """``fn`` over [B, K, ...] chunk-major inputs, GROUP chunks at a time."""
    bk = xs[0].shape[:2]
    flat = [x.reshape((-1,) + x.shape[2:]) for x in xs]
    out = lax.map(lambda args: fn(*args), flat, batch_size=min(GROUP, flat[0].shape[0]))
    return jax.tree.map(lambda y: y.reshape(bk + y.shape[1:]), out)


def _chunked(x):
    b, t = x.shape[:2]
    return x.reshape((b, t // CHUNK, CHUNK) + x.shape[2:])


def _forward(x, dt, a, b, c_, d, pos):
    """(y [B, T, H, P] float32, what the backward pass keeps — the chunks'
    starting states, where each chunk's starting state reaches its tokens and
    its decay over the chunk — and the state after the last token)."""
    dtype = x.dtype
    xc, dtc, bc, cc, pc = map(_chunked, (x, dt, b, c_, pos))
    y, s, gin, decay = _by_chunk(
        lambda *z: _chunk_free(*z, a, dtype), xc, dtc, bc, cc, pc)
    h0, final = _states(s, decay)
    g = b.shape[2]
    h0g = h0.reshape(h0.shape[:2] + (g, -1) + h0.shape[3:])
    inter = _mm("bkign,bkgrpn->bkigrp", cc, h0g, dtype)
    y = y + inter.reshape(y.shape) * gin[..., None] + d[:, None] * xc.astype(_F32)
    return y.reshape(x.shape), (h0, gin, decay), final


@jax.custom_vjp
def _ssd_core(x, dt, a, b, c_, d, pos):
    return _forward(x, dt, a, b, c_, d, pos)[0]


def _core_fwd(x, dt, a, b, c_, d, pos):
    y, kept, _ = _forward(x, dt, a, b, c_, d, pos)
    return y, (x, dt, a, b, c_, d, pos, kept)


def _core_bwd(res, dy):
    x, dt, a, b, c_, d, pos, (h0, gin, decay) = res
    dtype = x.dtype
    with scopes.scope(SCOPE):
        xc, dtc, bc, cc, pc, dyc = map(_chunked, (x, dt, b, c_, pos, dy))
        g = b.shape[2]
        # the gradient each chunk's output gives its starting state
        grouped = (dyc * gin[..., None]).reshape(dyc.shape[:3] + (g, -1) + dyc.shape[4:])
        dh0 = _mm("bkigrp,bkign->bkgrpn", grouped, cc, dtype)
        dh1 = _states_back(dh0.reshape(h0.shape), decay)
        dx, ddt, db, dc, da, dd = _by_chunk(
            lambda *z: _chunk_grads(*z, a, d, dtype), xc, dtc, bc, cc, pc, dyc, h0, dh1)
    return (dx.reshape(x.shape).astype(x.dtype), ddt.reshape(dt.shape),
            jnp.sum(da, axis=(0, 1)), db.reshape(b.shape).astype(b.dtype),
            dc.reshape(c_.shape).astype(c_.dtype), jnp.sum(dd, axis=(0, 1)), None)


_ssd_core.defvjp(_core_fwd, _core_bwd)


# ---------------------------------------------------------------------------
# the kernel path
# ---------------------------------------------------------------------------


def kernels_serve(group_heads: int, head_dim: int, state_dim: int) -> bool:
    """The layouts the Pallas kernels take (on a TPU): a group's heads fill
    whole lane tiles and whole sublane tiles (x's rows of ``group_heads x
    head_dim`` lanes, a head's ``[head_dim, Q]`` block in bfloat16), and B and
    C fill whole lane tiles. Any length does, padded to whole chunks."""
    return (group_heads % 8 == 0 and head_dim % 16 == 0
            and group_heads * head_dim % 128 == 0 and state_dim % 128 == 0)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _eye(q):
    return _iota((q, q), 0) == _iota((q, q), 1)


def _lane(row, k):
    """Entry ``k`` of rows [..., 1, Q] as [..., 1, 1]."""
    return jnp.sum(jnp.where(_iota(row.shape, row.ndim - 1) == k, row, 0.0), axis=-1,
                   keepdims=True)


def _column(row):
    """Rows [..., 1, Q] -> columns [..., Q, 1] (a masked sum over lanes:
    Mosaic moves no row of lanes into sublanes)."""
    return jnp.sum(jnp.where(_eye(row.shape[-1]), row, 0.0), axis=-1, keepdims=True)


def _row_of(col):
    """Columns [..., Q, 1] -> rows [..., 1, Q]."""
    return jnp.sum(jnp.where(_eye(col.shape[-2]), col, 0.0), axis=-2, keepdims=True)


def _chunk_masks(pos_col, pos_row):
    """A chunk's positions [Q, 1] and [1, Q] -> (same [Q, Q]: j <= i of one
    document; carry [1, Q] by i: no document starts at or before i inside the
    chunk; tail [1, Q] by j: j reaches the chunk's last token; through [1, 1]:
    the starting state reaches it)."""
    q = pos_row.shape[-1]
    i, j = _iota((q, q), 0), _iota((q, q), 1)
    same = (j <= i) & (pos_col >= i - j)
    carry = pos_row > _iota((1, q), 1)
    tail = jnp.sum(jnp.where(i == q - 1, same.astype(_F32), 0.0), axis=0, keepdims=True) > 0
    return same, carry, tail, _lane(carry.astype(_F32), q - 1) > 0


def _leaving(c, tail, through):
    """The heads' decays of what the chunk leaves, from their running sums
    [R, 1, Q]: (e [R, 1, Q] = exp(c_Q - c_j) where j reaches the last token,
    the starting state's decay over the chunk [R, 1, 1])."""
    last = _lane(c, c.shape[-1] - 1)
    e = jnp.where(tail, jnp.exp(jnp.minimum(last - c, 0.0)), 0.0)
    return e, jnp.where(through, jnp.exp(jnp.minimum(last, 0.0)), 0.0)


def _head_decays(c, same, carry):
    """The heads' (L [R, Q, Q] = exp(c_i - c_j) on a document's pairs, gin
    [R, 1, Q] = exp(c_i) where the starting state reaches i)."""
    el = jnp.exp(jnp.where(same, _column(c) - c, -jnp.inf))
    return el, jnp.where(carry, jnp.exp(jnp.minimum(c, 0.0)), 0.0)


def _rows(n):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(n * CHUNK, CHUNK), CHUNK)


def _heads_of(v, heads):
    """A chunk's rows [Q, R * P] -> [R, P, Q]: each head's block with its
    channels in sublanes, the heads a batch axis."""
    return v.T.reshape(heads, -1, v.shape[0])


def _flat(v):
    """[R, P, X] -> [R * P, X]: the group's heads as one product's rows."""
    return v.reshape(-1, v.shape[-1])


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cs_ref, pc_ref, pr_ref, d_ref, y_ref, s_ref, f_ref,
                *, dtype):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        f_ref[...] = jnp.zeros_like(f_ref)

    # the state this block starts from; f_ref's block is the same every step
    # of a group: it carries the state [R, P, N]
    s_ref[...] = f_ref[...]
    d = d_ref[...]
    heads = f_ref.shape[0]

    def chunk(n, carry):
        rows = _rows(n)
        xt = _heads_of(x_ref[rows, :], heads)
        bm, cm = b_ref[rows, :], c_ref[rows, :]
        same, carry_in, tail, through = _chunk_masks(pc_ref[rows, :], pr_ref[n])
        c, dt = cs_ref[n], dt_ref[n]
        el, gin = _head_decays(c, same, carry_in)
        e, decay = _leaving(c, tail, through)
        h = f_ref[...]
        xf = xt.astype(_F32)
        # y^T = x^T m^T + (H C^T) gin + D x^T, m = L o C B^T o dt_j
        m = el * _dot(cm, bm, _NT, dtype) * dt
        yt = (_dot(xt, m, _NT, dtype) + _dot(_flat(h), cm, _NT, dtype).reshape(xt.shape) * gin
              + d * xf)
        y_ref[rows, :] = _flat(yt).T
        f_ref[...] = decay * h + _dot(_flat(xf * (e * dt)), bm, _NN, dtype).reshape(h.shape)
        return carry

    lax.fori_loop(0, cs_ref.shape[0], chunk, 0)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cs_ref, pc_ref, pr_ref, d_ref, s_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcs_ref, dd_ref, dh_s, hs, *, dtype):
    from jax.experimental import pallas as pl

    heads, chunks = dh_s.shape[0], cs_ref.shape[0]
    q = CHUNK

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_s[...] = jnp.zeros_like(dh_s)

    d = d_ref[...]

    # the chunks' starting states from the block's
    hs[0] = s_ref[...]

    def forward(n, carry):
        rows = _rows(n)
        _, _, tail, through = _chunk_masks(pc_ref[rows, :], pr_ref[n])
        e, decay = _leaving(cs_ref[n], tail, through)
        xw = _heads_of(x_ref[rows, :], heads).astype(_F32) * (e * dt_ref[n])
        hs[n + 1] = decay * hs[n] + _dot(_flat(xw), b_ref[rows, :], _NN, dtype).reshape(
            dh_s.shape)
        return carry

    lax.fori_loop(0, chunks - 1, forward, 0)

    # chunks in reverse, dH (the gradient of the state a chunk leaves) carried
    def backward(k, carry):
        n = chunks - 1 - k
        rows = _rows(n)
        xt, dyt = _heads_of(x_ref[rows, :], heads), _heads_of(dy_ref[rows, :], heads)
        bm, cm = b_ref[rows, :], c_ref[rows, :]
        cb = _dot(cm, bm, _NT, dtype)
        same, carry_in, tail, through = _chunk_masks(pc_ref[rows, :], pr_ref[n])
        c, dt = cs_ref[n], dt_ref[n]
        el, gin = _head_decays(c, same, carry_in)
        e, decay = _leaving(c, tail, through)
        xf, h0, dh1 = xt.astype(_F32), hs[n], dh_s[...]
        # the pairs inside the chunk: y_i = sum_j m_ij x_j, m = L o C B^T o dt_j
        m = el * cb * dt
        dm = _dot(dyt, xt, _TN, dtype)  # [R, Q_i, Q_j]
        dmm = dm * m  # the gradient of each pair's exponent c_i - c_j
        dml = dm * el
        dcb = jnp.sum(dml * dt, axis=0)
        ddt = jnp.sum(dml * cb, axis=1, keepdims=True)
        dcs = _row_of(jnp.sum(dmm, axis=2, keepdims=True)) - jnp.sum(dmm, axis=1, keepdims=True)
        # what reads the starting state: exp(c_i) (C_i . h0)
        inter = _dot(_flat(h0), cm, _NT, dtype).reshape(xt.shape)  # [R, P, Q_i]
        dcs = dcs + gin * jnp.sum(dyt * inter, axis=1, keepdims=True)
        dyg = _flat(dyt * gin)
        dcm = _dot(dyg, _flat(h0), _TN, dtype)
        # the state the chunk leaves: decay h0 + sum_j w_j x_j B_j^T, w = e dt
        w = e * dt
        dsb = _dot(_flat(dh1), bm, _NT, dtype).reshape(xt.shape)  # [R, P, Q_j]
        dxt = _dot(dyt, m, _NN, dtype) + dsb * w + d * dyt
        dbm = _dot(_flat(xf * w), _flat(dh1), _TN, dtype)
        dw = jnp.sum(xf * dsb, axis=1, keepdims=True)
        ddt = ddt + dw * e
        dww = dw * w  # the gradient of the exponent c_Q - c_j
        dlast = jnp.sum(dww, axis=2, keepdims=True) + decay * jnp.sum(
            jnp.sum(dh1 * h0, axis=2, keepdims=True), axis=1, keepdims=True)
        dcs = dcs - dww + jnp.where(_iota(dww.shape, 2) == q - 1, dlast, 0.0)
        dh_s[...] = decay * dh1 + _dot(dyg, cm, _NN, dtype).reshape(dh1.shape)
        dx_ref[rows, :] = _flat(dxt).T.astype(dx_ref.dtype)
        dc_ref[rows, :] = (_dot(dcb, bm, _NN, dtype) + dcm).astype(dc_ref.dtype)
        db_ref[rows, :] = (_dot(dcb, cm, _TN, dtype) + dbm).astype(db_ref.dtype)
        ddt_ref[n], dcs_ref[n], dd_ref[n] = ddt, dcs, jnp.sum(dyt * xf, axis=1, keepdims=True)
        return carry

    lax.fori_loop(0, chunks, backward, 0)


def _specs(h, g, p, n, block, nb, reverse):
    """Block specs over a (sequence, group, block) grid: [B, T, H * P] rows of
    one group's heads, [B, T, G * N] rows of its B or C, [B, K, H, 1, Q]
    values by chunk, head and token, positions [B, T, 1] and [B, K, 1, Q], D
    [H, 1, Q], states [B, H, NB, P, N]; the blocks in reverse for the backward
    kernel."""
    from jax.experimental import pallas as pl

    r, chunks = h // g, block // CHUNK
    at = (lambda c: nb - 1 - c) if reverse else (lambda c: c)
    return dict(
        x=pl.BlockSpec((None, block, r * p), lambda i, j, c: (i, at(c), j)),
        bc=pl.BlockSpec((None, block, n), lambda i, j, c: (i, at(c), j)),
        heads=pl.BlockSpec((None, chunks, r, 1, CHUNK), lambda i, j, c: (i, at(c), j, 0, 0)),
        pos_col=pl.BlockSpec((None, block, 1), lambda i, j, c: (i, at(c), 0)),
        pos_row=pl.BlockSpec((None, chunks, 1, CHUNK), lambda i, j, c: (i, at(c), 0, 0)),
        d=pl.BlockSpec((r, 1, CHUNK), lambda i, j, c: (j, 0, 0)),
        state=pl.BlockSpec((None, r, None, p, n), lambda i, j, c: (i, j, at(c), 0, 0)),
        final=pl.BlockSpec((None, r, p, n), lambda i, j, c: (i, j, 0, 0)),
    )


def _kernel_inputs(x, dt, cs, b, c_, d, pos):
    """The kernels' layouts: x, B, C as rows, dt and the running sums as [B,
    K, H, 1, Q], the positions as a column and by chunk, D over a chunk's
    lanes."""
    bsz, t, h = dt.shape
    k = t // CHUNK
    by_chunk = lambda v: v.reshape(bsz, k, CHUNK, h, 1).transpose(0, 1, 3, 4, 2)  # noqa: E731
    flat = lambda v: v.reshape(bsz, t, -1)  # noqa: E731
    return (flat(x), flat(b), flat(c_), by_chunk(dt), by_chunk(cs), pos[..., None],
            pos.reshape(bsz, k, 1, CHUNK), jnp.broadcast_to(d[:, None, None], (h, 1, CHUNK)))


def _grid(x, b):
    _, t, h, p = x.shape
    g, n = b.shape[2:]
    block = math.gcd(t, BLOCK)
    return t, h, g, p, n, block, t // block


def _pallas_forward(x, dt, cs, b, c_, d, pos, interpret):
    """[B, T, ...] inputs -> (y [B, T, H, P] float32, the state each block
    starts from [B, H, NB, P, N], the state after the last token [B, H, P,
    N])."""
    from jax.experimental import pallas as pl

    bsz = x.shape[0]
    t, h, g, p, n, block, nb = _grid(x, b)
    spec = _specs(h, g, p, n, block, nb, False)
    y, states, final = pl.pallas_call(
        functools.partial(_fwd_kernel, dtype=x.dtype),
        grid=(bsz, g, nb),
        in_specs=[spec["x"], spec["bc"], spec["bc"], spec["heads"], spec["heads"],
                  spec["pos_col"], spec["pos_row"], spec["d"]],
        out_specs=[spec["x"], spec["state"], spec["final"]],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, h * p), _F32),
                   jax.ShapeDtypeStruct((bsz, h, nb, p, n), _F32),
                   jax.ShapeDtypeStruct((bsz, h, p, n), _F32)],
        compiler_params=None if interpret else _params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="ssd_fwd",
    )(*_kernel_inputs(x, dt, cs, b, c_, d, pos))
    return y.reshape(x.shape), states, final


def _pallas_backward(x, dt, cs, b, c_, d, pos, states, dy, interpret):
    """(dx, ddt, the running sums' gradient, dB, dC, dD)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz = x.shape[0]
    t, h, g, p, n, block, nb = _grid(x, b)
    spec = _specs(h, g, p, n, block, nb, True)
    r, chunks = h // g, block // CHUNK
    by_head = jax.ShapeDtypeStruct((bsz, t // CHUNK, h, 1, CHUNK), _F32)
    dx, db, dc, ddt, dcs, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, dtype=x.dtype),
        grid=(bsz, g, nb),
        in_specs=[spec["x"], spec["bc"], spec["bc"], spec["heads"], spec["heads"],
                  spec["pos_col"], spec["pos_row"], spec["d"], spec["state"], spec["x"]],
        out_specs=[spec["x"], spec["bc"], spec["bc"], spec["heads"], spec["heads"], spec["heads"]],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, h * p), x.dtype),
                   jax.ShapeDtypeStruct((bsz, t, g * n), b.dtype),
                   jax.ShapeDtypeStruct((bsz, t, g * n), c_.dtype), by_head, by_head, by_head],
        # dH carried between blocks; the chunks' starting states
        scratch_shapes=[pltpu.VMEM((r, p, n), _F32), pltpu.VMEM((chunks, r, p, n), _F32)],
        compiler_params=None if interpret else _params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="ssd_bwd",
    )(*_kernel_inputs(x, dt, cs, b, c_, d, pos), states, dy.reshape(bsz, t, -1))
    by_token = lambda v: v[:, :, :, 0].transpose(0, 1, 3, 2).reshape(dt.shape)  # noqa: E731
    return (dx.reshape(x.shape), by_token(ddt), by_token(dcs), db.reshape(b.shape),
            dc.reshape(c_.shape), jnp.sum(dd, axis=(0, 1, 3, 4)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _kernel_core(x, dt, cs, b, c_, d, pos, interpret):
    return _pallas_forward(x, dt, cs, b, c_, d, pos, interpret)[0]


def _kernel_fwd(x, dt, cs, b, c_, d, pos, interpret):
    y, states, _ = _pallas_forward(x, dt, cs, b, c_, d, pos, interpret)
    return y, (x, dt, cs, b, c_, d, pos, states)


def _kernel_bwd(interpret, res, dy):
    with scopes.scope(SCOPE):
        return _pallas_backward(*res, dy, interpret) + (None,)


_kernel_core.defvjp(_kernel_fwd, _kernel_bwd)


# ---------------------------------------------------------------------------
# the entry points
# ---------------------------------------------------------------------------


def _running_sums(a, positions):
    """a [B, T, H] -> c: ``_running`` chunk by chunk (restarting at a chunk's
    and a document's start), the kernels' exponents."""
    run = jax.vmap(jax.vmap(lambda v, p: _running(v, _masks(p)[0])))
    return run(_chunked(a), _chunked(positions)).reshape(a.shape)


def _prepare(x, dt, b, c_, positions, interpret):
    """Pad the sequence to whole chunks with tokens that add nothing to the
    state and read nothing back, and choose the path."""
    from tensorflowdistributedlearning_tpu.ops import pallas_kernels

    t = x.shape[1]
    pad = -t % CHUNK
    if pad:
        widen = lambda z: jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))  # noqa: E731
        x, dt, b, c_ = map(widen, (x, dt, b, c_))
        positions = jnp.concatenate(
            [positions, positions[:, -1:] + 1 + jnp.arange(pad, dtype=positions.dtype)], axis=1)
    if interpret is None:
        serve = pallas_kernels.pallas_platform_ok() and kernels_serve(
            x.shape[2] // b.shape[2], x.shape[3], b.shape[3])
        path = "pallas" if serve else "xla"
    else:
        path = "interpret" if interpret else "pallas"
    return (x, dt.astype(_F32), b, c_, positions.astype(jnp.int32)), path, t


def ssd(x, dt, a, b, c, d, positions, *, interpret=None):
    """x [B, T, H, P], b and c [B, T, G, N] in the compute dtype (G divides
    H: head h reads group h // (H / G)), dt [B, T, H] float32 (> 0), a [H]
    float32 (< 0), d [H] float32, positions [B, T] (0 at each document's
    first token) -> y [B, T, H, P] float32. ``interpret``: None chooses by
    backend and shape; True or False forces the kernel path (interpreted on
    the CPU, for tests)."""
    with scopes.scope(SCOPE):
        (x, dt, b, c, positions), path, t = _prepare(x, dt, b, c, positions, interpret)
        a, d = a.astype(_F32), d.astype(_F32)
        if path == "xla":
            return _ssd_core(x, dt, a, b, c, d, positions)[:, :t]
        cs = _running_sums(dt * a, positions)
        return _kernel_core(x, dt, cs, b, c, d, positions, path == "interpret")[:, :t]


def ssd_with_state(x, dt, a, b, c, d, positions, *, interpret=None):
    """``ssd`` and the state after the last token [B, H, P, N], undifferentiated
    (for tests)."""
    (x, dt, b, c, positions), path, t = _prepare(x, dt, b, c, positions, interpret)
    a, d = a.astype(_F32), d.astype(_F32)
    if path == "xla":
        y, _, final = _forward(x, dt, a, b, c, d, positions)
    else:
        y, _, final = _pallas_forward(x, dt, _running_sums(dt * a, positions), b, c, d, positions,
                                      path == "interpret")
    return y[:, :t], final
