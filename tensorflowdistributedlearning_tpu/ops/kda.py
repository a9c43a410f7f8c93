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
of ``C = CHUNK`` tokens that starts from ``S0``, with ``G`` the chunk's running
sum of ``g`` (restarting at a document's start): ``D[t, s] = exp(G_t - G_s)``
(a vector over the channels, s <= t), ``A[t, s] = beta_t sum_c k_t D[t, s]
k_s`` below the diagonal, ``T = (I + A)^-1``, ``U = T (beta v - (beta k
exp(G)) S0)``, ``O = (q exp(G)) S0 + M U`` with ``M[t, s] = sum_c q_t D[t, s]
k_s`` on and below the diagonal, and ``S_C = Diag(exp(G_C)) S0 + (k exp(G_C -
G))^T U``. A document start inside the chunk
masks the pairs that cross it and the rows that would read ``S0`` or carry
into ``S_C``. Every decay is formed as ``exp`` of a difference that is <= 0 —
``D`` pair by pair, never ``exp(G_t) exp(-G_s)`` — so a chunk whose summed
log-decay underflows float32 gives the exact result (0 where it underflows,
as the recurrence does). ``T`` is the product ``(I - A)(I + A^2)(I + A^4)..``
(``A`` is nilpotent), in float32 at the highest precision; the other products
take their operands in the inputs' dtype with float32 accumulation, and the
output and the gradients of q, k and v are written in that dtype. The
backward pass is the same chunk differentiated by hand (``_chunk_backward``),
chunks in reverse, from each chunk's starting state.

**Paths.** On a TPU, with K and V multiples of 128, two Pallas kernels
(``kda_fwd``, ``kda_bwd``): a grid step holds ``BLOCK`` tokens of one head,
runs their chunks in order with the state in VMEM and writes the state each
block starts from; the backward kernel takes the blocks in reverse, rebuilds
the chunks' starting states from the block's and carries ``dS`` between
blocks. Anything else runs the same chunk functions in XLA (a scan over
chunks; the custom backward pass as well). The forward's output and the
blocks' states carry ``KDA_NAME``: a recomputed decoder layer keeps them
(models/decoder.py:REMAT_POLICY), so its backward pass does not run the
forward kernel again.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tensorflowdistributedlearning_tpu.obs import scopes

# tokens a chunk: the pairwise decays are [CHUNK, CHUNK, K] (a power of two)
CHUNK = 16
# tokens a grid step of the kernels holds, and how often they write the state
BLOCK = 256
# what the forward writes that the backward reads: the output and the states
KDA_NAME = "kda_residual"
SCOPE = "decoder/attn_linear/kda"

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims, dtype):
    """A product with operands in ``dtype`` and float32 accumulation; float32
    operands at the highest precision."""
    precision = _HIGHEST if dtype == _F32 else None
    return lax.dot_general(a.astype(dtype), b.astype(dtype), dims, precision=precision,
                           preferred_element_type=_F32)


def _masks(pos):
    """pos [C, 1] (the tokens' positions in their documents) -> (t sees s
    [C, C], the same below the diagonal, rows that read the state carried in
    [C, 1], rows in the last token's document [C, 1], whether the state
    carried in reaches the chunk's end [1, 1]) — the last three as 0/1
    floats."""
    c = pos.shape[0]
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = (i - j <= pos) & (j <= i)
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    last = pos[c - 1:, :]
    return (same, same & (j < i), (pos > row).astype(_F32),
            (row >= (c - 1) - last).astype(_F32), (last >= c).astype(_F32))


def _decay(gc):
    """gc [C, K] -> exp(G_t - G_s) [C, C, K] for s <= t (1 above the diagonal,
    where the caller masks)."""
    return jnp.exp(jnp.minimum(gc[:, None, :] - gc[None, :, :], 0.0))


def _pair_sum(x, y, d):
    """sum_c x[t, c] y[s, c] d[t, s, c] -> [C, C]."""
    return jnp.sum(x[:, None, :] * y[None, :, :] * d, axis=-1)


def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower [C, C] ``a``."""
    c = a.shape[0]
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    n = -a
    t, p = jnp.where(i == j, 1.0, 0.0) + n, n
    for _ in range(int(math.log2(c)) - 1):
        p = _dot(p, p, _NN, _F32)
        t = t + _dot(t, p, _NN, _F32)
    return t


def _chunk_parts(st, q, k, v, gc, beta, pos, dtype):
    """What a chunk's forward and backward share. st [V, K] is the state
    carried in, transposed; q, k, gc [C, K], v [C, V] float32; beta [C, 1];
    pos [C, 1] int32."""
    same, strict, seen0, keep, carry = _masks(pos)
    d = _decay(gc)
    kb = k * beta
    t = _unit_lower_inverse(jnp.where(strict, _pair_sum(kb, k, d), 0.0))
    eg = jnp.exp(gc)
    kbg = kb * eg * seen0
    y = beta * v - _dot(kbg, st, _NT, dtype)
    u = _dot(t, y, _NN, dtype)
    gl = gc[-1:, :]
    # (a row of an earlier document has its own running sum: masked, and
    # kept finite)
    kd_decay = jnp.exp(jnp.minimum(gl - gc, 0.0)) * keep
    return dict(same=same, strict=strict, seen0=seen0, carry=carry, d=d, kb=kb, t=t, eg=eg,
                kbg=kbg, y=y, u=u, egl=jnp.exp(gl), kd_decay=kd_decay, kd=k * kd_decay)


def _chunk_forward(st, q, k, v, gc, beta, pos, dtype, want_out=True):
    """(o [C, V], the state after the chunk [V, K]); the state alone where
    ``want_out`` is False."""
    z = _chunk_parts(st, q, k, v, gc, beta, pos, dtype)
    st_new = st * (z["egl"] * z["carry"]) + _dot(z["u"], z["kd"], _TN, dtype)
    if not want_out:
        return st_new
    m = jnp.where(z["same"], _pair_sum(q, k, z["d"]), 0.0)
    qg = q * z["eg"] * z["seen0"]
    o = _dot(qg, st, _NT, dtype) + _dot(m, z["u"], _NN, dtype)
    return o, st_new


def _bilinear_grads(db, x, y, d):
    """Of ``B[t, s] = sum_c x[t, c] y[s, c] d[t, s, c]`` with ``d = exp(G_t -
    G_s)``, given dB: (dx, dy, dG)."""
    w = db[:, :, None] * d
    dx = jnp.sum(w * y[None, :, :], axis=1)
    dy = jnp.sum(w * x[:, None, :], axis=0)
    return dx, dy, x * dx - y * dy


def _chunk_backward(st, dst_new, do, q, k, v, gc, beta, pos, dtype):
    """The chunk's forward differentiated: given the state carried in, the
    cotangents of the state after the chunk and of o, -> (d state carried
    in, dq, dk, dv, d gc, d beta)."""
    z = _chunk_parts(st, q, k, v, gc, beta, pos, dtype)
    c = q.shape[0]
    m = jnp.where(z["same"], _pair_sum(q, k, z["d"]), 0.0)
    qg = q * z["eg"] * z["seen0"]
    # S_C = carry exp(G_C) S0 + kd^T U
    du = _dot(z["kd"], dst_new, _NT, dtype)
    dkd = _dot(z["u"], dst_new, _NN, dtype)
    dst = dst_new * (z["egl"] * z["carry"])
    dgl = z["egl"] * z["carry"] * jnp.sum(st * dst_new, axis=0, keepdims=True)
    # O = qg S0 + M U
    du = du + _dot(m, do, _TN, dtype)
    dm = jnp.where(z["same"], _dot(do, z["u"], _NT, dtype), 0.0)
    dqg = _dot(do, st, _NN, dtype)
    dst = dst + _dot(do, qg, _TN, dtype)
    # U = T Y, Y = beta v - kbg S0
    dt = _dot(du, z["y"], _NT, dtype)
    dy = _dot(z["t"], du, _TN, dtype)
    dbeta = jnp.sum(dy * v, axis=-1, keepdims=True)
    dv = beta * dy
    dkbg = -_dot(dy, st, _NN, dtype)
    dst = dst - _dot(dy, z["kbg"], _TN, dtype)
    # T = (I + A)^-1
    da = jnp.where(z["strict"], -_dot(_dot(z["t"], dt, _TN, _F32), z["t"], _NT, _F32), 0.0)
    # the decayed rows
    dk = dkd * z["kd_decay"]
    tmp = dkd * z["kd"]
    dgl = dgl + jnp.sum(tmp, axis=0, keepdims=True)
    dq = dqg * z["eg"] * z["seen0"]
    dkb = dkbg * z["eg"] * z["seen0"]
    dgc = dqg * qg + dkbg * z["kbg"] - tmp
    # A and M, pair by pair
    dx, dyk, dga = _bilinear_grads(da, z["kb"], k, z["d"])
    dxm, dym, dgm = _bilinear_grads(dm, q, k, z["d"])
    dkb = dkb + dx
    dq = dq + dxm
    dk = dk + dyk + dym + dkb * beta
    dbeta = dbeta + jnp.sum(dkb * k, axis=-1, keepdims=True)
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    dgc = dgc + dga + dgm + (row == c - 1).astype(_F32) * dgl
    return dst, dq, dk, dv, dgc, dbeta


# ---------------------------------------------------------------------------
# the XLA path: a scan over chunks, one (sequence, head) at a time
# ---------------------------------------------------------------------------


def _per_head(fn, shared):
    """vmap ``fn`` over heads (axis 1 of [B, H, ...] arrays; the argument
    ``shared``, the positions [B, T, 1], is every head's) and sequences."""
    n = fn.__code__.co_argcount
    inner = tuple(None if i == shared else 0 for i in range(n))
    return jax.vmap(jax.vmap(fn, in_axes=inner), in_axes=(0,) * n)


def _chunked(x, c):
    return x.reshape(x.shape[0] // c, c, *x.shape[1:])


def _xla_forward(q, k, v, gc, beta, pos, dtype):
    """[B, H, T, *] inputs (beta [B, H, T, 1], pos [B, T, 1]) -> (o [B, H, T,
    V], each chunk's starting state [B, H, N, V, K], the final state [B, H, V,
    K])."""

    def one(q, k, v, gc, beta, pos):
        def step(st, xs):
            o, st_new = _chunk_forward(st, *xs, dtype)
            return st_new, (o, st)

        xs = tuple(_chunked(x, CHUNK) for x in (q, k, v, gc, beta, pos))
        st0 = jnp.zeros((v.shape[-1], k.shape[-1]), _F32)
        final, (o, starts) = lax.scan(step, st0, xs)
        return o.reshape(-1, v.shape[-1]), starts, final

    return _per_head(one, 5)(q, k, v, gc, beta, pos)


def _xla_backward(q, k, v, gc, beta, pos, starts, do, dtype):
    def one(q, k, v, gc, beta, pos, starts, do):
        def step(dst, xs):
            st, dox, *rest = xs
            dst, *grads = _chunk_backward(st, dst, dox, *rest, dtype)
            return dst, tuple(grads)

        xs = (starts,) + tuple(_chunked(x, CHUNK) for x in (do, q, k, v, gc, beta, pos))
        dst0 = jnp.zeros(starts.shape[1:], _F32)
        _, grads = lax.scan(step, dst0, xs, reverse=True)
        return tuple(g.reshape(-1, g.shape[-1]) for g in grads)

    return _per_head(one, 5)(q, k, v, gc, beta, pos, starts, do)


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


def _chunk_inputs(refs, n):
    """The chunk ``n`` of a block: q, k, v, gc as float32, beta, pos."""
    rows = _rows(n)
    return tuple(r[rows, :].astype(_F32) for r in refs[:4]) + (refs[4][rows, :], refs[5][rows, :])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, p_ref, o_ref, s_ref, f_ref, *, dtype, block):
    from jax.experimental import pallas as pl

    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(c > 0)
    def _():
        s_ref[...] = f_ref[...]

    refs = (q_ref, k_ref, v_ref, g_ref, b_ref, p_ref)

    def chunk(n, st):
        o, st = _chunk_forward(st, *_chunk_inputs(refs, n), dtype)
        o_ref[_rows(n), :] = o.astype(o_ref.dtype)
        return st

    # f_ref's block is the same every step of a head: it carries the state
    f_ref[...] = lax.fori_loop(0, block // CHUNK, chunk, s_ref[...])


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, p_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, starts, *, dtype, block):
    from jax.experimental import pallas as pl

    c = pl.program_id(2)
    chunks = block // CHUNK
    refs = (q_ref, k_ref, v_ref, g_ref, b_ref, p_ref)

    @pl.when(c == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def forward(n, st):
        starts[n] = st
        return _chunk_forward(st, *_chunk_inputs(refs, n), dtype, want_out=False)

    lax.fori_loop(0, chunks, forward, s_ref[...])

    def backward(m, dst):
        n = chunks - 1 - m
        rows = _rows(n)
        dst, dq, dk, dv, dg, db = _chunk_backward(
            starts[n], dst, do_ref[rows, :].astype(_F32), *_chunk_inputs(refs, n), dtype)
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[rows, :] = dv.astype(dv_ref.dtype)
        dg_ref[rows, :], db_ref[rows, :] = dg, db
        return dst

    ds_ref[...] = lax.fori_loop(0, chunks, backward, ds_ref[...])


def _specs(b, h, t, kd, vd, block, reverse):
    """Block specs over a (sequence, head, block) grid: [B, T, H * dim] rows
    of one head, beta [B, H, T, 1], positions [B, T, 1], states [B, H, NB, V,
    K]; the blocks in reverse for the backward kernel."""
    from jax.experimental import pallas as pl

    nb = t // block
    at = (lambda c: nb - 1 - c) if reverse else (lambda c: c)
    rows = lambda dim: pl.BlockSpec((None, block, dim), lambda i, j, c: (i, at(c), j))  # noqa: E731
    return dict(
        k=rows(kd), v=rows(vd),
        beta=pl.BlockSpec((None, None, block, 1), lambda i, j, c: (i, j, at(c), 0)),
        pos=pl.BlockSpec((None, block, 1), lambda i, j, c: (i, at(c), 0)),
        state=pl.BlockSpec((None, None, None, vd, kd), lambda i, j, c: (i, j, at(c), 0, 0)),
        final=pl.BlockSpec((None, None, vd, kd), lambda i, j, c: (i, j, 0, 0)),
    )


def _pallas_forward(q, k, v, gc, beta, pos, dtype, interpret):
    """[B, T, H, *] inputs (beta [B, T, H], pos [B, T]) -> (o [B, T, H, V],
    the state each block starts from [B, H, NB, V, K], the final state [B, H,
    V, K])."""
    from jax.experimental import pallas as pl

    b, t, h, kd = q.shape
    vd = v.shape[-1]
    block = math.gcd(t, BLOCK)
    spec = _specs(b, h, t, kd, vd, block, False)
    flat = lambda x: x.reshape(b, t, -1)  # noqa: E731
    o, states, final = pl.pallas_call(
        functools.partial(_fwd_kernel, dtype=dtype, block=block),
        grid=(b, h, t // block),
        in_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"], spec["pos"]],
        out_specs=[spec["v"], spec["state"], spec["final"]],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * vd), q.dtype),
                   jax.ShapeDtypeStruct((b, h, t // block, vd, kd), _F32),
                   jax.ShapeDtypeStruct((b, h, vd, kd), _F32)],
        compiler_params=None if interpret else _params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="kda_fwd",
    )(flat(q), flat(k), flat(v), flat(gc), beta.transpose(0, 2, 1)[..., None], pos[..., None])
    return o.reshape(b, t, h, vd), states, final


def _pallas_backward(q, k, v, gc, beta, pos, states, do, dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, kd = q.shape
    vd = v.shape[-1]
    block = math.gcd(t, BLOCK)
    spec = _specs(b, h, t, kd, vd, block, True)
    flat = lambda x: x.reshape(b, t, -1)  # noqa: E731
    dq, dk, dv, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, dtype=dtype, block=block),
        grid=(b, h, t // block),
        in_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"], spec["pos"],
                  spec["state"], spec["v"]],
        out_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"]],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * kd), q.dtype)] * 2
        + [jax.ShapeDtypeStruct((b, t, h * vd), q.dtype),
           jax.ShapeDtypeStruct((b, t, h * kd), _F32), jax.ShapeDtypeStruct((b, h, t, 1), _F32)],
        scratch_shapes=[pltpu.VMEM((vd, kd), _F32), pltpu.VMEM((block // CHUNK, vd, kd), _F32)],
        compiler_params=None if interpret else _params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="kda_bwd",
    )(flat(q), flat(k), flat(v), flat(gc), beta.transpose(0, 2, 1)[..., None], pos[..., None],
      states, flat(do))
    shape = lambda x, dim: x.reshape(b, t, h, dim)  # noqa: E731
    return shape(dq, kd), shape(dk, kd), shape(dv, vd), shape(dg, kd), db[..., 0].transpose(0, 2, 1)


# ---------------------------------------------------------------------------
# the differentiable core and the entry point
# ---------------------------------------------------------------------------


def _heads_first(x):
    return jnp.moveaxis(x, 2, 1)


def _forward(q, k, v, gc, beta, pos, path):
    """(o [B, T, H, V] in the inputs' dtype, the backward pass's states, the
    final state [B, H, K, V])."""
    dtype = q.dtype
    if path == "xla":
        o, starts, final = _xla_forward(
            *map(_heads_first, (q, k, v, gc)), _heads_first(beta[..., None]), pos[..., None], dtype)
        return jnp.moveaxis(o, 1, 2).astype(dtype), starts, final.swapaxes(-1, -2)
    o, states, final = _pallas_forward(q, k, v, gc, beta, pos, dtype, path == "interpret")
    return o, states, final.swapaxes(-1, -2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kda_core(q, k, v, gc, beta, pos, path):
    return _forward(q, k, v, gc, beta, pos, path)[0]


def _core_fwd(q, k, v, gc, beta, pos, path):
    o, states, _ = _forward(q, k, v, gc, beta, pos, path)
    o, states = checkpoint_name(o, KDA_NAME), checkpoint_name(states, KDA_NAME)
    return o, (q, k, v, gc, beta, pos, states)


def _core_bwd(path, res, do):
    q, k, v, gc, beta, pos, states = res
    dtype = q.dtype
    with scopes.scope(SCOPE):
        if path == "xla":
            dq, dk, dv, dgc, dbeta = _xla_backward(
                *map(_heads_first, (q, k, v, gc)), _heads_first(beta[..., None]),
                pos[..., None], states, _heads_first(do.astype(_F32)), dtype)
            dq, dk, dv, dgc = (jnp.moveaxis(x, 1, 2) for x in (dq, dk, dv, dgc))
            dbeta = jnp.moveaxis(dbeta[..., 0], 1, 2)
        else:
            dq, dk, dv, dgc, dbeta = _pallas_backward(
                q, k, v, gc, beta, pos, states, do.astype(_F32), dtype, path == "interpret")
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dgc, dbeta, None)


_kda_core.defvjp(_core_fwd, _core_bwd)


def _running_sums(g, positions):
    """g [B, T, H, K] -> G: the running sums of g inside each chunk and each
    document, the sum restarting at a document's start. The pairs of one
    document read the differences they read from a sum over the chunk, and
    no gradient reaches one document's g from another's — as a masked
    product at the highest precision, whose gradient is exactly zero across
    a start (a plain running sum's would cancel there only to rounding)."""
    b, t, h, kd = g.shape
    pos = positions.reshape(b, t // CHUNK, CHUNK)
    i = jnp.arange(CHUNK)[:, None]
    j = jnp.arange(CHUNK)[None, :]
    within = ((i - j <= pos[..., :, None]) & (j <= i)).astype(_F32)
    sums = jnp.einsum("bnts,bnsx->bntx", within, g.reshape(b, t // CHUNK, CHUNK, h * kd),
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
    gc = _running_sums(g.astype(_F32), positions)
    if interpret is None:
        serve = pallas_kernels.pallas_platform_ok() and kernels_serve(q.shape[3], v.shape[-1])
        path = "pallas" if serve else "xla"
    else:
        path = "interpret" if interpret else "pallas"
    return (q, k, v, gc, beta.astype(_F32), positions.astype(jnp.int32)), path, t


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
