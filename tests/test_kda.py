"""Kimi Delta Attention (ops/kda.py) at a small size on the CPU: the chunked
form — the Pallas kernels in interpret mode and the XLA path — against the
recurrence one position at a time: the output, the state after the last
token, and the gradients of q, k, v, g and beta. The sequences hold document
starts inside a chunk and on a block's edge, and a channel whose summed
log-decay over a chunk underflows float32.

Tolerances: the recurrence and the chunked form compute the same sums in a
different order in float32 (the chunk's inverse is a product of powers, the
decays a difference of running sums), and a sound run reads under 1e-6 at
these sizes; 1e-5 leaves room for the order and none for a missing term, which
moves the output by its own size (above 1e-2 here)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowdistributedlearning_tpu.ops import kda as kda_lib


def recurrence(q, k, v, g, beta, positions):
    """[B, T, H, *] -> (o [B, T, H, V], the final state [B, H, K, V]): the
    delta rule one position at a time, the state zero at each document's start."""

    def one(q, k, v, g, beta, pos):
        def step(s, x):
            qt, kt, vt, gt, bt, pt = x
            s = jnp.where(pt == 0, 0.0, s) * jnp.exp(gt)[:, None]
            s = s - bt * jnp.outer(kt, kt @ s) + bt * jnp.outer(kt, vt)
            return s, s.T @ qt

        s, o = jax.lax.scan(step, jnp.zeros((k.shape[-1], v.shape[-1])), (q, k, v, g, beta, pos))
        return o, s

    heads = jax.vmap(one, in_axes=(1, 1, 1, 1, 1, None), out_axes=(1, 0))
    with jax.default_matmul_precision("highest"):
        return jax.vmap(heads)(q, k, v, g, beta, positions)


def _inputs(t, seed=0, b=2, h=2, d=128):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(b, t, h, d)) / np.sqrt(d), jnp.float32)
    k = jnp.asarray(r.normal(size=(b, t, h, d)), jnp.float32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jnp.asarray(r.normal(size=(b, t, h, d)), jnp.float32)
    # decays as the cell draws them (down to about -1.6 a token), and five
    # channels of one head at -8 a token for 20 tokens: a chunk's sum
    # underflows float32's exp
    g = -jnp.asarray(r.uniform(0.0, 1.6, size=(b, t, h, d)), jnp.float32)
    g = g.at[:, 20:40, 0, :5].set(-8.0)
    beta = jnp.asarray(r.uniform(size=(b, t, h)), jnp.float32)
    # documents that start inside a chunk (7, 23, 53) and on a block's edge (256)
    lengths = [[23, 30, t - 53], [7, 249, t - 256] if t > 256 else [7, t - 7]]
    positions = jnp.asarray([np.concatenate([np.arange(n) for n in row if n > 0])
                             for row in lengths], jnp.int32)
    return q, k, v, g, beta, positions


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-30))


@pytest.mark.parametrize("interpret,t", [(None, 64), (None, 512), (True, 64), (True, 512)])
def test_output_and_final_state_are_the_recurrences(interpret, t):
    args = _inputs(t)
    want_o, want_s = recurrence(*args)
    o, s = kda_lib.kda_with_state(*args, interpret=interpret)
    assert o.shape == want_o.shape and s.shape == want_s.shape
    assert _gap(o, want_o) < 1e-5 and _gap(s, want_s) < 1e-5
    # the underflowing channels' state is the recurrence's, zero where it forgot
    assert bool(jnp.all(jnp.isfinite(o)))


@pytest.mark.parametrize("interpret", [None, True])
def test_gradients_are_the_recurrences(interpret):
    q, k, v, g, beta, pos = _inputs(512 if interpret is None else 64, seed=1)
    w = jax.random.normal(jax.random.key(2), q.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    want = jax.grad(loss(lambda *a: recurrence(*a, pos)[0]), argnums=range(5))(q, k, v, g, beta)
    got = jax.grad(loss(lambda *a: kda_lib.kda(*a, pos, interpret=interpret)),
                   argnums=range(5))(q, k, v, g, beta)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert _gap(a, b) < 1e-5, name


def test_a_sequence_off_the_chunks_is_padded_and_cut():
    q, k, v, g, beta, pos = _inputs(64)
    cut = [x[:, :40] for x in (q, k, v, g, beta, pos)]
    o, s = kda_lib.kda_with_state(*cut)
    want_o, want_s = recurrence(*cut)
    assert o.shape[1] == 40 and _gap(o, want_o) < 1e-5 and _gap(s, want_s) < 1e-5


def test_the_delta_term_and_the_reset_are_there():
    """What a fault would change moves the output by far more than the
    tolerance: the state carried over a document's start; and beta = 0 keeps
    no value at all."""
    q, k, v, g, beta, pos = _inputs(64)
    o = kda_lib.kda(q, k, v, g, beta, pos)
    no_reset = kda_lib.kda(q, k, v, g, beta, jnp.broadcast_to(jnp.arange(64), pos.shape))
    assert _gap(no_reset, o) > 1e-2
    assert float(jnp.max(jnp.abs(kda_lib.kda(q, k, v, g, beta * 0.0, pos)))) == 0.0


def test_the_kernels_take_the_cells_head_size():
    assert kda_lib.kernels_serve(128, 128)
    assert not kda_lib.kernels_serve(64, 128) and not kda_lib.kernels_serve(128, 96)
