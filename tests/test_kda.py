"""Kimi Delta Attention (ops/kda.py) at a small size on the CPU: the chunked
form — the Pallas kernels in interpret mode and the XLA path, at the chunk of
64 and sub-chunks of 16 the chip runs — against the recurrence one position at
a time: the output, the state after the last token, and the gradients of q, k,
v, g and beta. The sequences hold document starts inside a sub-chunk, on a
sub-chunk's edge, on a chunk's edge and on a block's edge, and chunks whose
summed log-decay underflows float32.

Tolerances: the recurrence and the chunked form compute the same sums in a
different order in float32 (the chunk's inverse is a product of powers, the
decays a difference of running sums, factored through a token of the chunk
across sub-chunks), and a sound run reads under 1e-6 at these sizes; 1e-5
leaves room for the order and none for a missing term, which moves the output
by its own size (above 1e-2 here)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowdistributedlearning_tpu.ops import kda as kda_lib

# where documents start in each of the two sequences (T = 512 unless cut)
LAYOUTS = {
    # inside sub-chunks (7, 23, 53) and on a block's edge (256)
    "inside": lambda t: [[23, 30, t - 53], [7, 249, t - 256] if t > 256 else [7, t - 7]],
    # on sub-chunks' edges (16, 48, 80) and on chunks' edges (64, 128)
    "edges": lambda t: [[16, 32, 32, t - 80], [64, 64, t - 128] if t > 128 else [64, t - 64]],
}


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


def _inputs(t, seed=0, b=2, h=2, d=128, layout="inside"):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(b, t, h, d)) / np.sqrt(d), jnp.float32)
    k = jnp.asarray(r.normal(size=(b, t, h, d)), jnp.float32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jnp.asarray(r.normal(size=(b, t, h, d)), jnp.float32)
    # decays as the cell draws them (down to about -1.6 a token), and five
    # channels of one head at -8 a token for 20 tokens: a sub-chunk's sum
    # underflows float32's exp
    g = -jnp.asarray(r.uniform(0.0, 1.6, size=(b, t, h, d)), jnp.float32)
    g = g.at[:, 20:40, 0, :5].set(-8.0)
    if layout == "underflow":
        # every channel of head 1 at -1.6 a token over the second chunk: its
        # summed log-decay (-102) underflows, while each sub-chunk's (-25.6)
        # and each pair's inside it do not
        g = g.at[:, 64:128, 1, :].set(-1.6)
    beta = jnp.asarray(r.uniform(size=(b, t, h)), jnp.float32)
    lengths = LAYOUTS["inside" if layout == "underflow" else layout](t)
    positions = jnp.asarray([np.concatenate([np.arange(n) for n in row if n > 0])
                             for row in lengths], jnp.int32)
    return q, k, v, g, beta, positions


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-30))


@pytest.mark.parametrize("interpret,t,layout", [
    (None, 64, "inside"), (None, 512, "inside"), (True, 64, "inside"), (True, 512, "inside"),
    (None, 512, "edges"), (True, 512, "edges"), (None, 512, "underflow"), (True, 512, "underflow"),
])
def test_output_and_final_state_are_the_recurrences(interpret, t, layout):
    args = _inputs(t, layout=layout)
    want_o, want_s = recurrence(*args)
    o, s = kda_lib.kda_with_state(*args, interpret=interpret)
    assert o.shape == want_o.shape and s.shape == want_s.shape
    assert _gap(o, want_o) < 1e-5 and _gap(s, want_s) < 1e-5
    # the underflowing channels' state is the recurrence's, zero where it forgot
    assert bool(jnp.all(jnp.isfinite(o)))


@pytest.mark.parametrize("interpret,layout", [
    (None, "inside"), (True, "inside"), (None, "edges"), (True, "edges"), (None, "underflow"),
    (True, "underflow"),
])
def test_gradients_are_the_recurrences(interpret, layout):
    t = 512 if interpret is None else 256
    q, k, v, g, beta, pos = _inputs(t, seed=1, layout=layout)
    w = jax.random.normal(jax.random.key(2), q.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    want = jax.grad(loss(lambda *a: recurrence(*a, pos)[0]), argnums=range(5))(q, k, v, g, beta)
    got = jax.grad(loss(lambda *a: kda_lib.kda(*a, pos, interpret=interpret)),
                   argnums=range(5))(q, k, v, g, beta)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert _gap(a, b) < 1e-5, name


@pytest.mark.parametrize("interpret,t", [(None, 40), (None, 100), (True, 100)])
def test_a_sequence_off_the_chunks_is_padded_and_cut(interpret, t):
    q, k, v, g, beta, pos = _inputs(128)
    cut = [x[:, :t] for x in (q, k, v, g, beta, pos)]
    o, s = kda_lib.kda_with_state(*cut, interpret=interpret)
    want_o, want_s = recurrence(*cut)
    assert o.shape[1] == t and _gap(o, want_o) < 1e-5 and _gap(s, want_s) < 1e-5
    w = jax.random.normal(jax.random.key(3), want_o.shape)
    got = jax.grad(lambda *a: jnp.sum(kda_lib.kda(*a, cut[5], interpret=interpret) * w),
                   argnums=range(5))(*cut[:5])
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a, cut[5])[0] * w), argnums=range(5))(*cut[:5])
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert _gap(a, b) < 1e-5, name


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


def _equations(jaxpr):
    """Equations of ``jaxpr``, those of every sub-jaxpr (a loop's body) once."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    n += _equations(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    n += _equations(sub)
    return n


def _kernel_bodies(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] = _equations(eqn.params["jaxpr"])
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    _kernel_bodies(sub.jaxpr, out)
    return out


def test_the_kernel_bodies_stay_small_at_the_cells_widths():
    """The set-up's budget: every run traces and lowers each kernel body again
    (jax keeps no trace of a Pallas body), once a layer and twice a step, so
    a loop over rows, sub-chunks or chunks unrolled in Python would cost
    seconds of every run's set-up (four chunks a block unrolled would hold
    four times the equations). At the Kimi-Linear cell's widths (16,384
    tokens, 32 heads of 128, bfloat16), differentiated: the bodies hold at
    most three times the 123 and 312 equations (loop bodies counted once) of
    the chunk-of-16 kernels they replaced."""
    t, h, d = 16384, 32, 128
    bf16, f32 = jnp.bfloat16, jnp.float32
    shapes = [((1, t, h, d), bf16)] * 3 + [((1, t, h, d), f32), ((1, t, h), f32),
                                           ((1, t), jnp.int32), ((1, t, h, d), f32)]

    def loss(q, k, v, g, beta, positions, w):
        o = kda_lib.kda(q, k, v, g, beta, positions, interpret=False)
        return jnp.sum(o.astype(f32) * w)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(
        *(jax.ShapeDtypeStruct(s, dt) for s, dt in shapes))
    bodies = _kernel_bodies(jaxpr.jaxpr, {})
    assert set(bodies) == {"kda_fwd", "kda_bwd"}
    assert bodies["kda_fwd"] <= 3 * 123 and bodies["kda_bwd"] <= 3 * 312, bodies
