"""The Mamba-2 scan of ``ops/ssd.py`` on the CPU against the recurrence one
position at a time, on both paths — the batched XLA form and the Pallas
kernels in interpret mode: the output, the state after the last token and the
gradients of x, dt, A_log (through ``A = -exp(A_log)``), B, C and D, over
packed documents that start at a chunk's boundary and inside a chunk, at a
length that is not a whole number of chunks, and with a decay that underflows
float32 inside a chunk. At the cell's head layout the two paths agree with
each other, the kernel bodies stay small, and every body is traced once
whatever the length.

Tolerances: float32 operands at the highest precision on both sides, the
same mathematics in another order: 1e-5 of each quantity's largest entry.
Where the decays underflow, A's gradient sums over a chunk's pairs terms whose
exponents are differences of running sums near -300, of which float32 keeps
2e-5, and the sum cancels: 3e-4 there (against float64, the recurrence in
float32 reads 1e-7 and the chunked form 1.1e-4; every other gradient under
1e-6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tensorflowdistributedlearning_tpu.ops import ssd as ssd_lib


def _recurrence(x, dt, a, b, c, d, positions):
    """x [B, T, H, P], dt [B, T, H], b, c [B, T, G, N] -> (y, the final state)."""
    h = x.shape[2]
    bh = jnp.repeat(b, h // b.shape[2], axis=2)
    ch = jnp.repeat(c, h // c.shape[2], axis=2)

    def one(x, dt, bh, ch, pos):
        def step(state, z):
            xt, dtt, bt, ct, pt = z
            state = jnp.where(pt == 0, 0.0, state)
            state = jnp.exp(dtt * a)[:, None, None] * state + (dtt[:, None] * xt)[..., None] \
                * bt[:, None, :]
            y = jnp.einsum("hpn,hn->hp", state, ct, precision=lax.Precision.HIGHEST)
            return state, y + d[:, None] * xt

        final, y = lax.scan(step, jnp.zeros((h, x.shape[-1], b.shape[-1])), (x, dt, bh, ch, pos))
        return y, final

    return jax.vmap(one)(x, dt, bh, ch, positions)


def _inputs(t, starts, *, step=None, seed=0, heads=4, head=8, groups=2, state=16):
    r = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa: E731
    dt = np.exp(r.uniform(np.log(1e-3), np.log(0.1), (2, t, heads)))
    if step is not None:
        dt = np.full_like(dt, step)
    pos = np.zeros((2, t), np.int32)
    for row, row_starts in enumerate(starts):
        p = 0
        for i in range(t):
            p = 0 if i in row_starts else p + 1
            pos[row, i] = p
    return dict(x=f32(2, t, heads, head), dt=jnp.asarray(dt, jnp.float32),
                a_log=jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
                b=f32(2, t, groups, state), c=f32(2, t, groups, state), d=f32(heads),
                positions=jnp.asarray(pos))


CASES = {
    # documents starting at chunk boundaries and inside chunks, two rows apart
    "starts": (256, ([0, 128, 200], [0, 5, 130, 131]), None, 1e-5),
    # 300 tokens: two chunks and a part, a document starting at the last token
    "ragged": (300, ([0, 77, 299], [0]), None, 1e-5),
    # exp(dt A) = exp(-2) a token on the first head, exp(-8) on the last: the
    # decay over a chunk underflows float32 (exp(-256) and below)
    "underflow": (256, ([0], [0, 100]), 2.0, 3e-4),
}


# the path: None chooses (the XLA form on the CPU), True the kernels interpreted
PATHS = {"xla": None, "kernels": True}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_scan_is_the_recurrence(case, path):
    t, starts, step, tol = CASES[case]
    z = _inputs(t, starts, step=step)
    a = -jnp.exp(z["a_log"])
    args = (z["x"], z["dt"], a, z["b"], z["c"], z["d"])
    y, final = ssd_lib.ssd_with_state(*args, z["positions"], interpret=PATHS[path])
    want_y, want_final = _recurrence(*args, z["positions"])
    scale = lambda w: float(jnp.max(jnp.abs(w)))  # noqa: E731
    assert float(jnp.max(jnp.abs(y - want_y))) <= tol * scale(want_y)
    assert float(jnp.max(jnp.abs(final - want_final))) <= tol * scale(want_final)
    assert bool(jnp.all(jnp.isfinite(y)))

    cot = jnp.asarray(np.random.default_rng(1).standard_normal(y.shape), jnp.float32)
    names = ("x", "dt", "a_log", "b", "c", "d")

    def loss(fn):
        def f(x, dt, a_log, b, c, d):
            out = fn(x, dt, -jnp.exp(a_log), b, c, d, z["positions"])
            return jnp.sum((out[0] if isinstance(out, tuple) else out) * cot)
        return f

    scan = lambda *a: ssd_lib.ssd(*a, interpret=PATHS[path])  # noqa: E731
    got = jax.grad(loss(scan), argnums=range(6))(*(z[n] for n in names))
    want = jax.grad(loss(_recurrence), argnums=range(6))(*(z[n] for n in names))
    for name, g, w in zip(names, got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert float(jnp.max(jnp.abs(g - w))) <= tol * scale(w), (name, case)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_no_gradient_crosses_a_document_start(path):
    """The output of the second document does not depend on the first's
    inputs: their gradients are exactly zero, not zero to rounding."""
    z = _inputs(256, ([0, 60], [0, 60]))
    first = jnp.arange(256) < 60

    def later(x, dt):
        y = ssd_lib.ssd(x, dt, -jnp.exp(z["a_log"]), z["b"], z["c"], z["d"], z["positions"],
                        interpret=PATHS[path])
        return jnp.sum(jnp.where(first[None, :, None, None], 0.0, y))

    dx, ddt = jax.grad(later, argnums=(0, 1))(z["x"], z["dt"])
    assert float(jnp.max(jnp.abs(dx[:, :60]))) == 0.0
    assert float(jnp.max(jnp.abs(ddt[:, :60]))) == 0.0
    assert float(jnp.max(jnp.abs(dx[:, 60:]))) > 0.0


def _equations(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr its equations hold."""
    count = 0
    for eqn in jaxpr.eqns:
        count += 1
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    count += _equations(inner)
    return count


def _cell_scan_jaxpr(t, interpret):
    """The gradient's program at the cell's widths (64 heads of 64, 8 groups
    of 128, bfloat16) over t tokens; ``interpret`` False: the kernel path,
    traced (not lowered) on the CPU."""
    shapes = dict(x=(1, t, 64, 64), dt=(1, t, 64), b=(1, t, 8, 128), c=(1, t, 8, 128))

    def loss(x, dt, b, c):
        y = ssd_lib.ssd(x, dt, -jnp.ones(64), b, c, jnp.ones(64), jnp.zeros((1, t), jnp.int32),
                        interpret=interpret)
        return jnp.sum(y)

    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16 if k != "dt" else jnp.float32)
            for k, s in shapes.items()]
    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(*args).jaxpr


@pytest.mark.parametrize("interpret", [None, False], ids=["xla", "kernels"])
def test_the_scan_is_traced_once_whatever_the_length(interpret):
    """At the cell's widths: the gradient's program has as many equations at
    16,384 tokens as at 1,024 — the chunks are a batch axis and the passes
    over them scans (the XLA form), or loops inside the kernel bodies and the
    grid over the blocks (the kernels): nothing unrolled."""
    def size(t):
        return _equations(_cell_scan_jaxpr(t, interpret))

    assert size(16384) == size(1024)


def _kernel_bodies(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] = _equations(eqn.params["jaxpr"])
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _kernel_bodies(inner, out)
    return out


def test_the_kernel_bodies_stay_small_at_the_cells_widths():
    """Every run traces and lowers each kernel body again (jax keeps no trace
    of a Pallas body), once a layer and twice a step, so a loop over chunks or
    heads unrolled in Python would cost seconds of every run's set-up. The
    bodies (loop bodies counted once) hold no more equations than the KDA
    kernels of ``ops/kda.py`` at their cell's widths, 331 and 744."""
    bodies = _kernel_bodies(_cell_scan_jaxpr(1024, False), {})
    assert set(bodies) == {"ssd_fwd", "ssd_bwd"}
    assert bodies["ssd_fwd"] <= 331 and bodies["ssd_bwd"] <= 744, bodies


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernels_are_the_xla_form_at_the_cells_head_layout(dtype):
    """Two groups of the cell's layout (8 heads of 64 a group, B and C of
    128) over 384 tokens with documents starting inside chunks: the kernels
    (interpreted) against the XLA form, output and the six gradients. Both
    round at the same points, so what differs is the order of float32 sums:
    1e-5 of each quantity's largest entry, and for dx, dB and dC, which both
    write in the inputs' dtype, one rounding of it (2^-8 of the largest entry
    in bfloat16)."""
    z = _inputs(384, ([0, 77, 300], [0, 130, 131]), heads=16, head=64, groups=2, state=128)
    cast = lambda v: v.astype(dtype)  # noqa: E731
    args = (cast(z["x"]), z["dt"], -jnp.exp(z["a_log"] / 4), cast(z["b"]), cast(z["c"]), z["d"])
    cot = jnp.asarray(np.random.default_rng(1).standard_normal((2, 384, 16, 64)), jnp.float32)
    out = {}
    for interpret in (None, True):
        scan = lambda *a, i=interpret: ssd_lib.ssd(*a, z["positions"], interpret=i)  # noqa: E731
        out[interpret] = (scan(*args),) + jax.grad(
            lambda *a, f=scan: jnp.sum(f(*a) * cot), argnums=range(6))(*args)
    written = 2.0 ** -8 if dtype == "bfloat16" else 1e-5
    for name, got, want in zip(("y", "x", "dt", "a", "b", "c", "d"), out[True], out[None]):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        tol = written if name in ("x", "b", "c") else 1e-5
        assert float(jnp.max(jnp.abs(got - want))) <= tol * float(jnp.max(jnp.abs(want))), name


def test_the_path_is_chosen_by_backend_and_layout():
    """On the CPU ``ssd`` runs the XLA form whatever the layout; the kernels
    take the cell's (8 heads of 64 a group: 512 lanes, B and C of 128) and
    refuse a group that fills no whole tile."""
    jaxpr = _cell_scan_jaxpr(1024, None)
    assert not _kernel_bodies(jaxpr, {})
    assert ssd_lib.kernels_serve(8, 64, 128)
    assert ssd_lib.kernels_serve(16, 128, 256)
    assert not ssd_lib.kernels_serve(4, 64, 128)  # half a sublane tile of heads
    assert not ssd_lib.kernels_serve(8, 8, 128)  # 64 lanes a group
    assert not ssd_lib.kernels_serve(8, 64, 64)  # B and C of half a lane tile
