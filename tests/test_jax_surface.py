"""The code is written for the one jax that is installed: it calls
``jax.shard_map`` / ``jax.lax.axis_size`` / ``jax.lax.pcast`` /
``jax.typeof`` / ``jax.distributed.is_initialized`` directly, publishes
nothing onto jax, and importing the package warns about nothing."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tensorflowdistributedlearning_tpu.parallel.collectives import vma_of
from tensorflowdistributedlearning_tpu.train import step as step_lib


def test_importing_the_package_warns_about_nothing():
    """-W error turns any DeprecationWarning at import into a failure — the
    old version shim tripped `jax.lax.pvary is deprecated` on every import."""
    out = subprocess.run(
        [sys.executable, "-W", "error::DeprecationWarning", "-c",
         "import jax; before = jax.shard_map\n"
         "import tensorflowdistributedlearning_tpu\n"
         "import tensorflowdistributedlearning_tpu.train.step\n"
         "import tensorflowdistributedlearning_tpu.parallel.pipeline\n"
         "assert jax.shard_map is before  # nothing published onto jax"],
        capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 0, out.stderr[-2000:]


def test_the_version_shim_is_gone():
    import importlib.util

    assert importlib.util.find_spec(
        "tensorflowdistributedlearning_tpu.utils.jaxcompat"
    ) is None
    assert not hasattr(step_lib, "LEGACY_BRIDGE")


def test_shard_map_runs_with_keyword_api():
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("batch",))

    def f(x):
        return jax.lax.psum(x, "batch")

    g = jax.shard_map(f, mesh=mesh, in_specs=P("batch"), out_specs=P("batch"))
    out = g(jnp.arange(8.0))
    np.testing.assert_allclose(np.asarray(out), np.full((8,), 28.0))


def test_axis_size_inside_shard_map():
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("batch", "model"))

    def f(x):
        return (
            x
            * jax.lax.axis_size("batch")
            * jax.lax.axis_size(("batch", "model"))
        )

    g = jax.shard_map(f, mesh=mesh, in_specs=P("batch"), out_specs=P("batch"))
    np.testing.assert_allclose(
        np.asarray(g(jnp.ones((4,)))), np.full((4,), 32.0)
    )


def test_vma_of_tracks_varying_axes_and_pcast_marks_them():
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("batch",))
    seen = {}

    def f(x, w):
        seen["sharded"] = vma_of(x)
        seen["replicated"] = vma_of(w)
        seen["pcast"] = vma_of(jax.lax.pcast(w, ("batch",), to="varying"))
        return x

    jax.shard_map(
        f, mesh=mesh, in_specs=(P("batch"), P()), out_specs=P("batch")
    )(jnp.ones((8,)), jnp.ones((2,)))
    assert seen["sharded"] == frozenset({"batch"})
    assert seen["replicated"] == frozenset()
    assert seen["pcast"] == frozenset({"batch"})
    assert vma_of(jnp.ones(())) == frozenset()  # outside shard_map


def test_mean_grads_matches_the_single_device_gradient():
    """The sharded step's gradient normalization: the gradient of a
    REPLICATED parameter arrives already psum'd (the vma-aware transposition
    does it), so the mean divides by the axis size; a per-shard gradient
    takes a real pmean. Either way it equals the single-device gradient of
    the global-mean loss."""
    mesh = Mesh(
        np.array(jax.devices()[:8]).reshape(8, 1, 1),
        ("batch", "model", "sequence"),
    )
    x = jnp.arange(16.0).reshape(8, 2)
    w = jnp.ones((2,))

    def loss(w, x):
        return jnp.mean((x @ w) ** 2)

    ref = jax.grad(loss)(w, x)

    def sharded_grad(w, x):
        g = jax.grad(loss)(w, x)  # replicated w: auto-psum'd over the shards
        assert vma_of(g) == frozenset()
        return step_lib._mean_grads(g)

    g = jax.shard_map(
        sharded_grad, mesh=mesh, in_specs=(P(), P("batch")), out_specs=P()
    )
    np.testing.assert_allclose(np.asarray(g(w, x)), np.asarray(ref), rtol=1e-6)
