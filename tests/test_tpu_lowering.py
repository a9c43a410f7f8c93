"""What a TPU would be asked to run, checked on the CPU host.

Off-TPU every Pallas wrapper returns its XLA reference before it reaches
``pallas_call``, so a kernel the TPU lowering refuses (a block shape Mosaic
does not take, a VMEM budget compared against a symbolic batch) passes every
CPU test and fails the first time it meets a chip. ``jax.export`` lowers for
a platform the host does not have: these tests force the TPU dispatch and
lower every wrapper — at the shapes ``chip_smoke.py`` runs on the chip, and
with the symbolic batch serving artifacts are exported with — for
``platforms=["tpu"]``.

Lowering is not compiling: what Mosaic itself refuses only shows when XLA:TPU
compiles the module. ``tools/aot_preflight.py`` does that without a chip; the
last test here runs it where libtpu is installed.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export as jax_export

import chip_smoke
from tensorflowdistributedlearning_tpu.ops import pallas_kernels as pk
from tensorflowdistributedlearning_tpu.ops import quant_kernels as qk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the wrappers dispatch as they do on a TPU (compiled Pallas)."""
    monkeypatch.setattr(pk, "pallas_platform_ok", lambda: True)


def lower_for_tpu(fn, *specs) -> str:
    return jax_export.export(jax.jit(fn), platforms=["tpu"])(*specs).mlir_module()


_CASES = chip_smoke.kernel_cases()


@pytest.mark.parametrize("case", _CASES, ids=[c["name"] for c in _CASES])
def test_kernel_lowers_for_tpu_on_its_expected_path(case, on_tpu):
    """Every case of the chip smoke's kernel phase lowers, and holds a Mosaic
    call exactly where the wrapper's envelope says it takes the kernel."""
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in case["args"]]
    text = lower_for_tpu(case["fn"], *specs)
    assert chip_smoke.holds_mosaic_call(text) == (
        case["expected_path"] == "mosaic"
    )


def _symbolic_batch(*tail, dtype=jnp.bfloat16):
    (b,) = jax_export.symbolic_shape("b")
    return jax.ShapeDtypeStruct((b, *tail), dtype)


def test_int8_matmul_lowers_with_the_exported_symbolic_batch(on_tpu):
    """The VMEM budget must not depend on the row count: a served batch is the
    artifact's symbolic batch, and a budget compared against it cannot be
    decided at export."""
    wq = np.ones((2048, 1000), np.int8)
    ws = np.ones((1000,), np.float32)
    text = lower_for_tpu(
        lambda x: qk.int8_matmul(x, wq, ws, out_dtype=jnp.bfloat16),
        _symbolic_batch(2048),
    )
    assert chip_smoke.holds_mosaic_call(text)


def test_int8_conv2d_lowers_with_the_exported_symbolic_batch(on_tpu):
    wq = np.ones((3, 3, 256, 256), np.int8)
    ws = np.ones((256,), np.float32)
    text = lower_for_tpu(
        lambda x: qk.int8_conv2d(x, wq, ws, out_dtype=jnp.bfloat16),
        _symbolic_batch(13, 13, 256),
    )
    assert chip_smoke.holds_mosaic_call(text)


def test_int8_conv2d_says_so_when_mosaic_cannot_take_the_shape(on_tpu, caplog):
    """On a TPU a wrapper that cannot use its kernel logs it — once."""
    pk._warn_reference_once.cache_clear()
    wq = np.ones((3, 3, 64, 64), np.int8)
    ws = np.ones((64,), np.float32)
    spec = jax.ShapeDtypeStruct((4, 51, 51, 64), jnp.bfloat16)

    def conv(x):
        return qk.int8_conv2d(x, wq, ws, out_dtype=jnp.bfloat16)

    with caplog.at_level("WARNING"):
        text = lower_for_tpu(conv, spec)
        lower_for_tpu(conv, spec)
    assert not chip_smoke.holds_mosaic_call(text)
    said = [r for r in caplog.records if "int8_conv2d" in r.getMessage()]
    assert len(said) == 1 and "XLA reference on a TPU" in said[0].getMessage()


def test_reference_fallback_is_silent_off_tpu(caplog):
    """The silent reference stays for CPU runs, where it is the expected path."""
    pk._warn_reference_once.cache_clear()
    with caplog.at_level("WARNING"):
        pk.note_reference_fallback("int8_conv2d", "some reason")
    assert not caplog.records


def _flagship_serve_closure(serving_dtype: str):
    """The trainers' serving closure (train/trainer.py serving_fn) over a
    narrow, shallow flagship at the published 101x101x2 input."""
    from tensorflowdistributedlearning_tpu.config import ModelConfig
    from tensorflowdistributedlearning_tpu.models import build_model
    from tensorflowdistributedlearning_tpu.train import quantize
    from tensorflowdistributedlearning_tpu.train.step import SegmentationTask

    cfg = ModelConfig(n_blocks=(1, 1, 1), base_depth=16, dtype="bfloat16")
    model = build_model(cfg)
    variables = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 101, 101, 2)), train=False
        )
    )
    variables = jax.tree.map(
        lambda s: np.full(s.shape, 0.01, s.dtype), variables
    )
    qparams, qstats, section = quantize.quantize_state(
        variables["params"], variables["batch_stats"], serving_dtype
    )
    act_dtype = quantize.compute_dtype(serving_dtype)
    task = SegmentationTask()

    def forward(x):
        return model.apply(
            {
                "params": quantize.dequantize_pytree(qparams, act_dtype),
                "batch_stats": quantize.dequantize_pytree(qstats, act_dtype),
            },
            x.astype(act_dtype),
            train=False,
        )

    def serve(images):
        if section.get("compute_dtype") == "int8":
            with qk.int8_intercept(qparams, act_dtype):
                logits = forward(images)
        else:
            logits = forward(images)
        return quantize.cast_outputs_float32(task.predictions(logits))

    return serve


def test_flagship_serve_closure_exports_for_tpu_with_a_symbolic_batch(on_tpu):
    """`train --export-serving` on a TPU: the closure lowers for the whole
    ladder at once. The head is plain XLA — which XLA:TPU emits as ONE fusion
    reading the logits once and writing both outputs."""
    text = lower_for_tpu(
        _flagship_serve_closure("float32"),
        _symbolic_batch(101, 101, 2, dtype=jnp.float32),
    )
    assert not chip_smoke.holds_mosaic_call(text)


def test_int8_compute_flagship_exports_for_tpu_with_a_symbolic_batch(on_tpu):
    """`--serving-dtype int8-compute`: the intercepted layers reach the int8
    kernels with the symbolic batch, and the module still lowers."""
    text = lower_for_tpu(
        _flagship_serve_closure("int8-compute"),
        _symbolic_batch(101, 101, 2, dtype=jnp.float32),
    )
    assert chip_smoke.holds_mosaic_call(text)


@pytest.mark.slow
def test_kernels_compile_for_v5e_without_a_chip():
    """XLA:TPU + Mosaic compile of every kernel case against a v5e topology
    description (tools/aot_preflight.py): what lowering cannot see. In its own
    process — libtpu does not belong in the CPU suite's."""
    pytest.importorskip("libtpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "aot_preflight.py"),
         "--kernels"],
        capture_output=True, text=True, timeout=900, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
