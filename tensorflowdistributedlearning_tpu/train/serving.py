"""Standalone serving artifacts: serialized StableHLO via ``jax.export``.

The reference's BestExporter wrote SavedModel bundles an external TF-Serving
process could load without the training code (reference: model.py:190-204). The
JAX-native equivalent is ``jax.export``: the jitted inference function (with the
fold's best params baked in as constants) lowers to StableHLO and serializes to a
self-contained byte artifact; any process with jax installed — no framework code,
no checkpoint plumbing — can deserialize and call it.

Layout of an artifact directory:
    {dir}/serving.stablehlo   — the serialized Exported function
    {dir}/manifest.json       — input signature + metadata for humans/tools
"""

from __future__ import annotations

import json
import os
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ARTIFACT_NAME = "serving.stablehlo"
MANIFEST_NAME = "manifest.json"


def _manifest_dims(shape) -> list:
    """Manifest encoding of a shape: ints stay, symbolic dims (the polymorphic
    batch) become None — the same placeholder convention as the input spec."""
    return [int(d) if isinstance(d, int) else None for d in shape]


def _output_signature(out_tree) -> Dict[str, Dict]:
    """Flatten an output pytree of avals into ``{name: {shape, dtype}}``
    manifest entries, so clients can validate responses without calling the
    artifact. Dict outputs (both tasks' ``predictions``) name entries by key;
    other containers fall back to the jax key-path string."""
    import jax

    sig: Dict[str, Dict] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(out_tree)[0]:
        parts = []
        for p in path:
            for attr in ("key", "idx", "name"):
                if hasattr(p, attr):
                    parts.append(str(getattr(p, attr)))
                    break
            else:
                parts.append(str(p))
        name = "/".join(parts) if parts else "output"
        sig[name] = {
            "shape": _manifest_dims(leaf.shape),
            "dtype": str(leaf.dtype),
        }
    return sig


def export_serving_artifact(
    serve_fn: Callable,
    input_shape: Tuple[int, ...],
    directory: str,
    *,
    batch_polymorphic: bool = True,
    input_dtype: str = "float32",
    metadata: Dict | None = None,
    quantization: Dict | None = None,
) -> str:
    """Serialize ``serve_fn`` (a jittable ``images -> {...}`` closure with params
    baked in) for the given input signature; returns the artifact path.

    ``input_shape`` is the full input shape including the batch dimension;
    ``batch_polymorphic=True`` replaces the batch dim with a symbolic size so one
    artifact serves any batch size (the reference's ``[None, 101, 101, 2]``
    placeholder semantics, model.py:192).

    ``quantization`` is the manifest section ``train/quantize.py`` produced
    alongside the (possibly quantized) ``serve_fn`` — serving dtype, per-tensor
    scale metadata, source fingerprint. Validated before writing, so a corrupt
    section fails the EXPORT, not some later load.
    """
    from jax import export as jax_export

    if batch_polymorphic:
        (b,) = jax_export.symbolic_shape("b")
        spec_shape: Tuple = (b, *input_shape[1:])
    else:
        spec_shape = tuple(input_shape)
    spec = jax.ShapeDtypeStruct(spec_shape, jnp.dtype(input_dtype))
    exported = jax_export.export(jax.jit(serve_fn))(spec)
    payload = exported.serialize()

    os.makedirs(directory, exist_ok=True)
    artifact = os.path.join(directory, ARTIFACT_NAME)
    with open(artifact, "wb") as f:
        f.write(bytes(payload))
    manifest = {
        "input_shape": [None if batch_polymorphic else input_shape[0]]
        + list(input_shape[1:]),
        "input_dtype": str(jnp.dtype(input_dtype)),
        # the OUTPUT signature too: without it clients can't validate
        # responses (or pre-allocate) from the manifest alone. Read from what
        # export already traced (re-tracing via eval_shape trips shape-poly
        # restrictions the export lowering itself handles).
        "outputs": _output_signature(
            jax.tree_util.tree_unflatten(
                exported.out_tree, list(exported.out_avals)
            )
        ),
        "format": "jax.export serialized StableHLO",
        "platforms": list(getattr(exported, "platforms", ())),
        **(metadata or {}),
    }
    if quantization is not None:
        from tensorflowdistributedlearning_tpu.train import quantize

        manifest["quantization"] = quantize.validate_quantization(quantization)
    with open(os.path.join(directory, MANIFEST_NAME), "w") as f:
        json.dump(manifest, f, indent=2)
    return artifact


def load_serving_artifact(directory: str) -> Callable:
    """Deserialize an exported artifact; returns ``serve(images) -> outputs``.
    Needs only jax — none of this framework's modules or checkpoints. The
    input dtype comes from the manifest (an artifact exported for bfloat16
    inputs used to be silently fed float32); a MISSING manifest falls back to
    float32, the historical contract — a present-but-corrupt one (bad dtype
    string, invalid quantization section) raises, because executing an
    artifact whose self-description cannot be trusted is how silently-wrong
    answers ship."""
    from jax import export as jax_export

    with open(os.path.join(directory, ARTIFACT_NAME), "rb") as f:
        payload = f.read()
    exported = jax_export.deserialize(bytearray(payload))
    try:
        manifest = read_manifest(directory)
    except OSError:
        manifest = {"input_dtype": "float32"}
    dtype = jnp.dtype(manifest["input_dtype"])

    def serve(images) -> Dict:
        return exported.call(jnp.asarray(images, dtype))

    return serve


def read_manifest(directory: str) -> Dict:
    """Read + validate an artifact manifest. The ONE site that applies the
    legacy defaults (pre-input_dtype manifests mean float32; no
    ``quantization`` section means an unquantized float32 graph; a
    quantization section without ``compute_dtype`` means the storage dtype's
    historical arithmetic — f32/bf16/bf16-dequantized) and the one gate that
    rejects corrupt quantization metadata — every consumer (engine, loader,
    quantize-check, CLI) reads through here."""
    from tensorflowdistributedlearning_tpu.train import quantize

    with open(os.path.join(directory, MANIFEST_NAME)) as f:
        manifest = json.load(f)
    manifest.setdefault("input_dtype", "float32")
    if "quantization" in manifest:
        quantize.validate_quantization(manifest["quantization"])
        q = manifest["quantization"]
        if "compute_dtype" not in q and q.get("dtype") in quantize.SERVING_DTYPES:
            q["compute_dtype"] = quantize.default_compute_dtype(q["dtype"])
    return manifest
