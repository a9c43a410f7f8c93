"""Compile for a v5e with no chip: the cheap pre-flight before a chip run.

The installed libtpu compiles for a TPU it does not have. Given a topology
description (``jax.experimental.topologies.get_topology_desc``), XLA:TPU and
Mosaic compile a lowered program against shardings over the described
devices, on this CPU host, in seconds. That catches — before any chip time
is spent — what lowering alone cannot: a shape cast Mosaic refuses, a kernel
over its VMEM, a step that does not fit the chip's memory.

    python tools/aot_preflight.py --kernels
        every Pallas wrapper at the shapes chip_smoke.py runs on the chip:
        compiled, and on the path (Mosaic kernel / XLA reference) its
        envelope promises

    python tools/aot_preflight.py --train-step tgs_salt_bf16 [--chips 4]
        a preset's jitted train step at its published width and batch:
        compile seconds, temp memory per chip, Mosaic calls in the module

It compiles; it runs nothing. Numerics, fit at run time and speed need the
chip (chip_smoke.py). The host backend it runs on is the CPU (it pins that
itself); the TPU appears only as a compile target.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TOPOLOGY = "v5e:2x2"  # four v5e chips; one-chip programs use the first


def _force_tpu_dispatch() -> None:
    """The wrappers choose kernel-or-reference from the default backend, which
    here is the CPU; the program being compiled is for the TPU."""
    from tensorflowdistributedlearning_tpu.ops import pallas_kernels

    pallas_kernels.pallas_platform_ok = lambda: True


def preflight_kernels(topo) -> bool:
    import jax
    from jax.sharding import SingleDeviceSharding

    import chip_smoke

    sharding = SingleDeviceSharding(topo.devices[0])
    ok = True
    for case in chip_smoke.kernel_cases():
        specs = [
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in case["args"]
        ]
        row = {"name": case["name"], "expected_path": case["expected_path"]}
        t0 = time.perf_counter()
        try:
            compiled = jax.jit(case["fn"]).lower(*specs).compile()
            row["path"] = (
                "mosaic" if chip_smoke.holds_mosaic_call(compiled.as_text())
                else "reference"
            )
            row["ok"] = row["path"] == case["expected_path"]
        except Exception as e:  # noqa: BLE001 — one refusal is one row
            row.update(ok=False, error=f"{type(e).__name__}: {str(e)[:400]}")
        row["compile_s"] = round(time.perf_counter() - t0, 2)
        ok = ok and row["ok"]
        print(json.dumps(row), flush=True)
    return ok


def preflight_train_step(topo, preset_name: str, chips: int, batch) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflowdistributedlearning_tpu.configs import get_preset
    from tensorflowdistributedlearning_tpu.models import build_model, sample_input
    from tensorflowdistributedlearning_tpu.parallel import mesh as mesh_lib
    from tensorflowdistributedlearning_tpu.train import step as step_lib
    from tensorflowdistributedlearning_tpu.train.state import create_train_state

    preset = get_preset(preset_name)
    cfg = preset.model
    global_batch = batch or preset.global_batch
    mesh = mesh_lib.make_mesh(devices=list(topo.devices[:chips]))
    segmentation = cfg.num_classes is None and cfg.decoder is None
    task = (
        step_lib.SegmentationTask() if segmentation
        else step_lib.fit_task(cfg, preset.train)
    )
    replicated = NamedSharding(mesh, P())
    state = jax.eval_shape(
        lambda: create_train_state(
            build_model(cfg),
            step_lib.make_optimizer(preset.train),
            jax.random.PRNGKey(0),
            sample_input(cfg),
        )
    )
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=replicated),
        state,
    )

    def batch_spec(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=mesh_lib.batch_sharding(mesh, len(shape))
        )

    stream = getattr(task, "batches", None)
    if stream is not None:  # a task with a stream of its own: one batch of it
        example = next(stream(global_batch, seed=0, steps=1))
        batch = {k: batch_spec(v.shape, v.dtype) for k, v in example.items()}
    else:
        h, w = cfg.input_shape
        batch = {
            "images": batch_spec((global_batch, h, w, cfg.input_channels), np.float32),
            "labels": (
                batch_spec((global_batch, h, w, 1), np.float32) if segmentation
                else batch_spec((global_batch,), np.int32)
            ),
        }
    # the state donated, as the trainers run it: the updated state reuses
    # its buffers
    step = step_lib.make_train_step(mesh, task, donate=True)
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    row = {
        "preset": preset_name,
        "chips": chips,
        "global_batch": global_batch,
        "compile_s": round(time.perf_counter() - t0, 1),
        "mosaic_calls": compiled.as_text().count("tpu_custom_call"),
    }
    memory = compiled.memory_analysis()
    if memory is not None:
        row["temp_gib_per_chip"] = round(
            memory.temp_size_in_bytes / (1 << 30), 2
        )
        row["argument_gib_per_chip"] = round(
            memory.argument_size_in_bytes / (1 << 30), 2
        )
        row["output_gib_per_chip"] = round(
            memory.output_size_in_bytes / (1 << 30), 2
        )
        row["alias_gib_per_chip"] = round(
            memory.alias_size_in_bytes / (1 << 30), 2
        )
    print(json.dumps(row), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--kernels", action="store_true")
    parser.add_argument("--train-step", metavar="PRESET", default=None)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--batch-size", type=int, default=None,
                        help="global batch (default: the preset's)")
    args = parser.parse_args(argv)
    if not (args.kernels or args.train_step):
        parser.error("nothing to compile: pass --kernels or --train-step")

    # the host backend is the CPU; the TPU is only what we compile for
    os.environ["JAX_PLATFORMS"] = "cpu"
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY
    )
    print(json.dumps({
        "compile_target": topo.devices[0].device_kind,
        "devices": len(topo.devices),
        "note": "compiled for the TPU on a CPU host; nothing was run",
    }), flush=True)
    _force_tpu_dispatch()
    ok = True
    if args.kernels:
        ok = preflight_kernels(topo) and ok
    if args.train_step:
        # a step that does not compile raises: the traceback is the report
        preflight_train_step(
            topo, args.train_step, args.chips, args.batch_size
        )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
