"""Profile one preset's train step on the current backend and print the XLA op
breakdown.

This is the "where does the time go" probe: it builds the SAME train step
bench.py measures (preset model config, shard_map step, AOT-compiled
executable, timing that ends in block_until_ready), captures a
``jax.profiler`` trace around N timed steps, and folds the device plane into
coarse buckets with utils/xplane.py.

Usage (on the chip, or --platform cpu):
    python tools/profile_step.py --preset resnet50_classic_imagenet \
        --batch 256 --steps 5 --logdir /tmp/prof
Prints one JSON line: {"preset", "step_time_ms", "buckets": {...}, "top_ops": [...]}.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--preset", default="resnet50_classic_imagenet")
    parser.add_argument("--batch", type=int, default=256, help="per-chip batch")
    parser.add_argument("--steps", type=int, default=5, help="traced steps")
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--logdir", default="/tmp/tfdl_profile")
    parser.add_argument("--top", type=int, default=15)
    parser.add_argument(
        "--s2d",
        action="store_true",
        help="override stem_space_to_depth=True on the preset's model config",
    )
    parser.add_argument(
        "--no-trace",
        action="store_true",
        help="timing only (skip jax.profiler; faster, no breakdown)",
    )
    parser.add_argument(
        "--platform",
        default=None,
        help="force a backend (e.g. cpu)",
    )
    args = parser.parse_args(argv)

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from tensorflowdistributedlearning_tpu.utils import compile_cache

    compile_cache.configure()

    import numpy as np

    from tensorflowdistributedlearning_tpu.config import TrainConfig
    from tensorflowdistributedlearning_tpu.configs import PRESETS
    from tensorflowdistributedlearning_tpu.models import build_model
    from tensorflowdistributedlearning_tpu.parallel.mesh import (
        make_mesh,
        replicate,
        shard_batch,
    )
    from tensorflowdistributedlearning_tpu.train.state import create_train_state
    from tensorflowdistributedlearning_tpu.data.synthetic import (
        synthetic_segmentation_batch,
    )
    from tensorflowdistributedlearning_tpu.train.step import (
        ClassificationTask,
        SegmentationTask,
        make_optimizer,
        make_train_step,
    )
    from tensorflowdistributedlearning_tpu.utils import xplane
    from tensorflowdistributedlearning_tpu.utils.profiling import sync, trace

    cfg = PRESETS[args.preset].model
    if args.s2d:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, stem_space_to_depth=True)
    devices = jax.devices()
    n = len(devices)
    mesh = make_mesh(n)
    model = build_model(cfg)
    h, w = cfg.input_shape
    sample = np.zeros((1, h, w, cfg.input_channels), np.float32)
    state = replicate(
        create_train_state(model, make_optimizer(TrainConfig()), jax.random.PRNGKey(0), sample),
        mesh,
    )
    gen = np.random.default_rng(0)
    global_b = args.batch * n
    # segmentation presets (tgs_salt*) have no class count — dense [B,H,W,1]
    # labels and the SegmentationTask loss; classification presets get the
    # integer-label task bench.py's headline measures
    if cfg.num_classes:
        batch = {
            "images": gen.normal(0, 1, (global_b, h, w, cfg.input_channels)).astype(
                np.float32
            ),
            "labels": gen.integers(0, cfg.num_classes, global_b).astype(np.int32),
        }
        task = ClassificationTask()
    else:
        batch = synthetic_segmentation_batch(
            gen, global_b, input_shape=(h, w), channels=cfg.input_channels
        )
        task = SegmentationTask()
    batch = shard_batch(batch, mesh)
    step = make_train_step(mesh, task, donate=False)
    comp = step.lower(state, batch).compile()
    s = state
    for _ in range(max(args.warmup, 1)):  # >=1: the timed loop needs a synced start
        s, metrics = comp(s, batch)
    sync(metrics)

    import contextlib

    t0 = time.perf_counter()
    with contextlib.nullcontext() if args.no_trace else trace(args.logdir):
        for _ in range(args.steps):
            s, metrics = comp(s, batch)
        sync(metrics)
    dt = time.perf_counter() - t0

    if args.no_trace:
        print(
            json.dumps(
                {
                    "preset": args.preset,
                    "s2d": args.s2d,
                    "platform": devices[0].platform,
                    "global_batch": global_b,
                    "step_time_ms": round(dt / args.steps * 1000, 2),
                    "images_per_sec_per_chip": round(
                        global_b * args.steps / dt / n, 1
                    ),
                }
            ),
            flush=True,
        )
        return 0

    plane = "TPU" if devices[0].platform == "tpu" else "/host:CPU"
    rows = xplane.op_breakdown(args.logdir, plane_filter=plane)
    print(
        json.dumps(
            {
                "preset": args.preset,
                "platform": devices[0].platform,
                "global_batch": global_b,
                "step_time_ms": round(dt / args.steps * 1000, 2),
                "planes": xplane.plane_names(args.logdir),
                "buckets_ms": xplane.grouped_breakdown(rows),
                "top_ops": [dataclasses.asdict(r) for r in rows[: args.top]],
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
