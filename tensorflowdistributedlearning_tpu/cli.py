"""Command-line driver — the reference's notebook cells as a CLI.

The reference was driven by two notebooks that loaded Kaggle CSVs, binned mask
coverage into stratification classes, and called ``Model(...).train(X, y, 64, 10000)``
(reference: Untitled.ipynb cells 0-8, Test.ipynb cells 7-8; SURVEY §2.1 C13). This CLI
covers the same flows plus a synthetic smoke mode that needs no data on disk:

    python -m tensorflowdistributedlearning_tpu train   --data-dir D --model-dir M [...]
    python -m tensorflowdistributedlearning_tpu predict --test-dir T --model-dir M [...]
    python -m tensorflowdistributedlearning_tpu smoke   [--steps N]
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import shutil
import sys
from typing import Dict, List, Optional

import numpy as np


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--model-dir", required=True)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--n-devices", type=int, default=None)
    p.add_argument("--n-fold", type=int, default=5)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--input-shape", type=int, nargs=2, default=(101, 101))
    p.add_argument("--n-blocks", type=int, nargs="+", default=(3, 4, 6))
    p.add_argument("--base-depth", type=int, default=256)
    p.add_argument("--backbone", choices=("resnet", "xception"), default="resnet")
    p.add_argument("--block-type", choices=("bottleneck", "basic_block"),
                   default="bottleneck")
    p.add_argument("--dtype", choices=("float32", "bfloat16"), default="float32")
    p.add_argument("--sequence-parallel", type=int, default=1,
                   help="H-shard the backbone over this many devices per "
                   "data-parallel replica (halo-exchange spatial parallelism)")
    p.add_argument("--sync-bn", action="store_true",
                   help="synchronized cross-shard BatchNorm: statistics over "
                   "the GLOBAL batch instead of per shard (cross-replica BN; "
                   "+7.8 points at digits scale, DIGITS_RUN.json)")
    p.add_argument("--model-parallel", type=int, default=1,
                   help="channel-shard params/optimizer over this many devices "
                   "per replica (tensor parallelism; the K-fold trainer runs "
                   "it in shard_map's hybrid auto-model mode)")
    p.add_argument("--weight-update-sharding", action="store_true",
                   help="ZeRO-1: shard optimizer state and the weight update "
                   "across the data-parallel axis — per-chip optimizer memory "
                   "drops ~dp-fold at neutral step time, numerics unchanged "
                   "(arXiv:2004.13336)")


def _add_host_loop(p: argparse.ArgumentParser) -> None:
    """Host-loop overlap knobs shared by the training commands (train/fit).

    Defaults are None so the config's own defaults (TrainConfig or the
    preset's) stay the single source of truth — the flags only override."""
    p.add_argument("--prefetch-depth", type=int, default=None,
                   help="host→device input prefetch depth: the loader thread "
                   "stays this many placed batches ahead of the train loop "
                   "(>= 1; per-window queue-depth telemetry shows underruns "
                   "in telemetry-report; default: the config's, 2)")
    p.add_argument("--dispatch-ahead", type=int, default=None,
                   help="host-device overlap budget: dispatch at most this "
                   "many unretired train steps ahead of the device, with log "
                   "windows deferring their metric fetch one window so the "
                   "device queue never drains on a log line; 0 = the "
                   "synchronous legacy loop (numerics identical either way; "
                   "default: the config's, 2)")
    p.add_argument("--data-workers", type=int, default=None,
                   help="parallel input-service workers (data/service.py): "
                   "N background read+decode workers execute the index-keyed "
                   "global-shuffle batch plan; batch CONTENT is worker-count "
                   "invariant, so this is pure throughput. 0 = the legacy "
                   "in-line input streams (default: the config's, 2)")


def _add_observability(p: argparse.ArgumentParser) -> None:
    """Tracing/health knobs shared by the training commands (train/fit).

    Defaults are None so the config's own defaults stay the single source of
    truth — the flags only override."""
    p.add_argument("--trace-sample-rate", type=float, default=None,
                   help="fraction of traces (per train step / eval pass / "
                   "checkpoint) persisted as `trace` ledger events, "
                   "exportable via `telemetry-report --export-trace` as "
                   "Chrome/Perfetto JSON; 0 disables tracing (the config "
                   "default)")
    p.add_argument("--nan-guard", choices=("warn", "abort", "off"),
                   default=None,
                   help="NaN/Inf loss guard action: warn (alert and keep "
                   "training), abort (alert then stop at a recorded "
                   "boundary), off; default: the config's (warn). Drill "
                   "with --inject-fault nan-loss@N")
    p.add_argument("--profile-every-windows", type=int, default=None,
                   help="continuous profiling cadence: capture a short "
                   "windowed jax.profiler trace every N log windows, parse "
                   "it into a per-op roofline, and ledger profile_capture/"
                   "op_roofline events (obs/profiler.py). 0 disables (the "
                   "config default); overhead is gated <=2%% by `bench.py "
                   "--profile-overhead`. Alert-triggered postmortem "
                   "captures fire regardless of this cadence")


def _add_compile_cache(p: argparse.ArgumentParser) -> None:
    """The shared cold-start knob (train/fit/serve/serve-fleet) —
    utils/compile_cache.py."""
    p.add_argument("--compile-cache-dir", default=None, metavar="DIR",
                   help="persistent XLA compile cache: executables land in "
                   "DIR keyed on module + jaxlib + flags + device kinds, so "
                   "a second same-shape run (or the next replica/resize) "
                   "LOADS instead of compiling. JAX_COMPILATION_CACHE_DIR, "
                   "when set, wins over this flag; with neither, an "
                   "accelerator run caches in .jax_cache_tpu at the checkout "
                   "root and a CPU run does not cache. Hits/misses ride the "
                   "compile ledger events and telemetry-report's hit-ratio "
                   "line; an unwritable DIR is an error")


def _add_planner(p: argparse.ArgumentParser) -> None:
    """Layout-selection knobs shared by the training commands (train/fit) —
    parallel/planner.py."""
    p.add_argument("--parallelism", choices=("explicit", "auto"),
                   default="explicit",
                   help="'auto' derives the whole (dp, tp, pp, spatial, "
                   "zero1) layout from the model's exact param/opt-state "
                   "accounting, the per-chip HBM budget, and the device "
                   "topology (parallel/planner.py); any parallelism flag "
                   "you set explicitly stays pinned (explicit flags win). "
                   "'explicit' (default) runs your flags verbatim, "
                   "validated through the same planner so indivisible "
                   "degrees fail fast with a named constraint. Either way "
                   "the chosen plan rides the run-header ledger event; "
                   "inspect candidates with the `plan` subcommand")
    p.add_argument("--hbm-budget-gb", type=float, default=None,
                   help="per-chip HBM budget in GiB for the planner's "
                   "feasibility gate (default: the backend's reported "
                   "bytes_limit; CPU builds report none)")


def _add_resilience(p: argparse.ArgumentParser) -> None:
    """Flags shared by the training commands (train/fit) — resilience/."""
    from tensorflowdistributedlearning_tpu.resilience.preempt import (
        EXIT_PREEMPTED,
    )

    p.add_argument("--inject-fault", default=None, metavar="SPEC",
                   help="deterministic fault injection for drills and tests: "
                   "KIND@AT[xCOUNT] with KIND in raise|sigterm|io-data|"
                   "io-read|io-ckpt|nan-loss (e.g. 'sigterm@12' preempts "
                   "after step 12; 'raise@5-20' crashes at a seeded-random "
                   "step; 'io-ckpt@1' makes the first checkpoint write fail "
                   "transiently; 'nan-loss@2' poisons the 2nd observed loss "
                   "window with NaN — the health-monitor drill)")
    p.add_argument("--max-restarts", type=int, default=None,
                   help="run under the restart supervisor: relaunch this "
                   "command after crashes/preemptions (exponential backoff + "
                   "jitter) up to this many times, aborting early when no "
                   "step progress is made between restarts; 0 (the default) "
                   "= unsupervised. Under --elastic this is the SAME-SHAPE "
                   "restart budget for plain crashes (default 3 there; an "
                   "explicit 0 disables same-shape restarts)")
    p.add_argument("--preempt-notice-file", default=None, metavar="PATH",
                   help="also treat the appearance of this file as a "
                   "preemption notice (for environments that cannot deliver "
                   "SIGTERM to the training process); same semantics as the "
                   "signal: final checkpoint at the next step boundary, "
                   f"exit code {EXIT_PREEMPTED}")


def _add_elastic(p: argparse.ArgumentParser) -> None:
    """Elastic multi-process training (parallel/elastic.py): run N host-slot
    processes under the elastic coordinator; a host death or sustained
    straggler triggers a checkpoint-coordinated world resize instead of a
    dead run."""
    p.add_argument("--elastic", type=int, default=0, metavar="HOSTS",
                   help="run this command as an elastic multi-process pod of "
                   "HOSTS host-slot processes (jax.distributed over gloo on "
                   "CPU; one process per host on real pods): a SIGKILLed/"
                   "OOMed host triggers a coordinated drain (preemption "
                   "checkpoints where collectives still work), a planner "
                   "re-plan at the new world size, and a resume at HOSTS-1 "
                   "with ZeRO-1 optimizer state resharded and the data "
                   "service re-dealt; plain crashes restart same-shape "
                   "under the usual budget. 0 = off")
    p.add_argument("--min-hosts", type=int, default=1,
                   help="never resize below this world size: a resize that "
                   "would cross it aborts the run instead (elastic_abort)")
    p.add_argument("--devices-per-host", type=int, default=None,
                   help="force this many XLA host-platform devices per child "
                   "process (the CPU pod harness; real TPU hosts expose "
                   "their chips without it)")
    p.add_argument("--drain-timeout", type=float, default=45.0,
                   help="seconds survivors get to finish their preemption "
                   "checkpoint during a resize drain before being killed "
                   "(a DEAD peer can wedge their collectives; resume then "
                   "falls back to the last complete checkpoint)")
    p.add_argument("--no-straggler-evict", action="store_true",
                   help="disable straggler-triggered host eviction (the "
                   "coordinator still resizes on host death)")
    p.add_argument("--evict-threshold", type=float, default=1.25,
                   help="straggler skew threshold (worst-host mean step time "
                   "/ fleet median) a window must cross to count toward "
                   "eviction — obs/fleet.py's straggler attribution")
    p.add_argument("--evict-sustained", type=int, default=3,
                   help="consecutive alerted windows naming the SAME host "
                   "before it is evicted (a clean window resets the streak "
                   "— flapping hosts never oscillate the world)")
    p.add_argument("--evict-cooldown", type=float, default=60.0,
                   help="seconds after any resize during which no eviction "
                   "fires (the resized fleet re-warms, which looks exactly "
                   "like a straggler)")
    p.add_argument("--aot-standby", action="store_true",
                   help="after each generation settles, background-compile "
                   "the NEXT world size's (world-1) step function into the "
                   "shared --compile-cache-dir from a rank-for-rank standby "
                   "mini-world on a scratch workdir (cache keys bind the "
                   "process-local topology), so a resize's respawn loads "
                   "its executables instead of rebuilding them (requires "
                   "--compile-cache-dir; ledgered as aot_standby events, "
                   "measured by world_settled.settle_s)")
    p.add_argument("--host-inject-fault", action="append", default=[],
                   metavar="HOST:SPEC",
                   help="drill: pass --inject-fault SPEC to host-slot HOST "
                   "of the INITIAL generation (e.g. '1:sigkill-step@6' "
                   "vanishes host 1 after step 6 — the headline host-death "
                   "resize drill)")
    # the coordinator's child-process seam: one host slot of an explicit
    # jax.distributed world (also usable by hand for multi-host CPU/GPU runs)
    p.add_argument("--coordinator-address", default=None, metavar="HOST:PORT",
                   help="join an explicit jax.distributed cluster at this "
                   "coordinator (multihost.initialize); without it the run "
                   "is one process over this host's chips and joins "
                   "nothing. Set by the elastic coordinator for its "
                   "children")
    p.add_argument("--num-processes", type=int, default=None,
                   help="world size of the explicit jax.distributed cluster")
    p.add_argument("--process-id", type=int, default=None,
                   help="this process's rank in the explicit cluster")


def _add_auto_promote(p: argparse.ArgumentParser) -> None:
    """Close the train->serve loop from the training CLI: after
    --export-serving, hand the fresh artifact straight to a live fleet's
    promotion controller. The exit status IS the promotion verdict."""
    p.add_argument("--auto-promote", action="store_true",
                   help="after --export-serving, promote the exported "
                   "artifact onto the live serve-fleet found via "
                   "--fleet-workdir/--router: quantize-check admission "
                   "(manifest gate), shadow-compared canary, rolling "
                   "restart, auto-rollback — exit 0 only when the fleet "
                   "completes the flip (what the flywheel controller runs)")
    p.add_argument("--fleet-workdir", default=None, metavar="DIR",
                   help="the live fleet's workdir: its router endpoint is "
                   "read from the run-header ledger event")
    p.add_argument("--router", default=None, metavar="URL",
                   help="the live fleet router's base URL (overrides "
                   "--fleet-workdir)")
    p.add_argument("--promote-model", default=None,
                   help="multi-tenant fleet: the registry model to promote")
    p.add_argument("--promote-shadow-secs", type=float, default=None,
                   help="shadow window length for the auto-promotion "
                   "(default: the controller's)")
    p.add_argument("--promote-min-requests", type=int, default=None,
                   help="shadow compare floor (PromoteConfig "
                   "shadow_min_requests)")
    p.add_argument("--promote-max-disagree", type=float, default=None,
                   help="class-disagreement ceiling for the shadow compare "
                   "— a RETRAINED candidate legitimately disagrees with "
                   "the incumbent more than a re-quantized one, loosen "
                   "accordingly")
    p.add_argument("--promote-max-abs-delta", type=float, default=None,
                   help="max |delta| ceiling on float outputs during shadow")
    p.add_argument("--promote-max-mean-delta", type=float, default=None,
                   help="mean |delta| ceiling on float outputs during shadow")
    p.add_argument("--promote-min-iou", type=float, default=None,
                   help="mask-IoU floor for the shadow compare")
    p.add_argument("--promote-max-p99-ratio", type=float, default=None,
                   help="canary latency gate (PromoteConfig max_p99_ratio)")
    p.add_argument("--promote-timeout", type=float, default=600.0,
                   help="seconds to wait for a terminal promotion state")


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tensorflowdistributedlearning_tpu",
        description="TPU-native K-fold segmentation training framework",
    )
    sub = parser.add_subparsers(dest="command", required=True)

    p_train = sub.add_parser("train", help="K-fold cross-validated training")
    _add_common(p_train)
    p_train.add_argument("--data-dir", required=True,
                         help="directory with images/*.png and masks/*.png")
    p_train.add_argument("--lr", type=float, default=0.001)
    p_train.add_argument("--steps", type=int, default=10_000)
    p_train.add_argument("--save-best", type=int, default=5)
    p_train.add_argument("--checkpoint-every", type=int, default=500)
    p_train.add_argument("--eval-throttle-secs", type=int, default=300)
    p_train.add_argument("--export-serving", action="store_true",
                         help="after training, export the best fold's "
                         "standalone StableHLO serving artifact next to its "
                         "checkpoint ({fold_dir}/export/serving)")
    p_train.add_argument("--serving-dtype",
                         choices=("float32", "bfloat16", "int8",
                                  "int8-compute"),
                         default="float32",
                         help="post-training precision spec for "
                         "--export-serving (train/quantize.py): bfloat16 "
                         "casts params at export, int8 stores conv/dense "
                         "kernels as int8 with per-channel symmetric scales "
                         "(activations bf16), int8-compute stores the same "
                         "bytes and runs the matmul/conv arithmetic in int8 "
                         "via the quant kernels; quantized exports land in "
                         "export/serving-{spec} beside the float32 "
                         "reference and must pass quantize-check to ship")
    _add_auto_promote(p_train)
    _add_planner(p_train)
    _add_host_loop(p_train)
    _add_observability(p_train)
    _add_resilience(p_train)
    _add_compile_cache(p_train)

    p_pred = sub.add_parser("predict", help="fold x TTA ensemble prediction")
    _add_common(p_pred)
    p_pred.add_argument("--test-dir", required=True)
    p_pred.add_argument("--artifact-dir", default=None,
                        help="run inference from an exported StableHLO "
                        "serving artifact (through the bucketed serve "
                        "engine) instead of restoring checkpoints; "
                        "--model-dir is ignored")
    p_pred.add_argument("--no-tta", action="store_true",
                        help="disable test-time augmentation (single forward pass)")
    p_pred.add_argument("--output", default=None,
                        help="write predictions to this .npz (default: stdout summary)")
    p_pred.add_argument("--submission", default=None,
                        help="also write a Kaggle RLE submission csv here")

    p_smoke = sub.add_parser(
        "smoke", help="synthetic end-to-end training smoke (no data needed)"
    )
    p_smoke.add_argument("--steps", type=int, default=10)
    p_smoke.add_argument("--batch-size", type=int, default=8)
    p_smoke.add_argument("--n-devices", type=int, default=None)

    p_fit = sub.add_parser(
        "fit",
        help="single-run classification training from a named preset "
        "(streaming ImageFolder data; synthetic when --data-dir is omitted)",
    )
    p_fit.add_argument("--preset", required=True)
    p_fit.add_argument("--model-dir", required=True)
    p_fit.add_argument("--data-dir", default=None,
                       help="ImageFolder root with train/{class}/*.png "
                       "(+ optional val/); omitted = synthetic data")
    p_fit.add_argument("--steps", type=int, default=100)
    p_fit.add_argument("--batch-size", type=int, default=None,
                       help="global batch (default: the preset's)")
    p_fit.add_argument("--eval-every", type=int, default=None)
    p_fit.add_argument("--sequence-parallel", type=int, default=1)
    p_fit.add_argument("--sync-bn", action="store_true",
                       help="synchronized cross-shard BatchNorm (global-batch "
                       "statistics)")
    p_fit.add_argument("--model-parallel", type=int, default=1,
                       help="GSPMD tensor parallelism: shard params/optimizer "
                       "over this many devices per replica")
    p_fit.add_argument("--pipeline-parallel", type=int, default=1,
                       help="GPipe pipeline parallelism over ViT blocks: this "
                       "many stages per replica (backbone=vit presets only)")
    p_fit.add_argument("--pipeline-microbatches", type=int, default=None,
                       help="microbatches per local batch for the pipeline "
                       "schedule (default: one per stage; set >> stages to "
                       "shrink the fill/drain bubble)")
    p_fit.add_argument("--expert-parallel", type=int, default=1,
                       help="expert parallelism for MoE presets: one expert "
                       "per shard with all-to-all dispatch (must equal the "
                       "preset's moe_experts)")
    p_fit.add_argument("--weight-update-sharding", action="store_true",
                       default=None,
                       help="ZeRO-1: shard optimizer state and the weight "
                       "update across the data-parallel axis — per-chip "
                       "optimizer memory drops ~dp-fold at neutral step "
                       "time, numerics unchanged (arXiv:2004.13336); "
                       "default: the preset's setting")
    p_fit.add_argument("--eval-holdout-fraction", type=float, default=None,
                       help="with record shards and no val split: hold out "
                       "this fraction of train shards as the eval split")
    p_fit.add_argument("--optimizer", choices=("adam", "sgd", "lars"), default=None,
                       help="override the preset's optimizer (sgd = Nesterov "
                       "momentum, the standard ImageNet recipe; lars = "
                       "large-batch layer-wise scaling); requires "
                       "--lr when it differs from the preset's pairing")
    p_fit.add_argument("--lr", type=float, default=None,
                       help="override the preset's learning rate")
    p_fit.add_argument("--ema-decay", type=float, default=None,
                       help="track a parameter EMA at this decay (e.g. 0.9999) "
                       "and evaluate/export the averaged weights; 0 disables")
    p_fit.add_argument("--grad-accum", type=int, default=None,
                       help="accumulate gradients over this many sequential "
                       "microbatches per step (one optimizer update on their "
                       "mean): effective batch = accum x batch at one "
                       "microbatch's activation memory")
    p_fit.add_argument("--grad-clip", type=float, default=None,
                       help="clip gradients to this global l2 norm before the "
                       "optimizer update; 0 disables")
    p_fit.add_argument("--augmentation",
                       choices=("flip_crop", "crop", "none", "mixup", "cutmix"),
                       default=None,
                       help="override the preset's train augmentation policy "
                       "(crop drops the mirror — digits/text; none streams "
                       "batches untouched; mixup/cutmix add image/label "
                       "mixing on top of flip_crop)")
    p_fit.add_argument("--export-serving", action="store_true",
                       help="after training, export the best checkpoint's "
                       "standalone StableHLO serving artifact "
                       "({model_dir}/export/serving) and stamp its "
                       "drift_baseline (output distribution over the pinned "
                       "eval batch) into the manifest")
    p_fit.add_argument("--serving-dtype",
                       choices=("float32", "bfloat16", "int8",
                                "int8-compute"),
                       default="float32",
                       help="post-training precision spec for "
                       "--export-serving (quantized exports land in "
                       "export/serving-{spec}; int8-compute runs real int8 "
                       "matmul/conv arithmetic via ops/quant_kernels.py)")
    _add_auto_promote(p_fit)
    _add_planner(p_fit)
    _add_host_loop(p_fit)
    _add_observability(p_fit)
    _add_resilience(p_fit)
    _add_elastic(p_fit)
    _add_compile_cache(p_fit)

    p_plan = sub.add_parser(
        "plan",
        help="print the parallelism planner's candidate table for a model + "
        "batch + topology: chosen layout, predicted params/opt/activation "
        "bytes per chip (exact tree_bytes_per_device accounting for "
        "params+opt), headroom against the HBM budget, and why each "
        "rejected candidate lost (parallel/planner.py)",
    )
    p_plan.add_argument("--preset", default=None,
                        help="plan for a named preset's model+train config "
                        "(batch defaults to the preset's global batch)")
    p_plan.add_argument("--batch-size", type=int, default=None,
                        help="global batch (default: the preset's, else 64)")
    p_plan.add_argument("--n-devices", type=int, default=None)
    p_plan.add_argument("--hbm-gb", type=float, default=None,
                        help="per-chip HBM budget in GiB (default: the "
                        "backend's reported bytes_limit; CPU builds report "
                        "none — feasibility is then divisibility-only)")
    p_plan.add_argument("--grad-accum", type=int, default=None)
    # pin any subset of the layout; the planner fills the rest by score
    p_plan.add_argument("--model-parallel", type=int, default=None)
    p_plan.add_argument("--pipeline-parallel", type=int, default=None)
    p_plan.add_argument("--sequence-parallel", type=int, default=None)
    p_plan.add_argument("--expert-parallel", type=int, default=None)
    p_plan.add_argument("--weight-update-sharding", action="store_true",
                        default=None)
    # model args for preset-less planning (mirror `train`'s)
    p_plan.add_argument("--backbone", choices=("resnet", "xception", "vit"),
                        default="resnet")
    p_plan.add_argument("--input-shape", type=int, nargs=2, default=(101, 101))
    p_plan.add_argument("--n-blocks", type=int, nargs="+", default=(3, 4, 6))
    p_plan.add_argument("--base-depth", type=int, default=256)
    p_plan.add_argument("--block-type",
                        choices=("bottleneck", "basic_block"),
                        default="bottleneck")
    p_plan.add_argument("--dtype", choices=("float32", "bfloat16"),
                        default="float32")
    p_plan.add_argument("--num-classes", type=int, default=None,
                        help="classification head (default: the "
                        "segmentation head, like `train`)")
    p_plan.add_argument("--measured-margin-from", default=None,
                        metavar="WORKDIR",
                        help="close the activation-estimate feedback loop: "
                        "read the ledgered measured-vs-predicted "
                        "memory_watermark residual from this prior run's "
                        "workdir and add it to every candidate's budget "
                        "check (what the elastic coordinator does "
                        "automatically on re-plan)")
    p_plan.add_argument("--measured-costs-from", default=None,
                        metavar="WORKDIR",
                        help="close the cost-model feedback loop: score "
                        "candidates with the achieved FLOP/s and collective "
                        "bytes/s from this prior run's ledgered op_roofline "
                        "events (profile once with --profile-every-windows, "
                        "plan better forever after) instead of the analytic "
                        "peak-FLOPs table + ICI constant; the table then "
                        "shows measured vs analytic scores side by side and "
                        "the provenance rides the run header. Exits 2 when "
                        "the workdir has no roofline events")
    p_plan.add_argument("--json", action="store_true",
                        help="full machine-readable plan (chosen layout + "
                        "every candidate's verdict) instead of the table")

    p_serve = sub.add_parser(
        "serve",
        help="dynamic-batching HTTP inference server over an exported "
        "StableHLO artifact (bucketed compilation, bounded-queue "
        "backpressure, /v1/predict + /healthz + /metrics)",
    )
    p_serve.add_argument("--artifact-dir", default=None,
                         help="artifact directory from export_serving "
                         "(serving.stablehlo + manifest.json); required "
                         "unless --registry names the artifacts")
    p_serve.add_argument("--registry", default=None, metavar="PATH",
                         help="multi-tenant load: a registry.json "
                         "(serve/registry.py schema) — EVERY entry's "
                         "artifact loads into this replica as its own "
                         "engine + micro-batcher, requests route by the "
                         "payload's \"model\" key, and per-model SLOs / "
                         "bucket ladders / prewarm budgets apply")
    p_serve.add_argument("--model", default=None,
                         help="name this replica serves under (the registry "
                         "entry a fleet bound it to); stamps /healthz "
                         "identity, per-model metrics labels, and "
                         "serve_window events")
    p_serve.add_argument("--model-version", type=int, default=None,
                         help="registry version of the served artifact "
                         "(advertised on /healthz and /metrics; flips on "
                         "promote)")
    p_serve.add_argument("--prewarm-buckets", type=int, default=None,
                         help="warm only the first K buckets of the ladder "
                         "at spawn (smallest first); colder buckets compile "
                         "on first hit, ledgered per bucket as "
                         "serve/cold_bucket_hits — trades spawn-to-ready "
                         "time against first-request stalls")
    p_serve.add_argument("--visible-devices", default=None, metavar="IDS",
                         help="comma-separated accelerator ordinals this "
                         "replica may claim (exported as *_VISIBLE_DEVICES "
                         "before the runtime initializes) — how a "
                         "multi-tenant fleet places replicas on disjoint "
                         "chips")
    p_serve.add_argument("--host", default="127.0.0.1")
    p_serve.add_argument("--port", type=int, default=8000,
                         help="0 = any free port (printed on startup)")
    p_serve.add_argument("--buckets", type=int, nargs="+",
                         default=(1, 4, 16, 64),
                         help="batch-bucket ladder; each bucket is compiled "
                         "once at warmup, requests pad up to the smallest "
                         "fit — steady state never recompiles")
    p_serve.add_argument("--max-wait-ms", type=float, default=5.0,
                         help="micro-batcher coalescing window after the "
                         "first queued request")
    p_serve.add_argument("--queue-size", type=int, default=256,
                         help="bounded request queue; a full queue rejects "
                         "immediately with HTTP 429 (backpressure, not "
                         "unbounded memory)")
    p_serve.add_argument("--default-deadline-ms", type=float, default=None,
                         help="deadline applied to requests that carry none; "
                         "expired requests answer 504 without burning a "
                         "bucket slot")
    p_serve.add_argument("--workdir", default=None,
                         help="telemetry ledger dir (serve_window events in "
                         "{workdir}/telemetry.jsonl; default: the artifact "
                         "dir)")
    p_serve.add_argument("--window-secs", type=float, default=30.0,
                         help="ledger window cadence; 0 disables periodic "
                         "windows (final window still written on shutdown)")
    p_serve.add_argument("--trace-sample-rate", type=float, default=0.0,
                         help="fraction of requests whose queue/pad/compute "
                         "trace (keyed by the echoed x-request-id) persists "
                         "as `trace` ledger events; 0 disables tracing")
    p_serve.add_argument("--slo-p99-ms", type=float, default=None,
                         help="serving SLO: p99 latency target in ms, "
                         "enforced as a windowed error budget — breaches "
                         "write health_alert ledger events and flip /healthz "
                         "to status=degraded (the fleet-router drain signal)")
    p_serve.add_argument("--slo-error-budget", type=float, default=0.01,
                         help="fraction of requests per window allowed over "
                         "the p99 target before the SLO counts as breached "
                         "(0.01 = the p99 semantics)")
    p_serve.add_argument("--replica-id", type=int, default=0,
                         help="this replica's id in a serving fleet: stamped "
                         "on serve_window ledger events and /healthz, and "
                         "replica i>0 writes telemetry-{i}.jsonl so N "
                         "replicas sharing one --workdir produce per-replica "
                         "ledgers that telemetry-report merges (obs/fleet.py)")
    p_serve.add_argument("--inject-fault", default=None, metavar="SPEC",
                         help="serving-tier fault drill (resilience/faults.py"
                         "): 'sigkill@N' hard-kills this replica after its "
                         "Nth answered request — the deterministic mid-soak "
                         "replica death the fleet failover tests and "
                         "bench_serve --fleet's kill soak converge through")
    p_serve.add_argument("--seed", type=int, default=0,
                         help="seed for ranged --inject-fault specs")
    p_serve.add_argument("--capture-dir", default=None, metavar="DIR",
                         help="arm the traffic-capture tee (loop/capture.py): "
                         "sample accepted requests off the hot path into "
                         "record shards under DIR (self-labeled with the "
                         "served model's argmax), ledgered as capture_window "
                         "events — the raw material `records-ingest` folds "
                         "into a retraining dataset")
    p_serve.add_argument("--capture-fraction", type=float, default=1.0,
                         help="fraction of accepted requests the capture tee "
                         "samples (deterministic stride, not a coin flip)")
    p_serve.add_argument("--capture-quota-mb", type=float, default=64.0,
                         help="disk ceiling for captured shards: oldest "
                         "sealed shards are evicted first when the quota is "
                         "exceeded (the newest shard always survives)")
    p_serve.add_argument("--capture-records-per-shard", type=int, default=64,
                         help="records per sealed capture shard")
    p_serve.add_argument("--drift-threshold", type=float, default=None,
                         help="arm the DriftMonitor (obs/health.py): total-"
                         "variation distance between the serving output "
                         "class distribution and the artifact manifest's "
                         "promotion-time drift_baseline past this emits "
                         "drift_alert ledger events (the flywheel's retrain "
                         "trigger); requires a stamped baseline — skipped "
                         "with a warning otherwise")
    p_serve.add_argument("--drift-min-requests", type=int, default=20,
                         help="window floor before a drift verdict counts")
    p_serve.add_argument("--drift-sustain-windows", type=int, default=2,
                         help="consecutive over-threshold windows before the "
                         "alert fires (one weird window is noise)")
    _add_compile_cache(p_serve)

    p_fleet = sub.add_parser(
        "serve-fleet",
        help="multi-replica serving tier: N `serve` subprocesses (ephemeral "
        "ports, per-replica ledgers, restart-on-death supervision) behind a "
        "queue-depth/p99 load-balancing router with graceful 429 shedding, "
        "plus optional autoscaling on sustained queue depth and the SLO "
        "error budget (fleet_scale ledger events)",
    )
    p_fleet.add_argument("--artifact-dir", default=None,
                         help="artifact directory every replica serves "
                         "(export_serving output); required unless "
                         "--registry (or a registry.json in --workdir) "
                         "names per-model artifacts")
    p_fleet.add_argument("--registry", default=None, metavar="PATH",
                         help="multi-tenant fleet: a registry.json "
                         "(serve/registry.py schema). Each model entry "
                         "spawns its OWN replica set with its artifact, "
                         "bucket ladder, SLO, prewarm budget, fair-share "
                         "weight, and visible-device slots; the router "
                         "routes by the payload's \"model\" key and sheds "
                         "by fair share under saturation. When omitted, a "
                         "registry.json already in --workdir is picked up "
                         "automatically")
    p_fleet.add_argument("--chip-budget", type=int, default=None,
                         help="fleet-wide chip ceiling for per-model "
                         "autoscaling: sum(replicas x chips_per_replica) "
                         "never exceeds this — an over-budget scale-up is "
                         "ledgered as budget_deferred instead of applied")
    p_fleet.add_argument("--workdir", default=None,
                         help="shared fleet workdir: the controller writes "
                         "telemetry.jsonl, replica i telemetry-{i}.jsonl — "
                         "one telemetry-report merges the whole fleet "
                         "(default: the artifact dir)")
    p_fleet.add_argument("--host", default="127.0.0.1",
                         help="router bind host (replicas bind loopback)")
    p_fleet.add_argument("--port", type=int, default=8000,
                         help="router port; 0 = any free port (reported on "
                         "stdout and in the run-header ledger event)")
    p_fleet.add_argument("--replicas", type=int, default=2,
                         help="initial replica count")
    p_fleet.add_argument("--min-replicas", type=int, default=1)
    p_fleet.add_argument("--max-replicas", type=int, default=4)
    p_fleet.add_argument("--no-autoscale", action="store_true",
                         help="fix the fleet at --replicas (supervision and "
                         "routing still run; only scaling decisions are off)")
    p_fleet.add_argument("--queue-high", type=float, default=4.0,
                         help="autoscale pressure threshold: mean queued+"
                         "in-flight requests per replica that count as "
                         "sustained pressure")
    p_fleet.add_argument("--queue-low", type=float, default=0.25,
                         help="autoscale idle threshold (scale-down drain)")
    p_fleet.add_argument("--scale-sustain", type=int, default=3,
                         help="consecutive evaluations a signal must persist "
                         "before a scale decision")
    p_fleet.add_argument("--scale-cooldown-s", type=float, default=15.0,
                         help="seconds after a decision before the next may "
                         "fire")
    p_fleet.add_argument("--autoscale-interval-s", type=float, default=2.0,
                         help="seconds between autoscaler evaluations")
    p_fleet.add_argument("--poll-interval-s", type=float, default=0.5,
                         help="router -> replica /metrics poll cadence (the "
                         "queue-depth/p99/status the routing policy reads)")
    p_fleet.add_argument("--buckets", type=int, nargs="+",
                         default=(1, 4, 16, 64),
                         help="per-replica batch-bucket ladder")
    p_fleet.add_argument("--max-wait-ms", type=float, default=5.0,
                         help="per-replica continuous-batching coalesce "
                         "budget (idle arrivals only; backlog dispatches "
                         "immediately)")
    p_fleet.add_argument("--queue-size", type=int, default=256,
                         help="per-replica bounded request queue (full = "
                         "429 + Retry-After)")
    p_fleet.add_argument("--default-deadline-ms", type=float, default=None)
    p_fleet.add_argument("--window-secs", type=float, default=15.0,
                         help="replica + router ledger window cadence")
    p_fleet.add_argument("--slo-p99-ms", type=float, default=None,
                         help="per-replica serving SLO: breaches flip the "
                         "replica to status=degraded, which the router "
                         "routes around and the autoscaler scales on")
    p_fleet.add_argument("--slo-error-budget", type=float, default=0.01)
    p_fleet.add_argument("--max-restarts-per-replica", type=int, default=3,
                         help="supervision budget: a replica dying more "
                         "than this is abandoned (ledgered), not "
                         "crash-looped")
    p_fleet.add_argument("--replica-inject-fault", action="append",
                         default=None, metavar="ID:SPEC",
                         help="fault drill: pass --inject-fault SPEC to "
                         "replica ID's FIRST launch (e.g. '2:sigkill@200' "
                         "kills replica 2 after 200 answered requests; the "
                         "restart relaunches clean) — how the failover "
                         "tests and bench_serve --fleet's kill soak "
                         "schedule a deterministic mid-soak replica death")
    p_fleet.add_argument("--capture-dir", default=None, metavar="DIR",
                         help="arm every replica's traffic-capture tee: "
                         "replica i writes record shards under "
                         "DIR/replica-{i} (per-replica subdirs keep shard "
                         "sequences disjoint; records-ingest walks them "
                         "recursively)")
    p_fleet.add_argument("--capture-fraction", type=float, default=1.0)
    p_fleet.add_argument("--capture-quota-mb", type=float, default=64.0,
                         help="per-replica capture disk ceiling")
    p_fleet.add_argument("--capture-records-per-shard", type=int, default=64)
    p_fleet.add_argument("--drift-threshold", type=float, default=None,
                         help="arm every replica's DriftMonitor against the "
                         "artifact's stamped drift_baseline (drift_alert "
                         "ledger events — the flywheel retrain trigger)")
    p_fleet.add_argument("--drift-min-requests", type=int, default=20)
    p_fleet.add_argument("--drift-sustain-windows", type=int, default=2)
    _add_compile_cache(p_fleet)

    p_prom = sub.add_parser(
        "promote",
        help="roll a candidate artifact across a LIVE serve-fleet: "
        "quantize-check admission, shadow-compared canary (a traffic slice "
        "is duplicated to it, never answered from it), replica-by-replica "
        "rollout through the router's drain/readmit path, automatic "
        "rollback on accuracy/latency regression or canary crash-loop — "
        "the whole deployment ledgered (promotion_*/shadow_window events) "
        "and rendered by telemetry-report",
    )
    p_prom.add_argument("--candidate-dir", default=None,
                        help="the artifact directory to promote "
                        "(export_serving output); required unless --abort")
    p_prom.add_argument("--model", default=None,
                        help="multi-tenant fleet: promote ONLY this "
                        "registry model — its replicas roll, completion "
                        "flips its registry.json entry (version bump), and "
                        "every other tenant keeps serving untouched; "
                        "REQUIRED when the fleet serves more than one model")
    p_prom.add_argument("--reference-dir", default=None,
                        help="float32 reference for the quantize-check "
                        "admission gate (fingerprint pairing + accuracy "
                        "budgets); omitted = manifest-only admission")
    p_prom.add_argument("--workdir", default=None,
                        help="the live fleet's workdir: the router endpoint "
                        "is read from its run-header ledger event "
                        "(alternative to --router)")
    p_prom.add_argument("--router", default=None, metavar="URL",
                        help="the live fleet router's base URL (e.g. "
                        "http://127.0.0.1:8000); overrides --workdir")
    p_prom.add_argument("--shadow-secs", type=float, default=None,
                        help="shadow window length; 0 skips the shadow "
                        "phase (default: the controller's, 10)")
    p_prom.add_argument("--shadow-fraction", type=float, default=None,
                        help="slice of accepted traffic duplicated to the "
                        "canary (default 0.25)")
    p_prom.add_argument("--shadow-min-requests", type=int, default=None,
                        help="compared requests a shadow window needs "
                        "before it counts as evidence (an emptier window "
                        "HOLDS the phase; default 8)")
    p_prom.add_argument("--shadow-max-secs", type=float, default=None,
                        help="give up (roll back) when shadow traffic "
                        "stays below --shadow-min-requests this long "
                        "(default 120)")
    p_prom.add_argument("--min-iou", type=float, default=None,
                        dest="shadow_min_iou",
                        help="mask-IoU floor for the shadow compare "
                        "(default 0.90)")
    p_prom.add_argument("--max-disagree", type=float, default=None,
                        dest="shadow_max_disagree",
                        help="class-disagreement ceiling for the shadow "
                        "compare (default 0.10)")
    p_prom.add_argument("--max-abs-delta", type=float, default=None,
                        dest="shadow_max_abs_delta",
                        help="max |delta| ceiling on float outputs "
                        "(default 0.25)")
    p_prom.add_argument("--max-mean-delta", type=float, default=None,
                        dest="shadow_max_mean_delta",
                        help="mean |delta| ceiling on float outputs "
                        "(default 0.05)")
    p_prom.add_argument("--max-p99-ratio", type=float, default=None,
                        help="latency gate: canary/fleet p99 vs baseline "
                        "past this ratio (obs/compare noise-band verdict) "
                        "rolls back (default 1.5)")
    p_prom.add_argument("--observe-secs", type=float, default=None,
                        help="post-step observation dwell during rollout "
                        "(default 2)")
    p_prom.add_argument("--canary-inject-fault", default=None,
                        metavar="SPEC",
                        help="drill: pass `serve --inject-fault SPEC` to "
                        "the canary's FIRST launch (e.g. sigkill@25 kills "
                        "it mid-shadow; the monitor restarts it on the "
                        "candidate and the controller must converge)")
    p_prom.add_argument("--abort", action="store_true",
                        help="abort the fleet's in-flight promotion "
                        "(rolls back) instead of starting one")
    p_prom.add_argument("--timeout", type=float, default=600.0,
                        help="seconds to wait for a terminal state before "
                        "giving up (the promotion keeps running fleet-side)")
    p_prom.add_argument("--json", action="store_true",
                        help="print the final status as JSON instead of "
                        "the phase-by-phase progress log")

    p_qc = sub.add_parser(
        "quantize-check",
        help="accuracy gate between a float32 serving artifact and a "
        "quantized sibling: pinned eval batch, per-precision delta "
        "thresholds, quant_check ledger event; exit 1 on failure "
        "(promotion-pipeline gate)",
    )
    p_qc.add_argument("--reference-dir", required=True,
                      help="the float32 reference artifact directory")
    p_qc.add_argument("--candidate-dir", required=True,
                      help="the quantized candidate artifact directory "
                      "(its manifest quantization.dtype selects the "
                      "threshold set)")
    p_qc.add_argument("--batch-size", type=int, default=16,
                      help="pinned eval batch size (fixed-batch artifacts "
                      "pin their own)")
    p_qc.add_argument("--seed", type=int, default=0,
                      help="seed of the pinned eval batch")
    p_qc.add_argument("--max-abs-delta", type=float, default=None,
                      help="override the precision's max |delta| budget on "
                      "float outputs")
    p_qc.add_argument("--mean-abs-delta", type=float, default=None,
                      help="override the precision's mean |delta| budget")
    p_qc.add_argument("--min-iou", type=float, default=None,
                      help="override the precision's minimum mask IoU")
    p_qc.add_argument("--max-disagree", type=float, default=None,
                      help="override the precision's max class-disagreement "
                      "fraction")
    p_qc.add_argument("--allow-fingerprint-mismatch", action="store_true",
                      help="compare artifacts whose manifests carry "
                      "different source fingerprints (normally a hard fail: "
                      "the pair derives from different checkpoints)")
    p_qc.add_argument("--workdir", default=None,
                      help="telemetry ledger dir for the quant_check event "
                      "(default: the candidate dir)")

    p_ing = sub.add_parser(
        "records-ingest",
        help="fold captured traffic shards into a versioned training "
        "dataset: validate every candidate shard (full CRC re-read), dedup "
        "by content fingerprint against the dataset manifest, copy "
        "survivors in as train-*.tfrecord (+ .idx), bump the manifest "
        "version — idempotent (re-running is a ledgered no-op) and "
        "`fit --data-dir` can train on the result directly",
    )
    p_ing.add_argument("--capture-dir", required=True,
                       help="directory the serve-tier capture tee wrote "
                       "(walked recursively: per-replica subdirs merge)")
    p_ing.add_argument("--dataset-dir", required=True,
                       help="the versioned dataset root (dataset_manifest."
                       "json + train-*.tfrecord); created when missing")
    p_ing.add_argument("--prefix", default="train",
                       help="shard filename prefix (fit's split glob)")
    p_ing.add_argument("--workdir", default=None,
                       help="telemetry ledger dir for the records_ingest "
                       "event (default: the dataset dir)")
    p_ing.add_argument("--json", action="store_true",
                       help="print the ingest summary as JSON")

    p_fly = sub.add_parser(
        "flywheel",
        help="continuous-learning controller (loop/controller.py): watch a "
        "capture dir, ingest new traffic into the versioned dataset, and "
        "when the data-volume or drift trigger fires run the retrain "
        "command (everything after --), expecting it to train + "
        "--export-serving --auto-promote so its exit status is the "
        "promotion verdict — the full cycle ledgered as loop_trigger/"
        "loop_retrain/loop_promoted/loop_rejected events",
    )
    p_fly.add_argument("--capture-dir", required=True,
                       help="the serve-tier capture directory to ingest from")
    p_fly.add_argument("--dataset-dir", required=True,
                       help="versioned dataset the ingest step appends to "
                       "(and the retrain command should --data-dir)")
    p_fly.add_argument("--fleet-workdir", default=None,
                       help="the live fleet's workdir: scanned for "
                       "drift_alert events (the drift trigger) and the "
                       "default home of the flywheel's own ledger")
    p_fly.add_argument("--workdir", default=None,
                       help="flywheel telemetry ledger dir (default: "
                       "--fleet-workdir, written as a high-numbered "
                       "process ledger so telemetry-report merges it)")
    p_fly.add_argument("--min-new-records", type=int, default=256,
                       help="data-volume trigger: retrain once this many "
                       "new records accumulate since the last cycle; "
                       "0 disables (drift-only)")
    p_fly.add_argument("--no-drift-trigger", action="store_true",
                       help="ignore drift_alert events (volume-only)")
    p_fly.add_argument("--poll-secs", type=float, default=2.0,
                       help="ingest + trigger evaluation cadence")
    p_fly.add_argument("--max-cycles", type=int, default=None,
                       help="exit after this many retrain cycles (benches "
                       "and drills; default: run until signalled)")
    p_fly.add_argument("--max-wait-secs", type=float, default=None,
                       help="give up (exit 3) when no trigger fires for "
                       "this long")
    p_fly.add_argument("--cooldown-secs", type=float, default=0.0,
                       help="dwell after a cycle before the next trigger "
                       "may fire")
    p_fly.add_argument("retrain", nargs=argparse.REMAINDER,
                       help="the retrain command after `--`: CLI argv run "
                       "as a subprocess of this package's CLI (e.g. `-- fit "
                       "--preset elastic_smoke --data-dir DATASET "
                       "--export-serving --auto-promote --fleet-workdir W`)")

    sub.add_parser("presets", help="list the named BASELINE config presets")

    p_rep = sub.add_parser(
        "telemetry-report",
        help="render the goodput report from a workdir's telemetry.jsonl "
        "run ledger (+ xplane trace when one exists under it)",
    )
    p_rep.add_argument("workdir", nargs="?", default=None,
                       help="training workdir (model-dir) holding "
                       "telemetry.jsonl (+ telemetry-{i}.jsonl per extra "
                       "process/replica, merged automatically); optional "
                       "with --compare")
    p_rep.add_argument("--trace-dir", default=None,
                       help="xplane trace dir to merge (default: search the "
                       "workdir for *.xplane.pb)")
    p_rep.add_argument("--top", type=int, default=10,
                       help="device ops to list from the trace")
    p_rep.add_argument("--json", action="store_true",
                       help="machine-readable output")
    p_rep.add_argument("--export-trace", default=None, metavar="OUT_JSON",
                       help="instead of the report, export the last run's "
                       "sampled trace spans as Chrome/Perfetto trace-event "
                       "JSON (load in chrome://tracing or ui.perfetto.dev)")
    p_rep.add_argument("--straggler-threshold", type=float, default=None,
                       help="multi-host straggler alert threshold: a window "
                       "alerts when the slowest host's mean step time "
                       "exceeds this multiple of the fleet median "
                       "(default 1.25)")
    p_rep.add_argument("--registry-dir", default=None, metavar="DIR",
                       help="cross-run registry ({DIR}/runs.jsonl): "
                       "--register appends this workdir's summary row; "
                       "--compare operands may be registered run ids")
    p_rep.add_argument("--register", action="store_true",
                       help="append the workdir's run summary (config hash, "
                       "mesh, final metrics, goodput split, step-time "
                       "percentiles) to the registry and print the row")
    p_rep.add_argument("--compare", nargs=2, metavar=("RUN_A", "RUN_B"),
                       default=None,
                       help="instead of the report, emit structured "
                       "noise-aware deltas between two runs (workdir paths, "
                       "or registered run ids with --registry-dir): step "
                       "time, data/fetch wait, eval metrics, serving p99")

    p_top = sub.add_parser(
        "telemetry-top",
        help="live fleet console: a refreshing terminal view tailing the "
        "workdir's merged run ledgers (training goodput, serving backlog "
        "and p99, HBM headroom, chip-seconds cost rates, straggler and "
        "health flags); --once prints a single frame for scripts/CI",
    )
    p_top.add_argument("workdir",
                       help="the shared workdir whose telemetry.jsonl / "
                       "telemetry-{i}.jsonl ledgers to tail (a trainer's "
                       "model-dir or a serve/serve-fleet --workdir)")
    p_top.add_argument("--interval", type=float, default=2.0,
                       help="seconds between frame refreshes")
    p_top.add_argument("--once", action="store_true",
                       help="print one frame and exit (no screen clearing) — "
                       "the scripting/CI-smoke mode; an empty workdir "
                       "renders an honest 'no ledgers yet' frame, rc 0")

    p_idx = sub.add_parser(
        "records-index",
        help="write .idx count/offset sidecars for existing TFRecord shards "
        "(data/records.py write_shard_index) — new shards get them at "
        "write_classification_shards time; this backfills old datasets so "
        "count_records and the data service skip the full-file scan",
    )
    p_idx.add_argument("data_dir", help="directory holding *.tfrecord shards")
    p_idx.add_argument("--glob", default="*.tfrecord",
                       help="shard filename pattern (default: *.tfrecord)")

    p_doc = sub.add_parser(
        "doctor",
        help="diagnose the environment and (optionally) a dataset layout",
    )
    p_doc.add_argument("--data-dir", default=None,
                       help="dataset root to analyze: ImageFolder "
                       "({root}/train/{class}/*.png), record shards "
                       "({root}/train-*.tfrecord), or TGS-salt layout "
                       "({root}/images + {root}/masks)")
    p_doc.add_argument("--batch-size", type=int, default=None,
                       help="intended global batch: checked against the "
                       "device count and --grad-accum")
    p_doc.add_argument("--n-devices", type=int, default=None)
    p_doc.add_argument("--grad-accum", type=int, default=1)

    return parser


def _trainer(args):
    from tensorflowdistributedlearning_tpu.config import TrainConfig
    from tensorflowdistributedlearning_tpu.train.trainer import Trainer

    # host-loop overlap knobs only override when given; the TrainConfig
    # defaults are the single source of truth
    overlap = {}
    if getattr(args, "prefetch_depth", None) is not None:
        overlap["prefetch_depth"] = args.prefetch_depth
    if getattr(args, "dispatch_ahead", None) is not None:
        overlap["dispatch_ahead_steps"] = args.dispatch_ahead
    if getattr(args, "data_workers", None) is not None:
        overlap["data_service_workers"] = args.data_workers
    if getattr(args, "trace_sample_rate", None) is not None:
        overlap["trace_sample_rate"] = args.trace_sample_rate
    if getattr(args, "nan_guard", None) is not None:
        overlap["nan_guard"] = args.nan_guard
    if getattr(args, "profile_every_windows", None) is not None:
        overlap["profile_every_windows"] = args.profile_every_windows
    if getattr(args, "compile_cache_dir", None) is not None:
        overlap["compile_cache_dir"] = args.compile_cache_dir
    tcfg = TrainConfig(
        lr=getattr(args, "lr", 0.001),
        n_devices=args.n_devices,
        n_folds=args.n_fold,
        seed=args.seed,
        save_best=getattr(args, "save_best", 5),
        checkpoint_every_steps=getattr(args, "checkpoint_every", 500),
        eval_throttle_secs=getattr(args, "eval_throttle_secs", 300),
        sequence_parallel=getattr(args, "sequence_parallel", 1),
        model_parallel=getattr(args, "model_parallel", 1),
        sync_batch_norm=getattr(args, "sync_bn", False),
        weight_update_sharding=getattr(args, "weight_update_sharding", False),
        parallelism=getattr(args, "parallelism", None) or "explicit",
        hbm_budget_gb=getattr(args, "hbm_budget_gb", None),
        **overlap,
    )
    if tcfg.parallelism == "auto":
        # derive the layout BEFORE the Trainer builds its mesh; flags the
        # user set explicitly stay pinned (explicit flags win)
        import dataclasses

        from tensorflowdistributedlearning_tpu.config import ModelConfig
        from tensorflowdistributedlearning_tpu.parallel import (
            planner as planner_lib,
        )

        mcfg = ModelConfig(
            backbone=args.backbone,
            input_shape=tuple(args.input_shape),
            n_blocks=tuple(args.n_blocks),
            base_depth=args.base_depth,
            block_type=args.block_type,
            dtype=args.dtype,
        )
        pinned = {}
        if getattr(args, "sequence_parallel", 1) != 1:
            pinned["sequence_parallel"] = args.sequence_parallel
        if getattr(args, "model_parallel", 1) != 1:
            pinned["model_parallel"] = args.model_parallel
        if getattr(args, "weight_update_sharding", False):
            pinned["weight_update_sharding"] = True
        run_plan = planner_lib.plan(
            mcfg, tcfg, args.batch_size, pinned=pinned, source="auto"
        )
        tcfg = dataclasses.replace(tcfg, **run_plan.overrides())
        plan_header = run_plan.header()
    else:
        plan_header = None
    return Trainer(
        args.model_dir,
        getattr(args, "data_dir", ""),
        train_config=tcfg,
        plan=plan_header,
        backbone=args.backbone,
        input_shape=tuple(args.input_shape),
        n_blocks=tuple(args.n_blocks),
        base_depth=args.base_depth,
        block_type=args.block_type,
        dtype=args.dtype,
    )


def _best_fold(results: List[dict]) -> int:
    """Index of the fold a deployment would serve: highest mean IOU, falling
    back to lowest loss for task metrics without one."""
    if any("metrics/mean_iou" in r for r in results):
        return max(
            range(len(results)),
            key=lambda i: results[i].get("metrics/mean_iou", float("-inf")),
        )
    return min(
        range(len(results)),
        key=lambda i: results[i].get("loss", float("inf")),
    )


def cmd_train(args) -> int:
    from tensorflowdistributedlearning_tpu.data import pipeline as pipeline_lib

    trainer = _trainer(args)
    ids = pipeline_lib.discover_ids(args.data_dir)
    if not ids:
        print(f"No images found under {args.data_dir}/images", file=sys.stderr)
        return 1
    results = trainer.train(ids, batch_size=args.batch_size, steps=args.steps)
    out = {"folds": results, "n_params": trainer.params}
    if getattr(args, "export_serving", False) and results:
        fold = _best_fold(results)
        out["serving_fold"] = fold
        out["serving_artifact"] = _artifact_dir(trainer.export_serving(
            fold, serving_dtype=getattr(args, "serving_dtype", "float32")
        ))
        out["serving_dtype"] = getattr(args, "serving_dtype", "float32")
        _stamp_baseline(out["serving_artifact"])
    print(json.dumps(out))
    if getattr(args, "auto_promote", False):
        if not out.get("serving_artifact"):
            print(
                "auto-promote: nothing exported — pass --export-serving",
                file=sys.stderr,
            )
            return 2
        return _auto_promote(args, out["serving_artifact"])
    return 0


def _artifact_dir(path: Optional[str]) -> Optional[str]:
    """Exporters return the serialized-module PATH; every consumer (stamp,
    promote, serve --artifact-dir) wants the artifact DIRECTORY."""
    if path and os.path.isfile(path):
        return os.path.dirname(path)
    return path


def _stamp_baseline(artifact_dir: Optional[str]) -> None:
    """Stamp the drift baseline (the artifact's output distribution over a
    pinned batch) into a fresh export's manifest — the serving tier's
    DriftMonitor reads it. It is also the first execution of the artifact,
    so a failure here is an artifact that cannot run: it propagates."""
    if not artifact_dir:
        return
    from tensorflowdistributedlearning_tpu.serve.quant_check import (
        stamp_drift_baseline,
    )

    stamp_drift_baseline(artifact_dir)


def _predict_from_artifact(args) -> int:
    """``predict --artifact-dir``: inference through the bucketed serve engine
    from a standalone exported artifact — no checkpoint plumbing, no model
    code, just the data path's preprocessing contract (normalize + Laplacian
    channel) replayed from the manifest."""
    import jax.numpy as jnp

    from tensorflowdistributedlearning_tpu.data import augment as augment_lib
    from tensorflowdistributedlearning_tpu.data import pipeline as pipeline_lib
    from tensorflowdistributedlearning_tpu.serve import InferenceEngine
    from tensorflowdistributedlearning_tpu.train import serving as serving_lib

    engine = InferenceEngine.from_artifact(args.artifact_dir)
    manifest = serving_lib.read_manifest(args.artifact_dir)
    nchw = manifest.get("data_format") == "NCHW"
    channels = manifest["input_shape"][1 if nchw else -1]

    test_ds = pipeline_lib.InMemoryDataset.from_directory(
        args.test_dir, with_masks=False
    )
    images = test_ds.images  # [N, H, W, 1] normalized
    if channels == 2:  # the segmentation contract: image + Laplacian channel
        images = np.asarray(augment_lib.add_laplace_channel(jnp.asarray(images)))
    if nchw:
        images = np.transpose(images, (0, 3, 1, 2))

    step = engine.max_batch_size
    chunks = [
        engine.infer(images[i : i + step]) for i in range(0, len(images), step)
    ]
    outputs = {
        k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]
    }
    if args.submission and "mask" in outputs:
        from tensorflowdistributedlearning_tpu.data.kaggle import write_submission

        write_submission(args.submission, test_ds.ids, outputs["mask"])
    if args.output:
        np.savez(args.output, ids=np.asarray(test_ds.ids), **outputs)
        print(json.dumps({"written": args.output, "n": len(test_ds.ids)}))
    else:
        summary = {
            "n": len(test_ds.ids),
            "outputs": {k: list(v.shape) for k, v in outputs.items()},
            "bucket_hits": {str(b): n for b, n in engine.bucket_hits.items()},
        }
        if "mask" in outputs:
            summary["mean_mask_coverage"] = float(outputs["mask"].mean())
        print(json.dumps(summary))
    return 0


def cmd_predict(args) -> int:
    if getattr(args, "artifact_dir", None):
        return _predict_from_artifact(args)
    trainer = _trainer(args)
    pred = trainer.predict(
        args.test_dir, batch_size=args.batch_size, tta=not args.no_tta
    )
    if args.submission:
        from tensorflowdistributedlearning_tpu.data.kaggle import write_submission

        write_submission(args.submission, pred["ids"], pred["masks"])
    if args.output:
        np.savez(
            args.output,
            ids=np.asarray(pred["ids"]),
            probabilities=pred["probabilities"],
            masks=pred["masks"],
        )
        print(json.dumps({"written": args.output, "n": len(pred["ids"])}))
    else:
        coverage = float(pred["masks"].mean())
        print(json.dumps({"n": len(pred["ids"]), "mean_mask_coverage": coverage}))
    return 0


def cmd_smoke(args) -> int:
    """Synthetic segmentation training on whatever devices are visible."""
    import jax

    from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
    from tensorflowdistributedlearning_tpu.data.synthetic import synthetic_batches
    from tensorflowdistributedlearning_tpu.models import build_model
    from tensorflowdistributedlearning_tpu.parallel import mesh as mesh_lib
    from tensorflowdistributedlearning_tpu.train import step as step_lib
    from tensorflowdistributedlearning_tpu.train.state import create_train_state

    # same tiny architecture the test suite standardizes on: a smoke run checks
    # wiring (mesh, SPMD step, metrics), not model capacity — and matching the
    # suite's canonical config lets one compiled executable serve both
    cfg = ModelConfig(
        input_shape=(32, 32), n_blocks=(1, 1, 1), base_depth=8, width_multiplier=0.0625
    )
    tcfg = TrainConfig(n_devices=args.n_devices)
    mesh = mesh_lib.make_mesh(args.n_devices)
    model = build_model(cfg)
    state = mesh_lib.replicate(
        create_train_state(
            model,
            step_lib.make_optimizer(tcfg),
            jax.random.PRNGKey(0),
            np.zeros((1, 32, 32, 2), np.float32),
        ),
        mesh,
    )
    train_step = step_lib.make_train_step(mesh, step_lib.SegmentationTask())
    first = last = None
    for batch in synthetic_batches(
        "segmentation", args.batch_size, steps=args.steps,
        input_shape=(32, 32), channels=2,
    ):
        state, metrics = train_step(state, mesh_lib.shard_batch(batch, mesh))
        scalars = step_lib.compute_metrics(jax.device_get(metrics))
        first = first if first is not None else scalars["loss"]
        last = scalars["loss"]
    print(json.dumps({
        "steps": args.steps,
        "devices": mesh_lib.data_parallel_degree(mesh),
        "first_loss": first,
        "last_loss": last,
    }))
    return 0


def cmd_fit(args) -> int:
    from tensorflowdistributedlearning_tpu.train.fit import fit_preset

    if (
        getattr(args, "coordinator_address", None) is not None
        or getattr(args, "num_processes", None) is not None
        or getattr(args, "process_id", None) is not None
    ):
        # explicit jax.distributed world (one host slot of an elastic pod, or
        # a hand-launched multi-host run): must join BEFORE any jax call
        # initializes the backend. Without these flags the run is one
        # process over its host's chips and joins nothing
        from tensorflowdistributedlearning_tpu.parallel import multihost

        multihost.initialize(
            coordinator_address=args.coordinator_address,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    result = fit_preset(
        args.preset,
        args.model_dir,
        compile_cache_dir=getattr(args, "compile_cache_dir", None),
        data_dir=args.data_dir,
        steps=args.steps,
        batch_size=args.batch_size,
        eval_every_steps=args.eval_every,
        sequence_parallel=args.sequence_parallel,
        sync_batch_norm=getattr(args, "sync_bn", False),
        model_parallel=args.model_parallel,
        pipeline_parallel=args.pipeline_parallel,
        pipeline_microbatches=args.pipeline_microbatches,
        expert_parallel=args.expert_parallel,
        weight_update_sharding=args.weight_update_sharding,
        optimizer=args.optimizer,
        lr=args.lr,
        eval_holdout_fraction=args.eval_holdout_fraction,
        augmentation=args.augmentation,
        ema_decay=args.ema_decay,
        grad_accum_steps=args.grad_accum,
        grad_clip_norm=args.grad_clip,
        prefetch_depth=args.prefetch_depth,
        dispatch_ahead_steps=args.dispatch_ahead,
        data_service_workers=args.data_workers,
        trace_sample_rate=args.trace_sample_rate,
        nan_guard=args.nan_guard,
        profile_every_windows=args.profile_every_windows,
        parallelism=args.parallelism,
        hbm_budget_gb=args.hbm_budget_gb,
        export_serving=(
            getattr(args, "serving_dtype", "float32")
            if getattr(args, "export_serving", False)
            else None
        ),
    )
    if result.serving_artifact:
        result.serving_artifact = _artifact_dir(result.serving_artifact)
        _stamp_baseline(result.serving_artifact)
    summary = {
        "preset": args.preset,
        "steps": result.steps,
        "n_params": result.n_params,
        "final_metrics": result.final_metrics,
    }
    if result.serving_artifact:
        summary["serving_artifact"] = result.serving_artifact
    print(json.dumps(summary))
    if getattr(args, "auto_promote", False):
        if not result.serving_artifact:
            print(
                "auto-promote: nothing exported — pass --export-serving",
                file=sys.stderr,
            )
            return 2
        return _auto_promote(args, result.serving_artifact)
    return 0


def cmd_plan(args) -> int:
    """Print the parallelism planner's candidate table (or the full JSON
    plan): how `--parallelism auto` would lay this model out on this
    topology, with exact predicted bytes/chip and a named reason for every
    rejected candidate. Exit status: 0 = a feasible layout exists, 1 = the
    planner found none (or the pinned spec is infeasible), 2 = usage."""
    import dataclasses

    from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
    from tensorflowdistributedlearning_tpu.parallel import planner as planner_lib

    if args.preset:
        from tensorflowdistributedlearning_tpu.configs import get_preset

        try:
            preset = get_preset(args.preset)
        except ValueError as e:
            print(f"plan: {e}", file=sys.stderr)
            return 2
        mcfg, tcfg = preset.model, preset.train
        batch = args.batch_size or preset.global_batch
    else:
        mcfg = ModelConfig(
            backbone=args.backbone,
            input_shape=tuple(args.input_shape),
            n_blocks=tuple(args.n_blocks),
            base_depth=args.base_depth,
            block_type=args.block_type,
            dtype=args.dtype,
            num_classes=args.num_classes,
        )
        tcfg = TrainConfig()
        batch = args.batch_size or 64
    replace = {"n_devices": args.n_devices}
    if args.grad_accum is not None:
        replace["grad_accum_steps"] = args.grad_accum
    if args.hbm_gb is not None:
        replace["hbm_budget_gb"] = args.hbm_gb
    # strip the preset's own layout: the table should show what AUTO would
    # pick, with only the flags the user passed pinned on top
    replace.update(
        model_parallel=1, pipeline_parallel=1, sequence_parallel=1,
        expert_parallel=1, weight_update_sharding=False,
    )
    tcfg = dataclasses.replace(tcfg, **replace)
    pinned = {
        key: value
        for key, value in (
            ("model_parallel", args.model_parallel),
            ("pipeline_parallel", args.pipeline_parallel),
            ("sequence_parallel", args.sequence_parallel),
            ("expert_parallel", args.expert_parallel),
            ("weight_update_sharding", args.weight_update_sharding),
        )
        if value is not None
    }
    margin = None
    if args.measured_margin_from:
        margin = planner_lib.measured_margin_from_workdir(
            args.measured_margin_from
        )
        if margin is None:
            print(
                f"plan: no measured watermark residual under "
                f"{args.measured_margin_from} (CPU backends ledger none) — "
                "planning without margin",
                file=sys.stderr,
            )
    measured_costs = None
    if args.measured_costs_from:
        measured_costs = planner_lib.measured_costs_from_workdir(
            args.measured_costs_from
        )
        if measured_costs is None:
            # same contract as telemetry-report on a missing ledger: rc 2
            # plus a one-line hint — measured costs were asked for and none
            # exist, so silently falling back would misprice every candidate
            print(
                f"plan: no op_roofline events under "
                f"{args.measured_costs_from} — run with "
                "--profile-every-windows N to ledger roofline captures, "
                "then re-plan",
                file=sys.stderr,
            )
            return 2
    try:
        result = planner_lib.plan(
            mcfg, tcfg, batch, pinned=pinned, measured_margin_bytes=margin,
            measured_costs=measured_costs,
        )
    except planner_lib.PlanError as e:
        print(f"plan: {e}", file=sys.stderr)
        return 1
    print(
        json.dumps(result.to_json())
        if args.json
        else planner_lib.render_plan_table(result)
    )
    return 0 if result.chosen.feasible else 1


def cmd_records_index(args) -> int:
    """Backfill ``.idx`` count/offset sidecars for on-disk record shards."""
    import glob as glob_lib
    import os

    from tensorflowdistributedlearning_tpu.data import records as records_lib

    paths = sorted(
        glob_lib.glob(os.path.join(args.data_dir, args.glob))
    )
    if not paths:
        print(f"no shards matching {args.glob!r} under {args.data_dir}",
              file=sys.stderr)
        return 1
    total = 0
    for path in paths:
        n = len(records_lib.write_shard_index(path))
        total += n
        print(f"{records_lib.shard_index_path(path)}: {n} record(s)")
    print(json.dumps({"shards": len(paths), "records": total}))
    return 0


def cmd_telemetry_report(args) -> int:
    """Goodput report from the run ledger(s) — throughput trend, step-time
    percentiles, data-wait/compile/eval time split, recompiles, the fleet
    merge for multi-process workdirs, top device ops when a trace exists
    (obs/report.py). Also the front door for the cross-run registry and
    run-vs-run compare (obs/compare.py)."""
    from tensorflowdistributedlearning_tpu.obs import compare as compare_lib
    from tensorflowdistributedlearning_tpu.obs.report import report_workdir

    try:
        if getattr(args, "compare", None):
            ref_a, ref_b = args.compare
            result = compare_lib.compare_workdirs(
                ref_a, ref_b, registry_dir=args.registry_dir
            )
            print(
                json.dumps(result)
                if args.json
                else compare_lib.render_compare(result)
            )
            return 0
        if args.workdir is None:
            print(
                "telemetry-report: a workdir is required unless --compare "
                "is given",
                file=sys.stderr,
            )
            return 2
        if getattr(args, "export_trace", None):
            from tensorflowdistributedlearning_tpu.obs.trace import (
                write_chrome_trace,
            )

            # fleet-aware: raises the no-ledger FileNotFoundError itself
            n = write_chrome_trace(args.workdir, args.export_trace)
            print(json.dumps({
                "written": args.export_trace,
                "span_events": n,
            }))
            return 0
        if getattr(args, "register", False):
            if not args.registry_dir:
                print(
                    "telemetry-report: --register requires --registry-dir",
                    file=sys.stderr,
                )
                return 2
            row = compare_lib.register_run(args.registry_dir, args.workdir)
            print(json.dumps(row))
            return 0
        kwargs = {}
        if getattr(args, "straggler_threshold", None) is not None:
            kwargs["straggler_threshold"] = args.straggler_threshold
        print(
            report_workdir(
                args.workdir,
                trace_dir=args.trace_dir,
                top=args.top,
                as_json=args.json,
                **kwargs,
            )
        )
    except FileNotFoundError as e:
        # a CI pipeline pointing at the wrong dir (or a run that never wrote
        # a ledger) must FAIL here, loudly — rc 2 + a one-line hint, never a
        # clean exit it can silently pass on
        print(f"telemetry-report: {e}", file=sys.stderr)
        return 2
    except ValueError as e:
        print(f"telemetry-report: {e}", file=sys.stderr)
        return 1
    return 0


def cmd_telemetry_top(args) -> int:
    """The live operator console (obs/top.py): tail the workdir's merged
    ledgers and refresh a one-screen fleet view; ``--once`` for scripting."""
    from tensorflowdistributedlearning_tpu.obs.top import top

    return top(args.workdir, interval_s=args.interval, once=args.once)


def cmd_serve(args) -> int:
    """Serve an exported artifact over HTTP: warm every bucket, run the
    micro-batcher behind /v1/predict, drain gracefully on SIGINT/SIGTERM.
    Request-path telemetry lands in {workdir}/telemetry.jsonl; render it with
    ``telemetry-report``. With ``--registry`` the replica loads EVERY model
    entry (its own engine + micro-batcher each) and routes requests by the
    payload's ``model`` key."""
    import os
    import signal

    from tensorflowdistributedlearning_tpu.serve.registry import (
        DEFAULT_MODEL,
        read_registry,
    )

    if not args.artifact_dir and not args.registry:
        print(
            "serve: one of --artifact-dir or --registry is required",
            file=sys.stderr,
        )
        return 2
    if args.visible_devices:
        # device placement must land BEFORE the accelerator runtime
        # initializes (the first jax import below): every runtime reads its
        # own variable, so export the mask under each spelling
        for var in (
            "CUDA_VISIBLE_DEVICES",
            "HIP_VISIBLE_DEVICES",
            "TPU_VISIBLE_CHIPS",
        ):
            os.environ[var] = args.visible_devices

    from tensorflowdistributedlearning_tpu.obs import Telemetry
    from tensorflowdistributedlearning_tpu.resilience import faults
    from tensorflowdistributedlearning_tpu.serve import (
        InferenceEngine,
        MicroBatcher,
        ServingServer,
        bind_ephemeral,
    )

    # every model this replica serves: (entry, fleet-default fallbacks
    # resolved). Single-artifact stays the one-entry degenerate case.
    entries = None
    if args.registry:
        registry = read_registry(
            os.path.dirname(os.path.abspath(args.registry)),
            path=args.registry,
        )
        entries = list(registry.models.values())
        if args.model:
            entries = [registry.entry(args.model)]
    # bind BEFORE telemetry: with --port 0 the kernel picks the port, and the
    # run header (written at Telemetry construction) must carry the REAL one
    # — it is how a fleet test/manager spawning N replicas learns each
    # endpoint without port races
    sock = bind_ephemeral(args.host, args.port)
    port = sock.getsockname()[1]
    workdir = (
        args.workdir
        or args.artifact_dir
        or os.path.dirname(os.path.abspath(args.registry))
    )
    run_info = {
        "kind": "serve",
        "replica": args.replica_id,
        "artifact_dir": args.artifact_dir,
        "buckets": list(args.buckets),
        "max_wait_ms": args.max_wait_ms,
        "queue_size": args.queue_size,
        "port": port,
        "endpoint": f"http://{args.host}:{port}",
    }
    if args.model:
        run_info["model"] = args.model
    if entries is not None:
        run_info["models"] = {e.name: e.version for e in entries}
    if args.visible_devices:
        run_info["visible_devices"] = args.visible_devices
    telemetry = Telemetry(
        workdir,
        trace_sample_rate=args.trace_sample_rate,
        # fleet contract: replica i>0 writes telemetry-{i}.jsonl, so N
        # replicas sharing one workdir leave per-replica ledgers the
        # telemetry-report merge attributes individually (obs/fleet.py)
        process_index=args.replica_id,
        run_info=run_info,
    )
    if getattr(args, "inject_fault", None):
        # the serving-tier drill seam: sigkill@N fires off the request path
        # (serve/server.py) — a replica that vanishes mid-soak, on schedule
        faults.install(args.inject_fault, seed=getattr(args, "seed", 0))
    # continuous-learning arms (loop/): both apply to the PRIMARY model only
    # — the same single-model rule as the promotion shadow tee
    capture = drift = None
    primary_dir = (
        args.artifact_dir if entries is None else entries[0].artifact_dir
    )
    if getattr(args, "capture_dir", None):
        from tensorflowdistributedlearning_tpu.loop.capture import (
            TrafficCapture,
        )

        capture = TrafficCapture(
            args.capture_dir,
            sample_fraction=args.capture_fraction,
            records_per_shard=args.capture_records_per_shard,
            quota_bytes=int(args.capture_quota_mb * (1 << 20)),
        )
    if getattr(args, "drift_threshold", None) is not None:
        from tensorflowdistributedlearning_tpu.obs import health as health_lib
        from tensorflowdistributedlearning_tpu.train import (
            serving as serving_lib,
        )

        baseline = serving_lib.read_manifest(primary_dir).get(
            "drift_baseline"
        )
        if not baseline:
            logging.getLogger(__name__).warning(
                "serve: --drift-threshold set but %s carries no "
                "drift_baseline — export with a current train/fit "
                "--export-serving (or promote through the controller) to "
                "stamp one; drift monitoring disabled",
                primary_dir,
            )
        else:
            try:
                drift = health_lib.DriftMonitor(
                    baseline,
                    threshold=args.drift_threshold,
                    min_requests=args.drift_min_requests,
                    sustain_windows=args.drift_sustain_windows,
                )
            except ValueError as e:
                logging.getLogger(__name__).warning(
                    "serve: drift monitoring disabled: %s", e
                )
    if entries is None:
        # single-artifact (possibly model-labelled, fleet-spawned) load
        engine = InferenceEngine.from_artifact(
            args.artifact_dir,
            buckets=args.buckets,
            registry=telemetry.registry,
            tracer=telemetry.tracer,
        )
        warmup_s = engine.warmup(
            telemetry=telemetry, budget=args.prewarm_buckets
        )
        batcher = MicroBatcher(
            engine,
            max_wait_ms=args.max_wait_ms,
            max_queue=args.queue_size,
            default_deadline_ms=args.default_deadline_ms,
        )
        server = ServingServer(
            engine,
            batcher,
            host=args.host,
            port=args.port,
            telemetry=telemetry,
            window_secs=args.window_secs,
            slo_p99_ms=args.slo_p99_ms,
            slo_error_budget=args.slo_error_budget,
            replica_id=args.replica_id,
            sock=sock,
            model=args.model or DEFAULT_MODEL,
            registry_version=args.model_version,
            capture=capture,
            drift_monitor=drift,
        )
        warmup_field = {str(b): s for b, s in warmup_s.items()}
        models_field = (
            {args.model: args.model_version or 1} if args.model else None
        )
    else:
        from tensorflowdistributedlearning_tpu.obs.metrics import (
            MetricsRegistry,
        )

        engines = []
        for i, entry in enumerate(entries):
            # one MetricsRegistry per tenant: the primary rides the
            # telemetry registry (legacy single-tenant metric names keep
            # meaning "the whole replica"), later tenants isolate theirs
            engines.append(
                InferenceEngine.from_artifact(
                    entry.artifact_dir,
                    buckets=entry.buckets or tuple(args.buckets),
                    registry=(
                        telemetry.registry if i == 0 else MetricsRegistry()
                    ),
                    tracer=telemetry.tracer,
                )
            )
        warmup_field = {}
        # warm the engines CONCURRENTLY (each ladder already compiles in
        # parallel; engines are independent executables), so a multi-tenant
        # replica goes ready in ~its slowest model's time, not the sum —
        # and arm the recompile detector once, strictly after EVERY engine:
        # no engine's warmup compiles are flagged as steady-state recompiles
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(
            max_workers=len(engines), thread_name_prefix="engine-warmup"
        ) as pool:
            futs = [
                pool.submit(
                    eng.warmup,
                    telemetry=telemetry,
                    budget=entry.prewarm_budget,
                    mark_warm=False,
                )
                for entry, eng in zip(entries, engines)
            ]
            for entry, fut in zip(entries, futs):
                warmup_field.update(
                    {f"{entry.name}/{b}": s for b, s in fut.result().items()}
                )
        telemetry.mark_warm()
        first = entries[0]
        batcher = MicroBatcher(
            engines[0],
            max_wait_ms=args.max_wait_ms,
            max_queue=args.queue_size,
            default_deadline_ms=args.default_deadline_ms,
        )
        server = ServingServer(
            engines[0],
            batcher,
            host=args.host,
            port=args.port,
            telemetry=telemetry,
            window_secs=args.window_secs,
            slo_p99_ms=(
                first.slo_p99_ms
                if first.slo_p99_ms is not None
                else args.slo_p99_ms
            ),
            slo_error_budget=(
                first.slo_error_budget
                if first.slo_error_budget is not None
                else args.slo_error_budget
            ),
            replica_id=args.replica_id,
            sock=sock,
            model=first.name,
            registry_version=first.version,
            capture=capture,
            drift_monitor=drift,
        )
        for entry, eng in zip(entries[1:], engines[1:]):
            server.add_model(
                entry.name,
                eng,
                MicroBatcher(
                    eng,
                    max_wait_ms=args.max_wait_ms,
                    max_queue=args.queue_size,
                    default_deadline_ms=args.default_deadline_ms,
                ),
                version=entry.version,
                slo_p99_ms=entry.slo_p99_ms,
                slo_error_budget=(
                    entry.slo_error_budget
                    if entry.slo_error_budget is not None
                    else 0.01
                ),
            )
        models_field = {e.name: e.version for e in entries}
    server.start()
    ready = {
        "serving": server.url,
        "port": server.port,
        "replica": args.replica_id,
        "buckets": list(server.engine.buckets),
        "warmup_s": warmup_field,
        "ledger": workdir,
    }
    if models_field:
        ready["models"] = models_field
    print(json.dumps(ready), flush=True)
    # resilience contract for the serving tier: SIGTERM = graceful drain
    server.install_signal_handlers((signal.SIGINT, signal.SIGTERM))
    try:
        server.wait()
    finally:
        server.shutdown()
        faults.uninstall()
    return 0


def cmd_serve_fleet(args) -> int:
    """The serving tier: N supervised replicas behind the queue-depth/p99
    router, with optional autoscaling — one SIGTERM drains the whole fleet.
    All ledgers (controller + replicas) land in one workdir; render the
    merged story with ``telemetry-report``."""
    import os
    import signal

    from tensorflowdistributedlearning_tpu.obs import Telemetry
    from tensorflowdistributedlearning_tpu.serve import (
        AutoscaleConfig,
        FleetConfig,
        ServeFleet,
        bind_ephemeral,
    )
    from tensorflowdistributedlearning_tpu.serve.registry import (
        RegistryError,
        read_registry,
        registry_path,
    )

    if not args.artifact_dir and not args.registry and not (
        args.workdir and os.path.exists(registry_path(args.workdir))
    ):
        print(
            "serve-fleet: one of --artifact-dir or --registry is required "
            "(or a registry.json in --workdir)",
            file=sys.stderr,
        )
        return 2
    try:
        if args.registry:
            registry = read_registry(
                os.path.dirname(os.path.abspath(args.registry)),
                path=args.registry,
            )
        else:
            # workdir registry.json is picked up automatically; a plain
            # --artifact-dir fleet synthesizes the implicit one-entry
            # registry (fully legacy behavior)
            registry = read_registry(
                args.workdir or args.artifact_dir,
                default_artifact_dir=args.artifact_dir,
            )
    except RegistryError as e:
        print(f"serve-fleet: {e}", file=sys.stderr)
        return 2
    # the fleet default artifact backs legacy replicas and rollback spawns;
    # with a registry and no --artifact-dir, the first entry's stands in
    default_artifact_dir = (
        args.artifact_dir or next(iter(registry.models.values())).artifact_dir
    )

    fault_specs = {}
    for item in args.replica_inject_fault or ():
        rid, _, spec = item.partition(":")
        if not spec or not rid.isdigit():
            print(
                f"serve-fleet: bad --replica-inject-fault {item!r} "
                "(expected ID:SPEC, e.g. 2:sigkill@200)",
                file=sys.stderr,
            )
            return 2
        fault_specs[int(rid)] = spec
    sock = bind_ephemeral(args.host, args.port)
    port = sock.getsockname()[1]
    workdir = args.workdir or args.artifact_dir or os.path.dirname(
        os.path.abspath(args.registry)
    )
    run_info = {
        "kind": "serve-fleet",
        "artifact_dir": default_artifact_dir,
        "replicas": args.replicas,
        "autoscale": not args.no_autoscale,
        "port": port,
        "endpoint": f"http://{args.host}:{port}",
    }
    if not registry.implicit:
        run_info["models"] = {
            name: e.version for name, e in registry.models.items()
        }
        if args.chip_budget is not None:
            run_info["chip_budget"] = args.chip_budget
    # the controller only spawns and routes: it must never take the chip
    # its replicas need
    telemetry = Telemetry(workdir, run_info=run_info, controller=True)
    fleet = ServeFleet(
        FleetConfig(
            artifact_dir=default_artifact_dir,
            workdir=workdir,
            registry=registry,
            buckets=tuple(args.buckets),
            max_wait_ms=args.max_wait_ms,
            queue_size=args.queue_size,
            window_secs=args.window_secs,
            default_deadline_ms=args.default_deadline_ms,
            slo_p99_ms=args.slo_p99_ms,
            slo_error_budget=args.slo_error_budget,
            max_restarts_per_replica=args.max_restarts_per_replica,
            fault_specs=fault_specs or None,
            capture_dir=getattr(args, "capture_dir", None),
            capture_fraction=getattr(args, "capture_fraction", 1.0),
            capture_quota_mb=getattr(args, "capture_quota_mb", 64.0),
            capture_records_per_shard=getattr(
                args, "capture_records_per_shard", 64
            ),
            drift_threshold=getattr(args, "drift_threshold", None),
            drift_min_requests=getattr(args, "drift_min_requests", 20),
            drift_sustain_windows=getattr(args, "drift_sustain_windows", 2),
            compile_cache_dir=getattr(args, "compile_cache_dir", None),
        ),
        router_host=args.host,
        router_sock=sock,
        telemetry=telemetry,
        autoscale=(
            None
            if args.no_autoscale
            else AutoscaleConfig(
                min_replicas=args.min_replicas,
                max_replicas=args.max_replicas,
                queue_high=args.queue_high,
                queue_low=args.queue_low,
                sustain=args.scale_sustain,
                cooldown_s=args.scale_cooldown_s,
            )
        ),
        autoscale_interval_s=args.autoscale_interval_s,
        poll_interval_s=args.poll_interval_s,
        window_secs=args.window_secs,
        chip_budget=args.chip_budget,
    )
    fleet.start(args.replicas)
    ready = {
        "router": fleet.url,
        "port": port,
        "replicas": [
            {"replica": rid, "endpoint": url}
            for rid, url in fleet.manager.endpoints()
        ],
        "autoscale": not args.no_autoscale,
        "ledger": workdir,
    }
    if not registry.implicit:
        ready["models"] = {
            name: e.version for name, e in registry.models.items()
        }
    print(json.dumps(ready), flush=True)
    fleet.install_signal_handlers((signal.SIGINT, signal.SIGTERM))
    try:
        fleet.wait()
    finally:
        fleet.shutdown()
        telemetry.close(kind="serve-fleet")
    return 0


def _resolve_router_url(router: Optional[str],
                        workdir: Optional[str]) -> Optional[str]:
    """Where the live fleet's router listens: ``router`` verbatim, or the
    ``endpoint`` of the last serve-fleet run header in ``workdir``'s ledger —
    the same merged-workdir contract everything else in the fleet rides."""
    if router:
        return router.rstrip("/")
    if not workdir:
        return None
    from tensorflowdistributedlearning_tpu.obs.ledger import read_ledger

    try:
        events = read_ledger(workdir)
    except (OSError, ValueError):
        return None
    for e in reversed(events):
        if e.get("event") == "run_header" and e.get("kind") == "serve-fleet":
            return (e.get("endpoint") or "").rstrip("/") or None
    return None


def _drive_promotion(url: str, payload: Dict, *, timeout: float = 600.0,
                     json_out: bool = False):
    """POST a start/abort to a live fleet's /admin/promotion and follow the
    phase history to a terminal state. Shared by ``promote`` and the
    ``--auto-promote`` path of train/fit (the flywheel's retrain leg).
    Returns ``(rc, final_status_or_None)``: rc 0 = complete, 1 = rolled
    back / refused / aborted / timed out, 2 = usage or connectivity."""
    import time as time_lib
    import urllib.error
    import urllib.request

    def call(method: str, body=None):
        req = urllib.request.Request(
            url + "/admin/promotion",
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json"},
            method=method,
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read())

    try:
        status = call("POST", payload)
    except urllib.error.HTTPError as e:
        body = e.read().decode(errors="replace")
        print(f"promote: router answered {e.code}: {body}", file=sys.stderr)
        return 2, None
    except (OSError, ValueError) as e:
        print(f"promote: cannot reach router at {url}: {e}", file=sys.stderr)
        return 2, None

    terminal = ("complete", "rolled_back", "refused", "aborted", "idle")
    deadline = time_lib.monotonic() + timeout
    seen_phases = 0
    while True:
        history = status.get("history") or []
        if not json_out:
            for entry in history[seen_phases:]:
                detail = ", ".join(
                    f"{k}={v}"
                    for k, v in entry.items()
                    if k not in ("phase", "t") and v is not None
                )
                print(
                    f"promotion: {entry['phase']}"
                    + (f" ({detail})" if detail else ""),
                    flush=True,
                )
            seen_phases = len(history)
        if status.get("state") in terminal:
            break
        if time_lib.monotonic() >= deadline:
            print(
                f"promote: no terminal state after {timeout:.0f}s — "
                "the promotion is still running fleet-side; re-run to "
                "re-attach or pass --abort",
                file=sys.stderr,
            )
            return 1, status
        time_lib.sleep(0.5)
        try:
            status = call("GET")
        except (OSError, ValueError) as e:
            print(
                f"promote: lost the router mid-promotion: {e}",
                file=sys.stderr,
            )
            return 2, None
    if json_out:
        print(json.dumps(status))
    else:
        state = status.get("state")
        line = f"promotion {state}"
        if status.get("reason"):
            line += f": {status['reason']}"
        if status.get("artifacts"):
            line += f" — fleet artifacts: {status['artifacts']}"
        print(line, flush=True)
    return (0 if status.get("state") == "complete" else 1), status


def cmd_promote(args) -> int:
    """Drive a live fleet's promotion controller over /admin/promotion:
    start (or --abort), then follow the phase history until a terminal
    state. Exit status IS the verdict: 0 promoted, 1 rolled back / refused /
    aborted, 2 usage or connectivity errors."""
    import os

    if not args.abort and not args.candidate_dir:
        print(
            "promote: --candidate-dir is required (unless --abort)",
            file=sys.stderr,
        )
        return 2
    url = _resolve_router_url(args.router, args.workdir)
    if not url:
        print(
            "promote: no router found — pass --router URL, or --workdir "
            "pointing at a live serve-fleet's ledger dir",
            file=sys.stderr,
        )
        return 2

    if args.abort:
        payload = {"action": "abort"}
    else:
        payload = {
            "action": "start",
            "candidate_dir": os.path.abspath(args.candidate_dir),
        }
        if args.reference_dir:
            payload["reference_dir"] = os.path.abspath(args.reference_dir)
        if args.canary_inject_fault:
            payload["fault_spec"] = args.canary_inject_fault
        if args.model:
            payload["model"] = args.model
        for key in (
            "shadow_secs",
            "shadow_fraction",
            "shadow_min_requests",
            "shadow_max_secs",
            "shadow_min_iou",
            "shadow_max_disagree",
            "shadow_max_abs_delta",
            "shadow_max_mean_delta",
            "max_p99_ratio",
            "observe_secs",
        ):
            value = getattr(args, key, None)
            if value is not None:
                payload[key] = value
    rc, _ = _drive_promotion(
        url, payload, timeout=args.timeout, json_out=args.json
    )
    return rc


def _auto_promote(args, artifact_dir: str) -> int:
    """The ``--auto-promote`` tail of train/fit: hand the exported artifact
    to the live fleet's promotion controller and make the exit status the
    verdict. No ``reference_dir`` is sent — a retrained model carries a NEW
    source fingerprint, so the quantize-check pairing gate would refuse it;
    admission is manifest-parse, and the shadow compare (with the
    ``--promote-*`` bands) plus rollback is the real gate."""
    import os

    url = _resolve_router_url(
        getattr(args, "router", None), getattr(args, "fleet_workdir", None)
    )
    if not url:
        print(
            "auto-promote: no live fleet found — pass --router URL or "
            "--fleet-workdir pointing at the serve-fleet's ledger dir",
            file=sys.stderr,
        )
        return 2
    payload = {
        "action": "start",
        "candidate_dir": os.path.abspath(artifact_dir),
    }
    if getattr(args, "promote_model", None):
        payload["model"] = args.promote_model
    for flag, key in (
        ("promote_shadow_secs", "shadow_secs"),
        ("promote_min_requests", "shadow_min_requests"),
        ("promote_max_disagree", "shadow_max_disagree"),
        ("promote_max_abs_delta", "shadow_max_abs_delta"),
        ("promote_max_mean_delta", "shadow_max_mean_delta"),
        ("promote_min_iou", "shadow_min_iou"),
        ("promote_max_p99_ratio", "max_p99_ratio"),
    ):
        value = getattr(args, flag, None)
        if value is not None:
            payload[key] = value
    rc, status = _drive_promotion(
        url, payload, timeout=getattr(args, "promote_timeout", 600.0)
    )
    print(json.dumps({
        "auto_promote": True,
        "candidate_dir": os.path.abspath(artifact_dir),
        "state": (status or {}).get("state"),
        "rc": rc,
    }))
    return rc


def cmd_quantize_check(args) -> int:
    """Run the f32-vs-quantized accuracy gate (serve/quant_check.py) and
    ledger the verdict; exit status IS the gate."""
    from tensorflowdistributedlearning_tpu.obs import Telemetry
    from tensorflowdistributedlearning_tpu.serve.quant_check import (
        run_quant_check,
    )

    workdir = args.workdir or args.candidate_dir
    telemetry = Telemetry(
        workdir,
        run_info={
            "kind": "quant_check",
            "reference_dir": args.reference_dir,
            "candidate_dir": args.candidate_dir,
        },
    )
    try:
        result = run_quant_check(
            args.reference_dir,
            args.candidate_dir,
            batch_size=args.batch_size,
            seed=args.seed,
            thresholds={
                "max_abs_delta": args.max_abs_delta,
                "mean_abs_delta": args.mean_abs_delta,
                "min_iou": args.min_iou,
                "max_disagree": args.max_disagree,
            },
            allow_fingerprint_mismatch=args.allow_fingerprint_mismatch,
            telemetry=telemetry,
        )
    finally:
        telemetry.close()
    print(json.dumps(result))
    return 0 if result["passed"] else 1


def cmd_records_ingest(args) -> int:
    """One capture->dataset ingest pass (loop/ingest.py), ledgered as a
    ``records_ingest`` event. Idempotent: re-running over the same capture
    tree changes nothing (and says so)."""
    from tensorflowdistributedlearning_tpu.loop.ingest import ingest_shards
    from tensorflowdistributedlearning_tpu.obs import Telemetry

    telemetry = Telemetry(
        args.workdir or args.dataset_dir,
        run_info={
            "kind": "records-ingest",
            "capture_dir": args.capture_dir,
            "dataset_dir": args.dataset_dir,
        },
    )
    try:
        summary = ingest_shards(
            args.capture_dir,
            args.dataset_dir,
            prefix=args.prefix,
            telemetry=telemetry,
        )
    finally:
        telemetry.close(kind="records-ingest")
    if args.json:
        print(json.dumps(summary))
    else:
        print(
            f"ingest: dataset v{summary['version']} — "
            f"+{summary['new_shards']} shards "
            f"(+{summary['records_added']} records, "
            f"{summary['deduped']} duplicate, {summary['corrupt']} corrupt); "
            f"{summary['shards_total']} shards / "
            f"{summary['records_total']} records total"
        )
    return 0


def cmd_flywheel(args) -> int:
    """The continuous-learning daemon (loop/controller.py): ingest captured
    traffic, fire the retrain command on a data-volume or drift trigger,
    and let its --auto-promote exit status be the cycle's verdict."""
    import os
    import signal
    import subprocess

    from tensorflowdistributedlearning_tpu.loop.controller import (
        FLYWHEEL_PROCESS_INDEX,
        FlywheelConfig,
        FlywheelController,
    )
    from tensorflowdistributedlearning_tpu.obs import Telemetry

    retrain_argv = list(args.retrain or [])
    if retrain_argv and retrain_argv[0] == "--":
        retrain_argv = retrain_argv[1:]
    if not retrain_argv:
        print(
            "flywheel: no retrain command — append `-- fit --preset ... "
            "--data-dir DATASET --export-serving --auto-promote "
            "--fleet-workdir W`",
            file=sys.stderr,
        )
        return 2
    try:
        config = FlywheelConfig(
            capture_dir=args.capture_dir,
            dataset_dir=args.dataset_dir,
            fleet_workdir=(
                None if args.no_drift_trigger else args.fleet_workdir
            ),
            min_new_records=args.min_new_records,
            poll_secs=args.poll_secs,
            max_cycles=args.max_cycles,
            max_wait_secs=args.max_wait_secs,
            cooldown_secs=args.cooldown_secs,
        )
    except ValueError as e:
        print(f"flywheel: {e}", file=sys.stderr)
        return 2

    workdir = args.workdir or args.fleet_workdir or args.dataset_dir
    shared = args.fleet_workdir is not None and os.path.abspath(
        workdir
    ) == os.path.abspath(args.fleet_workdir)
    telemetry = Telemetry(
        workdir,
        # sharing the fleet's workdir: write a high-numbered per-process
        # ledger the report merges, NEVER the fleet controller's process-0
        # telemetry.jsonl
        process_index=FLYWHEEL_PROCESS_INDEX if shared else 0,
        # the retrain commands it spawns are the chip users
        controller=True,
        run_info={
            "kind": "flywheel",
            "capture_dir": args.capture_dir,
            "dataset_dir": args.dataset_dir,
            "fleet_workdir": args.fleet_workdir,
            "retrain": retrain_argv,
        },
    )

    def retrain(trigger, ingest_summary):
        argv = [
            sys.executable, "-m", "tensorflowdistributedlearning_tpu",
            *retrain_argv,
        ]
        env = dict(os.environ)
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = (
            pkg_root + os.pathsep + env["PYTHONPATH"]
            if env.get("PYTHONPATH")
            else pkg_root
        )
        proc = subprocess.run(
            argv, capture_output=True, text=True, env=env, check=False
        )
        # the child's output is the cycle's audit trail — surface it
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        result = {"rc": proc.returncode}
        # the retrain's JSON tail names the artifact: fit/train print
        # serving_artifact, the auto-promote verdict prints candidate_dir
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if not isinstance(obj, dict):
                continue
            cand = obj.get("candidate_dir") or obj.get("serving_artifact")
            if cand:
                result["candidate_dir"] = cand
                break
        if result.get("candidate_dir"):
            try:
                from tensorflowdistributedlearning_tpu.train import (
                    serving as serving_lib,
                )

                manifest = serving_lib.read_manifest(result["candidate_dir"])
                result["fingerprint"] = (
                    manifest.get("quantization") or {}
                ).get("source_fingerprint")
            except (OSError, ValueError, KeyError):
                pass
        return result

    controller = FlywheelController(
        config, retrain_fn=retrain, telemetry=telemetry
    )

    def _on_signal(signum, frame):
        controller.stop()

    previous = {}
    for sig in (signal.SIGINT, signal.SIGTERM):
        previous[sig] = signal.signal(sig, _on_signal)
    try:
        rc = controller.run()
    finally:
        for sig, handler in previous.items():
            signal.signal(sig, handler)
        telemetry.close(kind="flywheel")
    print(
        json.dumps({
            "flywheel": True,
            "cycles": controller.cycles,
            "promoted": controller.promoted,
            "rejected": controller.rejected,
            "rc": rc,
        }),
        flush=True,
    )
    return rc


def cmd_presets(args) -> int:
    from tensorflowdistributedlearning_tpu.configs import PRESETS

    print(
        json.dumps(
            {
                name: {
                    "description": p.description,
                    "global_batch": p.global_batch,
                    "backbone": p.model.backbone,
                    "num_classes": p.model.num_classes,
                    "input_shape": list(p.model.input_shape),
                    "dtype": p.model.dtype,
                }
                for name, p in PRESETS.items()
            },
            indent=2,
        )
    )
    return 0


def cmd_doctor(args) -> int:
    """Environment + dataset diagnosis: one JSON report, no side effects
    beyond a lazy native-library build attempt. The closest reference
    analogue is `utils.get_available_gpus` (utils.py:6-8) — this covers the
    whole stack a training run depends on."""
    import glob
    import os

    report: dict = {"ok": True}

    def problem(msg: str) -> None:
        report["ok"] = False
        report.setdefault("problems", []).append(msg)

    # backend probe in a BOUNDED child: a backend that hangs in
    # jax.devices() cannot be recovered in-process, and a diagnosis tool
    # that hangs on that failure is useless. The child inherits the
    # environment, so it probes the same backend the training commands
    # would use — and takes the chip only for as long as it lives.
    import subprocess

    probe = (
        "import jax, json\n"
        "d = jax.devices(); "
        "print(json.dumps({'platform': jax.default_backend(), "
        "'n_devices': len(d), 'device_kind': d[0].device_kind, "
        "'process_count': jax.process_count()}))"
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", probe],
            capture_output=True,
            text=True,
            timeout=90,
        )
        lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
        if out.returncode == 0 and lines:
            report["backend"] = json.loads(lines[-1])
        else:
            problem(
                "backend probe failed: "
                + (out.stderr.strip().splitlines() or ["no output"])[-1][:200]
            )
            report["backend"] = {"error": "probe failed"}
    except subprocess.TimeoutExpired:
        problem(
            "backend init timed out after 90s (jax.devices() hangs) — is "
            "another process holding the chip? A chip belongs to one "
            "process at a time"
        )
        report["backend"] = {"error": "init timeout"}

    from tensorflowdistributedlearning_tpu.data.records import _records_lib
    from tensorflowdistributedlearning_tpu.native import loader

    report["native"] = {
        "decode_io_cc": loader.native_available(),
        "records_cc": _records_lib() is not None,
    }
    for lib, present in report["native"].items():
        if not present:
            problem(
                f"native {lib} unavailable — the pure-Python fallback works "
                "but streams records/decodes images far slower (RECORDS_BENCH.json)"
            )

    n = args.n_devices or report["backend"].get("n_devices")
    if args.batch_size is not None and n is None:
        # Backend probe failed and the user gave no --n-devices: validating
        # divisibility against a guessed n=1 would bless batches the real
        # device count rejects. Report the section as unchecked instead.
        report["batch"] = {
            "global_batch": args.batch_size,
            "unchecked": "device count unknown (backend probe failed; "
            "pass --n-devices to check divisibility)",
        }
    elif args.batch_size is not None:
        batch: dict = {"global_batch": args.batch_size, "data_parallel": n}
        if args.batch_size % n:
            problem(
                f"batch {args.batch_size} not divisible by {n} devices "
                "(reference contract, model.py:156-159)"
            )
        elif args.grad_accum > 1 and (args.batch_size // n) % args.grad_accum:
            problem(
                f"per-shard batch {args.batch_size // n} not divisible by "
                f"grad_accum_steps={args.grad_accum}"
            )
        else:
            batch["per_shard"] = args.batch_size // n // args.grad_accum
        report["batch"] = batch

    if args.data_dir:
        d = args.data_dir
        data: dict = {"root": d}
        if not os.path.isdir(d):
            problem(f"data dir {d} does not exist")
        elif glob.glob(os.path.join(d, "train-*.tfrecord")):
            from tensorflowdistributedlearning_tpu.data import records as rec

            data["layout"] = "record-shards"
            for split in ("train", "val"):
                paths = sorted(
                    glob.glob(os.path.join(d, f"{split}-*.tfrecord"))
                )
                if not paths:
                    continue
                info = {"shards": len(paths)}
                try:
                    info["records"] = rec.count_records(paths)
                except ValueError as e:
                    problem(f"{split} shards corrupt: {e}")
                # like the batch check: when the probe failed the process
                # count is UNKNOWN — guessing 1 would bless a layout a real
                # multi-process run rejects; mark unchecked instead
                nproc = report["backend"].get("process_count")
                if split == "train" and nproc is None:
                    info["shards_per_process"] = "unchecked (backend probe failed)"
                elif split == "train" and len(paths) < nproc:
                    problem(
                        f"{len(paths)} train shards < {nproc} "
                        "processes — every process needs at least one"
                    )
                data[split] = info
        elif os.path.isdir(os.path.join(d, "train")):
            from tensorflowdistributedlearning_tpu.data import imagefolder

            data["layout"] = "imagefolder"
            try:
                ds = imagefolder.ImageFolder(
                    os.path.join(d, "train"), (32, 32), channels=3
                )
                data["train"] = {
                    "examples": len(ds),
                    "classes": ds.num_classes,
                }
            except Exception as e:  # noqa: BLE001 — report, don't crash
                problem(f"imagefolder scan failed: {e}")
        elif os.path.isdir(os.path.join(d, "images")):
            imgs = glob.glob(os.path.join(d, "images", "*.png"))
            masks = glob.glob(os.path.join(d, "masks", "*.png"))
            data["layout"] = "tgs-salt"
            data["images"], data["masks"] = len(imgs), len(masks)
            if len(imgs) != len(masks):
                problem(
                    f"{len(imgs)} images vs {len(masks)} masks — every "
                    "training image needs its mask"
                )
        else:
            problem(
                f"{d}: no recognized layout (expected train-*.tfrecord, "
                "train/{class}/, or images/ + masks/)"
            )
        report["data"] = data

    print(json.dumps(report, indent=2))
    return 0 if report["ok"] else 1


def _strip_flags(argv: List[str], names: List[str]) -> List[str]:
    """Remove ``--name VALUE`` / ``--name=VALUE`` (and bare ``--name`` for
    store-true flags whose next token is another flag) for every name in
    ``names``; everything else replays verbatim."""
    out: List[str] = []
    skip = False
    for token in argv:
        if skip:
            skip = False
            continue
        if token in names:
            skip = True
            continue
        if any(token.startswith(f"{name}=") for name in names):
            continue
        out.append(token)
    return out


def _strip_supervisor_flags(argv: List[str]) -> List[str]:
    """The child command the supervisor relaunches: this invocation minus
    ``--max-restarts`` (both ``--flag N`` and ``--flag=N`` forms) — every
    other flag, fault injection included, replays verbatim."""
    return _strip_flags(argv, ["--max-restarts"])


def _strip_elastic_flags(argv: List[str]) -> List[str]:
    """The child command the elastic coordinator launches: this invocation
    minus the coordinator-level knobs (children must never re-enter the
    coordinator), minus ``--max-restarts`` (the coordinator owns restarts),
    minus ``--batch-size``/``--inject-fault`` (re-issued per world size /
    per host slot)."""
    stripped = _strip_flags(argv, [
        "--elastic", "--min-hosts", "--devices-per-host", "--drain-timeout",
        "--evict-threshold", "--evict-sustained", "--evict-cooldown",
        "--host-inject-fault", "--max-restarts", "--batch-size",
        "--inject-fault",
    ])
    return [
        t for t in stripped
        if t not in ("--no-straggler-evict", "--aot-standby")
    ]


def _parse_host_faults(specs: List[str]) -> dict:
    """``--host-inject-fault HOST:SPEC`` entries -> {host_slot: fault_spec},
    validated eagerly (a typo'd drill must fail at parse time, not after the
    pod spawned)."""
    from tensorflowdistributedlearning_tpu.resilience import parse_fault_spec

    out = {}
    for item in specs:
        host, sep, spec = item.partition(":")
        if not sep or not host.isdigit() or not spec:
            raise SystemExit(
                f"fit: bad --host-inject-fault {item!r} (expected HOST:SPEC, "
                "e.g. 1:sigkill-step@6)"
            )
        parse_fault_spec(spec)  # raises ValueError on a bad spec
        out[int(host)] = spec
    return out


def _child_fingerprint(workdir: str) -> Dict:
    """The device fingerprint (platform, device_kind, n_devices,
    process_count) the newest chip-owning child recorded in ``workdir``'s
    ledger — how a controller learns what the devices are without
    initializing a backend itself."""
    from tensorflowdistributedlearning_tpu.obs import read_ledger

    for event in reversed(read_ledger(workdir)):
        if event.get("event") == "run_header" and "fingerprint" in event:
            return event["fingerprint"]
    raise RuntimeError(
        f"no child run header with a device fingerprint in {workdir} yet"
    )


def _elastic_whatif_plan(args, preset, local_bs, world, measured_margin_bytes):
    """The elastic coordinator's what-if plan at the (new) world size: a
    plain Topology, no devices touched — the coordinator only spawns the chip
    users and must not take a chip from them. What the devices are comes from
    --devices-per-host and from what a child saw (the device fingerprint in
    its run header). Children derive/validate their OWN layout again when
    they start (--parallelism auto re-plans live)."""
    from tensorflowdistributedlearning_tpu.parallel import (
        planner as planner_lib,
    )

    seen = _child_fingerprint(args.model_dir)
    dph = args.devices_per_host or (
        seen["n_devices"] // max(1, seen["process_count"])
    )
    budget = None
    if args.hbm_budget_gb:
        budget = int(args.hbm_budget_gb * (1 << 30))
    topo = planner_lib.Topology(
        n_devices=world * dph,
        local_device_count=dph,
        process_count=world,
        hbm_bytes_per_device=budget,
        device_kind=seen["device_kind"],
    )
    # pin the layout flags the operator passed explicitly, so the what-if
    # plan describes the world the children will actually train (the
    # children re-validate/derive their own layout again at startup)
    pinned = {}
    if args.model_parallel != 1:
        pinned["model_parallel"] = args.model_parallel
    if args.pipeline_parallel != 1:
        pinned["pipeline_parallel"] = args.pipeline_parallel
    if args.sequence_parallel != 1:
        pinned["sequence_parallel"] = args.sequence_parallel
    if args.expert_parallel != 1:
        pinned["expert_parallel"] = args.expert_parallel
    if args.weight_update_sharding is not None:
        pinned["weight_update_sharding"] = args.weight_update_sharding
    return planner_lib.plan(
        preset.model,
        preset.train,
        local_bs * world,
        topology=topo,
        pinned=pinned,
        measured_margin_bytes=measured_margin_bytes,
    ).header()


def _run_elastic(args, argv: List[str]) -> int:
    """``fit --elastic N``: re-exec this command as N host-slot child
    processes under the elastic coordinator (parallel/elastic.py). The
    GLOBAL batch scales with the world (per-host batch stays fixed, so the
    data-service sidecar re-validates across a resize and ZeRO-1 state
    reshards to the new dp); with ``--parallelism auto`` each generation's
    children re-derive their whole layout at the live world size, and the
    coordinator additionally ledgers the off-device what-if plan delta on
    every resize."""
    import os

    from tensorflowdistributedlearning_tpu.configs import get_preset
    from tensorflowdistributedlearning_tpu.parallel.elastic import (
        ElasticConfig,
        ElasticCoordinator,
    )
    from tensorflowdistributedlearning_tpu.resilience.supervisor import (
        shell_rc,
    )

    preset = get_preset(args.preset)
    hosts = args.elastic
    global_batch = args.batch_size or preset.global_batch
    if global_batch % hosts:
        raise SystemExit(
            f"fit: global batch {global_batch} not divisible by "
            f"--elastic {hosts} host(s)"
        )
    local_bs = global_batch // hosts
    host_faults = _parse_host_faults(args.host_inject_fault)
    base = _strip_elastic_flags(argv)

    def child_argv_fn(world, pid, coordinator, generation):
        child = [
            sys.executable, "-m", "tensorflowdistributedlearning_tpu",
            *base,
            "--batch-size", str(local_bs * world),
        ]
        if coordinator is not None:
            child += [
                "--coordinator-address", coordinator,
                "--num-processes", str(world),
                "--process-id", str(pid),
            ]
        if generation == 0 and pid in host_faults:
            child += ["--inject-fault", host_faults[pid]]
        return child

    _standby_scratch: dict = {}

    def standby_argv_fn(world, pid, coordinator):
        # One rank of the AOT standby mini-world: this same fit command,
        # pointed at a scratch workdir (shared by all standby ranks, like the
        # real pod shares --model-dir) with the next world's GLOBAL batch and
        # just enough steps to compile state-init + the train step. The
        # standby must be a rank-for-rank replica of the pod a resize would
        # spawn — cache keys bind the process-local backend topology, so
        # only rank p of a real `world`-process run writes the entry rank p
        # of the resized pod will load from --compile-cache-dir.
        import tempfile

        scratch = _standby_scratch.get(world)
        if scratch is None:
            scratch = tempfile.mkdtemp(prefix=f"tfdl-aot-standby-w{world}-")
            _standby_scratch[world] = scratch
        sb = _strip_flags(base, ["--model-dir", "--steps", "--eval-every"])
        sb = [t for t in sb if t not in ("--export-serving", "--auto-promote")]
        child = [
            sys.executable, "-m", "tensorflowdistributedlearning_tpu",
            *sb,
            "--model-dir", scratch,
            "--batch-size", str(local_bs * world),
            "--steps", "2",
            "--eval-every", "100000",
        ]
        if coordinator is not None:
            child += [
                "--coordinator-address", coordinator,
                "--num-processes", str(world),
                "--process-id", str(pid),
            ]
        return child

    aot_standby = bool(getattr(args, "aot_standby", False))
    if aot_standby and not getattr(args, "compile_cache_dir", None):
        print(
            "fit: --aot-standby needs --compile-cache-dir (the standby's "
            "compiles have nowhere to land) — standby disabled",
            file=sys.stderr,
        )
        aot_standby = False

    def plan_fn(world, measured_margin_bytes):
        return _elastic_whatif_plan(
            args, preset, local_bs, world, measured_margin_bytes
        )

    cfg = ElasticConfig(
        hosts=hosts,
        min_hosts=args.min_hosts,
        devices_per_host=args.devices_per_host,
        drain_timeout_s=args.drain_timeout,
        straggler_threshold=args.evict_threshold,
        straggler_sustained=(
            10**9 if args.no_straggler_evict else args.evict_sustained
        ),
        eviction_cooldown_s=args.evict_cooldown,
        # None (flag not given) = the elastic default of 3; an EXPLICIT 0
        # disables same-shape restarts (fail fast on deterministic crashes)
        max_restarts=3 if args.max_restarts is None else args.max_restarts,
        aot_standby=aot_standby,
        seed=getattr(args, "seed", 0),
    )
    child_env = dict(os.environ, TFDL_SUPERVISED_CHILD="1")
    try:
        result = ElasticCoordinator(
            child_argv_fn,
            args.model_dir,
            cfg,
            plan_fn=plan_fn,
            standby_argv_fn=standby_argv_fn if aot_standby else None,
            env=child_env,
        ).run()
    finally:
        # standby scratch workdirs hold throwaway checkpoints/ledgers; the
        # compiles they existed for are already in --compile-cache-dir
        for scratch in _standby_scratch.values():
            shutil.rmtree(scratch, ignore_errors=True)
    print(
        json.dumps(
            {
                "elastic": True,
                "ok": result.ok,
                "world_size": result.world_size,
                "resizes": result.resizes,
                "restarts": result.restarts,
                "evictions": result.evictions,
                "aborted": result.aborted,
                "final_step": result.final_step,
                "resize_downtime_s": result.resize_downtime_s,
                "post_resize_settle_s": result.post_resize_settle_s,
            }
        ),
        file=sys.stderr,
        flush=True,
    )
    if result.ok:
        return 0
    return shell_rc(result.exit_code) or 1


def _run_supervised(args, argv: List[str]) -> int:
    """``train/fit --max-restarts N``: re-exec this command under the restart
    supervisor (resilience/supervisor.py), rooted at the model dir's run
    ledger for progress tracking and restart accounting."""
    import os

    from tensorflowdistributedlearning_tpu.resilience.supervisor import Supervisor

    # the env marker (checked in main()) makes supervisor recursion
    # structurally impossible even if a --max-restarts spelling survives the
    # argv strip (argparse accepts prefix abbreviations like --max-rest)
    child_env = dict(os.environ, TFDL_SUPERVISED_CHILD="1")
    result = Supervisor(
        [sys.executable, "-m", "tensorflowdistributedlearning_tpu",
         *_strip_supervisor_flags(argv)],
        workdir=args.model_dir,
        max_restarts=args.max_restarts,
        seed=getattr(args, "seed", 0),
        env=child_env,
    ).run()
    print(
        json.dumps(
            {
                "supervised": True,
                "ok": result.ok,
                "restarts": result.restarts,
                "aborted": result.aborted,
                "final_step": result.final_step,
                "downtime_s": result.downtime_s,
            }
        ),
        file=sys.stderr,
        flush=True,
    )
    if result.ok:
        return 0
    # a child killed by signal N reports rc=-N; surface the conventional
    # 128+N instead of a negative value the shell would fold mod 256
    from tensorflowdistributedlearning_tpu.resilience.supervisor import (
        shell_rc,
    )

    return shell_rc(result.exit_code) or 1


def main(argv: Optional[List[str]] = None) -> int:
    logging.basicConfig(level=logging.INFO)
    from tensorflowdistributedlearning_tpu.utils.devices import apply_platform_env

    apply_platform_env()
    raw_argv = list(argv) if argv is not None else sys.argv[1:]
    args = build_parser().parse_args(raw_argv)
    # one compile cache for every command, placed before anything compiles
    # (utils/compile_cache.py: env, else the flag, else the checkout's fixed
    # dir; none on a CPU-pinned run). Touches no backend, so controllers
    # that only spawn chip users call it too.
    from tensorflowdistributedlearning_tpu.utils import compile_cache

    compile_cache.configure(getattr(args, "compile_cache_dir", None))
    if args.command in ("train", "fit"):
        import os

        if getattr(args, "elastic", 0) > 0 and not os.environ.get(
            "TFDL_SUPERVISED_CHILD"
        ):
            return _run_elastic(args, raw_argv)
        if (getattr(args, "max_restarts", None) or 0) > 0 and not os.environ.get(
            "TFDL_SUPERVISED_CHILD"
        ):
            return _run_supervised(args, raw_argv)
        from tensorflowdistributedlearning_tpu.resilience import faults, preempt

        if getattr(args, "inject_fault", None):
            faults.install(args.inject_fault, seed=getattr(args, "seed", 0))
        # first SIGTERM/SIGINT: checkpoint at the next step boundary and exit
        # EXIT_PREEMPTED; a second signal kills immediately
        preempt.install(notice_file=getattr(args, "preempt_notice_file", None))
        from tensorflowdistributedlearning_tpu.obs.health import (
            HealthAbortError,
        )

        try:
            return {"train": cmd_train, "fit": cmd_fit}[args.command](args)
        except preempt.PreemptedError as e:
            print(
                json.dumps({"preempted": True, "step": e.step}), flush=True
            )
            return preempt.EXIT_PREEMPTED
        except HealthAbortError as e:
            # the NaN guard's abort action: the health_alert ledger event
            # precedes this exit; surface a structured verdict, not a
            # traceback
            print(
                json.dumps({"health_abort": True, "reason": str(e)}),
                flush=True,
            )
            return 1
        finally:
            # embedding callers (tests, notebooks) must not inherit the
            # process-global handler/injector past the command
            preempt.uninstall()
            faults.uninstall()
    return {
        "train": cmd_train,
        "predict": cmd_predict,
        "smoke": cmd_smoke,
        "fit": cmd_fit,
        "serve": cmd_serve,
        "serve-fleet": cmd_serve_fleet,
        "promote": cmd_promote,
        "quantize-check": cmd_quantize_check,
        "records-ingest": cmd_records_ingest,
        "flywheel": cmd_flywheel,
        "presets": cmd_presets,
        "plan": cmd_plan,
        "records-index": cmd_records_index,
        "telemetry-report": cmd_telemetry_report,
        "telemetry-top": cmd_telemetry_top,
        "doctor": cmd_doctor,
    }[args.command](args)


if __name__ == "__main__":
    raise SystemExit(main())
