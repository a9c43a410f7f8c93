"""Read the numbers a cell's limits are set from, on the chip, in one
process: for each seed the program through its first steps (a window of one
step, so set-up is all it costs) against the reference; for the control
seeds, the reference in the program's place in int8, and each fault a
training cell can have planted in it.

    python3 perfbench/calibrate.py --workload <cell> --seeds 11,12,... \\
        --control-seeds 11,12,13 --out chiprun_out/cal_<cell>.jsonl

The benchmark's own runs never call this.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--kinds", default="int8,bf16,half_batch,prep",
                        help="which plants to read on the control seeds")
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from perfbench import compare, harness

    cell = harness.load_cell(args.workload)
    entry = importlib.import_module("perfbench.entries." + cell.traffic["entry"])
    reference = importlib.import_module("perfbench.reference." + cell.config["reference"])
    ref_cfg = cell.reference_cfg
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a", encoding="utf-8") as out:

        def record(**row):
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(json.dumps(row), flush=True)

        kept = {}
        for seed in [int(s) for s in args.seeds.split(",") if s]:
            got = {}
            t = time.perf_counter()
            result, checks = entry.run(
                cell, seed=seed, seconds=0.0, trace=False, process_t0=T0,
                warmup_steps=harness.FOLLOWED_STEPS, collect=got,
            )
            record(kind="program", seed=seed, seconds=time.perf_counter() - t,
                   numbers=got["numbers"],
                   peak_bytes=result["device"]["memory_peak_bytes"])
            if seed in control_seeds:
                kept[seed] = got
        # the plants come last: a program run that follows one in this process
        # starts with its programs evicted and takes minutes longer
        plants = {
            "int8": dict(quant=reference.quant_int8),
            "e4m3": dict(quant=reference.quant_e4m3),
            "bf16": dict(quant=reference.quant_bf16),
            "half_batch": dict(drop_half=True),
        }
        if cell.chips > 1:
            plants["no_exchange"] = dict(skip_exchange=True)
        wanted = [k for k in args.kinds.split(",") if k]
        for seed, got in kept.items():
            for name in wanted:
                if name not in plants:
                    continue
                t = time.perf_counter()
                out_c = jax.device_get(reference.train_steps(
                    ref_cfg, jax.device_put(got["params0"]), got["fed"],
                    shards=cell.chips, **plants[name]))
                numbers = compare.training_numbers(out_c, got["reference"], got["params0"],
                                                   head=reference.head_leaves(ref_cfg))
                record(kind=name, seed=seed, seconds=time.perf_counter() - t, numbers=numbers)
            if "prep" not in wanted:
                continue
            if cell.config["task"] == "segmentation":
                from perfbench.entries import train, training

                augment = cell.config["augment"]
                program_seed = training.train_config(cell, seed).seed
                expected = train.expected_feeds(reference, augment, program_seed, got["raw"])
                planted = train.expected_feeds(reference, augment, program_seed, got["raw"],
                                               matrix_quant=reference.quant_bf16)
                record(kind="prep_bf16_matrix", seed=seed, numbers=compare.seg_prepare_numbers(
                    planted, expected, reference.laplace_channel))
                untouched = [
                    {"images": jax.device_get(reference.laplace_channel(raw["images"])),
                     "labels": raw["masks"]} for _, raw in got["raw"]
                ]
                record(kind="prep_skipped", seed=seed, numbers=compare.seg_prepare_numbers(
                    untouched, expected, reference.laplace_channel))
                no_laplace = [
                    {"images": np.concatenate([e["images"][..., :1]] * 2, axis=-1),
                     "labels": e["labels"]} for e in expected
                ]
                record(kind="prep_no_laplace", seed=seed, numbers=compare.seg_prepare_numbers(
                    no_laplace, expected, reference.laplace_channel))
    return 0


if __name__ == "__main__":
    sys.exit(main())
