"""Read the numbers a decoder cell's limits are set from, on the chip, in one
process: for each seed the program through its first steps (a window of no
seconds, so set-up is all it costs) against the reference; then, on the
control seeds, the reference in the program's place with operands one
precision below (int8, fp8; bfloat16 for scale) and with each fault a decoder
step can have planted in it.

    python3 perfbench/calibrate_lm.py --workload <cell> --seeds 11,12,... \\
        --control-seeds 11 --out chiprun_out/cal_<cell>.jsonl

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

QUANTS = ("int8", "e4m3", "bf16")
FAULTS = ("top_k_minus_one", "no_renorm", "no_window", "no_yarn", "cross_documents",
          "capacity", "drop_half", "unchanged")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--kinds", default=",".join(QUANTS + FAULTS),
                        help="which plants to read on the control seeds")
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)

    import jax

    from perfbench import harness
    from perfbench.entries import lm_fit

    cell = harness.load_cell(args.workload)
    entry = importlib.import_module("perfbench.entries." + cell.traffic["entry"])
    reference = importlib.import_module("perfbench.reference." + cell.config["reference"])
    cfg = cell.config
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
            result, _ = entry.run(cell, seed=seed, seconds=0.0, trace=False, process_t0=T0,
                                  warmup_steps=harness.FOLLOWED_STEPS, collect=got)
            record(kind="program", seed=seed, seconds=time.perf_counter() - t,
                   numbers=got["numbers"], peak_bytes=result["device"]["memory_peak_bytes"])
            if seed in control_seeds:
                kept[seed] = got
        for seed, got in kept.items():
            for name in [k for k in args.kinds.split(",") if k]:
                plant = ({"quant": getattr(reference, "quant_" + name)} if name in QUANTS
                         else {"faults": (name,)})
                t = time.perf_counter()
                planted = reference.train_steps(cfg, jax.device_put(got["params0"]), got["fed"],
                                                **plant)
                numbers = lm_fit.lm_numbers(reference, cfg, planted, got["reference"],
                                            got["params0"])
                record(kind=name, seed=seed, seconds=time.perf_counter() - t, numbers=numbers)
    return 0


if __name__ == "__main__":
    sys.exit(main())
