"""``calibrate_lm.py`` for a cell of the ``lm_mixed_fit`` entry: the numbers its
limits are set from, on the chip, in one process — for each seed the program
through its first steps against the reference; then, on the control seeds, the
reference in the program's place with operands one precision below and with
each fault planted that a step of this family can have. The planted runs are
read with the entry's fourteen numbers, not ``lm_fit``'s eleven. Every row it
writes is then judged by the harness's own comparison (``compare.verdict``
with the cell's limits file) and written again with ``correct`` and the limits
it reads ``over`` to ``<out>.verdicts.jsonl``: a control is one only if it
comes out not correct there.

    python3 perfbench/calibrate_mixed.py --workload <cell> --seeds 11,12,... \\
        --control-seeds 11 --out chiprun_out/cal_<cell>.jsonl

The benchmark's own runs never call this.
"""

from __future__ import annotations

import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

QUANTS = ("int8", "e4m3")
# the faults of mellum_decoder.train_steps that touch what the decoders share
SHARED_FAULTS = ("top_k_minus_one", "capacity")


def main(argv=None) -> int:
    from perfbench import calibrate_lm, calibrate_sparse, harness
    from perfbench.entries import lm_fit, lm_mixed_fit
    from perfbench.reference import laguna_decoder

    argv = list(sys.argv[1:] if argv is None else argv)
    if "--kinds" not in argv:
        argv += ["--kinds", ",".join(QUANTS + laguna_decoder.FAULTS + SHARED_FAULTS)]
    out = argv[argv.index("--out") + 1]
    before = len(calibrate_sparse._rows(out))
    with mock.patch.object(lm_fit, "lm_numbers", lm_mixed_fit.mixed_numbers):
        rc = calibrate_lm.main(argv)
    limits = harness.load_cell(argv[argv.index("--workload") + 1]).limits
    with open(out + ".verdicts.jsonl", "a", encoding="utf-8") as f:
        for row in calibrate_sparse.judge(calibrate_sparse._rows(out)[before:], limits):
            f.write(json.dumps(row) + "\n")
            print("verdict %s seed %s: correct=%s over=%s" % (
                row["kind"], row["seed"], row["correct"], ",".join(row["over"]) or "-"), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
