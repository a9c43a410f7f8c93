"""Elastic resize bench: the headline host-death drill, measured.

Drives the REAL ``fit --elastic`` CLI end to end on the CPU pod harness:

1. writes tiny classification record shards;
2. runs a 2-host elastic world (``--devices-per-host 2`` → a dp4 mesh with
   ZeRO-1 on) fed by the streaming data service, with
   ``--host-inject-fault 1:sigkill-step@K`` vanishing host 1 after step K —
   the un-drainable host death;
3. lets the coordinator detect the death, drain the survivor (bounded — its
   collectives point at a dead peer), re-plan at world 1 via the parallelism
   planner, and resume with ZeRO-1 optimizer state resharded dp4→dp2 and the
   data service re-dealt to the new ``process_count``;
4. replays a CLEAN dp−1 run from the SAME checkpoint (copied resume-step
   checkpoint + data-state sidecar into a fresh workdir) and requires the
   final params BIT-IDENTICAL — the proof that the elastic path introduces
   no hidden state;
5. records the measured resize downtime and throughput-per-chip before/after
   the resize (from the ledger's ``cost`` events) into BENCH_ELASTIC.json.

``--check`` gates the result; the COMMITTED BENCH_ELASTIC.json replays as
hard gates in tools/regression_sentinel.py (an elastic-path PR must re-run
this bench and commit numbers that still clear them)::

    python tools/bench_elastic.py --check --json-out BENCH_ELASTIC.json
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRESET = "elastic_smoke"
# per-host batch of the drill (global batch = LOCAL_BS * world — the elastic
# contract keeps the per-host batch constant across resizes, so the data
# sidecar revalidates and the stream re-deals instead of refusing)
LOCAL_BS = 4


def _env(devices: int) -> Dict[str, str]:
    return dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
    )


def write_drill_shards(data_dir: str, *, n: int = 48, shards: int = 3) -> None:
    """Record shards matching the ``elastic_smoke`` preset's input shape, in
    a subprocess (shard writing needs no devices and must not initialize jax
    in the bench process)."""
    code = f"""
import sys
sys.path.insert(0, {REPO!r})
import numpy as np
from tensorflowdistributedlearning_tpu.data import records as rec
rng = np.random.default_rng(5)
images = [rng.integers(0, 255, (16, 16, 3), dtype=np.uint8)
          for _ in range({n})]
labels = list(rng.integers(0, 4, {n}))
rec.write_classification_shards({data_dir!r}, images, labels,
                                shards={shards})
"""
    subprocess.run(
        [sys.executable, "-c", code], env=_env(1), check=True,
        capture_output=True, text=True, cwd=REPO,
    )


def _read_ledger(path: str) -> List[Dict]:
    events = []
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    events.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return events


def run_elastic_drill(
    workdir: str,
    data_dir: str,
    *,
    steps: int = 12,
    kill_step: int = 8,
    hosts: int = 2,
    devices_per_host: int = 2,
    zero1: bool = True,
    drain_timeout: float = 30.0,
    timeout: int = 600,
    extra_argv: Optional[List[str]] = None,
) -> Dict:
    """The headline drill through the real CLI. Returns the measured facts;
    raises RuntimeError when the run itself failed. ``extra_argv`` appends
    drill variations (bench_coldstart reuses this for --compile-cache-dir /
    --aot-standby runs)."""
    argv = [
        sys.executable, "-m", "tensorflowdistributedlearning_tpu", "fit",
        "--preset", PRESET,
        "--model-dir", workdir,
        "--data-dir", data_dir,
        "--steps", str(steps),
        "--batch-size", str(LOCAL_BS * hosts),
        "--eval-every", "100000",
        "--elastic", str(hosts),
        "--min-hosts", "1",
        "--devices-per-host", str(devices_per_host),
        "--host-inject-fault", f"{hosts - 1}:sigkill-step@{kill_step}",
        "--drain-timeout", str(drain_timeout),
    ]
    if zero1:
        argv.append("--weight-update-sharding")
    if extra_argv:
        argv.extend(extra_argv)
    t0 = time.time()
    out = subprocess.run(
        argv, env=_env(devices_per_host), capture_output=True, text=True,
        timeout=timeout, cwd=REPO,
    )
    wall_s = time.time() - t0
    verdict_lines = [
        ln for ln in out.stderr.splitlines() if ln.startswith('{"elastic"')
    ]
    if out.returncode != 0 or not verdict_lines:
        raise RuntimeError(
            f"elastic drill failed rc={out.returncode}: "
            f"{out.stderr[-1500:]}"
        )
    verdict = json.loads(verdict_lines[-1])
    events = _read_ledger(os.path.join(workdir, "telemetry.jsonl"))
    resizes = [e for e in events if e.get("event") == "world_resize"]
    resumed = [e for e in events if e.get("event") == "resumed"]
    redeals = [e for e in events if e.get("event") == "data_redeal"]
    if not verdict.get("ok") or not resizes or not resumed:
        raise RuntimeError(
            f"drill did not resize+resume: verdict={verdict}, "
            f"resizes={len(resizes)}, resumed={len(resumed)}"
        )
    return {
        "verdict": verdict,
        "resize": resizes[-1],
        "resume_step": resumed[-1]["step"],
        "redeals": len(redeals),
        "wall_s": round(wall_s, 3),
        "events": events,
    }


def run_clean_comparison(
    golden_dir: str,
    data_dir: str,
    drill_dir: str,
    resume_step: int,
    *,
    steps: int = 12,
    new_world: int = 1,
    devices_per_host: int = 2,
    zero1: bool = True,
    timeout: int = 420,
) -> None:
    """A clean dp−1 run from the drill's resume checkpoint: copy that step's
    checkpoint + data-state sidecar into a fresh workdir and run plain
    ``fit`` at the post-resize world size. Its final params are the oracle
    the elastic run must match bit-for-bit."""
    ckpt_src = os.path.join(drill_dir, "checkpoints", str(resume_step))
    if not os.path.isdir(ckpt_src):
        raise RuntimeError(
            f"resume-step checkpoint {resume_step} was pruned from "
            f"{drill_dir} — shorten the drill (max_to_keep must retain it)"
        )
    os.makedirs(os.path.join(golden_dir, "checkpoints"), exist_ok=True)
    shutil.copytree(
        ckpt_src, os.path.join(golden_dir, "checkpoints", str(resume_step))
    )
    sidecar = os.path.join(
        drill_dir, "checkpoints", f"data_state-{resume_step}.json"
    )
    if os.path.exists(sidecar):
        shutil.copy(sidecar, os.path.join(golden_dir, "checkpoints"))
    argv = [
        sys.executable, "-m", "tensorflowdistributedlearning_tpu", "fit",
        "--preset", PRESET,
        "--model-dir", golden_dir,
        "--data-dir", data_dir,
        "--steps", str(steps),
        "--batch-size", str(LOCAL_BS * new_world),
        "--eval-every", "100000",
    ]
    if zero1:
        argv.append("--weight-update-sharding")
    out = subprocess.run(
        argv, env=_env(devices_per_host), capture_output=True, text=True,
        timeout=timeout, cwd=REPO,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"clean comparison run failed rc={out.returncode}: "
            f"{out.stderr[-1500:]}"
        )


def params_digest(model_dir: str, timeout: int = 240) -> Dict:
    """sha256 over the latest checkpoint's params+batch_stats leaves,
    computed in a subprocess (fresh interpreter, single device — the digest
    must not depend on the caller's jax state)."""
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--digest", model_dir],
        env=_env(1), capture_output=True, text=True, timeout=timeout,
        cwd=REPO,
    )
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        raise RuntimeError(
            f"digest of {model_dir} failed rc={out.returncode}: "
            f"{out.stderr[-800:]}"
        )
    return json.loads(lines[-1])


def _cmd_digest(model_dir: str) -> int:
    import hashlib

    sys.path.insert(0, REPO)
    import jax
    import numpy as np

    from tensorflowdistributedlearning_tpu.configs import get_preset
    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

    preset = get_preset(PRESET)
    trainer = ClassifierTrainer(model_dir, None, preset.model, preset.train)
    ckpt = trainer._checkpointer()
    try:
        state = ckpt.restore_latest(trainer._host_template())
    finally:
        ckpt.close()
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(
        {"p": state.params, "bs": state.batch_stats}
    ):
        h.update(np.asarray(jax.device_get(leaf)).tobytes())
    print(json.dumps({
        "step": int(jax.device_get(state.step)),
        "digest": h.hexdigest(),
    }))
    return 0


def throughput_per_chip_split(events: List[Dict], resize_t: float) -> Dict:
    """Median ``examples_per_chip_second`` of the clean cost windows before
    vs after the resize timestamp — the per-chip efficiency the resize must
    roughly preserve (each generation pays one fresh compile, excluded by
    taking the median, not the mean)."""

    def med(rows: List[float]) -> Optional[float]:
        return round(statistics.median(rows), 3) if rows else None

    before, after = [], []
    for e in events:
        if e.get("event") != "cost" or e.get("scope") != "train":
            continue
        rate = e.get("examples_per_chip_second")
        if rate is None:
            continue
        (before if e.get("t", 0) < resize_t else after).append(float(rate))
    out = {
        "before": med(before),
        "after": med(after),
        "windows_before": len(before),
        "windows_after": len(after),
    }
    if out["before"] and out["after"]:
        out["after_over_before"] = round(out["after"] / out["before"], 4)
    return out


def run_bench(args) -> Dict:
    with tempfile.TemporaryDirectory(prefix="bench_elastic_") as tmp:
        data_dir = os.path.join(tmp, "data")
        drill_dir = os.path.join(tmp, "drill")
        golden_dir = os.path.join(tmp, "golden")
        os.makedirs(data_dir)
        write_drill_shards(data_dir)
        drill = run_elastic_drill(
            drill_dir, data_dir,
            steps=args.steps, kill_step=args.kill_step,
            devices_per_host=args.devices_per_host,
            timeout=args.timeout,
        )
        resize = drill["resize"]
        run_clean_comparison(
            golden_dir, data_dir, drill_dir, drill["resume_step"],
            steps=args.steps, new_world=resize["new_world"],
            devices_per_host=args.devices_per_host,
        )
        a = params_digest(drill_dir)
        b = params_digest(golden_dir)
        record = {
            "bench": "elastic",
            # every child runs with JAX_PLATFORMS=cpu (_env)
            "platform": "cpu",
            "note": "CPU drill: counts and correctness checks; its timings are not device numbers",
            "preset": PRESET,
            "hosts": 2,
            "devices_per_host": args.devices_per_host,
            "steps": args.steps,
            "kill_step": args.kill_step,
            "zero1": True,
            "resize": {
                k: resize.get(k)
                for k in (
                    "old_world", "new_world", "reason", "progress_step",
                    "downtime_s", "rc",
                )
            },
            "resume_step": drill["resume_step"],
            "data_redeals": drill["redeals"],
            "final_step": a["step"],
            "bit_identical_resume": a == b,
            "throughput_per_chip": throughput_per_chip_split(
                drill["events"], resize["t"]
            ),
            "resize_downtime_s": drill["verdict"]["resize_downtime_s"],
            "wall_s": drill["wall_s"],
        }
    return record


def check_record(
    record: Dict,
    *,
    max_downtime_s: float,
    min_throughput_ratio: float,
) -> List[str]:
    """The bench's own gate (the sentinel replays the committed record with
    the same rules). Returns failure strings; empty = pass."""
    failures = []
    if not record.get("bit_identical_resume"):
        failures.append("bit_identical_resume != true (HARD)")
    resize = record.get("resize") or {}
    if resize.get("old_world") == resize.get("new_world"):
        failures.append("no world resize happened (HARD)")
    if resize.get("reason") != "host_death":
        failures.append(f"resize reason {resize.get('reason')} != host_death")
    downtime = record.get("resize_downtime_s")
    if downtime is None or downtime > max_downtime_s:
        failures.append(
            f"resize_downtime_s {downtime} > ceiling {max_downtime_s}"
        )
    ratio = (record.get("throughput_per_chip") or {}).get("after_over_before")
    if ratio is not None and ratio < min_throughput_ratio:
        failures.append(
            f"throughput_per_chip after/before {ratio} < floor "
            f"{min_throughput_ratio}"
        )
    return failures


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__.splitlines()[0]
    )
    parser.add_argument("--digest", default=None, metavar="MODEL_DIR",
                        help="internal: print the latest checkpoint's param "
                        "digest for MODEL_DIR and exit")
    parser.add_argument("--steps", type=int, default=12)
    parser.add_argument("--kill-step", type=int, default=8)
    parser.add_argument("--devices-per-host", type=int, default=2)
    parser.add_argument("--timeout", type=int, default=600)
    parser.add_argument("--json-out", default=None)
    parser.add_argument("--check", action="store_true",
                        help="gate on the drill's hard invariants "
                        "(bit-identical resume, a real resize, downtime "
                        "ceiling, throughput floor)")
    parser.add_argument("--max-downtime", type=float, default=60.0,
                        help="resize downtime ceiling in seconds (drain + "
                        "re-plan + respawn as the coordinator measured it; "
                        "generous — CI boxes are slow, and the committed "
                        "record is the real gate)")
    parser.add_argument("--min-throughput-ratio", type=float, default=0.4,
                        help="floor on median examples-per-chip-second "
                        "after/before the resize (per-chip efficiency must "
                        "survive the resize; dp shrinks but so does the "
                        "batch, so the per-chip rate should hold)")
    args = parser.parse_args(argv)
    if args.digest:
        return _cmd_digest(args.digest)

    record = run_bench(args)
    print(json.dumps(record, indent=1))
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    if args.check:
        failures = check_record(
            record,
            max_downtime_s=args.max_downtime,
            min_throughput_ratio=args.min_throughput_ratio,
        )
        for failure in failures:
            print(f"CHECK FAILED: {failure}", file=sys.stderr)
        return 1 if failures else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
