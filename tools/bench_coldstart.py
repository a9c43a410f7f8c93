"""Cold-start bench: compile-free restarts, replicas, and resizes, measured.

Drives the REAL CLI + serving seams end to end on the CPU harness and
records the three cold-start cliffs this codebase claims to have killed:

1. **Train rerun** — ``fit --compile-cache-dir`` twice with the same shape
   into a shared cache: the second run must ledger cache hits and reach its
   first step measurably faster (warmup is loads, not compiles).
2. **Replica time-to-ready** — the first run's ``--export-serving``
   artifact loaded twice into one replica cache dir: the first replica
   compiles the bucket ladder, the second must go ready in ≤ half that time
   with the ladder answered from the cache the first one filled.
3. **Elastic AOT standby** — the host-death resize drill with and without
   ``--aot-standby``: with the standby, the resized generation's compiles
   are served from the cache the standby mini-world populated, and the
   resume stays bit-identical to a clean run (the standby must never touch
   training math).

``--check`` gates the result; the COMMITTED BENCH_COLDSTART.json replays
as hard gates in tools/regression_sentinel.py (a cold-start-path PR must
re-run this bench and commit numbers that still clear them)::

    python tools/bench_coldstart.py --check --json-out BENCH_COLDSTART.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

TOOLS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TOOLS)
sys.path.insert(0, TOOLS)

import bench_elastic  # noqa: E402  — shared drill/shard/digest harness

PRESET = bench_elastic.PRESET
LOCAL_BS = bench_elastic.LOCAL_BS
_env = bench_elastic._env
_read_ledger = bench_elastic._read_ledger


# -- scenario 1: same-shape train rerun --------------------------------------


def run_train(
    workdir: str,
    data_dir: str,
    cache_dir: str,
    *,
    steps: int = 6,
    export_serving: bool = False,
    timeout: int = 420,
) -> Dict:
    """One plain ``fit`` through the real CLI with the persistent cache on.
    Returns ledger-derived facts: time from run header to the first stepped
    event (the warmup the cache is supposed to shrink) and the run_end
    cache counters."""
    argv = [
        sys.executable, "-m", "tensorflowdistributedlearning_tpu", "fit",
        "--preset", PRESET,
        "--model-dir", workdir,
        "--data-dir", data_dir,
        "--steps", str(steps),
        "--batch-size", str(LOCAL_BS),
        "--eval-every", "100000",
        "--compile-cache-dir", cache_dir,
    ]
    if export_serving:
        argv.append("--export-serving")
    out = subprocess.run(
        argv, env=_env(1), capture_output=True, text=True, timeout=timeout,
        cwd=REPO,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"train run failed rc={out.returncode}: {out.stderr[-1500:]}"
        )
    events = _read_ledger(os.path.join(workdir, "telemetry.jsonl"))
    header_t = next(
        (e["t"] for e in events if e.get("event") == "run_header"), None
    )
    first_step_t = next(
        (
            e["t"]
            for e in events
            if isinstance(e.get("step"), (int, float)) and e.get("t")
        ),
        None,
    )
    run_end = next(
        (e for e in reversed(events) if e.get("event") == "run_end"), {}
    )
    compiles = [e for e in events if e.get("event") == "compile"]
    if header_t is None or first_step_t is None:
        raise RuntimeError(f"train ledger in {workdir} has no header/steps")
    facts = {
        "time_to_first_step_s": round(first_step_t - header_t, 3),
        "cache_hits": run_end.get("compile_cache_hits"),
        "cache_misses": run_end.get("compile_cache_misses"),
        "ledgered_cache_hits": sum(
            1 for e in compiles if e.get("cache_hit") is True
        ),
        "compiles": len(compiles),
    }
    if export_serving:
        artifact = os.path.join(workdir, "export", "serving")
        if not os.path.isdir(artifact):
            raise RuntimeError(f"--export-serving left no artifact in {workdir}")
        facts["artifact"] = artifact
    return facts


# -- scenario 2: replica time-to-ready ----------------------------------------

_REPLICA_SCRIPT = """
import json, sys, time
sys.path.insert(0, {repo!r})
from tensorflowdistributedlearning_tpu.utils import compile_cache
assert compile_cache.configure({cache_dir!r})
t0 = time.monotonic()
from tensorflowdistributedlearning_tpu.serve.engine import InferenceEngine
engine = InferenceEngine.from_artifact({artifact!r})
engine.warmup()
print(json.dumps({{
    "time_to_ready_s": round(time.monotonic() - t0, 4),
    "stats": compile_cache.stats(),
    "warmed": sorted(int(b) for b in engine.warmed_buckets),
}}))
"""


def load_replica(artifact: str, cache_dir: str, timeout: int = 240) -> Dict:
    """Measure a serve replica's load→ready time in a fresh interpreter
    (1-device serving topology, own persistent cache): engine construction
    through warmup — the window a warm compile cache is meant to
    collapse. Interpreter/jax import time is excluded; both the cold and
    warm variants pay it identically and the fleet already ledgers the
    spawn-inclusive time_to_ready_s per replica."""
    script = _REPLICA_SCRIPT.format(
        repo=REPO, cache_dir=cache_dir, artifact=artifact
    )
    out = subprocess.run(
        [sys.executable, "-c", script], env=_env(1), capture_output=True,
        text=True, timeout=timeout, cwd=REPO,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"replica load failed rc={out.returncode}: {out.stderr[-1500:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def replica_cold_vs_warm(artifact: str, tmp: str) -> Dict:
    # one cache dir, two fresh interpreters: the first replica to load an
    # artifact pays the ladder compile, every later one loads it
    cache = os.path.join(tmp, "replica_cache")
    cold = load_replica(artifact, cache)
    warm = load_replica(artifact, cache)
    out = {
        "cold_time_to_ready_s": cold["time_to_ready_s"],
        "warm_time_to_ready_s": warm["time_to_ready_s"],
        "cold_misses": cold["stats"]["misses"],
        "warm_hits": warm["stats"]["hits"],
        "warm_misses": warm["stats"]["misses"],
        "warmed_buckets": warm["warmed"],
    }
    if cold["time_to_ready_s"]:
        out["warm_over_cold"] = round(
            warm["time_to_ready_s"] / cold["time_to_ready_s"], 4
        )
    return out


# -- scenario 3: elastic resize with the AOT standby ---------------------------


def elastic_standby_drill(
    tmp: str,
    data_dir: str,
    *,
    steps: int,
    kill_step: int,
    devices_per_host: int,
    timeout: int,
) -> Dict:
    """The bench_elastic host-death drill twice — persistent cache on both
    times, ``--aot-standby`` on the second — plus the clean-run comparison
    on the standby drill (the standby must not perturb training math)."""
    facts: Dict = {}
    for label, extra in (
        ("nostandby", []),
        ("standby", ["--aot-standby"]),
    ):
        workdir = os.path.join(tmp, f"drill_{label}")
        cache = os.path.join(tmp, f"cache_{label}")
        drill = bench_elastic.run_elastic_drill(
            workdir, data_dir,
            steps=steps, kill_step=kill_step,
            devices_per_host=devices_per_host, timeout=timeout,
            extra_argv=["--compile-cache-dir", cache, *extra],
        )
        resize_t = drill["resize"]["t"]
        post_hits = sum(
            1
            for e in drill["events"]
            if e.get("event") == "compile"
            and e.get("cache_hit") is True
            and e.get("t", 0) > resize_t
        )
        standby_events = [
            e for e in drill["events"] if e.get("event") == "aot_standby"
        ]
        facts[label] = {
            "post_resize_settle_s": drill["verdict"].get(
                "post_resize_settle_s"
            ),
            "resize_downtime_s": drill["verdict"]["resize_downtime_s"],
            "post_resize_cache_hits": post_hits,
            "standby_started": any(
                e.get("action") == "start" for e in standby_events
            ),
            # terminal lifecycle state: "ready" (finished before the death),
            # "superseded" (reaped at drain — every entry compiled so far is
            # already on disk), "failed", or None (never started)
            "standby_outcome": next(
                (
                    e.get("action")
                    for e in reversed(standby_events)
                    if e.get("action") != "start"
                ),
                None,
            ),
            "wall_s": drill["wall_s"],
        }
        facts[f"_drill_{label}"] = drill  # internal: clean-run comparison
    drill = facts.pop("_drill_standby")
    facts.pop("_drill_nostandby")
    golden = os.path.join(tmp, "golden")
    bench_elastic.run_clean_comparison(
        golden, data_dir, os.path.join(tmp, "drill_standby"),
        drill["resume_step"],
        steps=steps, new_world=drill["resize"]["new_world"],
        devices_per_host=devices_per_host,
    )
    a = bench_elastic.params_digest(os.path.join(tmp, "drill_standby"))
    b = bench_elastic.params_digest(golden)
    facts["bit_identical_resume"] = a == b
    ns, sb = facts["nostandby"], facts["standby"]
    if ns["post_resize_settle_s"] and sb["post_resize_settle_s"]:
        facts["settle_standby_over_nostandby"] = round(
            sb["post_resize_settle_s"] / ns["post_resize_settle_s"], 4
        )
    return facts


# -- record / gates ------------------------------------------------------------


def run_bench(args) -> Dict:
    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="bench_coldstart_") as tmp:
        data_dir = os.path.join(tmp, "data")
        cache = os.path.join(tmp, "train_cache")
        os.makedirs(data_dir)
        bench_elastic.write_drill_shards(data_dir)
        cold = run_train(
            os.path.join(tmp, "train_cold"), data_dir, cache,
            steps=args.train_steps, export_serving=True,
        )
        warm = run_train(
            os.path.join(tmp, "train_warm"), data_dir, cache,
            steps=args.train_steps,
        )
        rerun = {
            "cold_time_to_first_step_s": cold["time_to_first_step_s"],
            "warm_time_to_first_step_s": warm["time_to_first_step_s"],
            "cold_cache_hits": cold["cache_hits"],
            "warm_cache_hits": warm["cache_hits"],
            "warm_ledgered_cache_hits": warm["ledgered_cache_hits"],
            "warm_cache_misses": warm["cache_misses"],
        }
        if cold["time_to_first_step_s"]:
            rerun["warm_over_cold"] = round(
                warm["time_to_first_step_s"] / cold["time_to_first_step_s"],
                4,
            )
        replica = replica_cold_vs_warm(cold["artifact"], tmp)
        elastic = elastic_standby_drill(
            tmp, data_dir,
            steps=args.steps, kill_step=args.kill_step,
            devices_per_host=args.devices_per_host, timeout=args.timeout,
        )
    return {
        "bench": "coldstart",
        # every child runs with JAX_PLATFORMS=cpu (bench_elastic._env)
        "platform": "cpu",
        "note": "CPU drill: counts and correctness checks; its timings are not device numbers",
        "preset": PRESET,
        "train_steps": args.train_steps,
        "elastic_steps": args.steps,
        "kill_step": args.kill_step,
        "devices_per_host": args.devices_per_host,
        "train_rerun": rerun,
        "replica": replica,
        "elastic_standby": elastic,
        "wall_s": round(time.time() - t0, 3),
    }


def check_record(
    record: Dict,
    *,
    max_replica_ratio: float,
    max_rerun_ratio: float,
) -> List[str]:
    """The bench's own gate (the sentinel replays the committed record with
    the same rules). Returns failure strings; empty = pass."""
    failures = []
    rerun = record.get("train_rerun") or {}
    if not (rerun.get("warm_cache_hits") or 0) >= 1:
        failures.append(
            f"second train run ledgered {rerun.get('warm_cache_hits')} "
            "cache hits — persistent cache not serving reruns (HARD)"
        )
    ratio = rerun.get("warm_over_cold")
    if ratio is None or ratio > max_rerun_ratio:
        failures.append(
            f"warm/cold time-to-first-step {ratio} > ceiling "
            f"{max_rerun_ratio} — rerun warmup not reduced"
        )
    replica = record.get("replica") or {}
    if not (replica.get("warm_hits") or 0) >= 1:
        failures.append(
            "warm replica load had no cache hits — the first replica's cache "
            "not consumed (HARD)"
        )
    r_ratio = replica.get("warm_over_cold")
    if r_ratio is None or r_ratio > max_replica_ratio:
        failures.append(
            f"warm/cold replica time-to-ready {r_ratio} > ceiling "
            f"{max_replica_ratio}"
        )
    elastic = record.get("elastic_standby") or {}
    if not elastic.get("bit_identical_resume"):
        failures.append(
            "resume with --aot-standby not bit-identical to clean run (HARD)"
        )
    sb = elastic.get("standby") or {}
    if not sb.get("standby_started"):
        failures.append("aot standby never ledgered action=start (HARD)")
    if sb.get("standby_outcome") not in ("ready", "superseded"):
        failures.append(
            f"aot standby ended {sb.get('standby_outcome')!r} — expected "
            "ready (finished) or superseded (reaped at drain)"
        )
    if not (sb.get("post_resize_cache_hits") or 0) >= 1:
        failures.append(
            "resized generation had no compile-cache hits — standby entries "
            "not consumed"
        )
    ns_settle = (elastic.get("nostandby") or {}).get("post_resize_settle_s")
    sb_settle = sb.get("post_resize_settle_s")
    if ns_settle is not None and sb_settle is not None:
        # absolute delta, not a ratio: settle is quantized by the
        # coordinator's poll interval (~2s ticks on a ~6s base), so a ratio
        # gate flaps on one tick. 4s = two ticks of headroom; the contention
        # bug this gate exists for (standby competing with the respawn)
        # measured +6s before the drain-time reap fixed it.
        if sb_settle - ns_settle > 4.0:
            failures.append(
                f"standby drill settled {sb_settle - ns_settle:.1f}s slower "
                "than the no-standby drill — the standby is competing with "
                "the respawn instead of pre-warming it"
            )
    return failures


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--train-steps", type=int, default=6)
    parser.add_argument("--steps", type=int, default=14,
                        help="elastic drill steps (kill late enough that "
                        "the standby mini-world finishes compiling before "
                        "the host death)")
    parser.add_argument("--kill-step", type=int, default=10)
    parser.add_argument("--devices-per-host", type=int, default=2)
    parser.add_argument("--timeout", type=int, default=600)
    parser.add_argument("--json-out", default=None)
    parser.add_argument("--check", action="store_true",
                        help="gate on the cold-start invariants (warm "
                        "replica ≤ half cold, rerun cache hits, standby "
                        "consumed, bit-identical resume)")
    parser.add_argument("--max-replica-ratio", type=float, default=0.5,
                        help="ceiling on warm/cold replica time-to-ready "
                        "(a cache the first replica filled must at "
                        "least halve the next one's readiness)")
    parser.add_argument("--max-rerun-ratio", type=float, default=0.9,
                        help="ceiling on warm/cold train time-to-first-step "
                        "(generous: compile is most but not all of warmup)")
    args = parser.parse_args(argv)

    record = run_bench(args)
    print(json.dumps(record, indent=1))
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    if args.check:
        failures = check_record(
            record,
            max_replica_ratio=args.max_replica_ratio,
            max_rerun_ratio=args.max_rerun_ratio,
        )
        for failure in failures:
            print(f"CHECK FAILED: {failure}", file=sys.stderr)
        return 1 if failures else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
