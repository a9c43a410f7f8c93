"""Continuous-learning loop drill: serve -> capture -> drift -> retrain -> promote.

The headline end-to-end exercise for the ``loop/`` subsystem, driven entirely
through the REAL CLIs (``serve-fleet``, ``flywheel``, and the flywheel's own
``fit --export-serving --auto-promote`` retrain subprocess):

1. Export a synthetic seed artifact whose ``class`` output tracks the
   per-example input mean (same shape contract as the ``elastic_smoke``
   preset's export: ``(b, 16, 16, 3) -> {class, probabilities}``), and stamp
   its ``drift_baseline`` exactly like a production export.
2. Launch a 2-replica ``serve-fleet`` with the capture tee and the drift
   monitor armed, and run closed-loop clients against the router for the
   WHOLE drill — zero client-visible errors end to end is a committed gate.
3. Phase 1: standard-normal traffic (matches the pinned baseline) builds the
   captured dataset. Phase 2: mean-shifted traffic moves the served class
   distribution, and the DriftMonitor must fire a ``drift_alert``.
4. ``flywheel --max-cycles 1`` ingests the captured shards, fires on the
   alert, retrains on the REAL captured dataset, and its ``--auto-promote``
   (with loosened shadow bands — a retrained model legitimately disagrees
   with the incumbent) flips the fleet to the new fingerprint.

The committed BENCH_LOOP.json records cycle wall time, samples
captured/ingested, drift-trigger latency, the promoted fingerprint, and the
client error count; ``tools/regression_sentinel.py`` (``check_loop``) replays
those numbers as hard CI gates.

A synthetic seed model (the bench_serve idiom) rather than a barely-trained
preset model: four ``fit`` steps collapse the micro ResNet to one class for
ANY input, which would make the drift score identically zero — the drill
needs a seed whose output distribution genuinely follows its input
distribution so the alert is earned, not injected.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import signal as signal_lib
import subprocess
import sys
import tempfile
import threading
import time
import urllib.parse
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

H, W, C = 16, 16, 3  # the elastic_smoke preset's input shape
NUM_CLASSES = 4


def export_seed_artifact(directory: str) -> str:
    """Synthetic mean-responsive classifier through the real serving seam.

    ``class = argmin_c (mean(x) - center_c)^2`` over centers packed inside
    one std of the per-example mean (sigma = 1/sqrt(16*16*3) ~ 0.036 under
    standard-normal inputs), so baseline traffic spreads over classes 0-2
    and a +1.0 mean shift lands every example in class 3 — a total-variation
    distance of ~1.0, far past any sane threshold."""
    import jax
    import jax.numpy as jnp

    from tensorflowdistributedlearning_tpu.serve.quant_check import (
        stamp_drift_baseline,
    )
    from tensorflowdistributedlearning_tpu.train import quantize
    from tensorflowdistributedlearning_tpu.train import serving as serving_lib

    centers = jnp.asarray([-0.03, 0.0, 0.03, 0.5], jnp.float32)
    params = {"centers": centers}
    _, section = quantize.quantize_pytree(params, "float32")

    def serve(x):
        m = jnp.mean(x, axis=(1, 2, 3))
        logits = -((m[:, None] - params["centers"][None, :]) ** 2) / 0.002
        return {
            "class": jnp.argmax(logits, axis=-1).astype(jnp.int32),
            "probabilities": jax.nn.softmax(logits, axis=-1),
        }

    serving_lib.export_serving_artifact(
        serve,
        (1, H, W, C),
        directory,
        metadata={"task": "classification", "num_classes": NUM_CLASSES},
        quantization=section,
    )
    stamp_drift_baseline(directory)
    return directory


def spawn_fleet(artifact: str, workdir: str, capture_dir: str, args):
    """The real tier — ``serve-fleet`` CLI in its own process — with the
    capture tee and drift monitor armed; returns ``(proc, router_url)``."""
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get(
        "PYTHONPATH", ""))
    cmd = [
        sys.executable, "-m", "tensorflowdistributedlearning_tpu",
        "serve-fleet",
        "--artifact-dir", artifact,
        "--workdir", workdir,
        "--port", "0",
        "--replicas", str(args.replicas),
        "--no-autoscale",
        "--window-secs", str(args.window_secs),
        "--poll-interval-s", "0.25",
        "--capture-dir", capture_dir,
        "--capture-fraction", "1.0",
        "--capture-records-per-shard", "32",
        "--drift-threshold", str(args.drift_threshold),
        "--drift-min-requests", "20",
        "--drift-sustain-windows", "2",
    ]
    os.makedirs(workdir, exist_ok=True)
    log_fh = open(os.path.join(workdir, "controller.log"), "ab")
    try:
        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=log_fh, env=env, text=True
        )
    finally:
        log_fh.close()
    url: dict = {}

    def reader():
        for line in proc.stdout:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if "router" in obj:
                url["router"] = obj["router"]
                return

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    t.join(180)
    if "router" not in url:
        proc.kill()
        raise RuntimeError(
            f"serve-fleet not ready — see {workdir}/controller.log"
        )
    return proc, url["router"]


def stop_fleet(proc) -> None:
    if proc.poll() is not None:
        return
    proc.send_signal(signal_lib.SIGTERM)
    try:
        proc.wait(90)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(10)


class LoadGen:
    """Closed-loop clients for the whole drill. ``shift`` is mutable — the
    drift phase moves the input mean without dropping a single connection.
    Every non-200 answer is a client-visible error (the zero-errors gate);
    transient transport errors during replica flips count too — the router
    is supposed to absorb them."""

    def __init__(self, url: str, concurrency: int, seed: int = 11):
        self.parsed = urllib.parse.urlsplit(url)
        self.ok = 0
        self.errors = 0
        self.shift = 0.0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        rng = np.random.default_rng(seed)
        self._bodies = [
            rng.normal(0, 1, (1, H, W, C)).astype(np.float32)
            for _ in range(8)
        ]
        self.threads = [
            threading.Thread(target=self._run, args=(i,), daemon=True)
            for i in range(concurrency)
        ]
        for t in self.threads:
            t.start()

    def _run(self, i: int):
        conn = None
        n = 0
        while not self._stop.is_set():
            base = self._bodies[(i + n) % len(self._bodies)]
            n += 1
            body = json.dumps(
                {"instances": (base + self.shift).tolist()}
            )
            try:
                if conn is None:
                    conn = http.client.HTTPConnection(
                        self.parsed.hostname, self.parsed.port, timeout=30
                    )
                conn.request("POST", "/v1/predict", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                with self._lock:
                    if resp.status == 200:
                        self.ok += 1
                    else:
                        self.errors += 1
            except (OSError, http.client.HTTPException):
                try:
                    if conn is not None:
                        conn.close()
                except OSError:
                    pass
                conn = None
                with self._lock:
                    self.errors += 1
            time.sleep(0.01)

    def stop(self):
        self._stop.set()
        for t in self.threads:
            t.join(10)


def _ledger_events(workdir: str, kind: str) -> list:
    from tensorflowdistributedlearning_tpu.obs import fleet as obs_fleet

    out = []
    for led in obs_fleet.discover_ledgers(workdir):
        out.extend(e for e in led.events if e.get("event") == kind)
    return sorted(out, key=lambda e: e.get("t", 0.0))


def run_drill(args) -> dict:
    from tensorflowdistributedlearning_tpu.loop.controller import (
        scan_drift_alerts,
    )
    from tensorflowdistributedlearning_tpu.loop.ingest import (
        read_dataset_manifest,
    )

    root = tempfile.mkdtemp(prefix="bench_loop_")
    workdir = os.path.join(root, "fleet")
    capture_dir = os.path.join(root, "capture")
    dataset_dir = os.path.join(root, "dataset")
    seed_dir = export_seed_artifact(os.path.join(root, "seed"))
    t_drill0 = time.monotonic()
    proc, router = spawn_fleet(seed_dir, workdir, capture_dir, args)
    result: dict = {"router": router, "workdir": root}
    load = None
    try:
        load = LoadGen(router, args.concurrency)
        # phase 1: in-distribution traffic builds the captured dataset
        time.sleep(args.capture_secs)
        baseline_ok = load.ok
        if baseline_ok == 0:
            raise RuntimeError("no successful requests during capture phase")
        # phase 2: shift the input mean — the drift monitor must fire
        load.shift = args.shift
        t_shift = time.time()
        alert = None
        deadline = time.monotonic() + args.drift_timeout
        while time.monotonic() < deadline:
            alert = scan_drift_alerts(workdir, since_t=t_shift)
            if alert is not None:
                break
            time.sleep(0.25)
        if alert is None:
            raise RuntimeError(
                f"no drift_alert within {args.drift_timeout}s of the shift"
            )
        result["drift_alert"] = {
            "score": alert.get("score"),
            "threshold": alert.get("threshold"),
            "latency_s": round(alert["t"] - t_shift, 3),
        }
        # the flywheel closes the loop: ingest -> drift trigger -> retrain
        # (on the REAL captured dataset) -> auto-promote flips the fleet
        retrain_model_dir = os.path.join(root, "retrain")
        t_cycle0 = time.monotonic()
        fw = subprocess.run(
            [
                sys.executable, "-m", "tensorflowdistributedlearning_tpu",
                "flywheel",
                "--capture-dir", capture_dir,
                "--dataset-dir", dataset_dir,
                "--fleet-workdir", workdir,
                "--min-new-records", "0",
                "--poll-secs", "0.5",
                "--max-cycles", "1",
                "--max-wait-secs", str(args.drift_timeout),
                "--",
                "fit", "--preset", "elastic_smoke",
                "--model-dir", retrain_model_dir,
                "--data-dir", dataset_dir,
                "--steps", str(args.retrain_steps),
                "--export-serving",
                "--auto-promote",
                "--fleet-workdir", workdir,
                "--promote-shadow-secs", "2",
                "--promote-min-requests", "8",
                "--promote-max-disagree", "1.0",
                "--promote-max-abs-delta", "1e9",
                "--promote-max-mean-delta", "1e9",
                "--promote-min-iou", "0.0",
                "--promote-max-p99-ratio", "50.0",
            ],
            capture_output=True, text=True, timeout=900,
            env=dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get(
                "PYTHONPATH", ""), JAX_PLATFORMS="cpu"),
        )
        cycle_wall_s = round(time.monotonic() - t_cycle0, 3)
        tail = [ln for ln in fw.stdout.splitlines() if ln.startswith("{")]
        fw_summary = json.loads(tail[-1]) if tail else {}
        if fw.returncode != 0:
            raise RuntimeError(
                f"flywheel rc={fw.returncode}: "
                + fw.stderr.strip().splitlines()[-1][:300]
                if fw.stderr.strip() else f"flywheel rc={fw.returncode}"
            )
        # let the post-flip fleet answer shifted traffic for a beat — the
        # retrained model's OWN baseline covers it, so no new alert storm
        time.sleep(2.0)
        status = json.loads(urllib.request.urlopen(
            router + "/admin/promotion", timeout=10
        ).read())
        result["artifact_mix"] = status.get("artifacts")
        load.stop()
        # -- harvest the ledgers ------------------------------------------
        manifest = read_dataset_manifest(dataset_dir)
        triggers = _ledger_events(workdir, "loop_trigger")
        promoted = _ledger_events(workdir, "loop_promoted")
        completes = _ledger_events(workdir, "promotion_complete")
        windows = _ledger_events(workdir, "capture_window")
        per_replica: dict = {}
        for w in windows:
            per_replica[w.get("replica")] = w
        captured = sum(
            w.get("total_captured", 0) for w in per_replica.values()
        )
        drift_triggers = [
            t for t in triggers if t.get("reason") == "drift"
        ]
        trig_latency = None
        if drift_triggers and drift_triggers[-1].get("drift_alert_t"):
            trig_latency = round(
                max(0.0, drift_triggers[-1]["t"]
                    - drift_triggers[-1]["drift_alert_t"]), 3,
            )
        result.update({
            "replicas": args.replicas,
            "flywheel": {
                "rc": fw.returncode,
                "cycles": fw_summary.get("cycles"),
                "promoted": fw_summary.get("promoted"),
                "rejected": fw_summary.get("rejected"),
            },
            "cycle_wall_s": cycle_wall_s,
            "samples_captured": int(captured),
            "samples_ingested": int(manifest.get("records_total", 0)),
            "dataset_version": int(manifest.get("version", 0)),
            "drift_trigger_latency_s": trig_latency,
            "promoted_fingerprint": (
                completes[-1].get("fingerprint") if completes else None
            ),
            "loop_promoted_events": len(promoted),
            "client_ok": load.ok,
            "client_errors": load.errors,
            "drill_wall_s": round(time.monotonic() - t_drill0, 3),
        })
    finally:
        if load is not None:
            load.stop()
        stop_fleet(proc)
    return result


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--replicas", type=int, default=2)
    parser.add_argument("--concurrency", type=int, default=4)
    parser.add_argument("--window-secs", type=float, default=1.0)
    parser.add_argument("--capture-secs", type=float, default=6.0,
                        help="phase-1 (in-distribution) load duration — "
                        "what the retrain dataset is captured from")
    parser.add_argument("--shift", type=float, default=1.0,
                        help="input mean shift for the drift phase")
    parser.add_argument("--drift-threshold", type=float, default=0.35)
    parser.add_argument("--drift-timeout", type=float, default=60.0)
    parser.add_argument("--retrain-steps", type=int, default=4)
    parser.add_argument("--json-out", default=None)
    args = parser.parse_args()

    result = run_drill(args)
    # the fleet and the retrain it drives run with JAX_PLATFORMS=cpu
    result["platform"] = "cpu"
    result["note"] = "CPU drill: counts and correctness checks; its timings are not device numbers"
    print(json.dumps(result, indent=1))
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1)
    ok = (
        result.get("client_errors") == 0
        and result.get("flywheel", {}).get("promoted", 0) >= 1
        and result.get("samples_ingested", 0) > 0
        and result.get("promoted_fingerprint")
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
