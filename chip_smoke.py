"""chip_smoke.py — does the system still start on the chip?

Drives the flagship's normal path once, at published width, through the entry
points a user would call, on whatever TPU chips this machine shows:

    train --dtype bfloat16 --export-serving   (tgs_salt_bf16: 101x101x2,
                                               blocks (3,4,6), 41.7M params,
                                               global batch 64, 2 folds)
    serve --artifact-dir ...                  (ladder 1/4/16/64; requests of
                                               1, 5 and 64 instances)
    serve again                               (must load from the compile cache)
    serve-fleet --replicas 1                  (the controller must leave the
                                               chip to its replica)
    placement                                 (state and batch shards per device)
    kernels                                   (every Pallas wrapper, compiled,
                                               against its XLA reference)

A chip belongs to one process at a time, so this parent never imports jax or
the package: every phase is its own child, run one after another. It prints
one JSON line per phase and, as the last line of stdout,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and exits 0 — or exits non-zero. With no accelerator (``JAX_PLATFORMS=cpu``,
or jax finding none) it refuses: exit 2, nothing on stdout, nothing trained.
Everything it writes goes under one directory (``--out``, default
``chip_smoke_out`` beside this file — gigabytes: checkpoints, and an artifact
with 41.7M parameters baked in); the compile cache goes where
``utils/compile_cache.py`` resolves it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "tensorflowdistributedlearning_tpu"

EXIT_FAILED = 1
EXIT_NO_ACCELERATOR = 2

# the whole run must end inside 1200 s, compilation included; each phase gets
# its own bound so a hang names the phase it happened in
PHASE_TIMEOUT_S = {
    "probe": 120,
    "train": 660,
    "serve": 300,
    "serve_again": 180,
    "fleet": 240,
    "placement": 180,
    "kernels": 420,
}
DEADLINE_S = 1170

# tgs_salt_bf16 as configs.py defines it — ModelConfig's defaults (101x101x2,
# blocks (3,4,6), base depth 256, 41.7M parameters) in bfloat16: no width,
# depth or input-shape override
MODEL_FLAGS = ("--dtype", "bfloat16")
GLOBAL_BATCH = 64
STEPS_PER_FOLD = 80  # log windows of 20: one with the compile, two clean, one with the checkpoint
CHECKPOINT_EVERY = 60
N_IMAGES = 128
IMAGE_HW = 101


class PhaseFailed(Exception):
    pass


def check(cond: bool, message: str) -> None:
    if not cond:
        raise PhaseFailed(message)


# -- children: everything this script starts, so everything it can stop -------

_children: list = []


def _start(argv, log_path: str, **popen_kwargs) -> subprocess.Popen:
    """Start a child in its own process group, stderr to ``log_path``."""
    err = open(log_path + ".stderr", "w")
    proc = subprocess.Popen(
        argv,
        cwd=HERE,
        stderr=err,
        start_new_session=True,
        text=True,
        **popen_kwargs,
    )
    proc._log = err  # closed in _stop
    _children.append(proc)
    return proc


def _stop(proc: subprocess.Popen) -> None:
    """Kill the child's whole process group — the leader if it still runs, and
    any straggler it left behind if it does not."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    proc.wait(timeout=30)
    proc._log.close()


def _stop_all() -> None:
    for proc in _children:
        _stop(proc)


def _on_signal(signum, frame) -> None:
    _stop_all()
    sys.exit(128 + signum)


def _stderr_tail(log_path: str, n: int = 2500) -> str:
    try:
        with open(log_path + ".stderr", errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


def run_to_end(name: str, argv, out_dir: str, timeout_s: float) -> str:
    """Run a child to completion; returns its stdout. Raises PhaseFailed on a
    non-zero exit or a timeout (the child's group is killed)."""
    log = os.path.join(out_dir, name)
    with open(log + ".stdout", "w+") as out:
        proc = _start(argv, log, stdout=out)
        try:
            rc = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            _stop(proc)
            raise PhaseFailed(
                f"{name}: no end after {timeout_s:.0f}s — killed. stderr "
                f"tail:\n{_stderr_tail(log)}"
            )
        finally:
            _stop(proc)
        out.seek(0)
        stdout = out.read()
    check(rc == 0, f"{name}: exit code {rc}. stderr tail:\n{_stderr_tail(log)}")
    return stdout


def last_json_line(text: str, what: str) -> dict:
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise PhaseFailed(f"{what}: no JSON line on stdout")


def read_ledger(path: str) -> list:
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return events


def check_header(events: list, what: str, device: dict) -> dict:
    """The run header must say which device produced the ledger — and it must
    be the one the probe saw."""
    headers = [e for e in events if e.get("event") == "run_header"]
    check(bool(headers), f"{what}: ledger has no run_header")
    fp = headers[-1].get("fingerprint") or {}
    check(
        fp.get("platform") == device["platform"]
        and fp.get("device_kind") == device["kind"]
        and fp.get("n_devices") == device["count"],
        f"{what}: run header fingerprint {fp} is not "
        f"{device['count']}x {device['kind']} on {device['platform']}",
    )
    return headers[-1]


def check_no_recompiles(events: list, what: str) -> dict:
    late = [
        e for e in events
        if e.get("event") == "compile"
        and e.get("post_warmup")
        and not e.get("cache_hit")
    ]
    check(not late, f"{what}: {len(late)} compile(s) after warm-up: {late[:3]}")
    ends = [e for e in events if e.get("event") == "run_end"]
    check(bool(ends), f"{what}: ledger has no run_end")
    end = ends[-1]
    check(
        not end.get("interrupted"), f"{what}: run ended interrupted: {end}"
    )
    return end


def peak_device_bytes(events: list) -> dict:
    """Per-device peak bytes from the ledger's memory snapshots (one at each
    fold's start: the second one has seen the whole first fold)."""
    peaks: dict = {}
    for e in events:
        if e.get("event") != "memory":
            continue
        for dev, stats in (e.get("devices") or {}).items():
            peak = stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
            peaks[dev] = max(peaks.get(dev, 0), int(peak))
    return peaks


def peak_watermark_bytes(events: list):
    """The run's highest allocator watermark (sampled after compile, eval and
    checkpoint phases), or None when none was ledgered."""
    marks = [
        e.get("peak_bytes", 0) for e in events
        if e.get("event") == "memory_watermark"
    ]
    return max(marks) if marks else None


# -- the dataset ---------------------------------------------------------------


def write_dataset(data_dir: str, seed: int = 0) -> None:
    """A seeded synthetic salt-layout dataset: 101x101 8-bit PNGs, images/ +
    masks/. Each image is noise over a few bright discs and its mask is the
    discs, so there is something to learn in a handful of steps."""
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[:IMAGE_HW, :IMAGE_HW]
    for sub in ("images", "masks"):
        os.makedirs(os.path.join(data_dir, sub), exist_ok=True)
    for i in range(N_IMAGES):
        mask = np.zeros((IMAGE_HW, IMAGE_HW), bool)
        for _ in range(int(rng.integers(0, 4))):
            cy, cx = rng.uniform(10, IMAGE_HW - 10, 2)
            r = rng.uniform(8, 30)
            mask |= (yy - cy) ** 2 + (xx - cx) ** 2 < r * r
        image = rng.normal(90, 25, mask.shape) + 70 * mask
        name = f"s{i:04d}.png"
        Image.fromarray(np.clip(image, 0, 255).astype(np.uint8)).save(
            os.path.join(data_dir, "images", name)
        )
        Image.fromarray((mask * 255).astype(np.uint8)).save(
            os.path.join(data_dir, "masks", name)
        )


# -- phases (parent side) ------------------------------------------------------


def phase_probe(out_dir: str) -> dict:
    stdout = run_to_end(
        "probe",
        [sys.executable, os.path.abspath(__file__), "--child", "probe"],
        out_dir,
        PHASE_TIMEOUT_S["probe"],
    )
    return last_json_line(stdout, "probe")


def phase_train(out_dir: str, device: dict) -> dict:
    data_dir = os.path.join(out_dir, "data")
    model_dir = os.path.join(out_dir, "model")
    write_dataset(data_dir)
    t0 = time.monotonic()
    stdout = run_to_end(
        "train",
        [
            sys.executable, "-m", PACKAGE, "train",
            "--data-dir", data_dir,
            "--model-dir", model_dir,
            *MODEL_FLAGS,
            "--batch-size", str(GLOBAL_BATCH),
            "--n-fold", "2",
            "--steps", str(STEPS_PER_FOLD),
            "--checkpoint-every", str(CHECKPOINT_EVERY),
            "--save-best", "1",
            "--eval-throttle-secs", "0",
            "--nan-guard", "abort",
            "--export-serving",
        ],
        out_dir,
        PHASE_TIMEOUT_S["train"],
    )
    wall_s = time.monotonic() - t0
    result = last_json_line(stdout, "train")
    check("serving_artifact" in result, f"train: nothing exported: {result}")
    events = read_ledger(os.path.join(model_dir, "telemetry.jsonl"))
    header = check_header(events, "train", device)
    mesh = header.get("mesh") or {}
    check(
        mesh.get("batch") == device["count"]
        and all(v == 1 for k, v in mesh.items() if k != "batch"),
        f"train: mesh {mesh} is not {device['count']}x1x1 over the batch axis",
    )
    end = check_no_recompiles(events, "train")

    step_s, span_ms = [], []
    losses: dict = {}
    for e in events:
        if e.get("event") != "step_window":
            continue
        loss = (e.get("scalars") or {}).get("loss")
        check(
            loss is not None and math.isfinite(loss),
            f"train: window at step {e.get('step')} has loss {loss}",
        )
        losses.setdefault(e.get("fold"), []).append(loss)
        if not e.get("dirty") and e.get("images_per_sec"):
            step_s.append(GLOBAL_BATCH / e["images_per_sec"])
            span_ms.append((e.get("step_time_ms") or {}).get("mean_ms"))
    check(sorted(losses) == [0, 1], f"train: windows for folds {sorted(losses)}")
    for fold, series in losses.items():
        check(
            len(series) == STEPS_PER_FOLD // 20 and series[-1] < series[0],
            f"train: fold {fold} loss did not fall: {series}",
        )
    for kind, per_fold in (("checkpoint", 2), ("eval", 2)):
        n = sum(1 for e in events if e.get("event") == kind)
        check(
            n >= 2 * per_fold,
            f"train: {n} {kind} event(s), expected {2 * per_fold}",
        )
    for e in events:
        if e.get("event") == "eval":
            bad = {
                k: v for k, v in e["metrics"].items() if not math.isfinite(v)
            }
            check(not bad, f"train: non-finite eval metrics {bad}")
    check(step_s, "train: no clean window to read a step time from")

    artifact = result["serving_artifact"]
    with open(os.path.join(artifact, "manifest.json")) as f:
        manifest = json.load(f)
    check(
        manifest.get("platforms") == [device["platform"]],
        f"train: artifact platforms {manifest.get('platforms')}, not "
        f"[{device['platform']!r}]",
    )
    check(
        "drift_baseline" in manifest,
        "train: the drift baseline was not stamped into the manifest",
    )
    peaks = peak_device_bytes(events)
    check(
        len(peaks) == device["count"] and all(v > 0 for v in peaks.values()),
        f"train: memory in use on {len(peaks)} of {device['count']} devices: "
        f"{peaks}",
    )
    return {
        "artifact": artifact,
        "n_params": result.get("n_params"),
        "mesh": mesh,
        "wall_s": round(wall_s, 1),
        "compile_s": end.get("compile_total_s"),
        "compiles": end.get("compiles"),
        "cache_hits": end.get("compile_cache_hits", 0),
        "cache_misses": end.get("compile_cache_misses", 0),
        # smoke readings over the clean 20-step log windows, not a benchmark:
        # the window's wall time per step (it includes the per-window image
        # summaries and the loader), and the host's mean step span
        "window_s_per_step": round(sorted(step_s)[len(step_s) // 2], 5),
        "step_span_ms": span_ms,
        "loss_first_last": {
            str(f): [round(s[0], 4), round(s[-1], 4)] for f, s in losses.items()
        },
        "peak_bytes": peak_watermark_bytes(events),
        "peak_bytes_per_device": peaks,
    }


def _http(method: str, url: str, body=None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:500]}


def _ready_line(
    proc: subprocess.Popen, stdout_path: str, what: str, timeout_s: float
) -> dict:
    """The server prints one JSON line on stdout when every bucket is warm."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with open(stdout_path) as f:
            for line in f:
                line = line.strip()
                if line.startswith("{") and line.endswith("}"):
                    return json.loads(line)
        check(
            proc.poll() is None,
            f"{what}: exited with {proc.returncode} before it was ready. "
            f"stderr tail:\n{_stderr_tail(stdout_path[:-len('.stdout')])}",
        )
        time.sleep(0.2)
    raise PhaseFailed(
        f"{what}: not ready after {timeout_s:.0f}s. stderr tail:\n"
        f"{_stderr_tail(stdout_path[:-len('.stdout')])}"
    )


def _instances(n: int, seed: int) -> list:
    import numpy as np

    x = np.random.default_rng(seed).normal(0, 1, (n, IMAGE_HW, IMAGE_HW, 2))
    return np.round(x, 3).tolist()


def _check_prediction(status: int, body: dict, n: int, what: str) -> list:
    check(status == 200, f"{what}: HTTP {status}: {body}")
    preds = body.get("predictions") or {}
    probs, mask = preds.get("probabilities"), preds.get("mask")
    check(
        probs is not None and mask is not None and body.get("n") == n,
        f"{what}: response keys {sorted(preds)} n={body.get('n')}",
    )
    check(
        len(probs) == n and len(mask) == n,
        f"{what}: {len(probs)} probabilities / {len(mask)} masks for {n}",
    )
    import numpy as np

    probs, mask = np.asarray(probs, np.float64), np.asarray(mask)
    check(
        probs.shape == (n, IMAGE_HW, IMAGE_HW, 1) and mask.shape == probs.shape,
        f"{what}: shapes {probs.shape} / {mask.shape}",
    )
    check(
        bool(np.isfinite(probs).all())
        and float(probs.min()) >= 0.0 and float(probs.max()) <= 1.0,
        f"{what}: probabilities outside [0, 1] or not finite",
    )
    check(
        set(np.unique(mask).tolist()) <= {0.0, 1.0},
        f"{what}: mask holds values other than 0 and 1",
    )
    return probs


def _serve(name: str, argv_tail, out_dir: str, device: dict, requests) -> dict:
    """Start a server, wait for ready, send ``requests`` (instance counts),
    read /healthz and /metrics, SIGTERM, expect rc 0; then read its ledger."""
    import numpy as np

    workdir = os.path.join(out_dir, name)
    log = os.path.join(out_dir, name)
    t0 = time.monotonic()
    with open(log + ".stdout", "w") as out:
        proc = _start(
            [sys.executable, "-m", PACKAGE, *argv_tail,
             "--workdir", workdir, "--port", "0"],
            log,
            stdout=out,
        )
    try:
        ready = _ready_line(
            proc, log + ".stdout", name, PHASE_TIMEOUT_S[name] - 60
        )
        ready_s = time.monotonic() - t0
        url = ready.get("serving") or ready.get("router")
        first = None
        agreement = {}
        for n in requests:
            status, body = _http(
                "POST", url + "/v1/predict",
                {"instances": _instances(n, seed=7)},
            )
            probs = _check_prediction(status, body, n, f"{name}: {n} instances")
            # the same instance through another bucket's executable: each
            # bucket is its own XLA program (other tilings, other bf16
            # roundings), and after a handful of steps the net is sharp
            # enough for a pixel at the threshold to land on either side —
            # so agreement is judged over the image, not at its worst pixel
            if first is None:
                first = probs[0]
            else:
                delta = np.abs(probs[0] - first)
                agreement[str(n)] = {
                    "mean_abs_delta": round(float(delta.mean()), 6),
                    "max_abs_delta": round(float(delta.max()), 6),
                    "mask_agreement": round(float(
                        ((probs[0] > 0.5) == (first > 0.5)).mean()), 6),
                }
                check(
                    agreement[str(n)]["mean_abs_delta"] < 1e-2
                    and agreement[str(n)]["mask_agreement"] > 0.99,
                    f"{name}: instance 0 answered differently in a request "
                    f"of {requests[0]} and one of {n}: {agreement[str(n)]}",
                )
        status, health = _http("GET", url + "/healthz")
        check(
            status == 200 and health.get("ok") is True,
            f"{name}: /healthz {status} {health}",
        )
        status, metrics = _http("GET", url + "/metrics")
        check(status == 200, f"{name}: /metrics {status}")
        # what an operator sends: one SIGTERM to the process itself (a fleet
        # controller drains its own replicas)
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{name}: still running 90s after SIGTERM")
        check(rc == 0, f"{name}: exit code {rc} after SIGTERM. stderr tail:\n"
              + _stderr_tail(log))
    finally:
        _stop(proc)
    return {
        "ready": ready, "ready_s": round(ready_s, 1), "metrics": metrics,
        "workdir": workdir, "agreement": agreement,
    }


def _serve_summary(events: list, end: dict, served: dict) -> dict:
    warm = [e for e in events if e.get("event") == "serve_warmup"]
    return {
        "ready_s": served["ready_s"],
        "warmup_s": warm[-1]["buckets"] if warm else None,
        "compile_s": end.get("compile_total_s"),
        "compiles": end.get("compiles"),
        "cache_hits": end.get("compile_cache_hits", 0),
        "cache_misses": end.get("compile_cache_misses", 0),
    }


def phase_serve(out_dir: str, device: dict, artifact: str) -> dict:
    served = _serve(
        "serve", ["serve", "--artifact-dir", artifact], out_dir, device,
        requests=(1, 5, 64),
    )
    check(
        served["ready"].get("buckets") == [1, 4, 16, 64],
        f"serve: ladder {served['ready'].get('buckets')}",
    )
    events = read_ledger(os.path.join(served["workdir"], "telemetry.jsonl"))
    check_header(events, "serve", device)
    end = check_no_recompiles(events, "serve")
    metrics = served["metrics"]
    counters = (metrics.get("registry") or {}).get("counters") or {}
    cold = {
        k: v for k, v in counters.items()
        if k.startswith("serve/cold_bucket_hits/") and v
    }
    check(not cold, f"serve: cold bucket hits {cold}")
    check(
        metrics.get("buckets") == {"1": 1, "4": 0, "16": 1, "64": 1},
        f"serve: bucket hits {metrics.get('buckets')} for requests of 1, 5, 64",
    )
    check(
        end.get("completed") == 3,
        f"serve: run_end says {end.get('completed')} completed, sent 3",
    )
    out = _serve_summary(events, end, served)
    out["peak_bytes"] = (metrics.get("memory") or {}).get("peak_bytes")
    out["instance_0_across_buckets"] = served["agreement"]
    return out


def phase_serve_again(out_dir: str, device: dict, artifact: str) -> dict:
    """The same server a second time: its warm-up must be loads, not compiles."""
    served = _serve(
        "serve_again", ["serve", "--artifact-dir", artifact], out_dir, device,
        requests=(),
    )
    events = read_ledger(os.path.join(served["workdir"], "telemetry.jsonl"))
    check_header(events, "serve_again", device)
    end = check_no_recompiles(events, "serve_again")
    out = _serve_summary(events, end, served)
    check(
        out["cache_hits"] >= 4 and out["cache_misses"] == 0,
        f"serve_again: {out['cache_hits']} cache hits, "
        f"{out['cache_misses']} misses — the second start compiled",
    )
    return out


def phase_fleet(out_dir: str, device: dict, artifact: str) -> dict:
    """One replica behind the router. The controller spawns the chip user, so
    it must not have taken the chip itself: the replica reaching ready and
    answering through the router is the proof."""
    served = _serve(
        "fleet",
        ["serve-fleet", "--artifact-dir", artifact, "--replicas", "1",
         "--no-autoscale"],
        out_dir, device, requests=(5,),
    )
    workdir = served["workdir"]
    controller = read_ledger(os.path.join(workdir, "telemetry.jsonl"))
    headers = [e for e in controller if e.get("event") == "run_header"]
    check(
        bool(headers) and headers[-1].get("controller") is True
        and "fingerprint" not in headers[-1],
        f"fleet: controller header {headers[-1:] or None} — it asked jax for "
        "devices",
    )
    ledgers = sorted(
        f for f in os.listdir(workdir)
        if f.startswith("telemetry-") and f.endswith(".jsonl")
    )
    check(len(ledgers) == 1, f"fleet: replica ledgers {ledgers}, expected one")
    replica = read_ledger(os.path.join(workdir, ledgers[0]))
    check_header(replica, "fleet replica", device)
    end = check_no_recompiles(replica, "fleet replica")
    return {
        "ready_s": served["ready_s"],
        "replicas": served["ready"].get("replicas"),
        "replica_cache_hits": end.get("compile_cache_hits", 0),
        "replica_cache_misses": end.get("compile_cache_misses", 0),
    }


def phase_child_json(name: str, out_dir: str) -> dict:
    report_path = os.path.join(out_dir, f"{name}.json")
    run_to_end(
        name,
        [sys.executable, os.path.abspath(__file__), "--child", name,
         "--report", report_path],
        out_dir,
        PHASE_TIMEOUT_S[name],
    )
    with open(report_path) as f:
        return json.load(f)


def phase_placement(out_dir: str, device: dict) -> dict:
    report = phase_child_json("placement", out_dir)
    n = device["count"]
    check(
        report["batch_shard_devices"] == n and report["state_devices"] == n,
        f"placement: batch shards on {report['batch_shard_devices']} and "
        f"state on {report['state_devices']} of {n} devices",
    )
    check(
        report["batch_shard_rows"] == [GLOBAL_BATCH // n] * n,
        f"placement: shard rows {report['batch_shard_rows']}",
    )
    in_use = report["bytes_in_use"]
    check(
        len(in_use) == n and all(v > 0 for v in in_use.values()),
        f"placement: bytes in use per device {in_use}",
    )
    del report["device"]
    return report


def phase_kernels(out_dir: str, device: dict) -> dict:
    report = phase_child_json("kernels", out_dir)
    bad = [
        c for c in report["cases"]
        if not c["ok"] or c["path"] != c["expected_path"]
    ]
    check(
        not bad,
        "kernels: " + "; ".join(
            f"{c['name']}: path {c['path']} (expected {c['expected_path']})"
            f", max err {c.get('max_err')} (tol {c.get('tol')})"
            f"{' — ' + c['error'] if c.get('error') else ''}"
            for c in bad
        ),
    )
    # the line stays short; kernels.json in the output directory has each
    # case's compile and run seconds and its error against the reference
    return {
        "compile_s": report["compile_s"],
        "peak_bytes": report["peak_bytes"],
        "worst_err_over_tol": max(
            c["max_err"] / c["tol"] for c in report["cases"]
        ),
        "paths": {c["name"]: c["path"] for c in report["cases"]},
    }


# -- children (these import jax and the package) -------------------------------


def _device_summary() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def child_probe() -> int:
    """What the machine is, as the program sees it. Exits non-zero — and says
    nothing on stdout — unless jax finds a TPU and the package imports."""
    import jax
    import jaxlib

    from tensorflowdistributedlearning_tpu.native import loader
    from tensorflowdistributedlearning_tpu.utils import compile_cache

    device = _device_summary()
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: jax found platform {device['platform']!r}, no TPU",
            file=sys.stderr,
        )
        return EXIT_NO_ACCELERATOR
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    cache_dir, cache_source = compile_cache.resolve()
    stats = jax.local_devices()[0].memory_stats() or {}
    print(json.dumps({
        "device": device,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "bytes_limit": stats.get("bytes_limit"),
        "compile_cache": {
            "dir": cache_dir,
            "source": cache_source,
            "entries_at_start": (
                len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
            ),
        },
        "image_decoder": "native" if loader.native_available() else "PIL",
    }))
    return 0


def child_placement(report_path: str) -> int:
    """Where the trainer's own placement calls put the flagship's state and a
    batch of 64: one shard of the batch per device, the state on every one."""
    import jax
    import numpy as np

    from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
    from tensorflowdistributedlearning_tpu.models import build_model
    from tensorflowdistributedlearning_tpu.parallel import mesh as mesh_lib
    from tensorflowdistributedlearning_tpu.parallel import multihost
    from tensorflowdistributedlearning_tpu.train import step as step_lib
    from tensorflowdistributedlearning_tpu.train.state import create_train_state
    from tensorflowdistributedlearning_tpu.utils import compile_cache

    compile_cache.configure()
    cfg = ModelConfig(dtype="bfloat16")
    mesh = mesh_lib.make_mesh(None)
    state = mesh_lib.replicate(
        create_train_state(
            build_model(cfg),
            step_lib.make_optimizer(TrainConfig()),
            jax.random.PRNGKey(0),
            np.zeros((1, IMAGE_HW, IMAGE_HW, cfg.input_channels), np.float32),
        ),
        mesh,
    )
    batch = multihost.global_shard_batch(
        {
            "images": np.zeros(
                (GLOBAL_BATCH, IMAGE_HW, IMAGE_HW, 1), np.float32
            ),
            "masks": np.zeros(
                (GLOBAL_BATCH, IMAGE_HW, IMAGE_HW, 1), np.float32
            ),
        },
        mesh,
    )
    jax.block_until_ready((state.params, batch))
    shards = batch["images"].addressable_shards
    leaf = jax.tree.leaves(state.params)[0]
    report = {
        "device": _device_summary(),
        "mesh": dict(zip(mesh.axis_names, (int(s) for s in mesh.devices.shape))),
        "batch_shard_devices": len({s.device for s in shards}),
        "batch_shard_rows": [int(s.data.shape[0]) for s in shards],
        "state_devices": len({s.device for s in leaf.addressable_shards}),
        "bytes_in_use": {
            str(d): int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in jax.local_devices()
        },
    }
    with open(report_path, "w") as f:
        json.dump(report, f)
    return 0


def kernel_cases() -> list:
    """Every Pallas wrapper a TPU run can reach, at the shapes it is reached
    with. Each case: ``name``, ``fn`` and its XLA ``ref`` over the same
    ``args``, ``tol`` (max abs error allowed, relative to the reference's
    largest value), and ``expected_path`` — ``mosaic`` where the wrapper's
    envelope takes the kernel, ``reference`` where it says it cannot.

    int8 shapes are what ``--serving-dtype int8-compute`` routes through the
    interceptor for ``resnet50_classic_imagenet`` (the dense head, its
    stride-1 convs) and for the flagship; the depthwise shape is the
    flagship's ASPP map; attention is ViT-S/16's [b, 196, 6, 64] (this repo's
    ViT pools, so no class token) and the 197 a class token would make it.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowdistributedlearning_tpu.ops import flash_attention as fa
    from tensorflowdistributedlearning_tpu.ops import pallas_kernels as pk
    from tensorflowdistributedlearning_tpu.ops import quant_kernels as qk
    from tensorflowdistributedlearning_tpu.parallel.ring_attention import (
        attention_reference,
    )
    from tensorflowdistributedlearning_tpu.train.quantize import quantize_pytree

    rng = np.random.default_rng(0)
    bf16 = jnp.bfloat16
    cases = []

    def add(name, fn, ref, args, tol, expected_path="mosaic"):
        cases.append(dict(name=name, fn=fn, ref=ref, args=args, tol=tol,
                          expected_path=expected_path))

    def normal(shape, dtype=np.float32, scale=1.0):
        return jnp.asarray(rng.normal(0, scale, shape).astype(np.float32), dtype)

    def qweight(shape):
        qtree, _ = quantize_pytree(
            {"m": {"kernel": rng.normal(0, 0.5, shape).astype(np.float32)}},
            "int8",
        )
        rec = qtree["m"]["kernel"]
        return jnp.asarray(rec["q"]), jnp.asarray(rec["scale"])

    for t in (196, 197):
        q, k, v = (normal((8, t, 6, 64), bf16) for _ in range(3))
        add(f"flash_attention fwd [8,{t},6,64]",
            fa.flash_attention, attention_reference, (q, k, v), 2e-2)

    def attn_loss(attend):
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1, 2))

    q, k, v = (normal((8, 196, 6, 64), bf16) for _ in range(3))
    add("flash_attention value+grad [8,196,6,64]",
        attn_loss(fa.flash_attention), attn_loss(attention_reference),
        (q, k, v), 4e-2)

    wq, ws = qweight((2048, 1000))
    bias = normal((1000,), scale=0.1)
    for b in (1, 4, 16, 64):
        add(f"int8_matmul [{b},2048]x[2048,1000]",
            lambda x: qk.int8_matmul(x, wq, ws, bias=bias, out_dtype=bf16),
            lambda x: qk.int8_matmul_reference(
                x, wq, ws, bias=bias, out_dtype=bf16),
            (normal((b, 2048), bf16),), 2e-2)

    conv_shapes = [
        # the flagship's int8-compute export
        ((13, 13, 1024), (1, 1, 1024, 256), "mosaic"),
        ((13, 13, 2048), (1, 1, 2048, 512), "mosaic"),
        ((13, 13, 256), (3, 3, 256, 256), "mosaic"),
        ((26, 26, 128), (3, 3, 128, 128), "mosaic"),
        ((26, 26, 512), (3, 3, 512, 1), "mosaic"),
        ((1, 1, 1024), (1, 1, 1024, 256), "mosaic"),
        # 51 rows of 64 lanes: Mosaic cannot flatten the tap
        ((51, 51, 64), (3, 3, 64, 64), "reference"),
        # resnet50_classic_imagenet's
        ((56, 56, 64), (3, 3, 64, 64), "mosaic"),
        ((28, 28, 128), (3, 3, 128, 128), "mosaic"),
        ((14, 14, 256), (3, 3, 256, 256), "mosaic"),
        ((7, 7, 512), (3, 3, 512, 512), "mosaic"),
        ((7, 7, 2048), (1, 1, 2048, 512), "mosaic"),
        # over the VMEM block budget
        ((112, 112, 64), (3, 3, 64, 64), "reference"),
    ]
    for (h, w, c), wshape, path in conv_shapes:
        cq, cs = qweight(wshape)
        cbias = normal((wshape[-1],), scale=0.1)
        add(f"int8_conv2d [4,{h},{w},{c}] w{list(wshape)}",
            lambda x, cq=cq, cs=cs, cbias=cbias: qk.int8_conv2d(
                x, cq, cs, padding="SAME", bias=cbias, out_dtype=bf16),
            lambda x, cq=cq, cs=cs, cbias=cbias: qk.int8_conv2d_reference(
                x, cq, cs, padding="SAME", bias=cbias, out_dtype=bf16),
            (normal((4, h, w, c), bf16),), 2e-2, path)

    x = normal((32, 13, 13, 1024), bf16)
    w = normal((3, 3, 1024), scale=0.3)
    for rate in (1, 2, 4, 8):
        add(f"depthwise_conv2d [32,13,13,1024] rate {rate}",
            lambda x, w, rate=rate: pk.depthwise_conv2d(x, w, rate),
            lambda x, w, rate=rate: pk.depthwise_conv2d_reference(x, w, rate),
            (x, w), 2e-2)

    def dw_loss(conv):
        def loss(x, w):
            return jnp.sum(conv(x, w, 2).astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1))

    add("depthwise_conv2d value+grad [32,13,13,1024] rate 2",
        dw_loss(pk.depthwise_conv2d), dw_loss(pk.depthwise_conv2d_reference),
        (x, w), 4e-2)

    # not on any CLI path today (bench_kernels.py times them)
    c = 256
    xb = normal((8, 26, 26, c), bf16)
    bn = [normal((c,), scale=0.2) + 1.0, normal((c,), scale=0.2),
          normal((c,), scale=0.2), jnp.abs(normal((c,))) + 0.5]
    add("fused_bn_act [8,26,26,256]",
        lambda x, *p: pk.fused_bn_act(x, *p),
        lambda x, *p: pk.fused_bn_act_reference(x, *p), (xb, *bn), 2e-2)
    res = normal((8, 26, 26, c), bf16)
    add("fused_bn_act + residual [8,26,26,256]",
        lambda x, r, *p: pk.fused_bn_act(x, *p, residual=r),
        lambda x, r, *p: pk.fused_bn_act_reference(x, *p, residual=r),
        (xb, res, *bn), 2e-2)
    add("fused_bias_act [64,1000]",
        lambda x, b: pk.fused_bias_act(x, b, act="relu"),
        lambda x, b: pk.fused_bias_act_reference(x, b, act="relu"),
        (normal((64, 1000)), normal((1000,))), 1e-5)
    return cases


def holds_mosaic_call(compiled_text: str) -> bool:
    """Whether a compiled module's text holds a Mosaic (Pallas TPU) kernel."""
    return "tpu_custom_call" in compiled_text


def child_kernels(report_path: str) -> int:
    """Compile (never interpret) every case, run it, compare it with its XLA
    reference at highest matmul precision, and name the path it took."""
    import jax
    import numpy as np

    from tensorflowdistributedlearning_tpu.utils import compile_cache

    compile_cache.configure()
    device = _device_summary()
    if device["platform"] != "tpu":
        print("chip_smoke kernels: no TPU", file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    results = []
    for case in kernel_cases():
        row = {k: case[k] for k in ("name", "expected_path", "tol")}
        try:
            t0 = time.perf_counter()
            compiled = jax.jit(case["fn"]).lower(*case["args"]).compile()
            row["compile_s"] = round(time.perf_counter() - t0, 3)
            row["path"] = (
                "mosaic" if holds_mosaic_call(compiled.as_text())
                else "reference"
            )
            jax.block_until_ready(compiled(*case["args"]))
            t0 = time.perf_counter()
            out = jax.block_until_ready(compiled(*case["args"]))
            row["run_s"] = round(time.perf_counter() - t0, 6)
            with jax.default_matmul_precision("highest"):
                want = jax.block_until_ready(
                    jax.jit(case["ref"])(*case["args"])
                )
            err = 0.0
            for got_leaf, want_leaf in zip(
                jax.tree.leaves(out), jax.tree.leaves(want)
            ):
                g = np.asarray(got_leaf, np.float64)
                r = np.asarray(want_leaf, np.float64)
                if g.shape != r.shape or not np.isfinite(g).all():
                    err = float("inf")
                    break
                err = max(
                    err,
                    float(np.abs(g - r).max() / max(np.abs(r).max(), 1e-6)),
                )
            row["max_err"] = round(err, 6) if math.isfinite(err) else "inf"
            row["ok"] = err <= case["tol"]
        except Exception as e:  # noqa: BLE001 — one kernel's failure is a row
            row.update(ok=False, path=row.get("path", "failed"),
                       error=f"{type(e).__name__}: {str(e)[:400]}")
        results.append(row)
    stats = jax.local_devices()[0].memory_stats() or {}
    report = {
        "device": device,
        "cases": results,
        "compile_s": round(sum(r.get("compile_s", 0) for r in results), 2),
        "peak_bytes": stats.get("peak_bytes_in_use"),
    }
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1)
    return 0


# -- the run -------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"))
    parser.add_argument("--child", default=None, help=argparse.SUPPRESS)
    parser.add_argument("--report", default=None, help=argparse.SUPPRESS)
    args = parser.parse_args(argv)

    if args.child == "probe":
        return child_probe()
    if args.child == "placement":
        return child_placement(args.report)
    if args.child == "kernels":
        return child_kernels(args.report)

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.lower().split(","):
        print(
            f"chip_smoke: JAX_PLATFORMS={platforms!r} leaves no TPU to run "
            "on — this script does not run small on a CPU",
            file=sys.stderr,
        )
        return EXIT_NO_ACCELERATOR

    out_dir = os.path.abspath(args.out)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    previous = {
        sig: signal.signal(sig, _on_signal)
        for sig in (signal.SIGTERM, signal.SIGINT)
    }
    t_start = time.monotonic()
    try:
        try:
            probe = phase_probe(out_dir)
        except PhaseFailed as e:
            # no accelerator, or no program beside this script: no result
            print(f"chip_smoke: {e}", file=sys.stderr)
            return EXIT_NO_ACCELERATOR
        device = probe["device"]
        where = {
            "platform": device["platform"],
            "device_kind": device["kind"],
            "n_devices": device["count"],
        }
        print(json.dumps({"phase": "probe", "ok": True, **where, **{
            k: v for k, v in probe.items() if k != "device"}}), flush=True)

        state: dict = {}
        phases = [
            ("train", lambda: phase_train(out_dir, device)),
            ("serve", lambda: phase_serve(out_dir, device, state["artifact"])),
            ("serve_again",
             lambda: phase_serve_again(out_dir, device, state["artifact"])),
            ("fleet", lambda: phase_fleet(out_dir, device, state["artifact"])),
            ("placement", lambda: phase_placement(out_dir, device)),
            ("kernels", lambda: phase_kernels(out_dir, device)),
        ]
        failed = []
        for name, run in phases:
            if "artifact" not in state and name in (
                "serve", "serve_again", "fleet"
            ):
                failed.append(name)
                print(json.dumps({"phase": name, "ok": False, **where,
                                  "error": "no artifact: train failed"}),
                      flush=True)
                continue
            if time.monotonic() - t_start > DEADLINE_S:
                failed.append(name)
                print(json.dumps({"phase": name, "ok": False, **where,
                                  "error": "out of time"}), flush=True)
                continue
            t0 = time.monotonic()
            try:
                result = run()
            except PhaseFailed as e:
                failed.append(name)
                print(json.dumps({"phase": name, "ok": False, **where,
                                  "error": str(e)[-3000:]}), flush=True)
                continue
            if name == "train":
                state["artifact"] = result["artifact"]
            print(json.dumps({
                "phase": name, "ok": True, **where,
                "phase_s": round(time.monotonic() - t0, 1), **result,
            }), flush=True)
        total_s = round(time.monotonic() - t_start, 1)
        if failed:
            print(json.dumps({"ok": False, "failed": failed,
                              "total_s": total_s, "device": device}),
                  flush=True)
            return EXIT_FAILED
        print(json.dumps({"phase": "all", "ok": True, **where,
                          "total_s": total_s}), flush=True)
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0
    finally:
        _stop_all()
        for sig, handler in previous.items():
            signal.signal(sig, handler)


if __name__ == "__main__":
    sys.exit(main())
