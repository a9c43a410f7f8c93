"""Continuous profiling: bounded-overhead roofline/MFU captures on a cadence.

The repo could WRITE profiles (``utils/profiling.trace``) and READ them
offline (``utils/xplane`` CLI), but a profile only existed when someone
hand-ran both after the fact — the MFU campaign the roadmap grades against
(arXiv:2204.06514 treats MFU as the first-class training metric) can't run
on a number that isn't continuously measured. This module closes that gap:

- :class:`ContinuousProfiler` captures SHORT windowed ``jax.profiler`` traces
  on a log-window cadence (``TrainConfig.profile_every_windows``), on demand
  (serve ``/admin/profile``), and at alert chokepoints (a ``step_time`` or
  SLO ``health_alert`` auto-captures ONE rate-limited postmortem, linked to
  the triggering ``alert_id``);
- each capture stops after :attr:`capture_steps` train steps (not a whole
  window) so the steady-state overhead stays inside the <=2% budget
  (``bench.py --profile-overhead``, CI-gated);
- the capture parses through ``utils/xplane`` into a per-op roofline
  classification — compute-bound (conv/matmul) vs HBM-bound (fusion, reduce,
  copy, other) vs collective, achieved FLOP/s per chip against the device
  peak table, per-phase MFU — ledgered as ``profile_capture`` +
  ``op_roofline`` events (docs/LEDGER_SCHEMA.md);
- ``planner.measured_costs_from_workdir`` reads those rooflines back so
  ``plan --measured-costs-from`` scores layouts with THIS box's measured
  rates instead of analytic constants.

MFU here is the standard analytic-FLOPs convention: the planner's
``6 * param_count * global_batch`` per-step FLOP model priced against
measured wall time and the published bf16 peak of the chip
(``utils/peaks.PEAKS``). On a CPU host MFU is ABSENT — never a fabricated 0/0; set ``TFDL_PEAK_FLOPS``
to price against an explicit peak (the CI drill does).

Failure stance matches the rest of obs/: a profiler hiccup (unsupported
backend, torn capture, full disk) degrades to a logged warning and a
counted error — it never takes down training or serving.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, List, Optional

from tensorflowdistributedlearning_tpu.obs import scopes as scopes_lib
from tensorflowdistributedlearning_tpu.obs import trace as trace_lib
from tensorflowdistributedlearning_tpu.utils import xplane

logger = logging.getLogger(__name__)

PROFILE_CAPTURE_EVENT = "profile_capture"
OP_ROOFLINE_EVENT = "op_roofline"

# health_alert monitors that auto-trigger a postmortem capture: a step-time
# regression (training) or a degraded SLO (serving) is exactly the moment a
# profile answers "what changed", and both are transition-based alerts (one
# event per degradation, not one per window)
TRIGGER_MONITORS = ("step_time", "slo")

# xplane DEFAULT_GROUPS buckets → roofline class. Conv/matmul run the MXU:
# compute-bound. Collectives are the interconnect. Everything else a TPU
# spends step time on (fusions, reductions, copies, infeed) is dominated by
# HBM traffic — the standard roofline reading of an op breakdown.
_COMPUTE_BUCKETS = ("conv", "matmul")
_COLLECTIVE_BUCKETS = ("collectives",)


def resolve_peak_flops() -> Optional[float]:
    """Peak bf16 FLOP/s per chip of this process's device for MFU accounting
    (the one peaks table, utils/peaks.py): ``None`` on a CPU host — the
    caller must then OMIT MFU, not price against a made-up peak — and an
    error on a TPU the table does not know. ``TFDL_PEAK_FLOPS`` overrides
    (lets CI drill the MFU path on CPU).

    Deliberately NOT ``Topology.peak_flops()``: the planner's CPU what-if
    constant is fine for relative candidate ordering but would turn CPU MFU
    into a meaningless absolute number."""
    env = os.environ.get("TFDL_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            logger.warning("ignoring unparseable TFDL_PEAK_FLOPS=%r", env)
    import jax

    from tensorflowdistributedlearning_tpu.utils import peaks as peaks_lib

    device = jax.devices()[0]
    peaks = peaks_lib.device_peaks(device.device_kind, device.platform)
    return peaks.bf16_flops if peaks else None


def build_roofline(
    rows: List[xplane.OpTime],
    *,
    busy_s: Optional[float] = None,
    steps: Optional[int] = None,
    step_flops: Optional[Dict] = None,
    phase: str = "train",
    top: int = 5,
) -> Dict:
    """One ``op_roofline`` event body from an op breakdown.

    ``busy_s`` is the measured wall time of the captured ``steps`` (for
    windowed captures: the SUM of the captured step spans — the same basis as
    the ledger's ``step_time_ms``, so the roofline MFU and the report's
    goodput MFU agree on a steady-state run). ``step_flops`` is the
    telemetry's analytic pricing (:meth:`Telemetry.set_step_flops`)."""
    groups = xplane.grouped_breakdown(rows)
    total_ms = sum(groups.values())
    compute_ms = sum(groups.get(b, 0.0) for b in _COMPUTE_BUCKETS)
    collective_ms = sum(groups.get(b, 0.0) for b in _COLLECTIVE_BUCKETS)
    hbm_ms = max(0.0, total_ms - compute_ms - collective_ms)
    out: Dict = {
        "phase": phase,
        "total_ms": round(total_ms, 3),
        "buckets": groups,
        "classes": {
            "compute_frac": round(compute_ms / total_ms, 4) if total_ms else 0.0,
            "hbm_frac": round(hbm_ms / total_ms, 4) if total_ms else 0.0,
            "collective_frac": (
                round(collective_ms / total_ms, 4) if total_ms else 0.0
            ),
        },
        "top_ops": [
            {
                "name": r.name,
                "total_ms": r.total_ms,
                "fraction": r.fraction,
                "class": (
                    "compute"
                    if xplane.classify_bucket(r.name) in _COMPUTE_BUCKETS
                    else "collective"
                    if xplane.classify_bucket(r.name) in _COLLECTIVE_BUCKETS
                    else "hbm"
                ),
            }
            for r in rows[:top]
        ],
    }
    hbm_rows = [
        r for r in rows
        if xplane.classify_bucket(r.name)
        not in _COMPUTE_BUCKETS + _COLLECTIVE_BUCKETS
    ]
    if hbm_rows:
        out["top_hbm_op"] = {
            "name": hbm_rows[0].name,
            "total_ms": hbm_rows[0].total_ms,
            "fraction": hbm_rows[0].fraction,
        }
    flops_per_step = (step_flops or {}).get("flops_per_step")
    n_devices = (step_flops or {}).get("n_devices") or 1
    if flops_per_step and steps and busy_s and busy_s > 0:
        achieved = flops_per_step * steps / busy_s / n_devices
        out["analytic_flops_per_step"] = float(flops_per_step)
        out["achieved_flops_per_sec_per_chip"] = round(achieved, 3)
        peak = (step_flops or {}).get("peak_flops_per_chip")
        if peak:
            out["peak_flops_per_chip"] = float(peak)
            # per-phase MFU: every analytic FLOP of the captured steps
            # against their measured wall — the headline number
            out["mfu"] = round(achieved / peak, 4)
            if compute_ms > 0:
                # per-op-class MFU: the same FLOPs against time spent in the
                # compute-class ops ONLY — how hard the MXU runs while it
                # runs; the gap to `mfu` is what HBM + collectives cost
                out["compute_mfu"] = round(
                    flops_per_step * steps
                    / (compute_ms / 1e3)
                    / n_devices
                    / peak,
                    4,
                )
        collective_bytes = (step_flops or {}).get("collective_bytes_per_step")
        if collective_bytes and collective_ms > 0:
            # achieved per-chip collective bandwidth: the planner's priced
            # per-chip collective volume against measured collective-bucket
            # time — what measured-costs planning replaces ICI_BYTES_PER_SEC
            # with
            out["achieved_collective_bytes_per_sec"] = round(
                collective_bytes * steps / (collective_ms / 1e3), 3
            )
            out["collective_bytes_per_step"] = float(collective_bytes)
    return out


class ContinuousProfiler:
    """Windowed/timed ``jax.profiler`` captures, parsed and ledgered.

    One instance per producer (trainer or serve replica), attached to its
    :class:`~tensorflowdistributedlearning_tpu.obs.telemetry.Telemetry` via
    ``telemetry.set_profiler``. Three capture paths:

    - **cadence** (``every_windows > 0``): every N-th log window starts a
      capture that stops after :attr:`capture_steps` train steps;
    - **alert** (:meth:`on_alerts` / :meth:`trigger`): a ``step_time``/``slo``
      health alert starts ONE postmortem capture, rate-limited by
      :attr:`min_trigger_interval_s` and stamped with the alert id;
    - **admin** (:meth:`capture_timed`): an explicit N-second capture (the
      serve ``/admin/profile`` endpoint), background by default.

    With ``every_windows=0`` and nothing triggered, the profiler is
    byte-inert: no logdir, no ledger events, one pointer check per step.
    """

    def __init__(
        self,
        telemetry,
        *,
        every_windows: int = 0,
        logdir: Optional[str] = None,
        capture_steps: int = 3,
        min_trigger_interval_s: float = 300.0,
        phase: str = "train",
        plane_filter: Optional[str] = None,
        top_ops: int = 5,
    ):
        self.telemetry = telemetry
        self.every_windows = max(0, int(every_windows))
        workdir = getattr(telemetry, "workdir", None)
        self.logdir = logdir or (
            os.path.join(workdir, "profile") if workdir else None
        )
        self.capture_steps = max(1, int(capture_steps))
        self.min_trigger_interval_s = float(min_trigger_interval_s)
        self.phase = phase
        self.plane_filter = plane_filter
        self.top_ops = top_ops
        # the fast-path flag Telemetry.span checks once per train step
        self.capturing = False
        self.captures = 0
        self.rate_limited = 0
        self.errors = 0
        self._active: Optional[Dict] = None
        self._lock = threading.Lock()
        self._last_trigger: Optional[float] = None
        self._finalize_thread: Optional[threading.Thread] = None

    @property
    def enabled(self) -> bool:
        """Cadence capture armed (triggered/admin captures work regardless,
        as long as a logdir is resolvable)."""
        return self.every_windows > 0 and self.logdir is not None

    # -- capture lifecycle -------------------------------------------------

    def _begin(
        self,
        reason: str,
        *,
        step: Optional[int] = None,
        alert_id: Optional[str] = None,
        seconds: Optional[float] = None,
    ) -> Optional[Dict]:
        if self.logdir is None:
            return None
        with self._lock:
            if self._active is not None:
                return None  # capture-during-capture: the running one wins
            capture_id = trace_lib.new_id()
            capture_dir = os.path.join(self.logdir, f"capture-{capture_id}")
            try:
                import jax

                os.makedirs(capture_dir, exist_ok=True)
                jax.profiler.start_trace(capture_dir)
            except Exception as e:  # noqa: BLE001 — never kill the producer
                self.errors += 1
                logger.warning("profile capture failed to start: %s", e)
                return None
            rec: Dict = {
                "capture_id": capture_id,
                "dir": capture_dir,
                "reason": reason,
                "t0": time.perf_counter(),
                "steps": 0,
                "busy_s": 0.0,
            }
            if step is not None:
                rec["step"] = step
            if alert_id is not None:
                rec["alert_id"] = alert_id
            if seconds is not None:
                rec["seconds"] = float(seconds)
            self._active = rec
            self.capturing = True
            return rec

    def note_step(self, duration_s: float = 0.0) -> None:
        """One train step finished under an active windowed capture (called
        from ``Telemetry.span`` with the step span's wall time). Stops the
        capture once ``capture_steps`` steps are in — the bounded-overhead
        contract."""
        rec = self._active
        if rec is None or "seconds" in rec or rec.get("finalizing"):
            return  # timed captures stop on their own clock
        rec["steps"] += 1
        rec["busy_s"] += float(duration_s)
        if rec["steps"] >= self.capture_steps:
            self._finish()

    def _finish(self, wait: bool = False) -> None:
        # stop_trace serializes + writes the trace planes and the parse walks
        # them — ~1s for a multi-step window, far over the per-step budget —
        # so everything past flipping `capturing` runs off the train thread.
        # `_active` stays set until the finalize lands, which is what makes
        # back-to-back _begin calls refuse instead of double-starting TSL.
        with self._lock:
            rec = self._active
            if rec is None or rec.get("finalizing"):
                return
            rec["finalizing"] = True
            self.capturing = False
        window_s = time.perf_counter() - rec["t0"]

        def _do() -> None:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                self.errors += 1
                logger.warning("profile capture failed to stop: %s", e)
            try:
                self._ledger_capture(rec, window_s)
                self.captures += 1
            except Exception as e:  # noqa: BLE001 — parse/ledger best-effort
                self.errors += 1
                logger.warning("profile capture %s not ledgered: %s",
                               rec["capture_id"], e)
            finally:
                with self._lock:
                    if self._active is rec:
                        self._active = None

        if wait:
            _do()
            return
        t = threading.Thread(target=_do, daemon=True, name="profile-finalize")
        self._finalize_thread = t
        t.start()

    def _ledger_capture(self, rec: Dict, window_s: float) -> None:
        rows: List[xplane.OpTime] = []
        skipped = 0
        try:
            rows, skipped = xplane.op_breakdown_with_errors(
                rec["dir"], plane_filter=self._plane_filter()
            )
        except FileNotFoundError:
            # backend wrote no planes (profiler unsupported): the capture
            # event still records the attempt, with ops=0
            pass
        capture: Dict = {
            "capture_id": rec["capture_id"],
            "reason": rec["reason"],
            "logdir": rec["dir"],
            "window_s": round(window_s, 6),
            "ops": len(rows),
            "skipped_plane_files": skipped,
        }
        for key in ("step", "alert_id", "seconds", "steps"):
            if key in rec and rec[key] is not None:
                capture[key] = rec[key]
        self.telemetry.event(PROFILE_CAPTURE_EVENT, **capture)
        if not rows:
            return
        steps = rec.get("steps") or None
        busy_s = rec.get("busy_s") or None
        roofline = build_roofline(
            rows,
            busy_s=busy_s,
            steps=steps,
            step_flops=getattr(self.telemetry, "step_flops", None),
            phase=self.phase,
            top=self.top_ops,
        )
        roofline["capture_id"] = rec["capture_id"]
        roofline["reason"] = rec["reason"]
        if skipped:
            roofline["skipped_plane_files"] = skipped
        for key in ("step", "alert_id"):
            if key in rec:
                roofline[key] = rec[key]
        # the same op times by the program's own scopes, where the run's
        # ledger holds the step programs' maps (obs/scopes.py)
        records = self.telemetry.program_scopes()
        if records:
            roofline["by_scope"] = scopes_lib.by_scope(
                records,
                ((scopes_lib.instruction_name(r.name), r.total_ms) for r in rows),
                steps,
            )
        self.telemetry.event(OP_ROOFLINE_EVENT, **roofline)

    def _plane_filter(self) -> str:
        if self.plane_filter is not None:
            return self.plane_filter
        try:
            import jax

            backend = jax.default_backend()
        except Exception:  # noqa: BLE001
            backend = ""
        if backend == "tpu":
            return "TPU"
        if backend == "gpu":
            return "GPU"
        # CPU: no device plane — the XLA:CPU op events (Eigen threadpool
        # lines) live on /host:CPU; naming it skips the event-less
        # /host:metadata plane, which is half the capture's parse bytes
        return "/host:CPU"

    # -- entry points ------------------------------------------------------

    def on_window(
        self,
        *,
        step: Optional[int] = None,
        windows: int = 0,
        alerts: Optional[List[Dict]] = None,
    ) -> None:
        """Window-boundary hook (called by ``Telemetry.window_event`` after
        the window is persisted): postmortem triggers first — an alert is a
        better reason to capture than the calendar — then the cadence."""
        for alert in alerts or ():
            if (
                alert.get("monitor") in TRIGGER_MONITORS
                and not alert.get("resolved")
            ):
                self.trigger(alert, step=step)
                break
        if (
            self.every_windows
            and windows > 0
            and windows % self.every_windows == 0
        ):
            self._begin("cadence", step=step)

    def trigger(
        self,
        alert: Dict,
        *,
        step: Optional[int] = None,
        seconds: Optional[float] = None,
    ) -> Optional[Dict]:
        """Postmortem capture for a health alert: rate-limited (at most one
        per ``min_trigger_interval_s``), stamped with the alert's id.
        ``seconds`` switches to a timed capture (serving, where no train
        steps will stop a windowed one)."""
        now = time.monotonic()
        if (
            self._last_trigger is not None
            and now - self._last_trigger < self.min_trigger_interval_s
        ):
            self.rate_limited += 1
            return None
        alert_id = alert.get("alert_id")
        if seconds is not None:
            out = self.capture_timed(
                seconds, reason="alert", alert_id=alert_id
            )
        else:
            rec = self._begin("alert", step=step, alert_id=alert_id)
            out = {"capture_id": rec["capture_id"]} if rec else None
        if out is not None:
            self._last_trigger = now
        return out

    def capture_timed(
        self,
        seconds: float = 1.0,
        *,
        reason: str = "admin",
        alert_id: Optional[str] = None,
        wait: bool = False,
    ) -> Optional[Dict]:
        """Explicit N-second capture (serve ``/admin/profile``): returns
        ``{capture_id, seconds, status}`` immediately (the capture finishes
        and ledgers on a background thread), or ``None`` when a capture is
        already in flight."""
        seconds = max(0.05, float(seconds))
        rec = self._begin(reason, alert_id=alert_id, seconds=seconds)
        if rec is None:
            return None
        def _run() -> None:
            time.sleep(seconds)
            self._finish(wait=True)  # already off the hot path

        t = threading.Thread(
            target=_run, daemon=True, name="profile-capture"
        )
        t.start()
        if wait:
            t.join()
        return {
            "capture_id": rec["capture_id"],
            "seconds": seconds,
            "status": "complete" if wait else "started",
        }

    def close(self) -> None:
        """Finish (stop + parse + ledger) any capture still in flight — the
        trainers call this from ``Telemetry.close`` so a run ending mid-
        capture still lands its events before the ledger closes."""
        self._finish(wait=True)
        t = self._finalize_thread
        if t is not None and t.is_alive():
            t.join(timeout=30.0)
