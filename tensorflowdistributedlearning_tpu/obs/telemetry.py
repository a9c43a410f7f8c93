"""The ``Telemetry`` façade the trainers wire in: spans + ledger + detector.

One object per training run, constructed against the run's workdir. It owns:

- a ``MetricsRegistry`` the span API records into (``span("data_wait")`` /
  ``span("step")`` / ``span("eval")`` — each span is host wall time, also
  annotated into any active ``jax.profiler`` trace so ledger windows and
  xplane timelines line up);
- a ``RunLedger`` (``telemetry.jsonl``; only process 0 writes under
  multi-host — spans still accumulate everywhere, they are process-local);
- a ``RecompileDetector`` attributing compiles to the active span and writing
  them to the ledger; post-warmup recompiles are additionally WARNED, because
  they are the silent goodput killer the whole subsystem exists to catch.

Span accounting semantics (honest about async dispatch): ``data_wait`` is the
host blocked on the input iterator — loader-bound time. ``step`` is the rest
of the loop body; with async dispatch the device sync lands on the log
window's ``device_get``, which the trainers also run inside a ``step`` span,
so per-WINDOW totals are real wall time even though individual step samples
measure dispatch+backpressure. The window event carries both the split and
the per-step percentiles.

One host timeline: every clock reading this module persists
(``startup_phase.t0_mono``, ``step_window.step_start_mono`` /
``step_done_mono``) is ``time.perf_counter()`` of the
writing process — the clock the spans are timed on, and, through the
``TraceAnnotation`` each span opens, the one a profiler capture shows them on.
From the entry of ``Trainer.train`` / ``ClassifierTrainer.fit`` to the end of
a fold every host second lies in a named span: ``startup/<phase>`` spans
before the loop (each persisted as a ``startup_phase`` event with the
compiles that fell into it), window spans inside it, and ``host_other_s`` —
the window's wall less every named span — says what is still unnamed.

``NULL_TELEMETRY`` is the disabled instance (no workdir, no ledger, no
detector, spans are near-free) so trainer code never branches on None.
"""

from __future__ import annotations

import collections
import contextlib
import json
import logging
import os
import time
from typing import Deque, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from tensorflowdistributedlearning_tpu.obs import capacity as capacity_lib
from tensorflowdistributedlearning_tpu.obs import scopes as scopes_lib
from tensorflowdistributedlearning_tpu.obs import trace as trace_lib
from tensorflowdistributedlearning_tpu.obs.ledger import RunLedger
from tensorflowdistributedlearning_tpu.obs.metrics import (
    MetricsRegistry,
    time_summary,
    window_count,
    window_total_s,
)
from tensorflowdistributedlearning_tpu.obs.recompile import (
    CompileEvent,
    RecompileDetector,
)

logger = logging.getLogger(__name__)

# span names the trainers use; anything else is allowed, these are the schema
SPAN_DATA_WAIT = "data_wait"
SPAN_STEP = "step"
SPAN_EVAL = "eval"
# host blocked waiting on a device value (the async loop's bounded
# dispatch-ahead and deferred window fetch — train/async_loop.py); disjoint
# from data_wait/step like the other window spans
SPAN_FETCH_WAIT = "fetch_wait"
# checkpoint save wall time (the trainers wrap periodic/forced saves): drained
# with the window it fell into (`checkpoint_s`), and a trace boundary — sampled
# runs show checkpoint spans in the exported timeline
SPAN_CHECKPOINT = "checkpoint"
# host blocked at a cross-process sync point (parallel/multihost.py wraps its
# multihost_utils calls in `barrier_probe`): on a healthy fleet this is ~0 on
# the slowest host and largest on the fastest, so per-host barrier_wait is the
# signal that separates "slow host" from "slow network" in the fleet report
SPAN_BARRIER = "barrier_wait"
# the two calls inside the `step` span, each timed on its own: dispatching the
# input program (`prepare`) and the train step. Children of `step` — their
# seconds are part of `compute_s`, and what is left of it is the span's self
# time. A per-call median tells a call that blocks every step from a mean
# made by two slow calls. A compile inside one is the loop span's
# (Telemetry.compile_phase): `step` is what the trainers mark warm
SPAN_DISPATCH_PREPARE = "dispatch_prepare"
SPAN_DISPATCH_STEP = "dispatch_step"
# the deferred window's write-out (the reduction of the fetched metrics to
# scalars, TB scalars, the window event, health and profiler hooks —
# train/async_loop.py wraps the trainers' emit) and the
# train-phase image grids (an extra forward and three device_gets)
SPAN_WINDOW_EMIT = "window_emit"
SPAN_IMAGE_SUMMARY = "image_summary"
# spans named `startup/<phase>` are the start-up timeline: closing one
# persists a `startup_phase` event (Telemetry.span)
STARTUP_PREFIX = "startup/"
# the phase that straddles loop iterations: from the first `next(batches)` to
# the moment the dispatch tracker retires step 1 (Telemetry.begin_first_step)
PHASE_FIRST_STEP = "first_step"

# the spans a log window drains, with the step_window field each total is
# written under. In the trainers' single-process loops they are top-level and
# disjoint, so their seconds and `host_other_s` add up to the window's
# `wall_s`; where one nests in another (`barrier_wait` inside `eval` on a
# multi-host run) its field still reads its own seconds, and `host_other_s`
# counts every second once: it is taken from the spans that closed at the
# top of the stack (Telemetry.span)
_WINDOW_SPAN_FIELDS = (
    (SPAN_DATA_WAIT, "data_wait_s"),
    (SPAN_STEP, "compute_s"),
    (SPAN_FETCH_WAIT, "fetch_wait_s"),
    (SPAN_BARRIER, "barrier_wait_s"),
    (SPAN_WINDOW_EMIT, "window_emit_s"),
    (SPAN_IMAGE_SUMMARY, "image_summary_s"),
    (SPAN_CHECKPOINT, "checkpoint_s"),
    (SPAN_EVAL, "eval_s"),
)
# the children of `step`, drained with the window but not added to its sum
_DISPATCH_SPANS = (SPAN_DISPATCH_PREPARE, SPAN_DISPATCH_STEP)

# per-step clock readings kept between two window boundaries; a run that
# never writes windows (a non-main host) must not grow without bound
_MAX_STEP_MARKS = 8192

# registry histogram the input prefetcher records its ready-queue depth into
# (data/pipeline.py:device_prefetch); drained per window like the spans, so
# prefetch underruns are visible in the ledger and telemetry-report
PREFETCH_DEPTH_HISTOGRAM = "prefetch/queue_depth"

# data-service backpressure telemetry (data/service.py): reorder-buffer depth
# at each consumer take, one sample per consumer-blocked-on-workers event
# (an underrun: the device side is about to starve), per-batch worker busy
# seconds (utilization = busy / (workers x window wall)), and the live worker
# count. Drained per window like the prefetch gauge; rendered by
# telemetry-report's prefetch section and watched by the data_starved monitor
DATA_READY_HISTOGRAM = "data_service/ready_depth"
DATA_UNDERRUN_HISTOGRAM = "data_service/underruns"
DATA_WORKER_BUSY_HISTOGRAM = "data_service/worker_busy"
DATA_WORKERS_GAUGE = "data_service/workers"


def run_fingerprint() -> Dict:
    """Device/process fingerprint for the run header — enough to answer
    "what hardware produced this ledger" from the file alone."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": getattr(devices[0], "device_kind", "unknown"),
        "n_devices": len(devices),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "jax_version": jax.__version__,
    }


class _StartupMark(NamedTuple):
    """An open start-up phase: its name, the clock at entry and how many
    compiles the detector had heard by then."""

    phase: str
    t0: float
    compiles: int


class Telemetry:
    """Per-run telemetry: span timing, JSONL ledger, recompile detection."""

    def __init__(
        self,
        workdir: Optional[str],
        *,
        run_info: Optional[Dict] = None,
        enabled: bool = True,
        memory_every_windows: int = 5,
        is_main: Optional[bool] = None,
        trace_sample_rate: float = 0.0,
        health=None,
        process_index: Optional[int] = None,
        process_count: Optional[int] = None,
        capacity_sampling: bool = True,
        controller: bool = False,
        hold_header: bool = False,
    ):
        """``hold_header=True`` is for a producer that builds its telemetry
        before it knows all of its run header (the trainers build it first
        thing, so that spans and the compile listener cover the whole start;
        the parallelism plan comes later): the header and every event after
        it are held in memory, each with the time it was made, until
        :meth:`finish_header` — or :meth:`close` — writes them out in order.

        ``controller=True`` is for a process that SPAWNS the chip users
        (the serve-fleet controller, the flywheel): a chip belongs to one
        process, and a parent that initializes a jax backend takes it from
        its children. Such a telemetry asks jax nothing — its run header
        says ``controller`` where a chip owner's carries the device
        fingerprint, which the children's own ledgers record."""
        self.enabled = enabled and workdir is not None
        # the run's workdir (None when disabled) — the continuous profiler
        # (obs/profiler.py) roots its capture dirs under it
        self.workdir = workdir if self.enabled else None
        # attached via set_profiler; None = no profiling (one pointer check
        # per step/window is the whole cost of the hook points)
        self.profiler = None
        # analytic per-step FLOP pricing (set_step_flops): what turns
        # measured step time into a first-class windowed `mfu` field and
        # prices the profiler's rooflines
        self.step_flops: Optional[Dict] = None
        # capacity/cost layer (obs/capacity.py): per-phase HBM watermarks and
        # chip-seconds accounting, sampled on the WINDOW cadence (never per
        # step — the <=1% overhead gate, bench.py --capacity-overhead).
        # Constructed unconditionally (cheap, no backend touch) so callers
        # never branch on None; only an enabled Telemetry emits events.
        self.capacity_sampling = bool(capacity_sampling)
        self.watermarks = capacity_lib.WatermarkTracker()
        self.cost = capacity_lib.CostMeter()
        self.registry = MetricsRegistry()
        self._span_stack: List[str] = []
        # the K-fold trainer sets the fold it is in; start-up phases carry it
        self.fold: Optional[int] = None
        # the one host timeline (module docstring): the previous window
        # boundary, per-step clock readings since it, and the start-up phase
        # a retired step will close
        self._window_t0 = time.perf_counter()
        # seconds of the window that lay in a span of any name, each counted
        # once (spans closed at the top of the stack)
        self._named_s = 0.0
        self._step_starts: Deque[float] = collections.deque(
            maxlen=_MAX_STEP_MARKS
        )
        self._steps_done: Deque[Tuple[int, float]] = collections.deque(
            maxlen=_MAX_STEP_MARKS
        )
        self._first_step: Optional[_StartupMark] = None
        # the program_scopes records this run's ledger holds, by identity (a
        # step program keeps one record per compile for the process's life)
        self._program_scopes: Dict[int, Dict] = {}
        # (kind, fields) held back until finish_header(); None = write through
        self._held: Optional[List[Tuple[str, Dict]]] = None
        self._windows = 0
        self._memory_every_windows = max(1, memory_every_windows)
        self._closed = False
        self.ledger: Optional[RunLedger] = None
        self.detector: Optional[RecompileDetector] = None
        # online health monitors (obs/health.py) consulted at every window
        # event; None = no monitoring (the trainers pass
        # HealthMonitor.from_train_config)
        self.health = health
        # per-unit tracing (obs/trace.py): sampled spans persist as `trace`
        # ledger events through the same writer — BUFFERED (no flush per
        # span: spans can fire several times per train step, and a syscall
        # per line steals CPU from compute; buffered lines land at the next
        # flushed event / flush() / close()). Rate 0 keeps the tracer
        # disabled and span() single-branch cheap.
        self.tracer = trace_lib.Tracer(
            emit=self._trace_event if self.enabled else None,
            sample_rate=trace_sample_rate if self.enabled else 0.0,
        )
        if not self.enabled:
            return
        if process_index is None:
            # the normal trainer path: this process's slot in the
            # jax.distributed cluster decides the ledger it writes. Explicit
            # process_index is for producers whose fleet identity is NOT a
            # jax process — serve replicas sharing one workdir pass their
            # replica id so each writes its own telemetry-{i}.jsonl.
            process_index, process_count = 0, 1
            if is_main is None and not controller:
                from tensorflowdistributedlearning_tpu.parallel import (
                    multihost,
                )

                info = multihost.process_info()
                process_index = info["process_index"]
                process_count = info["process_count"]
        process_index = int(process_index)
        if is_main is None:
            is_main = process_index == 0
        # any non-zero index writes a ledger (jax secondary process OR an
        # explicitly-identified serve replica); process 0 keeps the legacy
        # is_main gate
        if is_main or process_index > 0:
            # fleet ledger contract (obs/fleet.py): under multi-host EVERY
            # process writes its own ledger — process 0 the canonical
            # telemetry.jsonl, process i>0 telemetry-{i}.jsonl — so the merge
            # can attribute windows to hosts; single-process is unchanged
            from tensorflowdistributedlearning_tpu.obs.ledger import (
                per_process_filename,
            )

            self.ledger = RunLedger(
                workdir, filename=per_process_filename(process_index)
            )
            header = {
                "schema_version": 1,
                "process_index": process_index,
            }
            # only when actually known: an explicit process_index with no
            # count (a serve replica that cannot know the fleet size) must
            # not persist a fabricated count
            if process_count is not None:
                header["process_count"] = int(process_count)
            if os.environ.get("TFDL_SUPERVISED_CHILD"):
                # stamped by resilience/supervisor.py on its children: lets
                # obs/report tell a supervised session's relaunches apart
                # from later standalone runs in the same workdir
                header["supervised"] = True
            if controller:
                header["controller"] = True
            else:
                header["fingerprint"] = run_fingerprint()
            age = _process_age_s()
            if age is not None:
                # how long the process had lived before any training code
                # ran: interpreter start, imports, device init, whatever the
                # caller did before it built its trainer
                header["process_age_s"] = round(age, 3)
            if run_info:
                header.update(run_info)
            if hold_header:
                self._held = []
            self._event("run_header", **header)
        self.detector = RecompileDetector(
            phase_fn=lambda: self.compile_phase,
            on_event=self._on_compile,
        ).attach()

    # -- spans -------------------------------------------------------------

    @property
    def current_span(self) -> str:
        return self._span_stack[-1] if self._span_stack else ""

    @property
    def compile_phase(self) -> str:
        """The span a compile that ends now is attributed to: the innermost
        open one that is not a child of ``step``. The trainers mark the loop
        spans warm (``mark_warm(SPAN_STEP, SPAN_DATA_WAIT)``), so a recompile
        of the train step or the input program inside ``dispatch_step`` /
        ``dispatch_prepare`` has to read ``step`` to count as post-warm-up."""
        for name in reversed(self._span_stack):
            if name not in _DISPATCH_SPANS:
                return name
        return ""

    @contextlib.contextmanager
    def span(self, name: str):
        """Time a named host-side phase; nested spans attribute to the
        innermost name. Also opens a profiler TraceAnnotation so captured
        xplane traces carry the same phase names the ledger uses. A span
        named ``startup/<phase>`` is a start-up phase: closing it persists a
        ``startup_phase`` event."""
        if not self.enabled:
            yield
            return
        self._span_stack.append(name)
        startup = (
            self._startup_mark(name[len(STARTUP_PREFIX):])
            if name.startswith(STARTUP_PREFIX)
            else None
        )
        t0 = time.perf_counter() if startup is None else startup.t0
        if name == SPAN_STEP:
            self._step_starts.append(t0)
        try:
            import jax

            with jax.profiler.TraceAnnotation(f"obs/{name}"):
                if self.tracer.enabled:
                    # per-unit tracing: a top-level span roots its own
                    # (sampled) trace; nested spans join the enclosing one
                    with self.tracer.span(name):
                        yield
                else:
                    yield
        finally:
            t1 = time.perf_counter()
            dt = t1 - t0
            self.registry.histogram(f"span/{name}").record(dt)
            self._span_stack.pop()
            if not self._span_stack:
                self._named_s += dt
            if startup is not None:
                self._startup_event(startup, t1)
            prof = self.profiler
            if prof is not None and prof.capturing and name == SPAN_STEP:
                # an active windowed capture counts train steps (and their
                # wall time — the same basis as step_time_ms) so it can stop
                # after capture_steps; the common path costs one None check
                try:
                    prof.note_step(dt)
                except Exception:  # noqa: BLE001 — profiling never kills training
                    logger.warning("profiler note_step failed", exc_info=True)

    # -- the start-up timeline ---------------------------------------------

    def _startup_mark(self, phase: str) -> _StartupMark:
        det = self.detector
        return _StartupMark(
            phase, time.perf_counter(), det.compile_count if det else 0
        )

    def _startup_event(self, mark: _StartupMark, t1: float) -> None:
        """One ``startup_phase`` event: where the phase lies on the host
        timeline and the compiles that ended inside it — by time, not by
        attribution, so a nested span does not take them."""
        compiles = self.detector.events[mark.compiles:] if self.detector else []
        self._event(
            "startup_phase",
            name=mark.phase,
            parent="startup",
            fold=self.fold,
            t0_mono=round(mark.t0, 6),
            duration_s=round(t1 - mark.t0, 6),
            programs=len(compiles),
            cache_hits=sum(1 for e in compiles if e.cache_hit),
            cache_misses=sum(1 for e in compiles if e.cache_hit is False),
            compile_s=round(sum(e.duration_s for e in compiles), 6),
        )

    def begin_first_step(self) -> None:
        """Open the ``first_step`` start-up phase — call right before the
        loop's first ``next(batches)``. It straddles loop iterations, so it
        is no ``with`` block: the first retired step (:meth:`step_done`)
        closes it, with no synchronisation of its own. A loop that retires
        nothing (``dispatch_ahead_steps=0``, or fewer steps than the budget)
        closes it at its first window boundary, or when the run ends."""
        if not self.enabled:
            return
        self._end_first_step(time.perf_counter())
        self._first_step = self._startup_mark(PHASE_FIRST_STEP)

    def _end_first_step(self, t1: float) -> None:
        mark, self._first_step = self._first_step, None
        if mark is not None:
            self._startup_event(mark, t1)
            # the step program exists now; the phase keeps its meaning (it
            # ends when step one retires) and the records carry their seconds
            self.write_program_scopes()

    def write_program_scopes(self) -> None:
        """One ``program_scopes`` event for every compiled step program that
        was called since this was last asked and is not in this run's ledger
        yet (``obs/scopes.py``): right after ``first_step`` and when the run
        closes, where a later recompile's record lands. The record
        goes into the event; one that would make a line of more than
        ``scopes.INLINE_LIMIT_BYTES`` goes to ``program_scopes-<n>.json``
        beside the ledger, and the event names that file."""
        if self.ledger is None:
            return
        for record in scopes_lib.drain():
            if id(record) in self._program_scopes:
                continue
            event = dict(record)
            if len(json.dumps(record)) > scopes_lib.INLINE_LIMIT_BYTES:
                name = f"program_scopes-{len(self._program_scopes)}.json"
                try:
                    with open(os.path.join(os.path.dirname(self.ledger.path), name),
                              "w", encoding="utf-8") as f:
                        json.dump(record, f)
                except OSError:
                    logger.warning("could not write %s", name, exc_info=True)
                    continue
                for key in ("scopes", "passes", "chains", "ops", "mixed", "containers"):
                    del event[key]
                event["file"] = name
            self._program_scopes[id(record)] = record
            self._event(scopes_lib.PROGRAM_SCOPES_EVENT, **event)

    def program_scopes(self) -> List[Dict]:
        """The records :meth:`write_program_scopes` has written, oldest
        first."""
        return list(self._program_scopes.values())

    def step_done(self, step: int) -> None:
        """The dispatch tracker retired train step ``step`` (its
        ``block_until_ready`` just returned, blocked or not): read the clock.
        This is the HOST's observation of completion — an upper bound on
        when the device finished the step, tight while the host sits blocked
        in ``fetch_wait`` and loose by however long the host was busy
        elsewhere (an eval pass, a checkpoint) before it looked."""
        if not self.enabled:
            return
        now = time.perf_counter()
        self._steps_done.append((int(step), now))
        if self._first_step is not None:
            self._end_first_step(now)

    def _span_delta(self, name: str) -> List[float]:
        """Span samples recorded since the last window boundary. Draining
        (not marking) keeps per-step span histograms bounded by one window —
        a 500k-step run would otherwise retain ~1M floats nothing reads."""
        return self.registry.histogram(f"span/{name}").drain()

    def drain_window_samples(self) -> Dict[str, list]:
        """Drain the per-window samples NOW and hand them to the caller:
        this call IS the window boundary — ``wall_s`` runs from the previous
        call to this one.

        Deferred-emission callers (the async host loop) snapshot at the
        window BOUNDARY and pass the result back through
        ``window_event(samples=...)`` one window later, so a late-written
        window event still describes its own interval instead of the next
        one's. A loop drains once before its first step, so that its first
        window starts there and not with what ran before it."""
        if not self.enabled:
            return {}
        now = time.perf_counter()
        self._end_first_step(now)
        samples: Dict[str, list] = {
            name: self._span_delta(name)
            for name in (*(n for n, _ in _WINDOW_SPAN_FIELDS), *_DISPATCH_SPANS)
        }
        samples["wall_s"] = [now - self._window_t0]
        self._window_t0 = now
        samples["named_s"] = [self._named_s]
        self._named_s = 0.0
        samples["step_starts"] = list(self._step_starts)
        self._step_starts.clear()
        samples["steps_done"] = list(self._steps_done)  # (step, clock) pairs
        self._steps_done.clear()
        samples["prefetch_depth"] = self.registry.histogram(
            PREFETCH_DEPTH_HISTOGRAM
        ).drain()
        samples["data_ready_depth"] = self.registry.histogram(
            DATA_READY_HISTOGRAM
        ).drain()
        samples["data_underruns"] = self.registry.histogram(
            DATA_UNDERRUN_HISTOGRAM
        ).drain()
        samples["data_worker_busy"] = self.registry.histogram(
            DATA_WORKER_BUSY_HISTOGRAM
        ).drain()
        return samples

    # -- profiling / MFU ---------------------------------------------------

    def set_profiler(self, profiler) -> None:
        """Attach a ``ContinuousProfiler`` (obs/profiler.py). The telemetry
        drives its hook points: step spans count into active captures,
        window boundaries run the cadence + alert triggers, and close()
        finishes any capture in flight."""
        self.profiler = profiler

    def set_step_flops(
        self,
        flops_per_step: float,
        *,
        peak_flops_per_chip: Optional[float] = None,
        n_devices: Optional[int] = None,
        collective_bytes_per_step: Optional[float] = None,
    ) -> None:
        """Price this run's steps analytically so measured time becomes MFU.

        ``flops_per_step`` is the planner's dense-proxy model
        (``planner.dense_proxy_flops``: ``6 * param_count * global_batch``)
        for ONE optimizer step across the whole job, which the trainers apply
        only where it holds — transformer backbones; a convolutional run is
        not priced and its windows omit ``mfu``. ``peak_flops_per_chip``
        defaults to the device peak table
        (``obs.profiler.resolve_peak_flops``) and stays ``None`` on
        unknown kinds — every ``step_window`` then simply omits ``mfu``
        (never a fabricated 0/0). ``collective_bytes_per_step`` is the
        planner's priced per-chip collective volume, which lets rooflines
        report achieved collective bandwidth."""
        if not self.enabled:
            return
        if n_devices is None:
            try:
                import jax

                n_devices = len(jax.devices())
            except Exception:  # noqa: BLE001
                n_devices = 1
        if peak_flops_per_chip is None:
            from tensorflowdistributedlearning_tpu.obs.profiler import (
                resolve_peak_flops,
            )

            peak_flops_per_chip = resolve_peak_flops()
        self.step_flops = {
            "flops_per_step": float(flops_per_step),
            "n_devices": int(n_devices),
        }
        if peak_flops_per_chip:
            self.step_flops["peak_flops_per_chip"] = float(peak_flops_per_chip)
        if collective_bytes_per_step:
            self.step_flops["collective_bytes_per_step"] = float(
                collective_bytes_per_step
            )

    def _window_mfu(self, step_s: float) -> Optional[float]:
        """Model FLOPs utilization for a window with the given wall time per
        step; None unless both the analytic pricing and a real device peak
        are known."""
        sf = self.step_flops
        if not sf or not step_s or step_s <= 0:
            return None
        peak = sf.get("peak_flops_per_chip")
        if not peak:
            return None
        achieved = sf["flops_per_step"] / step_s / sf["n_devices"]
        return round(achieved / peak, 4)

    # -- events ------------------------------------------------------------

    def _event(self, kind: str, /, **fields) -> None:
        if self.ledger is None:
            return
        if self._held is not None:
            # the header is still open (finish_header): keep the event, with
            # the time it was made, behind it
            self._held.append((kind, {"t": time.time(), **fields}))
            return
        self.ledger.event(kind, **fields)

    def finish_header(self, **run_info) -> None:
        """Add what the run header still lacked (``hold_header=True``) and
        write it out, followed by every event held behind it, each stamped
        with the time it was made. A no-op when nothing is held."""
        held, self._held = self._held, None
        if not held:
            return
        held[0][1].update(run_info)  # the run_header itself
        for kind, fields in held:
            self.ledger.event(kind, **fields)

    def _trace_event(self, fields: Dict) -> None:
        if self._held is not None:
            self._event(trace_lib.TRACE_EVENT, **fields)
        elif self.ledger is not None:
            self.ledger.event_buffered(trace_lib.TRACE_EVENT, **fields)

    def flush(self) -> None:
        """Push buffered (trace) events to disk — for readers of a live
        ledger; flushed events and close() do this implicitly."""
        if self.ledger is not None:
            self.ledger.flush()

    def event(self, kind: str, /, **fields) -> None:
        """Append an arbitrary ledger event under this run's header — the
        extension point non-trainer producers (the serving stack's
        ``serve_window`` events, suite stages) write through, so every
        producer shares one schema, one writer, one failure stance."""
        self._event(kind, **fields)

    def window_event(
        self,
        step: int,
        *,
        steps: int,
        images_per_sec: Optional[float] = None,
        scalars: Optional[Dict[str, float]] = None,
        dirty: bool = False,
        samples: Optional[Dict[str, list]] = None,
        examples: Optional[int] = None,
        **extra,
    ) -> None:
        """One per-log-window record: throughput, data-wait vs step-compute
        vs blocked-on-fetch split, per-step time percentiles, prefetch queue
        depth, recompiles seen this window. ``dirty`` marks windows containing
        compile/eval/checkpoint time (their throughput point is not
        steady-state). ``samples`` lets a deferred emitter pass the window's
        own boundary-snapshotted samples (``drain_window_samples``); default
        drains now."""
        if not self.enabled:
            return
        if samples is None:
            samples = self.drain_window_samples()
        compute = samples.get(SPAN_STEP, [])
        depth = samples.get("prefetch_depth", [])
        # exact totals even when a histogram ring capped the raw samples
        # (obs/metrics.py:SampleWindow)
        totals = {
            field: window_total_s(samples.get(name))
            for name, field in _WINDOW_SPAN_FIELDS
        }
        wait_s, compute_s = totals["data_wait_s"], totals["compute_s"]
        busy = (
            wait_s + compute_s + totals["fetch_wait_s"] + totals["barrier_wait_s"]
        )
        fields: Dict = {
            "step": step,
            "steps": steps,
            **{field: round(total, 6) for field, total in totals.items()},
            "data_wait_frac": round(wait_s / busy, 4) if busy else 0.0,
            "dirty": dirty,
            **extra,
        }
        wall_s = window_total_s(samples.get("wall_s"))
        if wall_s:
            # boundary to boundary, dirty windows too; what no span names is
            # what is left of it
            fields["wall_s"] = round(wall_s, 6)
            fields["host_other_s"] = round(
                wall_s - window_total_s(samples.get("named_s")), 6
            )
        for name in _DISPATCH_SPANS:
            calls = samples.get(name)
            if calls:
                fields[f"{name}_s"] = round(window_total_s(calls), 6)
                fields[f"{name}_ms"] = {
                    "p50": round(float(np.median(calls)) * 1e3, 3),
                    "max": round(max(calls) * 1e3, 3),
                }
        # the `step` span's entry per step; a synchronous loop's window fetch
        # runs under the same span after the last step and is not a step
        starts = samples.get("step_starts", [])[:steps]
        if starts:
            fields["step_start_mono"] = [round(t, 6) for t in starts]
        done = samples.get("steps_done")
        if done:
            # steps retire in order: entry i is step `step_done_first + i`
            # (step_done() says what a completion time means)
            times = [t for _, t in done]
            fields["step_done_first"] = done[0][0]
            fields["step_done_mono"] = [round(t, 6) for t in times]
            if len(times) > 1:
                gaps = np.diff(times) * 1e3
                fields["step_interval_ms"] = {
                    "p50": round(float(np.median(gaps)), 3),
                    "max": round(float(gaps.max()), 3),
                    "n": len(gaps),
                }
        if depth:
            # ready batches behind each consumer take: mean tells how full
            # the input prefetch queue ran, min 0 marks an underrun window
            fields["prefetch_queue_depth"] = {
                "mean": round(sum(depth) / len(depth), 2),
                "min": int(min(depth)),
            }
        svc_ready = samples.get("data_ready_depth", [])
        svc_under = samples.get("data_underruns", [])
        svc_busy = samples.get("data_worker_busy", [])
        if svc_ready or svc_under or svc_busy:
            # data-service backpressure for this window (data/service.py):
            # reorder-buffer depth at each take, consumer-starved events, and
            # worker utilization against the window's host wall time
            svc_fields: Dict = {"underruns": window_count(svc_under)}
            if svc_ready:
                svc_fields["ready_depth"] = {
                    "mean": round(sum(svc_ready) / len(svc_ready), 2),
                    "min": int(min(svc_ready)),
                }
            n_workers = self.registry.gauge(DATA_WORKERS_GAUGE).value
            if svc_busy and n_workers and busy > 0:
                svc_fields["worker_util"] = round(
                    min(1.0, window_total_s(svc_busy) / (n_workers * busy)), 3
                )
            fields["data_service"] = svc_fields
        if compute:
            s = time_summary(compute)
            fields["step_time_ms"] = {
                k[:-2] + "_ms": round(v * 1000, 3)
                for k, v in s.items()
                if k.endswith("_s") and k != "total_s"
            }
        # first-class MFU: analytic step FLOPs (set_step_flops) against the
        # window's wall per step — the `step` span is dispatch plus
        # backpressure, no measure of a step; absent without pricing or a
        # known device peak (CPU) — never 0/0
        mfu = self._window_mfu(wall_s / steps if steps else 0.0)
        if mfu is not None:
            fields["mfu"] = mfu
        if images_per_sec is not None:
            fields["images_per_sec"] = round(float(images_per_sec), 2)
        if scalars:
            fields["scalars"] = {k: float(v) for k, v in scalars.items()}
        if self.detector is not None:
            fields["recompiles_post_warmup"] = self.detector.post_warmup_count
        self._event("step_window", **fields)
        if self.capacity_sampling:
            # chip-seconds attribution for the window (obs/capacity.py):
            # compute_s is device-busy wall time on every chip (SPMD), so the
            # cost event rides the same cadence as the window itself
            cost_fields = self.cost.train_window(
                compute_s, steps, examples=examples, step=step
            )
            if cost_fields:
                self._event(capacity_lib.COST_EVENT, **cost_fields)
        self._windows += 1
        if self._windows % self._memory_every_windows == 0:
            self.memory_event(step=step)
        alerts: List[Dict] = []
        try:
            if self.health is not None:
                # AFTER the window is persisted: alerts (and a NaN-guard
                # abort) land in a ledger that already tells the window's
                # story
                alerts = (
                    self.health.observe_window(self, step, scalars or {}, fields)
                    or []
                )
        finally:
            # profiler hooks run even when a health abort is propagating —
            # the alert that ends the run is the one most worth a capture at
            # the NEXT opportunity; failures degrade to a warning
            if self.profiler is not None:
                try:
                    self.profiler.on_window(
                        step=step, windows=self._windows, alerts=alerts
                    )
                except Exception:  # noqa: BLE001 — never kill training
                    logger.warning("profiler window hook failed", exc_info=True)

    def eval_event(
        self, step: int, metrics: Dict[str, float], duration_s: float, **extra
    ) -> None:
        self._event(
            "eval",
            step=step,
            duration_s=round(duration_s, 6),
            metrics={k: float(v) for k, v in metrics.items()},
            **extra,
        )
        # eval just ran: if the pass pushed the allocator's peak past the
        # train watermark, the eval phase owns the new high-water mark
        self.sample_watermark(capacity_lib.PHASE_EVAL, step=step)

    def checkpoint_event(self, step: int, **extra) -> None:
        self._event("checkpoint", step=step, **extra)
        self.sample_watermark(capacity_lib.PHASE_CKPT, step=step)

    def memory_event(self, step: Optional[int] = None, **extra) -> None:
        """Per-device HBM snapshot (``profiling.memory_stats``) plus host RSS —
        on backends without the device query (CPU builds) the host side still
        makes the snapshot meaningful. ``extra`` fields ride along verbatim:
        the trainers attach exact per-device state accounting
        (``opt_state_bytes_per_device``/``params_bytes_per_device``, from
        ``train.state.tree_bytes_per_device``) so the weight-update-sharding
        saving is visible in the ledger even where the allocator query is
        unavailable."""
        if not self.enabled:
            return
        from tensorflowdistributedlearning_tpu.utils.profiling import (
            memory_stats,
        )

        try:
            devices = memory_stats()
        except Exception:  # noqa: BLE001 — a failed probe must not crash
            devices = {}
        fields: Dict = {"devices": devices, **extra}
        rss = _host_rss_bytes()
        if rss is not None:
            fields["host_rss_bytes"] = rss
        if step is not None:
            fields["step"] = step
        self._event("memory", **fields)
        # capacity layer (obs/capacity.py): the trainers' exact
        # tree_bytes_per_device accounting becomes the watermark tracker's
        # prediction, and every memory snapshot doubles as a watermark sample
        # attributed to the phase that was running
        predicted = (extra.get("params_bytes_per_device") or 0) + (
            extra.get("opt_state_bytes_per_device") or 0
        )
        if predicted:
            self.watermarks.set_predicted(predicted)
        # reuse the snapshot already in hand: one allocator query per window
        self.sample_watermark(self._memory_phase(), step=step, stats=devices)

    def _memory_phase(self) -> str:
        """Which lifecycle phase owns a watermark sampled NOW: the active
        eval/checkpoint span wins; otherwise "step" once the train step is
        warm, "compile" before that (the first windows' peaks are the
        compiler's workspace, not steady state)."""
        span = self.current_span
        if span == SPAN_EVAL:
            return capacity_lib.PHASE_EVAL
        if span == SPAN_CHECKPOINT:
            return capacity_lib.PHASE_CKPT
        if self.detector is not None and self.detector.is_warm(SPAN_STEP):
            return capacity_lib.PHASE_STEP
        return capacity_lib.PHASE_COMPILE

    def sample_watermark(
        self,
        phase: str,
        step: Optional[int] = None,
        stats: Optional[Dict] = None,
    ) -> Optional[Dict]:
        """Query the allocator once (or reuse the caller's ``stats``
        snapshot), attributed to ``phase``; ledger a ``memory_watermark``
        event when the peak advanced and feed the headroom health monitor.
        The monitor runs on EVERY sample — not only peak advances — so a
        trend-triggered degraded state can resolve once the peak plateaus.
        No-op (None) when telemetry or capacity sampling is off, and on
        backends without the allocator query."""
        if not (self.enabled and self.capacity_sampling):
            return None
        fields = self.watermarks.sample(phase, step=step, stats=stats)
        if fields:
            self._event(capacity_lib.WATERMARK_EVENT, **fields)
        observe = getattr(self.health, "observe_memory", None)
        if observe is not None:
            headroom = self.watermarks.headroom()
            if headroom and headroom.get("bytes_limit"):
                observe(self, step, headroom)
        return fields

    def mark_warm(self, *phases: str) -> None:
        """Steady state reached for ``phases`` (none = all): compiles
        attributed to a warm phase from now on are recompiles. The trainers
        mark the train spans warm after the first log window and ``eval``
        warm after the first eval pass."""
        if self.detector is not None:
            self.detector.mark_warm(*phases)

    # a run dispatches hundreds of trivial sub-ms executables (placement,
    # schedule evals); ledger lines are reserved for compiles that cost real
    # time — post-warmup recompiles are ALWAYS written, they are the signal
    _COMPILE_LEDGER_MIN_S = 0.01

    def _on_compile(self, event: CompileEvent) -> None:
        # cache-served compiles are fast by construction, so the min-duration
        # gate would hide exactly the events that prove the cache works —
        # any compile with a cache verdict is ledgered unconditionally
        if (
            event.post_warmup
            or event.cache_hit is not None
            or event.duration_s >= self._COMPILE_LEDGER_MIN_S
        ):
            fields = {
                "duration_s": round(event.duration_s, 6),
                "phase": event.phase,
                "post_warmup": event.post_warmup,
            }
            if event.cache_hit is not None:
                fields["cache_hit"] = event.cache_hit
                if event.cache_hit:
                    fields["saved_s"] = round(event.saved_s, 6)
            self._event("compile", **fields)
        if event.post_warmup:
            logger.warning(
                "post-warmup recompilation #%d detected (%.2fs, during %r) — "
                "on TPU this stalls every chip; check for shape drift in the "
                "input pipeline or Python-level jit cache misses",
                self.detector.post_warmup_count if self.detector else 0,
                event.duration_s,
                event.phase or "unattributed",
            )

    def close(self, **final_fields) -> None:
        """End-of-run: one ``run_end`` event (pass final metrics/step), then
        detach the compile listener and close the ledger. Idempotent — the
        trainers close with final metrics on success and ``interrupted=True``
        from their finally blocks, so an exception exit is recorded as
        interrupted rather than silently looking completed."""
        if self._closed:
            return
        self._closed = True
        if not self.enabled:
            return
        # a run that ended before its header was finished, or inside its
        # first step, still tells how far it got
        self.finish_header()
        self._end_first_step(time.perf_counter())
        self.write_program_scopes()
        if self.profiler is not None:
            # finish any capture in flight BEFORE run_end/close so its
            # events land inside this run's ledger
            try:
                self.profiler.close()
            except Exception:  # noqa: BLE001
                logger.warning("profiler close failed", exc_info=True)
        if self.detector is not None:
            final_fields.setdefault(
                "recompiles_post_warmup", self.detector.post_warmup_count
            )
            final_fields.setdefault("compiles", self.detector.compile_count)
            final_fields.setdefault(
                "compile_total_s", round(self.detector.compile_total_s, 3)
            )
            if self.detector.cache_hit_count or self.detector.cache_miss_count:
                final_fields.setdefault(
                    "compile_cache_hits", self.detector.cache_hit_count
                )
                final_fields.setdefault(
                    "compile_cache_misses", self.detector.cache_miss_count
                )
                final_fields.setdefault(
                    "compile_saved_s", round(self.detector.cache_saved_s, 3)
                )
            self.detector.detach()
        self._event("run_end", **final_fields)
        if self.ledger is not None:
            self.ledger.close()


def _process_age_s() -> Optional[float]:
    """Seconds since this process started, from ``/proc/self/stat`` (field
    22, ``starttime``, in clock ticks since boot) against ``/proc/uptime``;
    None where there is no procfs."""
    try:
        with open("/proc/self/stat") as f:
            # the command name (field 2) may hold spaces: count from its ")"
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime_s = float(f.read().split()[0])
        return uptime_s - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def _host_rss_bytes() -> Optional[int]:
    try:
        page = os.sysconf("SC_PAGE_SIZE")  # 64KiB-page kernels exist
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * page
    except (OSError, ValueError, IndexError):
        return None


# The disabled instance trainer code holds when telemetry is off — every
# method is a cheap no-op, so call sites never branch on None.
NULL_TELEMETRY = Telemetry(None, enabled=False)
